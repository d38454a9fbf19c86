"""Regression data for the KRR cells, made on the device from a key.

Copied from ``chip_smoke.py``'s ``regression_data`` so that the yardstick
does not change with the program: standardized features and a target drawn
from the Gaussian kernel's own function class, y = Σ_j a_j k(x, c_j) over
256 random centres, scaled to unit variance, plus 0.1 noise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def gaussian(A, B, bandwidth: float):
    """exp(−‖a − b‖² / (2σ²)) at f32 contraction precision."""
    a2 = jnp.sum(A * A, axis=-1)[:, None]
    b2 = jnp.sum(B * B, axis=-1)[None, :]
    d2 = jnp.maximum(a2 + b2 - 2.0 * jnp.matmul(A, B.T, precision=HIGHEST), 0.0)
    return jnp.exp(-d2 / (2.0 * bandwidth**2))


@partial(jax.jit, static_argnames=("n_train", "n_test", "p", "bandwidth"))
def regression_data(key, n_train: int, n_test: int, p: int, bandwidth: float):
    """(X_train, y_train, X_test, y_test), f32, in one program."""
    kx, kc, ka, ke = jax.random.split(key, 4)
    X = jax.random.normal(kx, (n_train + n_test, p), jnp.float32)
    centres = jax.random.normal(kc, (256, p), jnp.float32)
    f = jnp.matmul(gaussian(X, centres, bandwidth),
                   jax.random.normal(ka, (256,)), precision=HIGHEST)
    f = (f - jnp.mean(f)) / jnp.std(f)
    y = f + 0.1 * jax.random.normal(ke, f.shape)
    return X[:n_train], y[:n_train], X[n_train:], y[n_train:]
