"""Positive semi-definite kernel functions used by the KRR experiments.

All functions map (n, p), (m, p) -> (n, m) and are jit/vmap friendly.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp


# Contraction precision of the f32 statistical numerics (sketch applications,
# Gram matrices, solves, predictions, the Pallas kernels' MXU contractions).
# The TPU's default f32 contraction rounds its inputs to bf16 — ~1e-3
# relative error, the size of the effects these estimators resolve; HIGHEST
# keeps f32 accuracy (and changes nothing on the CPU).  Every f32
# contraction of the package goes through the helpers below.
_F32_PRECISION = jax.lax.Precision.HIGHEST


def f32_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at f32 contraction precision."""
    return jnp.matmul(a, b, precision=_F32_PRECISION)


def f32_einsum(spec: str, *operands: jax.Array) -> jax.Array:
    """``jnp.einsum`` at f32 contraction precision."""
    return jnp.einsum(spec, *operands, precision=_F32_PRECISION)


def f32_dot(a: jax.Array, b: jax.Array, contract=((1,), (0,))) -> jax.Array:
    """Batch-free ``dot_general`` over the ``contract`` dimension pairs at
    f32 contraction precision with an f32 result — also inside Pallas
    kernels, where it lowers to Mosaic's fp32 contract precision."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(contract, ((), ())),
        precision=_F32_PRECISION, preferred_element_type=jnp.float32)

# Rows per partial Gram of ``f32_gram``: one MXU contraction runs over this
# many rows; the partials are summed with compensation.
GRAM_ROWS = 2048


@partial(jax.jit, static_argnames=("rows", "parts"))
def f32_gram(a: jax.Array, b: jax.Array, *, rows: int = GRAM_ROWS,
             parts: bool = False):
    """Aᵀ B (x, y) for A (n, x), B (n, y) over a long row axis n, in f32.

    A single contraction over all n rows accumulates its rounding along the
    whole axis (on the TPU the normal-equation Grams of a 463k-row KRR fit
    lost enough to move predictions by ~5e-4 between two summation orders).
    Here each block of ``rows`` rows is one contraction and the partials are
    summed with Knuth's two-sum, so the result is as accurate as one block's.
    ``parts`` returns the (sum, compensation) pair for a caller that reduces
    further (the data mesh's psum)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    n = a.shape[0]
    nb = -(-n // rows)
    if nb <= 1:
        g = f32_dot(a, b, ((0,), (0,)))
        return (g, jnp.zeros_like(g)) if parts else g

    def block(carry, i):
        hi, lo = carry
        # the last block is clamped to end at row n: mask the rows it shares
        # with the block before
        start = jnp.minimum(i * rows, n - rows)
        ab = jax.lax.dynamic_slice_in_dim(a, start, rows)
        bb = jax.lax.dynamic_slice_in_dim(b, start, rows)
        fresh = (start + jnp.arange(rows)) >= i * rows
        g = f32_dot(jnp.where(fresh[:, None], ab, 0.0), bb, ((0,), (0,)))
        s = hi + g
        t = s - hi
        return (s, lo + ((hi - (s - t)) + (g - t))), None

    zero = jnp.zeros((a.shape[1], b.shape[1]), jnp.float32)
    (hi, lo), _ = jax.lax.scan(block, (zero, zero), jnp.arange(nb))
    return (hi, lo) if parts else hi + lo


# Relative rounding floor of the expanded squared distance ‖a‖²+‖b‖²−2a·b in
# f32: below SQDIST_FLOOR·(‖a‖²+‖b‖²) the expansion cannot tell a distance
# from 0 (it is what duplicate points leave behind).
SQDIST_FLOOR = 16 * 1.1920929e-07


def snap_sqdist(a2: jax.Array, b2: jax.Array, ab: jax.Array) -> jax.Array:
    """‖a‖² + ‖b‖² − 2a·b with everything under the expansion's rounding
    floor snapped to exactly 0.  Without the snap the sqrt of the Laplacian
    and Matérn kernels turns the f32 rounding noise of a zero distance into
    a ~1e-3 error in k(a, a).  Shared verbatim by the Pallas matrix-free
    kernel, so both paths see the same distances."""
    d2 = a2 + b2 - 2.0 * ab
    return jnp.where(d2 > SQDIST_FLOOR * (a2 + b2), d2, 0.0)


def _sqdist(A: jax.Array, B: jax.Array) -> jax.Array:
    # numerically-guarded pairwise squared distances at f32 precision
    a2 = jnp.sum(A * A, axis=-1)[:, None]
    b2 = jnp.sum(B * B, axis=-1)[None, :]
    return snap_sqdist(a2, b2, f32_matmul(A, B.T))


def gaussian_kernel(A, B, bandwidth: float = 1.0):
    """exp(-||a-b||² / (2σ²))."""
    return jnp.exp(-_sqdist(A, B) / (2.0 * bandwidth**2))


def laplacian_kernel(A, B, bandwidth: float = 1.0):
    """k(a, b) = exp(−‖a − b‖ / bandwidth) — the L2 Laplacian (exponential)
    kernel, (a, p) × (b, p) → (a, b)."""
    d = jnp.sqrt(_sqdist(A, B) + 1e-30)
    return jnp.exp(-d / bandwidth)


def matern_kernel(A, B, bandwidth: float = 1.0, nu: float = 1.5):
    """Matérn with ν ∈ {0.5, 1.5, 2.5} (closed forms)."""
    r = jnp.sqrt(_sqdist(A, B) + 1e-30) / bandwidth
    if nu == 0.5:
        return jnp.exp(-r)
    if nu == 1.5:
        c = math.sqrt(3.0)
        return (1.0 + c * r) * jnp.exp(-c * r)
    if nu == 2.5:
        c = math.sqrt(5.0)
        return (1.0 + c * r + 5.0 * r * r / 3.0) * jnp.exp(-c * r)
    raise ValueError(f"unsupported nu={nu}")


@lru_cache(maxsize=None)
def _get_kernel_cached(name: str, bandwidth: float, nu: float):
    if name == "gaussian":
        return partial(gaussian_kernel, bandwidth=bandwidth)
    if name == "laplacian":
        return partial(laplacian_kernel, bandwidth=bandwidth)
    if name == "matern":
        return partial(matern_kernel, bandwidth=bandwidth, nu=nu)
    raise ValueError(f"unknown kernel {name}")


def get_kernel(name: str, bandwidth: float = 1.0, nu: float = 1.5):
    """Kernel callable for a (name, bandwidth, nu) config — CACHED, so equal
    configs return the IDENTICAL object.  ``functools.partial`` compares by
    identity, and the callable rides in pytree aux data (``SketchedKRR``), so
    a fresh partial per call would make two models fitted through equal
    operators carry unequal treedefs — un-stackable, un-vmappable, and a jit
    retrace per model."""
    return _get_kernel_cached(name, float(bandwidth), float(nu))
