"""Random weights of a dense decoder LM, made on the device from a key.

One jitted program makes every leaf in the type it is served in, layer by
layer under ``lax.map`` so that no temporary outgrows one layer.  Matrices
are uniform with the variance of a fan-in initialisation, 1 / fan_in; the
embedding and the output head have standard deviation 0.02; norm weights
are uniform in ±0.1 (the block scales by 1 + w).

The layout is the benchmark's own (``(L, ...)`` stacks under ``layers``);
a runner maps it onto the program's pytree, and the reference reads it as
it is.  Both get it from here, from the same seed.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def sizes(model: dict) -> dict:
    """Static sizes from a configuration's ``model`` entry."""
    D, H = model["d_model"], model["n_heads"]
    return {"L": model["n_layers"], "D": D, "H": H,
            "Hkv": model["n_kv_heads"], "Dh": model.get("head_dim") or D // H,
            "F": model["d_ff"], "V": model["vocab_size"]}


def _uniform(key, shape, std, dtype):
    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


def _layer(key, s, dtype):
    D, H, Hkv, Dh, F = s["D"], s["H"], s["Hkv"], s["Dh"], s["F"]
    k = jax.random.split(key, 9)
    return {
        "attn_norm": _uniform(k[0], (D,), 0.1 / math.sqrt(3.0), jnp.float32),
        "wq": _uniform(k[1], (D, H * Dh), D ** -0.5, dtype),
        "wk": _uniform(k[2], (D, Hkv * Dh), D ** -0.5, dtype),
        "wv": _uniform(k[3], (D, Hkv * Dh), D ** -0.5, dtype),
        "wo": _uniform(k[4], (H * Dh, D), (H * Dh) ** -0.5, dtype),
        "mlp_norm": _uniform(k[5], (D,), 0.1 / math.sqrt(3.0), jnp.float32),
        "w_gate": _uniform(k[6], (D, F), D ** -0.5, dtype),
        "w_up": _uniform(k[7], (D, F), D ** -0.5, dtype),
        "w_down": _uniform(k[8], (F, D), F ** -0.5, dtype),
    }


@partial(jax.jit, static_argnames=("frozen", "dtype"))
def _make(key, frozen, dtype):
    s = dict(frozen)
    ke, kh, kn, kl = jax.random.split(key, 4)
    layers = jax.lax.map(
        lambda i: _layer(jax.random.fold_in(kl, i), s, dtype),
        jnp.arange(s["L"]))
    return {
        "embed": _uniform(ke, (s["V"], s["D"]), 0.02, dtype),
        "head": _uniform(kh, (s["V"], s["D"]), 0.02, dtype),
        "final_norm": _uniform(kn, (s["D"],), 0.1 / math.sqrt(3.0), jnp.float32),
        "layers": layers,
    }


def make(key, s: dict, dtype=jnp.bfloat16) -> dict:
    """All weights for sizes ``s`` (see ``sizes``), on the default device."""
    return _make(key, tuple(sorted(s.items())), jnp.dtype(dtype))


def n_bytes(w: dict) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(w))
