"""Dense SwiGLU FFN."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init


def init_ffn(key, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi_gate": dense_init(k1, (d_model, d_ff)),
        "wi_up": dense_init(k2, (d_model, d_ff)),
        "wo": dense_init(k3, (d_ff, d_model)),
        "norm": jnp.zeros((d_model,), jnp.float32),
    }


@jax.named_scope("mlp")
def ffn_forward(p, h: jax.Array) -> jax.Array:
    g = jax.nn.silu((h @ p["wi_gate"]).astype(jnp.float32)).astype(h.dtype)
    u = h @ p["wi_up"]
    return (g * u) @ p["wo"]
