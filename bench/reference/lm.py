"""Plain float32 reference of the served decoder LM's forward pass.

Straight ``jax.numpy`` under HIGHEST contraction precision: no kernels, no
cache, no batching.  It reads the weights in the layout of
``bench/gen/lm_weights.py``, upcast to float32 one layer at a time inside a
``lax.scan``, so that a 32-layer model at full width fits beside its
activations.  It imports nothing of the system under test.

The block is the one the system under test builds, which departs from the
published stablelm-3b-4e1t (its configuration file lists these too):

- RMSNorm scaled by (1 + w), with no bias, where the model uses LayerNorm
  with weight and bias;
- rotary embedding over the whole head (NeoX half split), where the model
  rotates the first 25% of each head;
- the token embedding multiplied by sqrt(d_model) before the first block,
  which the model does not do.

Attention is causal multi-head attention (grouped when n_kv_heads < n_heads),
the MLP is SwiGLU, the output head is untied.

``control`` names a control, the same forward one precision step below
what the configuration states: ``"fp8"`` rounds the inputs of every
projection, MLP and head matmul to float8 e4m3 (weights scaled per output
channel, activations per row), the step below the bfloat16 weights;
``"attn_bf16"`` computes attention's scores, softmax and weighted sum in
bfloat16, the step below the float32 attention.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, control):
    """x (T, i) @ w (i, o) in float32."""
    if control == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta):
    """x (T, heads, Dh): rotate the two halves of every head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    s, c = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attend(q, k, v, causal, control):
    """Causal attention of q (T, H, Dh) over k, v (T, H, Dh)."""
    dt = jnp.bfloat16 if control == "attn_bf16" else jnp.float32
    q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)
    return o.astype(jnp.float32)


@partial(jax.jit, static_argnames=("frozen", "control"))
def _logits(w, tokens, rows, frozen, control):
    m = dict(frozen)
    H, Hkv, Dh, eps = m["H"], m["Hkv"], m["Dh"], m["eps"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    causal = pos[None, :] <= pos[:, None]

    def layer(h, lw):
        lw = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lw)
        x = _rms(h, lw["attn_norm"], eps)
        q = _rope(_mm(x, lw["wq"], control).reshape(T, H, Dh), pos, m["theta"])
        k = _rope(_mm(x, lw["wk"], control).reshape(T, Hkv, Dh), pos, m["theta"])
        v = _mm(x, lw["wv"], control).reshape(T, Hkv, Dh)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        o = _attend(q, k, v, causal, control)
        h = h + _mm(o.reshape(T, H * Dh), lw["wo"], control)
        x = _rms(h, lw["mlp_norm"], eps)
        g = jax.nn.silu(_mm(x, lw["w_gate"], control)) * _mm(x, lw["w_up"], control)
        return h + _mm(g, lw["w_down"], control), None

    h = w["embed"][tokens].astype(jnp.float32) * math.sqrt(m["D"])
    h, _ = jax.lax.scan(layer, h, w["layers"])
    x = _rms(h[rows], w["final_norm"].astype(jnp.float32), eps)
    return _mm(x, w["head"].astype(jnp.float32).T, control)


def logits(w: dict, tokens, rows, model: dict, control: str | None = None):
    """Logits (len(rows), V) at positions ``rows`` of one sequence
    ``tokens`` (T,), after the whole causal forward."""
    frozen = (("D", model["d_model"]), ("H", model["n_heads"]),
              ("Hkv", model["n_kv_heads"]),
              ("Dh", model.get("head_dim") or model["d_model"] // model["n_heads"]),
              ("eps", float(model["norm_eps"])), ("theta", float(model["rope_theta"])))
    return _logits(w, jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32),
                   frozen, control)


def served_gaps(ref_logits, tokens) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at that position (0 where it is the argmax)."""
    ref = np.asarray(ref_logits, np.float64)
    tokens = np.asarray(tokens)
    if tokens.shape != ref.shape[:1] or tokens.min() < 0 or tokens.max() >= ref.shape[1]:
        return np.full(ref.shape[:1], np.inf)
    return ref.max(axis=1) - ref[np.arange(len(tokens)), tokens]
