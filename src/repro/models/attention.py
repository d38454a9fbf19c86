"""Attention blocks: GQA + RoPE, chunked-causal (memory-safe prefill), sliding
window, KV-cache decode, and AccumSketch (paper technique) compressed decode."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.sketched_attention import (
    SketchCache,
    init_sketch_cache,
    sketch_decode_attend,
    sketch_prefill_attend,
    update_sketch_cache,
)
from repro.models.common import apply_rope, dense_init
from repro.sharding import model_split

NEG_INF = -1e30


def init_attn(key, cfg: ModelConfig):
    H, Hkv, Dh, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H * Dh)),
        "wk": dense_init(ks[1], (D, Hkv * Dh)),
        "wv": dense_init(ks[2], (D, Hkv * Dh)),
        "wo": dense_init(ks[3], (H * Dh, D)),
        "norm": jnp.zeros((D,), jnp.float32),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * Dh,), jnp.float32)
        p["bk"] = jnp.zeros((Hkv * Dh,), jnp.float32)
        p["bv"] = jnp.zeros((Hkv * Dh,), jnp.float32)
    return p


def _qkv(p, h, cfg: ModelConfig, sin, cos):
    B, S, D = h.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    return q, k, v


def _chunked_causal(
    q, k, v, cfg: ModelConfig, *, window: int | None, q_chunk: int, out_dtype
) -> jax.Array:
    """Chunked-causal attention core shared by `attn_forward` / `attn_prefill`:
    q (B, S, H, Dh), k/v (B, S, Hkv, Dh) → (B, S, H·Dh) pre-output-projection,
    scanned over query chunks so peak memory is O(B·H·q_chunk·S) not O(B·H·S²)."""
    B, S = q.shape[:2]
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // Hkv
    # head-aligned TP: shard the KV-head axis (padded if it doesn't divide)
    # so the QKᵀ/AV contractions stay shard-local — see sharding.constrain
    from repro.sharding import constrain
    pol = cfg.sharding_policy
    head_tp = "tp!" if cfg.attn_head_tp else None
    k = constrain(k, "dp", None, head_tp, None, policy=pol)
    v = constrain(v, "dp", None, head_tp, None, policy=pol)
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, jnp.float32))
    kpos = jnp.arange(S)

    nq = max(S // q_chunk, 1)
    qc = S // nq
    qs = q.reshape(B, nq, qc, Hkv, G, Dh).transpose(1, 0, 2, 3, 4, 5)  # (nq,B,qc,Hkv,G,Dh)
    qs = constrain(qs, None, "dp", None, head_tp, None, None, policy=pol)

    @jax.checkpoint  # backward recomputes the (·,qc,S) logits: the chunk scan
    def body(i, qblk):  # must not stack per-chunk score residuals (O(S²))
        qpos = i * qc + jnp.arange(qc)
        logits = jnp.einsum(
            "bqhgd,bshd->bhgqs", qblk.astype(jnp.float32), k.astype(jnp.float32)
        ) * scale
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > (qpos[:, None] - window)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        o = jnp.einsum(
            "bhgqs,bshd->bqhgd", jax.nn.softmax(logits, axis=-1), v.astype(jnp.float32)
        )
        return o.astype(out_dtype)

    out = jax.lax.map(lambda args: body(*args), (jnp.arange(nq), qs))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H * Dh)


@jax.named_scope("attention")
def attn_forward(
    p, h: jax.Array, cfg: ModelConfig, sin, cos, *,
    window: int | None = None, q_chunk: int = 512,
) -> jax.Array:
    """Causal (optionally sliding-window) attention, scanned over query chunks
    so peak memory is O(B·H·q_chunk·S) instead of O(B·H·S²)."""
    q, k, v = _qkv(p, h, cfg, sin, cos)
    out = _chunked_causal(q, k, v, cfg, window=window, q_chunk=q_chunk,
                          out_dtype=h.dtype)
    return out @ p["wo"]


# --------------------------------------------------------------------------- #
# Decode: exact KV cache
# --------------------------------------------------------------------------- #

class KVCache(NamedTuple):
    """Exact KV cache with heads folded into the minor axis: (B, S_max,
    Hkv·Dh).  A (…, Hkv, Dh) layout pads Dh to the TPU's 128 lanes (1.6× at
    stablelm-3b's Dh = 80) and made XLA re-tile the whole cache inside the
    decode loop; at stablelm-3b width, batch 4 and a 2k context that no
    longer fit a 16 GB chip.

    In a `DecodeCache` each leaf is stacked over the layers, (n_layers, B,
    S_max, Hkv·Dh), and decode keeps that stack as one resident buffer: the
    layer scan of `decode_step` carries it, each layer reads its K and V
    from it in place, in the stored dtype and layout (`attn_decode`), and
    writes back only the new token's row.  Passing the stack to the scan as
    `xs`/`ys` instead slices each layer out and builds a new stack, and an
    `.astype(float32)` of a layer's cache is one more full copy (in a
    transposed layout at Dh = 80): each is a whole-cache pass through HBM
    per step, ~27 of the 44 ms of a stablelm-3b decode step on a TPU v5e."""

    k: jax.Array  # (B, S_max, Hkv·Dh)
    v: jax.Array  # (B, S_max, Hkv·Dh)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> KVCache:
    shp = (batch, max_len, cfg.n_kv_heads * cfg.head_dim)
    return KVCache(jnp.zeros(shp, dtype), jnp.zeros(shp, dtype))


@jax.named_scope("attention")
def attn_prefill(
    p, h: jax.Array, cache: KVCache, cfg: ModelConfig, sin, cos, *,
    window: int | None = None, q_chunk: int = 512,
) -> tuple[jax.Array, KVCache]:
    """Batched exact-cache prefill: chunked-causal attention for all L prompt
    tokens (positions 0..L-1) plus ONE bulk KV-cache write — replaces L
    sequential `attn_decode` dispatches. Sliding-window (ring-buffer) caches
    keep exactly the last S_cache tokens at slot t % S_cache, matching what L
    sequential ring writes would leave behind. Returns (out (B, L, D), cache)."""
    B, L, _ = h.shape
    q, k, v = _qkv(p, h, cfg, sin, cos)
    out = _chunked_causal(q, k, v, cfg, window=window, q_chunk=q_chunk,
                          out_dtype=h.dtype) @ p["wo"]
    S_cache = cache.k.shape[1]
    kc = k.astype(cache.k.dtype).reshape(B, L, -1)
    vc = v.astype(cache.v.dtype).reshape(B, L, -1)
    if L <= S_cache:
        cache = KVCache(
            jax.lax.dynamic_update_slice(cache.k, kc, (0, 0, 0)),
            jax.lax.dynamic_update_slice(cache.v, vc, (0, 0, 0)),
        )
    else:
        ring = (jnp.arange(L - S_cache, L)) % S_cache
        cache = KVCache(
            cache.k.at[:, ring].set(kc[:, L - S_cache:]),
            cache.v.at[:, ring].set(vc[:, L - S_cache:]),
        )
    return out, cache


def _exact_parts(x: jax.Array, dtype) -> tuple[tuple[jax.Array, ...], jax.lax.Precision]:
    """``x`` as the operands of a contraction with cache values of ``dtype``
    such that every product is exact in float32: ``x`` at HIGHEST against a
    float32 cache; against a bfloat16 one, bfloat16 ``x`` as it is and any
    other ``x`` as three bfloat16 parts that sum to it exactly (8 + 8 + 8 of
    float32's 24 significant bits, over the same exponent range)."""
    if dtype == jnp.float32:
        return (x.astype(jnp.float32),), jax.lax.Precision.HIGHEST
    if dtype != jnp.bfloat16:
        raise ValueError(f"exact decode reads a float32 or bfloat16 cache, not {dtype}")
    if x.dtype == jnp.bfloat16:
        return (x,), jax.lax.Precision.DEFAULT
    # reduce_precision and not a float32 → bfloat16 → float32 round trip,
    # which a compiler allowed excess precision may fold to the identity and
    # so zero the remainders
    r, parts = x.astype(jnp.float32), []
    for _ in range(3):
        hi = jax.lax.reduce_precision(r, exponent_bits=8, mantissa_bits=7)
        parts.append(hi.astype(jnp.bfloat16))
        r = r - hi
    return tuple(parts), jax.lax.Precision.DEFAULT


def _decode_attend(q: jax.Array, ck: jax.Array, cv: jax.Array, pos, *,
                   per_head: bool = False) -> jax.Array:
    """Softmax attention of one query per sequence over an exact cache as it
    is stored: q (B, H, Dh), ck/cv (B, S, Hkv·Dh), slot s valid iff s <= pos
    → (B, H, Dh) float32.  Logits and softmax are float32, and every product
    of a cache value is exact in float32 (`_exact_parts`), as in an upcast:
    only the summation order differs.

    By default neither contraction upcasts or re-lays-out the cache: the
    query enters as a block-diagonal (Hkv·Dh, H) matrix, so the scores are
    one (S, Hkv·Dh) @ (Hkv·Dh, H) product per sequence, and the weighted sum
    is (H, S) @ (S, Hkv·Dh), of which each head keeps its own Dh-wide block.
    That is Hkv times the useful multiplies (by count ~0.9 ms a stablelm-3b
    step at the bf16 peak, the probabilities' three parts included), done
    while the cache streams from HBM; a (B, S, Hkv, Dh) view of the cache
    instead pads Dh = 80 to 128 lanes and XLA copies the whole cache to make
    it.  ``per_head`` takes that view all the same, for a cache split over
    devices along S or Hkv·Dh: there the block-diagonal products contract
    over the split axis, so (B, S, H) scores or (B, 3H, Hkv·Dh) partial
    outputs would be summed across devices; per KV head, each device keeps
    its own sums."""
    B, H, Dh = q.shape
    S, HD = ck.shape[1:]
    Hkv = HD // Dh
    G = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, jnp.float32))
    if per_head:
        ck, cv = ck.reshape(B, S, Hkv, Dh), cv.reshape(B, S, Hkv, Dh)
        parts, prec = _exact_parts(q.reshape(B, Hkv, G, Dh), ck.dtype)
        s = jnp.einsum("bhgd,bshd->bshg", jnp.concatenate(parts, axis=2), ck,
                       precision=prec, preferred_element_type=jnp.float32)
        s = s.reshape(B, S, Hkv, len(parts), G).sum(axis=3).reshape(B, S, H)
    else:
        own = jnp.arange(Hkv)[:, None] == jnp.arange(H)[None, :] // G
        qbd = jnp.where(own[None, :, None, :], q.transpose(0, 2, 1)[:, None], 0)
        parts, prec = _exact_parts(qbd.reshape(B, HD, H), ck.dtype)
        s = jnp.einsum("bsk,bkn->bsn", ck, jnp.concatenate(parts, axis=-1),
                       precision=prec, preferred_element_type=jnp.float32)
        s = s.reshape(B, S, len(parts), H).sum(axis=2)
    logits = jnp.where((jnp.arange(S) <= pos)[None, :, None], s * scale, NEG_INF)
    probs = jax.nn.softmax(logits, axis=1)
    if per_head:
        parts, prec = _exact_parts(probs.reshape(B, S, Hkv, G), cv.dtype)
        o = jnp.einsum("bshg,bshd->bhgd", jnp.concatenate(parts, axis=3), cv,
                       precision=prec, preferred_element_type=jnp.float32)
        return o.reshape(B, Hkv, len(parts), G, Dh).sum(axis=2).reshape(B, H, Dh)
    parts, prec = _exact_parts(probs, cv.dtype)
    o = jnp.einsum("bsn,bsk->bnk", jnp.concatenate(parts, axis=-1), cv,
                   precision=prec, preferred_element_type=jnp.float32)
    o = o.reshape(B, len(parts), H, Hkv, Dh)
    return jnp.where(own.T[None, None, :, :, None], o, 0.0).sum(axis=(1, 3))


@jax.named_scope("attention")
def attn_decode(
    p, h_t: jax.Array, cache: KVCache, layer: jax.Array, pos: jax.Array,
    cfg: ModelConfig, sin_t, cos_t, *, write_pos: jax.Array | None = None,
) -> tuple[jax.Array, KVCache]:
    """One-token decode against layer ``layer`` of the stacked exact cache
    (leaves (n_layers, B, S_cache, Hkv·Dh), see `KVCache`). h_t: (B, 1, D);
    pos: scalar current absolute index.  Writes the token's K/V row in place
    at (layer, :, write_pos, :) and reads the layer's K/V where they lie,
    per KV head where the current mesh splits the cache over devices
    (`_decode_attend`).  Returns (out (B, 1, D), the stack).

    `write_pos` defaults to pos; a ring-buffer (sliding-window) cache passes
    pos % window. Validity mask: slot s is valid iff s <= pos (for a full
    cache) — for a ring buffer once pos >= S_cache-1 every slot is valid,
    which the same comparison yields since pos keeps growing."""
    B = h_t.shape[0]
    H, Dh = cfg.n_heads, cfg.head_dim
    if write_pos is None:
        write_pos = pos
    q, k, v = _qkv(p, h_t, cfg, sin_t, cos_t)                       # (B,1,·,Dh)
    # the barrier keeps XLA from fusing the V projection into the row write:
    # fused, it laid the whole V stack out for the write, (S, B, Hkv·Dh), and
    # then copied each layer's V out of it to read it, and the stack back
    k, v = jax.lax.optimization_barrier(
        (k.astype(cache.k.dtype), v.astype(cache.v.dtype)))
    at = (layer, 0, write_pos, 0)
    cache = KVCache(
        jax.lax.dynamic_update_slice(cache.k, k.reshape(1, B, 1, -1), at),
        jax.lax.dynamic_update_slice(cache.v, v.reshape(1, B, 1, -1), at),
    )
    o = _decode_attend(q[:, 0], jax.lax.dynamic_index_in_dim(cache.k, layer, 0, False),
                       jax.lax.dynamic_index_in_dim(cache.v, layer, 0, False), pos,
                       per_head=model_split(cfg.sharding_policy))
    out = o.reshape(B, 1, H * Dh).astype(h_t.dtype) @ p["wo"]
    return out, cache


# --------------------------------------------------------------------------- #
# Decode: sketched (compressed) cache — the paper's technique in serving
# --------------------------------------------------------------------------- #

def init_attn_sketch_cache(cfg: ModelConfig, batch: int, dtype) -> SketchCache:
    """Sketched attention cache sized from cfg (`dtype` for k/v sums; mass f32)."""
    return init_sketch_cache(
        batch, cfg.n_kv_heads, cfg.sketch_attn.d_slots, cfg.head_dim, dtype
    )


@jax.named_scope("attention")
def attn_prefill_sketched(
    p, h: jax.Array, cache: SketchCache, cfg: ModelConfig, sin, cos,
    slot_table: jax.Array, *, chunk: int = 128,
) -> tuple[jax.Array, SketchCache]:
    """Batched sketched-cache prefill: one vectorized segment-sum scatter for
    all L tokens' (k, v) plus evolving-cache attention (position t sees the
    cache state after its own scatter — identical semantics to L sequential
    `attn_decode_sketched` dispatches, see `sketch_prefill_attend`).
    slot_table: (L, m_r) from `decode_slot_table`. Returns (out (B, L, D), cache)."""
    B, L, _ = h.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    q, k, v = _qkv(p, h, cfg, sin, cos)
    o, cache = sketch_prefill_attend(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        cache, slot_table, chunk=chunk,
    )
    out = o.transpose(0, 2, 1, 3).reshape(B, L, H * Dh).astype(h.dtype) @ p["wo"]
    return out, cache


@jax.named_scope("attention")
def attn_decode_sketched(
    p, h_t: jax.Array, cache: SketchCache, cfg: ModelConfig,
    sin_t, cos_t, slots: jax.Array,
) -> tuple[jax.Array, SketchCache]:
    """One-token decode over the AccumSketch-compressed cache: O(d_slots) per
    token and O(d_slots·Dh) memory regardless of context length."""
    B = h_t.shape[0]
    H, Dh = cfg.n_heads, cfg.head_dim
    q, k, v = _qkv(p, h_t, cfg, sin_t, cos_t)
    cache = update_sketch_cache(cache, k[:, 0], v[:, 0], slots)
    o = sketch_decode_attend(q[:, 0].reshape(B, H, Dh), cache)
    out = o.reshape(B, 1, H * Dh).astype(h_t.dtype) @ p["wo"]
    return out, cache
