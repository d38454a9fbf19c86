"""The composable LM: `pattern` × `n_superblocks` scanned with jax.lax.scan.

Scanning keeps the HLO size O(pattern) instead of O(n_layers) — this is what
makes 512-way multi-pod SPMD compiles tractable, and it is also where remat
(activation checkpointing) attaches.

Params pytree:
  embed      (V, D)            — input embedding (tied output head if cfg.tie)
  lm_head    (V, D) | absent   — untied output head
  final_norm (D,)
  blocks     {pos{i}: subtree stacked over n_superblocks}
  shared     {...}             — parameters for `attn_shared` kinds (Zamba2)
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.sketched_attention import SketchCache
from repro.models import attention as att
from repro.models import ffn as ffn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import chunked_xent, embed_init, rmsnorm, rope_table
from repro.sharding import constrain

Params = dict
PyTree = Any


# --------------------------------------------------------------------------- #
# Init
# --------------------------------------------------------------------------- #

def _init_block(key, kind: str, cfg: ModelConfig) -> Params:
    if kind in ("attn", "attn_local"):
        ka, kf = jax.random.split(key)
        p = {"attn": att.init_attn(ka, cfg)}
        if cfg.ffn == "dense":
            p["ffn"] = ffn_mod.init_ffn(kf, cfg.d_model, cfg.d_ff)
        elif cfg.ffn == "moe":
            p["ffn"] = moe_mod.init_moe(kf, cfg.d_model, cfg.moe)
        return p
    if kind == "mamba2":
        return {"mixer": ssm_mod.init_mamba2(key, cfg)}
    if kind == "mlstm":
        return {"mixer": xlstm_mod.init_mlstm(key, cfg)}
    if kind == "slstm":
        return {"mixer": xlstm_mod.init_slstm(key, cfg)}
    raise ValueError(f"unknown block kind {kind}")


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 4)
    params: Params = {
        "embed": embed_init(keys[0], (cfg.vocab_size, cfg.d_model)),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(keys[1], (cfg.vocab_size, cfg.d_model))

    # stacked per-superblock params (vmap over superblock index)
    blocks = {}
    for i, kind in enumerate(cfg.pattern):
        if kind == "attn_shared":
            continue
        kinit = jax.random.fold_in(keys[2], i)  # rng-stream: init-block
        sb_keys = jax.random.split(kinit, cfg.n_superblocks)
        blocks[f"pos{i}"] = jax.vmap(lambda k: _init_block(k, kind, cfg))(sb_keys)
    params["blocks"] = blocks

    shared = {}
    if "attn_shared" in cfg.pattern:
        ka, kf = jax.random.split(keys[3])
        shared["attn"] = att.init_attn(ka, cfg)
        shared["ffn"] = ffn_mod.init_ffn(kf, cfg.d_model, cfg.d_ff)
    params["shared"] = shared
    return params


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def active_param_count(cfg: ModelConfig, params: Params) -> int:
    """Active-per-token parameters (MoE: top_k of n_experts)."""
    total = param_count(params)
    if cfg.moe is None:
        return total
    expert_names = ("wi_gate", "wi_up", "wo")
    inactive = 0
    for pos in params["blocks"].values():
        ffn = pos.get("ffn", {})
        for n in expert_names:
            if n in ffn and ffn[n].ndim == 4:      # (n_sb, E, ·, ·)
                inactive += ffn[n].size * (1 - cfg.moe.top_k / cfg.moe.n_experts)
    return int(total - inactive)


# --------------------------------------------------------------------------- #
# Forward (training / prefill)
# --------------------------------------------------------------------------- #

def _block_forward(kind, bp, shared, h, cfg: ModelConfig, sin, cos, aux, q_chunk):
    eps = cfg.norm_eps
    if kind in ("attn", "attn_local", "attn_shared"):
        p = shared if kind == "attn_shared" else bp
        window = cfg.window if kind == "attn_local" else None
        h = h + att.attn_forward(
            p["attn"], rmsnorm(h, p["attn"]["norm"], eps), cfg, sin, cos,
            window=window, q_chunk=q_chunk,
        )
        if "ffn" in p:
            x = rmsnorm(h, p["ffn"]["norm"], eps)
            if cfg.ffn == "moe" and kind != "attn_shared":
                y, metrics = moe_mod.moe_forward(p["ffn"], x, cfg.moe)
                aux = aux + metrics.aux_loss
            else:
                y = ffn_mod.ffn_forward(p["ffn"], x)
            h = h + y
        return h, aux
    p = bp["mixer"]
    x = rmsnorm(h, p["norm"], eps)
    if kind == "mamba2":
        y = ssm_mod.mamba2_forward(p, x, cfg)
    elif kind == "mlstm":
        y = xlstm_mod.mlstm_forward(p, x, cfg)
    elif kind == "slstm":
        y = xlstm_mod.slstm_forward(p, x, cfg)
    else:
        raise ValueError(kind)
    return h + y, aux


def _remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    pol = {
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "full": jax.checkpoint_policies.nothing_saveable,
    }[policy]
    return jax.checkpoint(fn, policy=pol, prevent_cse=False)


def forward(
    params: Params, tokens: jax.Array, cfg: ModelConfig, *,
    cond: jax.Array | None = None, q_chunk: int = 512, remat: str = "dots",
) -> tuple[jax.Array, jax.Array]:
    """tokens (B, S) [+ cond (B, Sc, D)] → (h_final (B, S_tot, D), aux_loss)."""
    B, S = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0)
    h = h * jnp.sqrt(jnp.asarray(cfg.d_model, h.dtype))
    if cond is not None:
        h = jnp.concatenate([cond.astype(h.dtype), h], axis=1)
    h = constrain(h, "dp", None, None, policy=cfg.sharding_policy)  # batch on DP axes
    S_tot = h.shape[1]
    sin, cos = rope_table(jnp.arange(S_tot), cfg.head_dim, cfg.rope_theta)
    shared = params["shared"]

    def superblock(carry, sb_params):
        h, aux = carry
        h = constrain(h, "dp", None, None, policy=cfg.sharding_policy)  # pin scan carry
        for i, kind in enumerate(cfg.pattern):
            bp = sb_params.get(f"pos{i}")
            h, aux = _block_forward(kind, bp, shared, h, cfg, sin, cos, aux, q_chunk)
        return (h, aux), None

    body = _remat_wrap(superblock, remat)
    (h, aux), _ = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.float32)), params["blocks"]
    )
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return h, aux


def output_embedding(params: Params) -> jax.Array:
    return params.get("lm_head", params["embed"])


def loss_fn(
    params: Params, tokens: jax.Array, labels: jax.Array, cfg: ModelConfig, *,
    cond: jax.Array | None = None, q_chunk: int = 512, remat: str = "dots",
) -> tuple[jax.Array, dict]:
    h, aux = forward(params, tokens, cfg, cond=cond, q_chunk=q_chunk, remat=remat)
    B, S = tokens.shape
    if cond is not None:
        # loss only on the token (non-conditioning) positions
        Sc = cond.shape[1]
        mask = jnp.concatenate(
            [jnp.zeros((B, Sc), jnp.float32), jnp.ones((B, S), jnp.float32)], axis=1
        )
        labels_full = jnp.concatenate([jnp.zeros((B, Sc), labels.dtype), labels], axis=1)
    else:
        mask, labels_full = jnp.ones((B, S), jnp.float32), labels
    xent, count = chunked_xent(h, output_embedding(params), labels_full, mask)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
    loss = xent + aux_w * aux
    return loss, {"xent": xent, "aux": aux, "tokens": count}


@jax.named_scope("head")
def _head_logits(h_last: jax.Array, emb: jax.Array) -> jax.Array:
    """(B, D) @ (V, D)ᵀ → (B, V) f32. bf16 operands with f32 accumulation:
    `emb.T.astype(f32)` would materialize a full-vocab f32 weight copy (2.5 GB
    for qwen1.5-110b) on every decode step."""
    return jnp.einsum("bd,vd->bv", h_last, emb,
                      preferred_element_type=jnp.float32)


def prefill(
    params: Params, tokens: jax.Array, cfg: ModelConfig, *,
    cond: jax.Array | None = None, q_chunk: int = 512,
) -> jax.Array:
    """Prefill pass → last-position logits (B, V)."""
    h, _ = forward(params, tokens, cfg, cond=cond, q_chunk=q_chunk, remat="none")
    return _head_logits(h[:, -1], output_embedding(params))


# --------------------------------------------------------------------------- #
# Decode with per-block caches
# --------------------------------------------------------------------------- #

class DecodeCache(NamedTuple):
    blocks: PyTree        # {pos{i}: state stacked over superblocks}


def _init_block_cache(kind, cfg: ModelConfig, batch, max_len, dtype, use_sketch):
    if kind in ("attn", "attn_shared"):
        if use_sketch:
            # AccumSketch-compressed cache (paper technique): O(d_slots) memory.
            # Honors the caller's dtype for k_sum/v_sum (the seed hardcoded
            # f32 — 2× the memory the config asked for); mass stays f32 inside
            # init_sketch_cache regardless.
            return att.init_attn_sketch_cache(cfg, batch, dtype)
        return att.init_kv_cache(cfg, batch, max_len, dtype)
    if kind == "attn_local":
        return att.init_kv_cache(cfg, batch, min(max_len, cfg.window), dtype)
    if kind == "mamba2":
        return ssm_mod.init_ssm_state(cfg, batch, dtype)
    if kind == "mlstm":
        return xlstm_mod.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return xlstm_mod.init_slstm_state(cfg, batch)
    raise ValueError(kind)


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16, *,
    use_sketch: bool = False,
) -> DecodeCache:
    """use_sketch=True → attention caches are AccumSketch-compressed (paper
    technique): O(d_slots) memory per layer instead of O(max_len)."""
    blocks = {}
    for i, kind in enumerate(cfg.pattern):
        one = _init_block_cache(kind, cfg, batch, max_len, dtype, use_sketch)
        blocks[f"pos{i}"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_superblocks,) + x.shape), one
        )
    return DecodeCache(blocks)


def _block_decode(kind, bp, shared, h, state, layer, cfg, sin_t, cos_t, pos, slots,
                  use_sketch):
    """One block's decode step.  An exact `KVCache` state is the whole
    stack over superblocks, indexed by ``layer``; any other state is this
    layer's own."""
    eps = cfg.norm_eps
    if kind in ("attn", "attn_local", "attn_shared"):
        p = shared if kind == "attn_shared" else bp
        x = rmsnorm(h, p["attn"]["norm"], eps)
        if isinstance(state, SketchCache):
            y, state = att.attn_decode_sketched(p["attn"], x, state, cfg, sin_t, cos_t, slots)
        else:
            # a ring-buffer sliding-window cache writes at pos % window
            y, state = att.attn_decode(
                p["attn"], x, state, layer, pos, cfg, sin_t, cos_t,
                write_pos=pos % state.k.shape[2] if kind == "attn_local" else None,
            )
        h = h + y
        if "ffn" in p:
            x = rmsnorm(h, p["ffn"]["norm"], eps)
            if cfg.ffn == "moe" and kind != "attn_shared":
                y, _ = moe_mod.moe_forward(p["ffn"], x, cfg.moe)
            else:
                y = ffn_mod.ffn_forward(p["ffn"], x)
            h = h + y
        return h, state
    p = bp["mixer"]
    x = rmsnorm(h, p["norm"], eps)
    if kind == "mamba2":
        y, state = ssm_mod.mamba2_decode(p, x, state, cfg)
    elif kind == "mlstm":
        y, state = xlstm_mod.mlstm_decode(p, x, state, cfg)
    elif kind == "slstm":
        y, state = xlstm_mod.slstm_decode(p, x, state, cfg)
    else:
        raise ValueError(kind)
    return h + y, state


def _block_prefill(kind, bp, shared, h, state, cfg, sin, cos, slot_table, q_chunk):
    """One block's batched prefill: full-sequence forward + cache state as if
    the L tokens had been decoded one by one (see `prefill_with_cache`)."""
    eps = cfg.norm_eps
    if kind in ("attn", "attn_local", "attn_shared"):
        p = shared if kind == "attn_shared" else bp
        x = rmsnorm(h, p["attn"]["norm"], eps)
        if isinstance(state, SketchCache):
            y, state = att.attn_prefill_sketched(
                p["attn"], x, state, cfg, sin, cos, slot_table
            )
        else:
            window = cfg.window if kind == "attn_local" else None
            y, state = att.attn_prefill(
                p["attn"], x, state, cfg, sin, cos, window=window, q_chunk=q_chunk
            )
        h = h + y
        if "ffn" in p:
            x = rmsnorm(h, p["ffn"]["norm"], eps)
            if cfg.ffn == "moe" and kind != "attn_shared":
                y, _ = moe_mod.moe_forward(p["ffn"], x, cfg.moe)
            else:
                y = ffn_mod.ffn_forward(p["ffn"], x)
            h = h + y
        return h, state
    # recurrent mixers have per-token decode transitions only — run them as an
    # inner scan over tokens (still ONE dispatch; the sequential dependence is
    # inherent to the state recurrence, not a Python-loop artifact)
    p = bp["mixer"]
    x = rmsnorm(h, p["norm"], eps)
    decode_fn = {
        "mamba2": ssm_mod.mamba2_decode,
        "mlstm": xlstm_mod.mlstm_decode,
        "slstm": xlstm_mod.slstm_decode,
    }[kind]

    def tok(st, x_t):
        y, st = decode_fn(p, x_t[:, None], st, cfg)
        return st, y[:, 0]

    state, ys = jax.lax.scan(tok, state, x.swapaxes(0, 1))
    return h + ys.swapaxes(0, 1), state


def prefill_with_cache(
    params: Params, tokens: jax.Array, cfg: ModelConfig, cache: DecodeCache, *,
    slot_table: jax.Array | None = None, q_chunk: int = 512,
) -> tuple[jax.Array, DecodeCache]:
    """Batched prefill: consume all L prompt tokens in ONE dispatch and return
    (last-position logits (B, V), updated DecodeCache) — the state the
    sequential decode loop would reach after positions 0..L-1, at chunked
    `forward` cost instead of L jitted dispatches.

    Exact caches get a bulk KV write, sketched caches one vectorized
    segment-sum scatter (bitwise-identical to the token loop's cache);
    `slot_table` (L, m_r) from `decode_slot_table` is required when the cache
    contains SketchCache states."""
    B, L = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0)
    h = h * jnp.sqrt(jnp.asarray(cfg.d_model, h.dtype))
    h = constrain(h, "dp", None, None, policy=cfg.sharding_policy)
    sin, cos = rope_table(jnp.arange(L), cfg.head_dim, cfg.rope_theta)
    shared = params["shared"]

    def superblock(h, xs):
        sb_params, sb_cache = xs
        h = constrain(h, "dp", None, None, policy=cfg.sharding_policy)
        new_states = {}
        for i, kind in enumerate(cfg.pattern):
            bp = sb_params.get(f"pos{i}")
            h, st = _block_prefill(
                kind, bp, shared, h, sb_cache[f"pos{i}"], cfg, sin, cos,
                slot_table, q_chunk,
            )
            new_states[f"pos{i}"] = st
        return h, new_states

    h, new_blocks = jax.lax.scan(superblock, h, (params["blocks"], cache.blocks))
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(h[:, -1], output_embedding(params))
    return logits, DecodeCache(new_blocks)


def decode_step(
    params: Params, cache: DecodeCache, token_t: jax.Array, pos: jax.Array,
    cfg: ModelConfig, *, slots: jax.Array | None = None, use_sketch: bool = False,
) -> tuple[jax.Array, DecodeCache]:
    """One decoding step. token_t: (B,) int32; pos: scalar int32 (current index).

    Returns (logits (B, V), updated cache). The scan mirrors forward().  It
    carries the exact `KVCache` stacks, which each layer indexes, reads in
    place and writes one row of (see `KVCache`); the small sketched and
    recurrent states go through it per layer, as `xs` and `ys`."""
    h = jnp.take(params["embed"], token_t[:, None], axis=0)
    h = h * jnp.sqrt(jnp.asarray(cfg.d_model, h.dtype))
    h = constrain(h, "dp", None, None, policy=cfg.sharding_policy)
    sin_t, cos_t = rope_table(pos[None], cfg.head_dim, cfg.rope_theta)
    shared = params["shared"]
    kv = {n: c for n, c in cache.blocks.items() if isinstance(c, att.KVCache)}
    rest = {n: c for n, c in cache.blocks.items() if n not in kv}

    def superblock(carry, xs):
        h, kv = carry
        sb_params, sb_rest, layer = xs
        kv, new_rest = dict(kv), {}
        for i, kind in enumerate(cfg.pattern):
            name = f"pos{i}"
            states = kv if name in kv else new_rest
            state = kv[name] if name in kv else sb_rest[name]
            h, states[name] = _block_decode(
                kind, sb_params.get(name), shared, h, state, layer, cfg,
                sin_t, cos_t, pos, slots, use_sketch,
            )
        return (h, kv), new_rest

    (h, kv), rest = jax.lax.scan(
        superblock, (h, kv),
        (params["blocks"], rest, jnp.arange(cfg.n_superblocks)),
    )
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(h[:, 0], output_embedding(params))
    return logits, DecodeCache({**rest, **kv})
