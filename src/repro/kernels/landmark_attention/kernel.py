"""Pallas TPU kernels: the O(S·L) stages of AccumAttention (sketched attention).

`landmark_attention` — out = softmax(q k̃ᵀ/√Dh + bias) @ M, with L = d_slots
landmarks. The landmark set is small by construction (that is the paper's
point), so k̃ and M stay resident in VMEM across the whole grid while q streams
through in (bq, Dh) tiles — one softmax pass per tile, no online-softmax
bookkeeping needed (full row of logits fits in VREGs). The bias lane carries
the decode path's log-mass correction (and −1e30 padding/empty-slot masks), so
the same kernel serves `sketch_decode_attend` and the prefill F-stage.

`landmark_stats` — the fused single-sweep variant for `accum_attention`: ONE
pass over the key/value sequence computes BOTH

    W    = softmax(q̃ k̃ᵀ/√Dh)          (L, L)   — landmark row, kt resident
    BmV  = softmax(q̃ Kᵀ/√Dh) · V       (L, Dv)  — online-softmax accumulation

The F·M product cannot join this sweep: M = W⁺(BmV) needs the completed W
(Newton–Schulz pseudo-inverse) before any F row can be applied — the fusion
boundary is data dependence, not tiling. What the fusion buys is never
materializing the (L, S) Bm softmax: running (max, denom, acc) live in VMEM
scratch across S tiles, flash-attention style.

Grids are strict here (S % block == 0, MXU-aligned dims in production);
`ops.py` pads arbitrary shapes and masks the padding via the scalar-prefetch
valid counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels_math import f32_dot

# `landmark_attention` upcasts every operand to f32 and contracts at f32
# precision (``f32_dot``): at the TPU's default precision sketched decode
# would disagree with exact f32 attention at ~1e-3 where its slots are
# singletons.  (`landmark_stats` feeds an approximate Newton–Schulz
# pseudo-inverse and keeps the default: at f32 precision its L = 1024 tiles
# overflow the 16 MiB scoped VMEM.)


def _kernel(q_ref, kt_ref, M_ref, b_ref, out_ref, *, scale: float):
    q = q_ref[...].astype(jnp.float32)
    kt = kt_ref[...].astype(jnp.float32)
    logits = f32_dot(q, kt, ((1,), (1,))) * scale + b_ref[...]  # (bq, L)
    mx = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - mx)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = f32_dot(p, M_ref[...].astype(jnp.float32))
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def landmark_attention(
    q: jax.Array, kt: jax.Array, M: jax.Array, bias: jax.Array | None = None, *,
    bq: int = 256, interpret: bool | None = None,
) -> jax.Array:
    """q: (S, Dh); kt: (L, Dh); M: (L, Dv); bias: (L,) f32 or None → (S, Dv).

    Strict-grid kernel (S % bq == 0) — `ops.landmark_attend` is the padded,
    autotuned entry point. `interpret=None` autodetects the backend
    (compiled Mosaic on TPU, interpreter elsewhere)."""
    if interpret is None:
        from repro.kernels.accum_apply.ops import default_interpret

        interpret = default_interpret()
    S, Dh = q.shape
    L, Dv = M.shape
    assert kt.shape == (L, Dh)
    bq = min(bq, S)
    assert S % bq == 0, (S, bq)
    if bias is None:
        bias = jnp.zeros((L,), jnp.float32)
    scale = 1.0 / (Dh ** 0.5)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=(S // bq,),
        in_specs=[
            pl.BlockSpec((bq, Dh), lambda i: (i, 0)),
            pl.BlockSpec((L, Dh), lambda i: (0, 0)),   # landmarks VMEM-resident
            pl.BlockSpec((L, Dv), lambda i: (0, 0)),
            pl.BlockSpec((1, L), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, Dv), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, Dv), q.dtype),
        interpret=interpret,
        name="landmark_attention",
    )(q, kt, M, bias.astype(jnp.float32)[None, :])


def _stats_kernel(nv_ref, qt_ref, kt_ref, k_ref, v_ref, W_ref, BmV_ref,
                  m_ref, d_ref, acc_ref, *, bs: int, scale: float):
    i = pl.program_id(0)
    ns = pl.num_programs(0)
    qt = qt_ref[...].astype(jnp.float32)

    @pl.when(i == 0)
    def _init():
        # landmark-row softmax W while k̃ is VMEM-resident; padded landmark
        # columns (index ≥ nv_ref[1]) masked to −inf
        kt = kt_ref[...].astype(jnp.float32)
        wl = jax.lax.dot_general(
            qt, kt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        lcol = jax.lax.broadcasted_iota(jnp.int32, wl.shape, 1)
        wl = jnp.where(lcol < nv_ref[1], wl, -1e30)
        mw = jnp.max(wl, axis=-1, keepdims=True)
        pw = jnp.exp(wl - mw)
        W_ref[...] = (pw / jnp.sum(pw, axis=-1, keepdims=True)).astype(W_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        d_ref[...] = jnp.zeros(d_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # online-softmax fold of this S tile into (max, denom, Bm·V accumulator)
    kb = k_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        qt, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                             # (L, bs)
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + i * bs
    logits = jnp.where(col < nv_ref[0], logits, -1e30)    # padded keys → −inf
    m_old = m_ref[:, :1]
    m_new = jnp.maximum(m_old, jnp.max(logits, axis=-1, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    p = jnp.exp(logits - m_new)
    d_new = d_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    d_ref[...] = jnp.broadcast_to(d_new, d_ref.shape)

    @pl.when(i == ns - 1)
    def _finalize():
        BmV_ref[...] = (acc_ref[...] / d_ref[:, :1]).astype(BmV_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_valid", "l_valid", "bs", "interpret"))
def landmark_stats(
    qt: jax.Array, kt: jax.Array, k: jax.Array, v: jax.Array, *,
    n_valid: int, l_valid: int, bs: int = 512, interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused (W, Bm·V) in one sweep over the S axis (see module docstring).

    qt, kt: (L, Dh); k: (S, Dh); v: (S, Dv). `n_valid` / `l_valid` are the
    un-padded S / L extents (padded keys and landmark columns are masked to
    −inf; padded landmark ROWS produce garbage rows the caller slices off).
    Returns (W (L, L) f32, BmV (L, Dv) f32). Strict grid: S % bs == 0."""
    if interpret is None:
        from repro.kernels.accum_apply.ops import default_interpret

        interpret = default_interpret()
    L, Dh = qt.shape
    S, Dv = v.shape
    assert kt.shape == (L, Dh) and k.shape == (S, Dh)
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    scale = 1.0 / (Dh ** 0.5)
    nv = jnp.asarray([n_valid, l_valid], jnp.int32)
    return pl.pallas_call(
        functools.partial(_stats_kernel, bs=bs, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S // bs,),
            in_specs=[
                pl.BlockSpec((L, Dh), lambda i, *_: (0, 0)),
                pl.BlockSpec((L, Dh), lambda i, *_: (0, 0)),
                pl.BlockSpec((bs, Dh), lambda i, *_: (i, 0)),
                pl.BlockSpec((bs, Dv), lambda i, *_: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((L, L), lambda i, *_: (0, 0)),
                pl.BlockSpec((L, Dv), lambda i, *_: (0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((L, 1), jnp.float32),
                pltpu.VMEM((L, 1), jnp.float32),
                pltpu.VMEM((L, Dv), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((L, L), jnp.float32),
            jax.ShapeDtypeStruct((L, Dv), jnp.float32),
        ),
        interpret=interpret,
        name="landmark_stats",
    )(nv, qt, kt, k, v)
