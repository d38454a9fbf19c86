"""Pallas TPU kernels: vectorized gather→GEMM accumulation-sketch application.

The accumulation sketch S = Σ_i S_(i) has m non-zeros per column, described by
``idx``/``coef`` of shape (m, d).  The kernels turn the sparse application
into dense GEMMs the MXU can chew on:

  1. per grid step, materialize the (bn, bd) *coefficient block* of S for the
     step's K columns in VMEM by comparing a broadcasted row-iota against the
     (m, bd) index rows of the output column block (one-hot build: m
     vectorized compares, no scatter);
  2. contract the (bm, bn) K tile with that block (``f32_dot``, f32 precision)
     into an f32 VMEM accumulator; the K columns (the contraction axis) are
     the innermost grid axis, so VMEM holds one K tile, never a K row.

The idx/coef pieces ride in as ordinary VMEM blocks, so the one-hot block is
built from vectors at any width: d is tiled by bd (a multiple of the 128-lane
tile, or d itself) in every kernel's grid.

``accum_sketch_both`` fuses the two sketch applications of the paper's §3.3,

    C = K S          (n, d)
    W = Sᵀ K S = SᵀC (d, d)

into one sweep over K per output column block: the (d/bd, R/bm, N/bn) grid
accumulates C row-tiles in a f32 VMEM scratch across column chunks, and on
each row-tile's last chunk folds SᵀC into the (d, bd) column block of W,
which stays resident for the block's whole sweep.  This avoids a second pass
over — and a second HBM read of — C.

VMEM budget (f32, bm=256, bn=2048, bd=128): K tile 2 MiB (double-buffered
4 MiB) + one-hot block bn·bd·4 (1 MiB) + the (bm, d) row one-hot and the
(d, bd) W block (1 MiB and 0.5 MiB at d = 1024) + the (bm, bd) accumulator
and output tiles — under the 16 MiB scoped VMEM of a TPU v5e core at any N.
``tests/test_tpu_compile.py`` compiles each kernel for a v5e.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels_math import f32_dot, snap_sqdist


def _coef_block(idx: jax.Array, coef: jax.Array, *, base, nrows: int) -> jax.Array:
    """(nrows, ncols) dense block of S covering S rows [base, base+nrows) and
    the columns described by ``idx``/``coef``, an (m, ncols) piece of the
    sketch loaded from VMEM.

    One-hot build: a broadcasted row-iota is compared against each sub-sketch's
    index row; matches deposit that sub-sketch's coefficient.  Colliding draws
    (same index, same column, different i) sum, exactly like Σ_i S_(i).
    """
    m, ncols = idx.shape
    rid = jax.lax.broadcasted_iota(jnp.int32, (nrows, ncols), 0) + base
    blk = jnp.zeros((nrows, ncols), jnp.float32)
    for i in range(m):
        blk = blk + jnp.where(rid == idx[i:i + 1, :],
                              coef[i:i + 1, :].astype(jnp.float32), 0.0)
    return blk


def _accumulate(acc_ref, part, c) -> None:
    """acc = part on the first contraction chunk, acc += part after it."""
    @pl.when(c == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(c > 0)
    def _accum():
        acc_ref[...] = acc_ref[...] + part


def _sketch_cols_specs(m: int, bd: int, index_map) -> list:
    """BlockSpecs of the (m, bd) idx and coef pieces of one output column
    block."""
    return [pl.BlockSpec((m, bd), index_map)] * 2


# --------------------------------------------------------------------------- #
# K·S — vectorized gather→GEMM
# --------------------------------------------------------------------------- #

def _gemm_kernel(idx_ref, coef_ref, K_ref, out_ref, acc_ref, *, bn: int):
    c = pl.program_id(2)
    # S block for this step's K columns [c·bn, (c+1)·bn) and the output
    # column block's (m, bd) sketch piece: indices outside the chunk never
    # match the offset iota
    sblk = _coef_block(idx_ref[...], coef_ref[...], base=c * bn, nrows=bn)
    _accumulate(acc_ref, f32_dot(K_ref[...].astype(jnp.float32), sblk), c)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bd", "bn", "interpret"))
def accum_apply(
    K: jax.Array, idx: jax.Array, coef: jax.Array, *,
    bm: int = 256, bd: int = 128, bn: int = 2048, interpret: bool = True,
) -> jax.Array:
    """K: (R, N); idx/coef: (m, d). Returns K S (R, d) via MXU GEMM tiles.

    Grid (R/bm, d/bd, N/bn), the contraction chunks innermost: each step
    contracts a (bm, bn) K tile with the matching (bn, bd) one-hot block of S
    into an f32 VMEM accumulator, so VMEM holds a K tile, never a K row.
    Shapes must tile exactly (R % bm == 0, d % bd == 0, N % bn == 0) — the
    ops.py wrappers pad arbitrary shapes."""
    R, N = K.shape
    m, d = idx.shape
    bm, bd, bn = min(bm, R), min(bd, d), min(bn, N)
    assert R % bm == 0 and d % bd == 0 and N % bn == 0, (R, bm, d, bd, N, bn)
    return pl.pallas_call(
        functools.partial(_gemm_kernel, bn=bn),
        grid=(R // bm, d // bd, N // bn),
        in_specs=[*_sketch_cols_specs(m, bd, lambda r, j, c: (0, j)),
                  pl.BlockSpec((bm, bn), lambda r, j, c: (r, c))],
        out_specs=pl.BlockSpec((bm, bd), lambda r, j, c: (r, j)),
        scratch_shapes=[pltpu.VMEM((bm, bd), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((R, d), K.dtype),
        interpret=interpret,
        name="accum_apply",
    )(idx, coef, K)


# --------------------------------------------------------------------------- #
# fused (K·S, Sᵀ·K·S) — one sweep over K per output column block
# --------------------------------------------------------------------------- #

def _both_kernel(idxc_ref, coefc_ref, idx_ref, coef_ref, K_ref, C_ref, W_ref,
                 acc_ref, *, bm: int, bn: int):
    r, c = pl.program_id(1), pl.program_id(2)
    # S chunk for the columns of K in this grid step: S rows [c·bn, (c+1)·bn)
    # of this output column block.  Indices outside the chunk simply never
    # match the offset iota — the column-chunked partial products need no
    # explicit masking.
    scols = _coef_block(idxc_ref[...], coefc_ref[...], base=c * bn, nrows=bn)
    _accumulate(acc_ref, f32_dot(K_ref[...].astype(jnp.float32), scols), c)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        C_tile = acc_ref[...]                                      # (bm, bd)
        C_ref[...] = C_tile.astype(C_ref.dtype)
        # fold this row-tile's contribution Sᵀ_tile · C_tile into W's column
        # block while the tile is still VMEM-resident — no second pass, no
        # HBM re-read of C
        srows = _coef_block(idx_ref[...], coef_ref[...], base=r * bm,
                            nrows=bm)                              # (bm, d)
        _accumulate(W_ref, f32_dot(srows, C_tile, ((0,), (0,))), r)  # (d, bd)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bd", "interpret"))
def accum_sketch_both(
    K: jax.Array, idx: jax.Array, coef: jax.Array, *,
    bm: int = 256, bn: int = 2048, bd: int = 128, interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused (C, W) = (K S, SᵀK S) for (logically square) K.

    Grid (d/bd, R/bm, N/bn), output column blocks outermost and column chunks
    innermost: C row-tiles accumulate over chunks in a f32 scratch; each
    row-tile's last chunk writes its (bm, bd) C tile and folds SᵀC into the
    (d, bd) column block of W, which stays resident across the block's whole
    sweep over K.  K is read d/bd times; d must tile by bd.
    K may arrive rectangular from zero-padding as long as every sketch index
    is < min(R, N) — padded rows of S are all-zero and contribute nothing.
    W is returned in float32 (it feeds a d×d solve, not a matmul chain)."""
    R, N = K.shape
    m, d = idx.shape
    bm, bn, bd = min(bm, R), min(bn, N), min(bd, d)
    assert R % bm == 0 and N % bn == 0 and d % bd == 0, (R, N, d, bm, bn, bd)
    return pl.pallas_call(
        functools.partial(_both_kernel, bm=bm, bn=bn),
        grid=(d // bd, R // bm, N // bn),
        in_specs=[
            *_sketch_cols_specs(m, bd, lambda j, r, c: (0, j)),
            *_sketch_cols_specs(m, d, lambda j, r, c: (0, 0)),
            pl.BlockSpec((bm, bn), lambda j, r, c: (r, c)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bd), lambda j, r, c: (r, j)),
            pl.BlockSpec((d, bd), lambda j, r, c: (0, j)),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bd), jnp.float32)],
        out_shape=(
            jax.ShapeDtypeStruct((R, d), K.dtype),
            jax.ShapeDtypeStruct((d, d), jnp.float32),
        ),
        interpret=interpret,
        name="accum_sketch_both",
    )(idx, coef, idx, coef, K)


# --------------------------------------------------------------------------- #
# Sᵀ·M — true left-apply, M streamed in ROW tiles (no Mᵀ copy)
# --------------------------------------------------------------------------- #

def _left_kernel(idx_ref, coef_ref, M_ref, out_ref, *, bn: int):
    t = pl.program_id(0)
    # dense (bn, d) block of S covering S rows [t·bn, (t+1)·bn): each sketch
    # index lands in exactly one row tile, so the per-tile partial products
    # Sᵀ_tile · M_tile sum to Sᵀ M with no masking
    sblk = _coef_block(idx_ref[...], coef_ref[...], base=t * bn, nrows=bn)
    _accumulate(out_ref, f32_dot(sblk, M_ref[...].astype(jnp.float32),
                              ((0,), (0,))), t)                    # (d, c)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def accum_apply_left(
    M: jax.Array, idx: jax.Array, coef: jax.Array, *,
    bn: int = 2048, interpret: bool = True,
) -> jax.Array:
    """Sᵀ M for M of shape (N, c) → (d, c), streaming M in ROW tiles.

    The transpose-free counterpart of ``accum_apply``: M keeps its row-major
    layout (the layout the row-tiled kernels produce C in), each grid step
    contracts the tile's dense (bn, d) one-hot block of S against the (bn, c)
    M tile, and the (d, c) output is revisited and accumulated across steps —
    the same pattern as the fused kernel's W accumulation.  N must tile by bn
    (the ops.py wrapper pads)."""
    N, c = M.shape
    m, d = idx.shape
    bn = min(bn, N)
    assert N % bn == 0, (N, bn)
    return pl.pallas_call(
        functools.partial(_left_kernel, bn=bn),
        grid=(N // bn,),
        in_specs=[*_sketch_cols_specs(m, d, lambda t: (0, 0)),
                  pl.BlockSpec((bn, c), lambda t: (t, 0))],
        out_specs=pl.BlockSpec((d, c), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, c), jnp.float32),
        interpret=interpret,
        name="accum_apply_left",
    )(idx, coef, M)


# --------------------------------------------------------------------------- #
# single-slab progressive step — C ← a·C + K·T̃ in one fused pass
# --------------------------------------------------------------------------- #

def _step_kernel(a_ref, idx_ref, coef_ref, K_ref, Cin_ref, out_ref, acc_ref,
                 *, bn: int):
    c = pl.program_id(2)
    sblk = _coef_block(idx_ref[...], coef_ref[...], base=c * bn, nrows=bn)
    _accumulate(acc_ref, f32_dot(K_ref[...].astype(jnp.float32), sblk), c)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        rescaled = a_ref[0].astype(jnp.float32) * Cin_ref[...].astype(jnp.float32)
        out_ref[...] = (rescaled + acc_ref[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bd", "bn", "interpret"))
def accum_step_slab(
    K: jax.Array, idx: jax.Array, coef: jax.Array, Cin: jax.Array,
    a: jax.Array, *, bm: int = 256, bd: int = 128, bn: int = 2048,
    interpret: bool = True,
) -> jax.Array:
    """One progressive-accumulation increment: a·Cin + K·T̃ for a SINGLE
    sub-sampling slab (idx/coef of shape (1, d), rescale scalar ``a`` of
    shape (1,) riding in SMEM via scalar prefetch).

    Same gather→GEMM formulation and (R/bm, d/bd, N/bn) reduction grid as
    ``accum_apply`` (the m=1 one-hot block feeds the MXU), with the running
    C's rescale fused into the last chunk's tile write, so the engine's
    m → m+1 step is one kernel launch and one read of C."""
    R, N = K.shape
    _, d = idx.shape
    bm, bd, bn = min(bm, R), min(bd, d), min(bn, N)
    assert R % bm == 0 and d % bd == 0 and N % bn == 0, (R, bm, d, bd, N, bn)
    assert Cin.shape == (R, d), (Cin.shape, R, d)
    return pl.pallas_call(
        functools.partial(_step_kernel, bn=bn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,             # a in SMEM
            grid=(R // bm, d // bd, N // bn),
            in_specs=[
                *_sketch_cols_specs(1, bd, lambda r, j, c, _: (0, j)),
                pl.BlockSpec((bm, bn), lambda r, j, c, _: (r, c)),
                pl.BlockSpec((bm, bd), lambda r, j, c, _: (r, j)),
            ],
            out_specs=pl.BlockSpec((bm, bd), lambda r, j, c, _: (r, j)),
            scratch_shapes=[pltpu.VMEM((bm, bd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((R, d), Cin.dtype),
        interpret=interpret,
        name="accum_step_slab",
    )(a, idx, coef, K, Cin)


# --------------------------------------------------------------------------- #
# batched rank-B progressive growth — B slabs folded in ONE sweep over K
# --------------------------------------------------------------------------- #

def _grow_kernel(a_ref, idxc_ref, coefc_ref, idx_ref, coef_ref, K_ref, Cin_ref,
                 C_ref, TtG_ref, TtC_ref, acc_ref, *, bm: int, bn: int):
    r, c = pl.program_id(1), pl.program_id(2)

    # T chunk for this grid step's K columns: T rows [c·bn, (c+1)·bn) of this
    # output column block.  The B slabs enter as ONE (m=B)-row coefficient
    # block already normalized for the grown size t+B — the per-step
    # sqrt(k/(k+1)) survivor rescales telescope into the single scalar ``a``
    # applied to Cin below.
    scols = _coef_block(idxc_ref[...], coefc_ref[...], base=c * bn, nrows=bn)
    _accumulate(acc_ref, f32_dot(K_ref[...].astype(jnp.float32), scols), c)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        G_tile = acc_ref[...]                                 # K·T row tile
        Cin_tile = Cin_ref[...].astype(jnp.float32)
        C_ref[...] = (a_ref[0].astype(jnp.float32) * Cin_tile
                      + G_tile).astype(C_ref.dtype)
        # fold BOTH W pieces' column blocks while the tiles are VMEM-resident:
        # TᵀK T = Tᵀ(K T) = ΣᵣTᵣᵀ Gᵣ and TᵀC_old = ΣᵣTᵣᵀ Cinᵣ — no second
        # pass over K, G, or C
        trows = _coef_block(idx_ref[...], coef_ref[...], base=r * bm,
                            nrows=bm)                             # (bm, d)
        _accumulate(TtG_ref, f32_dot(trows, G_tile, ((0,), (0,))), r)
        _accumulate(TtC_ref, f32_dot(trows, Cin_tile, ((0,), (0,))), r)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bd", "interpret"))
def accum_grow_slabs(
    K: jax.Array, idx: jax.Array, coef: jax.Array, Cin: jax.Array,
    a: jax.Array, *, bm: int = 256, bn: int = 2048, bd: int = 128,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched rank-B progressive increment in one grid sweep over K per
    output column block:

        C_new = a·Cin + K·T        (n, d)
        TᵀG   = Tᵀ K T             (d, d)   — from the G tiles, in-kernel
        TᵀC   = Tᵀ Cin             (d, d)   — from the Cin tiles, in-kernel

    where T is the B-slab batch block (idx/coef of shape (B, d), coefficients
    normalized for the grown size) and ``a`` the telescoped survivor rescale,
    riding in SMEM via scalar prefetch.  The caller assembles
    W_new = a²·W + a·(TᵀC + TᵀCᵀ) + TᵀG — every W piece comes out of the same
    pass that produced C, so folding B slabs reads K d/bd times whatever B
    (B sequential ``accum_step_slab`` launches read it B·d/bd times).

    Grid (d/bd, R/bm, N/bn), same accumulation scheme as
    ``accum_sketch_both``; K may be rectangular from padding as long as every
    index is < min(R, N)."""
    R, N = K.shape
    m, d = idx.shape
    bm, bn, bd = min(bm, R), min(bn, N), min(bd, d)
    assert R % bm == 0 and N % bn == 0 and d % bd == 0, (R, N, d, bm, bn, bd)
    assert Cin.shape == (R, d), (Cin.shape, R, d)
    return pl.pallas_call(
        functools.partial(_grow_kernel, bm=bm, bn=bn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,             # a in SMEM
            grid=(d // bd, R // bm, N // bn),
            in_specs=[
                *_sketch_cols_specs(m, bd, lambda j, r, c, _: (0, j)),
                *_sketch_cols_specs(m, d, lambda j, r, c, _: (0, 0)),
                pl.BlockSpec((bm, bn), lambda j, r, c, _: (r, c)),
                pl.BlockSpec((bm, bd), lambda j, r, c, _: (r, j)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bd), lambda j, r, c, _: (r, j)),
                pl.BlockSpec((d, bd), lambda j, r, c, _: (0, j)),
                pl.BlockSpec((d, bd), lambda j, r, c, _: (0, j)),
            ],
            scratch_shapes=[pltpu.VMEM((bm, bd), jnp.float32)],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((R, d), Cin.dtype),
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((d, d), jnp.float32),
        ),
        interpret=interpret,
        name="accum_grow_slabs",
    )(a, idx, coef, idx, coef, K, Cin)


# --------------------------------------------------------------------------- #
# matrix-free C = K(X, X)·S — fused kernel-eval → GEMM, K never materialized
# --------------------------------------------------------------------------- #

def _kernel_eval(d2: jax.Array, kernel: str, bandwidth: float, nu: float) -> jax.Array:
    """Elementwise PSD kernel on squared distances, mirroring
    ``core/kernels_math.py`` EXACTLY (same guards, same closed forms) so the
    matrix-free path is bit-compatible with a materialized K."""
    if kernel == "gaussian":
        return jnp.exp(-d2 / (2.0 * bandwidth**2))
    r = jnp.sqrt(d2 + 1e-30)
    if kernel == "laplacian":
        return jnp.exp(-r / bandwidth)
    if kernel == "matern":
        r = r / bandwidth
        if nu == 0.5:
            return jnp.exp(-r)
        if nu == 1.5:
            c = math.sqrt(3.0)
            return (1.0 + c * r) * jnp.exp(-c * r)
        if nu == 2.5:
            c = math.sqrt(5.0)
            return (1.0 + c * r + 5.0 * r * r / 3.0) * jnp.exp(-c * r)
        raise ValueError(f"unsupported nu={nu}")
    raise ValueError(f"unknown kernel {kernel}")


def _matfree_kernel(X_ref, L_ref, cf_ref, out_ref, *, kernel: str,
                    bandwidth: float, nu: float):
    """Grid step (row tile r, sub-sketch i): evaluate the (bm, d) kernel block
    K(X_tile, L_i) in VMEM via the pairwise-sqdist + closed-form formulation
    and fold it into the row tile's output with sub-sketch i's (1, d)
    coefficient row.  Column j of block i only ever meets coefficient
    (i, j), so the combination is an elementwise scale, not a GEMM."""
    i = pl.program_id(1)
    x = X_ref[...].astype(jnp.float32)                             # (bm, p)
    l = L_ref[...].astype(jnp.float32)                             # (d, p)
    x2 = jnp.sum(x * x, axis=1)[:, None]
    l2 = jnp.sum(l * l, axis=1)[None, :]
    d2 = snap_sqdist(x2, l2, f32_dot(x, l, ((1,), (1,))))
    part = (_kernel_eval(d2, kernel, bandwidth, nu)
            * cf_ref[pl.ds(i, 1), :].astype(jnp.float32))         # (bm, d)
    _accumulate(out_ref, part, i)


@functools.partial(
    jax.jit, static_argnames=("kernel", "bandwidth", "nu", "bm", "interpret"))
def matfree_apply(
    X: jax.Array, L: jax.Array, coef: jax.Array, *, kernel: str,
    bandwidth: float = 1.0, nu: float = 1.5, bm: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """C = Σ_i K(X, L_i)·diag(coef_i) without materializing any n×n object.

    X: (n, p) query rows; L: (m, d, p) landmark rows (the sketch's sampled
    points, L[i, j] = X_train[idx[i, j]]); coef: (m, d) combination
    coefficients (a zero coefficient drops its landmark whatever its kernel
    value).  Grid (n/bm, m), sub-sketches innermost: VMEM holds one (bm, p)
    row tile, ONE sub-sketch's (d, p) landmarks and a few (bm, d) slabs —
    independent of n and of m.

    n must tile by bm (the ops.py wrapper pads); returns (n, d) f32."""
    n, p = X.shape
    m, d = coef.shape
    assert L.shape == (m, d, p), (L.shape, m, d, p)
    bm = min(bm, n)
    assert n % bm == 0, (n, bm)
    return pl.pallas_call(
        functools.partial(_matfree_kernel, kernel=kernel, bandwidth=bandwidth,
                          nu=nu),
        grid=(n // bm, m),
        in_specs=[
            pl.BlockSpec((bm, p), lambda r, i: (r, 0)),
            pl.BlockSpec((None, d, p), lambda r, i: (i, 0, 0)),
            pl.BlockSpec((m, d), lambda r, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, d), lambda r, i: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
        name="matfree_apply",
    )(X, L, coef)
