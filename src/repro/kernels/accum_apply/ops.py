"""Public entry points for the accum_apply kernel family.

This layer makes the kernels shape- and backend-agnostic:

  * ``interpret`` defaults to backend autodetection — compiled Mosaic on TPU,
    interpreter everywhere else (CPU CI, tests).
  * block sizes come from a MEASURED autotune cache: the first eager call at
    a (shape, dtype, backend) key times candidate tilings on the caller's
    real arrays and persists the winner to ``REPRO_AUTOTUNE_CACHE`` (default
    ``~/.cache/repro/autotune.json``); jitted/traced calls and disabled or
    corrupt caches fall back to a fixed heuristic per entry point;
  * arbitrary shapes are zero-padded up to the block grid and sliced back
    (padded K rows/columns contribute nothing; padded sketch columns carry
    coef 0);
  * K's columns (the contraction axis) are tiled inside the kernels' grids,
    so any width is one pallas_call with a VMEM-sized K tile
    (``_col_block``);
  * ``sketch_both_kernel`` exposes the fused (K S, SᵀK S) single-sweep kernel,
    ``sketch_left_kernel`` applies Sᵀ M through the true left-apply kernel
    (M streamed in row tiles — no Mᵀ copy);
  * ``sketch_step_kernel`` is the single-slab accumulate entry point used by
    the progressive engine: a·C + K·T̃ in one fused launch (MXU path for the
    m → m+1 increment);
  * ``accum_grow_kernel`` is the BATCHED rank-B accumulate entry point:
    a·C + K·T for a B-slab batch block plus both d×d W pieces (TᵀKT, TᵀC)
    folded from the SAME single sweep over K — the engine's m → m+B growth
    reads K once instead of B times.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sketch import AccumSketch
from repro.kernels.accum_apply import autotune
from repro.resilience import faults
from repro.kernels.accum_apply.kernel import (
    accum_apply,
    accum_apply_left,
    accum_grow_slabs,
    accum_sketch_both,
    accum_step_slab,
    matfree_apply,
)
from repro.util import env_flag

COL_TILE_BYTES = 2 * 1024 * 1024   # one (bm, bn) K tile; double-buffered 4 MiB
LANES = 128                        # the lane tile: output column blocks are
                                   # d itself or a multiple of it


def default_interpret() -> bool:
    """False (compiled Mosaic) on TPU, True (interpreter) elsewhere.

    Overridable with REPRO_PALLAS_INTERPRET=0/1 for A/B runs."""
    return env_flag("REPRO_PALLAS_INTERPRET", jax.default_backend() != "tpu")


def autotune_blocks(R: int, N: int, d: int, m: int, dtype,
                    *, interpret: bool | None = None) -> tuple[int, int]:
    """(bm, bd) for the gather→GEMM kernels: measured-cache hit, else
    (256, min(d, LANES)).

    This is the lookup side only — it never times anything, so it is safe at
    trace time.  The entry points below measure candidate tilings on their
    real (concrete) arrays via ``autotune.measured_blocks`` and persist the
    winner, which this lookup then serves to every later (including jitted)
    call at the same (shape, dtype, backend) key.  The K column chunk that
    bounds VMEM is derived from bm (``_col_block``), so no (bm, bd) pair can
    overflow VMEM at any N."""
    if interpret is None:
        interpret = default_interpret()
    hit = autotune.lookup("accum_apply", (R, N, d, m), dtype, interpret,
                          arity=2)
    if hit is not None:
        return hit
    return min(256, R), min(d, LANES)


def _col_block(N: int, bm: int, itemsize: int) -> int:
    """K column chunk bn for a bm-row tile: the (bm, bn) tile stays within
    ``COL_TILE_BYTES`` (bn a multiple of 128 lanes); narrow K is one chunk."""
    bn = max(128, COL_TILE_BYTES // (bm * itemsize) // 128 * 128)
    return N if N <= bn else bn


def _gemm_candidates(R: int, d: int, fallback: tuple[int, int]) -> list[tuple[int, int]]:
    """Candidate (bm, bd) tilings for the gather→GEMM family: the fallback
    plus a taller and a shorter row tile, and a wider lane tile.  bd is d
    itself or a multiple of the 128-lane tile — the only output blocks
    Mosaic accepts."""
    bds = {fallback[1], min(d, 128), min(d, 256)}
    bms = {fallback[0], min(R, 128), min(R, 512)}
    return [(bm, bd) for bm in sorted(bms) for bd in sorted(bds)][:6]


def _pad_rows(K: jax.Array, mult: int) -> jax.Array:
    pad = (-K.shape[0]) % mult
    return jnp.pad(K, ((0, pad), (0, 0))) if pad else K


def _pad_sketch(idx: jax.Array, coef: jax.Array, mult: int):
    """Pad sketch columns to a multiple of ``mult`` with idx 0 / coef 0 —
    zero-coefficient columns gather nothing and are sliced off the output."""
    pad = (-idx.shape[1]) % mult
    if pad:
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
        coef = jnp.pad(coef, ((0, 0), (0, pad)))
    return idx, coef


def _pad_cols(K: jax.Array, mult: int) -> jax.Array:
    pad = (-K.shape[1]) % mult
    return jnp.pad(K, ((0, 0), (0, pad))) if pad else K


def _apply_padded(K, idx, coef, *, bm, bd, interpret):
    """accum_apply on arbitrary (R, N, d): pad to the block grid, slice back
    (padded K columns are never indexed by the sketch)."""
    R, N = K.shape
    d = idx.shape[1]
    bm_e = min(bm, R)
    bd_e = min(bd, d)
    bn = _col_block(N, bm_e, K.dtype.itemsize)
    Kp = _pad_cols(_pad_rows(K, bm_e), bn)
    idx_p, coef_p = _pad_sketch(idx, coef, bd_e)
    out = accum_apply(Kp, idx_p, coef_p, bm=bm_e, bd=bd_e, bn=bn,
                      interpret=interpret)
    return out[:R, :d]


def sketch_right_kernel(
    K: jax.Array, sk: AccumSketch, *, bm: int | None = None,
    bd: int | None = None, interpret: bool | None = None,
) -> jax.Array:
    """K S via the Pallas kernel — one pallas_call at any width of K (the
    kernel's grid tiles the contraction axis)."""
    faults.fault_point("kernel.dispatch")
    if interpret is None:
        interpret = default_interpret()
    R, N = K.shape
    m, d = sk.indices.shape
    coef = sk.coef.astype(jnp.float32)
    if bm is None and bd is None:
        fb = autotune_blocks(R, N, d, m, K.dtype, interpret=interpret)
        bm, bd = autotune.measured_blocks(
            "accum_apply", (R, N, d, m), K.dtype, interpret,
            _gemm_candidates(R, d, fb),
            lambda c: _apply_padded(K, sk.indices, coef, bm=c[0], bd=c[1],
                                    interpret=interpret),
            fb, concrete=autotune.is_concrete(K, sk.indices, coef))
    else:
        a_bm, a_bd = autotune_blocks(R, N, d, m, K.dtype, interpret=interpret)
        bm = a_bm if bm is None else bm
        bd = a_bd if bd is None else bd
    return _apply_padded(K, sk.indices, coef, bm=bm, bd=bd, interpret=interpret)


def sketch_left_kernel(
    sk: AccumSketch, M: jax.Array, *, bn: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Sᵀ M (d, c) via the true left-apply kernel, M streamed in row tiles.

    The earlier implementation computed (Mᵀ S)ᵀ, materializing Mᵀ — an
    O(n·c) transposed copy in a column-major layout the row-tiled kernel was
    never tuned for.  ``accum_apply_left`` keeps M row-major and accumulates
    the (d, c) output across row tiles instead.  Returns float32 (the output
    feeds d×d solves)."""
    faults.fault_point("kernel.dispatch")
    if interpret is None:
        interpret = default_interpret()
    N, c = M.shape
    d = sk.d
    coef = sk.coef.astype(jnp.float32)
    if bn is None:
        # the (bn, c) M tile and the (bn, d) one-hot block of S within
        # ~2 MiB of f32 VMEM; the interpreter wants few large steps (per-step
        # dispatch dominates there)
        bn = min(4096 if interpret else 2048,
                 max(8, COL_TILE_BYTES // (4 * (c + d)) // 8 * 8))
    bn_e = min(bn, N)
    Mp = _pad_rows(M, bn_e)
    idx_p, coef_p = _pad_sketch(sk.indices, coef, min(8, max(d, 1)))
    out = accum_apply_left(Mp, idx_p, coef_p, bn=bn_e, interpret=interpret)
    return out[:d]


def sketch_step_kernel(
    K: jax.Array, idx_row: jax.Array, coef_row: jax.Array, C: jax.Array,
    a: jax.Array, *, bm: int | None = None, bd: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-slab accumulate entry point: a·C + K·T̃ for one sub-sampling
    matrix described by ``idx_row``/``coef_row`` of shape (d,).

    The progressive engine's m → m+1 increment routes here so the column
    gather hits the MXU gather→GEMM path with the running C's rescale fused
    in.  Arbitrary shapes are padded to the block grid and sliced back."""
    if interpret is None:
        interpret = default_interpret()
    R, N = K.shape
    d = idx_row.shape[0]
    a_bm, a_bd = autotune_blocks(R, N, d, 1, K.dtype, interpret=interpret)
    bm_e = min(a_bm if bm is None else bm, R)
    bd_e = min(a_bd if bd is None else bd, d)
    bn = _col_block(N, bm_e, K.dtype.itemsize)
    Kp = _pad_cols(_pad_rows(K, bm_e), bn)
    Cp = _pad_rows(C, bm_e)
    idx_p, coef_p = _pad_sketch(idx_row[None, :].astype(jnp.int32),
                                coef_row.astype(jnp.float32)[None, :], bd_e)
    dpad = idx_p.shape[1] - d
    if dpad:
        Cp = jnp.pad(Cp, ((0, 0), (0, dpad)))
    a_arr = jnp.asarray(a, jnp.float32).reshape((1,))
    out = accum_step_slab(Kp, idx_p, coef_p, Cp, a_arr, bm=bm_e, bd=bd_e,
                          bn=bn, interpret=interpret)
    return out[:R, :d]


def accum_grow_kernel(
    K: jax.Array, idx_blk: jax.Array, coef_blk: jax.Array, C: jax.Array,
    a: jax.Array, *, bm: int | None = None, bn: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched rank-B accumulate entry point: fold the B-slab batch block
    (idx/coef of shape (B, d), coefficients at the grown normalization) into
    the running C in ONE sweep over K, returning ``(C_new, TᵀG, TᵀC)`` with
    C_new = a·C + K·T and both d×d W pieces folded from the same pass —
    K is read once for all B slabs where B sequential ``sketch_step_kernel``
    calls read it B times.

    Arbitrary (R, N, d) are padded to the block grid and sliced back (padded
    rows/columns of K are zero and padded sketch columns carry coefficient 0,
    so every output is exact).  Block sizes come from the measured autotune
    cache when available."""
    if interpret is None:
        interpret = default_interpret()
    R, N = K.shape
    B, d = idx_blk.shape
    coef32 = coef_blk.astype(jnp.float32)
    a_arr = jnp.asarray(a, jnp.float32).reshape((1,))
    idx32 = idx_blk.astype(jnp.int32)

    def run(blocks):
        bm_e, bn_e = min(blocks[0], R), min(blocks[1], N)
        rpad, cpad = (-R) % bm_e, (-N) % bn_e
        Kp = jnp.pad(K, ((0, rpad), (0, cpad))) if (rpad or cpad) else K
        idx_p, coef_p = _pad_sketch(idx32, coef32, min(d, LANES))
        dpad = idx_p.shape[1] - d
        Cp = _pad_rows(C, bm_e)
        if dpad:
            Cp = jnp.pad(Cp, ((0, 0), (0, dpad)))
        Cn, TtG, TtC = accum_grow_slabs(Kp, idx_p, coef_p, Cp, a_arr,
                                        bm=bm_e, bn=bn_e, interpret=interpret)
        return Cn[:R, :d], TtG[:d, :d], TtC[:d, :d]

    if bm is None and bn is None:
        fb = autotune_both_blocks(N, interpret)
        bm, bn = autotune.measured_blocks(
            "accum_grow", (R, N, d, B), K.dtype, interpret,
            [fb, (256, min(N, 2048)), (min(R, 1024), min(N, 4096))],
            run, fb, concrete=autotune.is_concrete(K, idx_blk, coef_blk, C))
    else:
        fb = autotune_both_blocks(N, interpret)
        bm = fb[0] if bm is None else bm
        bn = fb[1] if bn is None else bn
    return run((bm, bn))


def matfree_cols_kernel(
    Xq: jax.Array, landmarks: jax.Array, coef: jax.Array, *, kernel: str,
    bandwidth: float = 1.0, nu: float = 1.5, bm: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """C = K(Xq, X)·S straight from data rows via the fused Pallas kernel —
    each sub-sketch's (tile, d) kernel block is evaluated in VMEM and scaled
    by its coefficients in the same grid step; no n×n object ever exists.

    Xq: (nq, p) query rows; landmarks: (m·d, p) sampled rows X[sk.indices];
    coef: (m, d).  Arbitrary nq is row-padded to the tile and sliced back.
    Returns (nq, d) float32."""
    faults.fault_point("kernel.dispatch")
    if interpret is None:
        interpret = default_interpret()
    nq, p = Xq.shape
    m, d = coef.shape
    L = landmarks.reshape(m, d, p)

    def run(blocks):
        bm_e = min(blocks[0], nq)
        Xp = _pad_rows(Xq, bm_e)
        out = matfree_apply(Xp, L, coef, kernel=kernel, bandwidth=bandwidth,
                            nu=nu, bm=bm_e, interpret=interpret)
        return out[:nq]

    if bm is None:
        # heuristic fallback: the few live f32 (bm, d) slabs ≲ 2 MiB of VMEM,
        # bm a multiple of the 8-row sublane tile; measuring tries taller
        # tiles and skips the ones the compiler refuses
        fb = min(512, max(8, (128 * 1024) // max(d, 1) // 8 * 8))
        (bm,) = autotune.measured_blocks(
            "matfree_cols", (nq, p, d, m, kernel), Xq.dtype, interpret,
            [(min(nq, b),) for b in (fb, 2 * fb, 4 * fb)], run, (fb,),
            concrete=autotune.is_concrete(Xq, landmarks, coef))
    return run((bm,))


def autotune_both_blocks(n: int, interpret: bool, d: int = 0, m: int = 0,
                         dtype=jnp.float32) -> tuple[int, int]:
    """(bm, bn) for the fused single-sweep kernels: measured-cache hit first
    (when ``d``/``m`` identify the shape), else the PR-1 defaults — compiled
    TPU wants VMEM-sized tiles (bm·bn·4B ≤ 2 MiB); the interpreter wants few,
    large grid steps (per-step dispatch dominates there — measured 3–4× on
    the CPU benchmark host)."""
    if d and m:
        hit = autotune.lookup("sketch_both", (n, d, m), dtype, interpret,
                              arity=2)
        if hit is not None:
            return hit
    if interpret:
        return min(2048, n), min(4096, n)
    return 256, 2048


def sketch_both_kernel(
    K: jax.Array, sk: AccumSketch, *, bm: int | None = None,
    bn: int | None = None, interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused (C, W) = (K S, SᵀK S) in one sweep over square K (n, n).

    W accumulates across grid steps in the kernel — no second pass over C and
    no second HBM read. Arbitrary n and d are padded to the block grid (padded
    S rows are never indexed, so W is exact) and sliced back. W is float32."""
    faults.fault_point("kernel.dispatch")
    if interpret is None:
        interpret = default_interpret()
    n, n2 = K.shape
    assert n == n2, "sketch_both_kernel expects square K"
    d = sk.d
    coef = sk.coef.astype(jnp.float32)
    idx_p, coef_p = _pad_sketch(sk.indices, coef, min(d, LANES))

    def run(blocks):
        bm_e, bn_e = min(blocks[0], n), min(blocks[1], n)
        # pad rows and columns of K to the (bm, bn) grid
        rpad, cpad = (-n) % bm_e, (-n) % bn_e
        Kp = jnp.pad(K, ((0, rpad), (0, cpad))) if (rpad or cpad) else K
        C, W = accum_sketch_both(Kp, idx_p, coef_p, bm=bm_e, bn=bn_e,
                                 interpret=interpret)
        return C[:n, :d], W[:d, :d]

    if bm is None and bn is None:
        fb = autotune_both_blocks(n, interpret, d, sk.m, K.dtype)
        blocks = autotune.measured_blocks(
            "sketch_both", (n, d, sk.m), K.dtype, interpret,
            [fb, (256, min(n, 2048)), (min(n, 1024), min(n, 4096))], run, fb,
            concrete=autotune.is_concrete(K, sk.indices, coef))
    else:
        fb = autotune_both_blocks(n, interpret, d, sk.m, K.dtype)
        blocks = (fb[0] if bm is None else bm, fb[1] if bn is None else bn)
    return run(blocks)
