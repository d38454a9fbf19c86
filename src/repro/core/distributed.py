"""Multi-device sharded sketching: row-shard X (and C) over a 1-D data mesh.

The paper's pitch is that accumulation pins the *effective* matrix size at
d×d while n grows without bound — but every earlier path computed C = K·S on
a single device, capping n at one host's memory.  This layer removes that cap
with a ``shard_map``-based data-parallel decomposition over a ``("data",)``
mesh:

  * X (n, p) and C (n, d) are sharded along rows; each device computes its
    (n/D, d) tile of C through the EXISTING backends (the fused Pallas
    kernel-eval→GEMM kernel or the ``lax.scan`` streaming path) with the
    m·d landmark rows and combination coefficients replicated — kernel
    evaluations never cross devices;
  * every n-reduction — W = SᵀC, CᵀC / Cᵀy in the KRR solvers, the holdout
    row gathers, the Hutchinson probe contractions, and the progressive
    engine's T̃ᵀC piece — reduces with a ``psum`` over the data axis; only
    d-vectors and d×d blocks ever cross devices;
  * sketch CONSTRUCTION is untouched: indices/signs/probs are drawn exactly
    as on one device (replicated RNG), so the sharded paths produce bitwise
    identical index draws to the single-device ones — dense ≡ sharded
    equivalence is a reduction-order question only (≤ 1e-5 rel, pinned by
    ``tests/test_distributed.py``).

Row counts that do not divide the mesh are zero-padded up to it; padded C
rows are masked to exact zeros inside the mapped bodies (so downstream psum
reductions are exact) and sliced off at the public boundary.

Entry points are threaded through the usual dispatchers — pass ``mesh=`` (a
``jax.sharding.Mesh`` with a ``"data"`` axis, ``True`` for one over all
devices, or an int device count) to ``apply.sketch_both``, the engine
(``accum_step`` / ``accum_grow*`` / ``grow_sketch_both``), the estimator
factories, ``krr_sketched_fit*``, and ``spectral_cluster``.  Force D local
devices on CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=D``
(before the first jax import), as the CI leg and
``benchmarks/distributed_bench.py`` do.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.streams import HOLDOUT_STREAM as _HOLDOUT_STREAM
from repro.core import apply as A
from repro.core.kernel_op import (
    KernelOperator,
    _scan_row_chunks,
    stream_cols,
    stream_cols_slabs,
)
from repro.core.kernels_math import f32_einsum, f32_gram, f32_matmul
from repro.core.sketch import AccumSketch, AccumState

DATA_AXIS = "data"


_shard_map = functools.partial(jax.shard_map, check_vma=False)


# --------------------------------------------------------------------------- #
# mesh plumbing
# --------------------------------------------------------------------------- #

def make_data_mesh(num_devices: int | None = None) -> Mesh:
    """1-D ``("data",)`` mesh over the first ``num_devices`` devices (all by
    default)."""
    devs = jax.devices()
    num = len(devs) if num_devices is None else num_devices
    if num > len(devs):
        raise ValueError(
            f"data mesh needs {num} devices, found {len(devs)} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={num} before "
            "the first jax import to emulate them on CPU")
    return Mesh(np.asarray(devs[:num]), (DATA_AXIS,))


def resolve_mesh(mesh) -> Mesh:
    """Normalize the ``mesh=`` argument the dispatchers accept: ``True`` →
    a data mesh over all devices, a positive int → over that many, a
    ``Mesh`` → itself (must carry a ``"data"`` axis).  ``False``/``0`` are
    rejected explicitly — the dispatchers gate on ``mesh is not None``, so
    the unsharded path is ``mesh=None``, and silently building an empty mesh
    would crash with an opaque division error deep in the padding."""
    if mesh is True:
        return make_data_mesh()
    if isinstance(mesh, bool):
        raise ValueError("mesh=False is not a disable switch — pass "
                         "mesh=None for the unsharded path")
    if isinstance(mesh, int):
        if mesh < 1:
            raise ValueError(f"mesh device count must be ≥ 1, got {mesh}")
        return make_data_mesh(mesh)
    if isinstance(mesh, Mesh):
        if DATA_AXIS not in mesh.axis_names:
            raise ValueError(
                f"mesh {mesh.axis_names} has no '{DATA_AXIS}' axis")
        return mesh
    raise TypeError(f"mesh must be True, an int, or a Mesh; got {mesh!r}")


def _data_size(mesh: Mesh) -> int:
    return mesh.shape[DATA_AXIS]


def shard_rows(arr: jax.Array, mesh: Mesh) -> jax.Array:
    """Place ``arr`` row-sharded over the data axis (benchmarks; the mapped
    entry points reshard their inputs as needed, so this is never required
    for correctness)."""
    spec = P(DATA_AXIS, *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


def _padded_rows(n: int, D: int) -> int:
    return -(-n // D) * D


def _pad_to(arr: jax.Array, total: int) -> jax.Array:
    pad = total - arr.shape[0]
    if pad == 0:
        return arr
    return jnp.pad(arr, ((0, pad),) + ((0, 0),) * (arr.ndim - 1))


def _operator_required(K) -> KernelOperator:
    op = A._operator(K)
    if op is None:
        raise ValueError(
            "mesh= sharding requires a matrix-free KernelOperator — a dense "
            "(n, n) K already fits on one device, which is the regime "
            "sharding exists to escape")
    return op


# --------------------------------------------------------------------------- #
# reduction primitives: gathers / grams over row-sharded arrays
# --------------------------------------------------------------------------- #

def sharded_take_rows(M: jax.Array, idx: jax.Array, mesh: Mesh) -> jax.Array:
    """M[idx] (|idx|, c) for row-sharded M: each device contributes the rows
    it owns (masked local gather), summed with a psum — the data-dependent
    gather SPMD propagation would otherwise realize by replicating M."""
    mesh = resolve_mesh(mesh)
    D = _data_size(mesh)
    N = M.shape[0]
    rows = _padded_rows(N, D) // D
    Mp = _pad_to(M, rows * D)

    def body(mb, ib):
        lo = jax.lax.axis_index(DATA_AXIS) * rows
        inside = (ib >= lo) & (ib < lo + rows)
        local = jnp.where(inside, ib - lo, 0)
        r = jnp.take(mb, local, axis=0) * inside[:, None].astype(mb.dtype)
        return jax.lax.psum(r, DATA_AXIS)

    return _shard_map(
        body, mesh=mesh, in_specs=(P(DATA_AXIS, None), P(None)),
        out_specs=P(None, None))(Mp, idx)


def sharded_gram(Am: jax.Array, Bm: jax.Array, mesh: Mesh) -> jax.Array:
    """Aᵀ B (x, y) for row-sharded A (N, x), B (N, y): per-device partial
    grams psum-reduced — the N-sized contraction never leaves its shard."""
    mesh = resolve_mesh(mesh)
    D = _data_size(mesh)
    assert Am.shape[0] == Bm.shape[0], (Am.shape, Bm.shape)
    total = _padded_rows(Am.shape[0], D)
    Ap, Bp = _pad_to(Am, total), _pad_to(Bm, total)

    def body(ab, bb):
        hi, lo = f32_gram(ab, bb, parts=True)
        return jax.lax.psum(hi, DATA_AXIS) + jax.lax.psum(lo, DATA_AXIS)

    return _shard_map(
        body, mesh=mesh, in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=P(None, None))(Ap, Bp)


def sharded_sketch_left(sk: AccumSketch, M: jax.Array, mesh: Mesh) -> jax.Array:
    """W = Sᵀ M (d, c) for row-sharded M: the m·d landmark rows are gathered
    shard-locally and psum-reduced, then contracted with the (replicated)
    combination coefficients."""
    rows = sharded_take_rows(M, sk.indices.reshape(-1), mesh)       # (m·d, c)
    rows = rows.reshape(sk.m, sk.d, M.shape[-1])
    return f32_einsum("mdc,md->dc", rows, sk.coef.astype(rows.dtype))


# --------------------------------------------------------------------------- #
# sharded C = K(·)·S — per-device tiles through the existing backends
# --------------------------------------------------------------------------- #

def _tile_cols_fn(op: KernelOperator, use_kernel: bool, chunk: int | None,
                  *, slabwise: bool = False):
    """(X_tile, landmarks, coef) → C_tile through the backend the
    single-device path would use (Pallas kernel-eval→GEMM or scanned jnp).
    ``slabwise`` routes multi-slab blocks through ``stream_cols_slabs`` —
    the batched engine's narrow-GEMM accumulation — instead of the wide
    slab (the Pallas path keeps the wide block either way)."""
    kf = op.kernel_fn

    def tile(xb, lm, coef):
        if use_kernel:
            from repro.kernels.accum_apply.ops import matfree_cols_kernel
            return matfree_cols_kernel(xb, lm, coef, kernel=op.kernel,
                                       bandwidth=op.bandwidth, nu=op.nu)
        if slabwise and coef.shape[0] > 1:
            return stream_cols_slabs(xb, lm, coef, kf,
                                     chunk=None if chunk is None
                                     else min(chunk, xb.shape[0]))
        return stream_cols(xb, lm, coef, kf,
                           chunk=None if chunk is None
                           else min(chunk, xb.shape[0]))

    return tile


def sharded_weighted_cols(
    op: KernelOperator, Xq: jax.Array, idx: jax.Array, coef: jax.Array,
    mesh: Mesh, *, chunk: int | None = None, use_kernel: bool | None = None,
) -> jax.Array:
    """K(Xq, ·)·S (nq, d) with Xq row-sharded over the data mesh — the
    sharded core primitive behind C, prediction, and the engine's slabs.
    Landmarks ride replicated; each device evaluates only its tile's kernel
    block."""
    mesh = resolve_mesh(mesh)
    D = _data_size(mesh)
    if use_kernel is None:
        use_kernel = A.default_use_kernel()
    nq = Xq.shape[0]
    rows = _padded_rows(nq, D) // D
    if chunk is None:
        # slab-size budget, independent of the per-device row count — gating
        # on rows would re-disable streaming for exactly the large-n
        # workloads sharding spreads below the row threshold
        chunk = op._auto_chunk(idx.size)
    lm = jnp.take(op.X, idx.reshape(-1), axis=0)
    tile = _tile_cols_fn(op, use_kernel, chunk)

    def body(xb, lm_, cf):
        return tile(xb, lm_, cf)

    C = _shard_map(
        body, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(None, None), P(None, None)),
        out_specs=P(DATA_AXIS, None))(_pad_to(Xq, rows * D), lm, coef)
    return C[:nq] if rows * D != nq else C


def sharded_sketch_both(
    op: KernelOperator, sk: AccumSketch, mesh: Mesh, *,
    chunk: int | None = None, use_kernel: bool | None = None,
    padded: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(C, W) = (K S, SᵀK S) in ONE mapped launch: each device computes its
    C tile locally, gathers the landmark rows it owns, and W arrives as a
    psum of the per-shard SᵀC partials — no second pass over C.

    ``padded`` returns C with its ⌈n/D⌉·D rows (the padded rows exact zeros),
    row-sharded: one (⌈n/D⌉, d) tile per device.  The public (n, d) C is
    replicated on every device when D does not divide n (JAX shards a
    dimension only evenly), so callers that reduce C further keep it
    padded."""
    mesh = resolve_mesh(mesh)
    D = _data_size(mesh)
    if use_kernel is None:
        use_kernel = A.default_use_kernel()
    n = op.n
    rows = _padded_rows(n, D) // D
    m, d = sk.indices.shape
    if chunk is None:
        chunk = op._auto_chunk(sk.indices.size)    # slab budget, as above
    lm = jnp.take(op.X, sk.indices.reshape(-1), axis=0)
    coef = sk.coef
    tile = _tile_cols_fn(op, use_kernel, chunk)

    def body(xb, lm_, cf, idx_flat):
        lo = jax.lax.axis_index(DATA_AXIS) * rows
        cb = tile(xb, lm_, cf)
        # padded global rows → exact zeros (they are sliced off the public C,
        # but the W gather and any later reduction must not see garbage)
        live = (lo + jnp.arange(rows)) < n
        cb = jnp.where(live[:, None], cb, 0)
        inside = (idx_flat >= lo) & (idx_flat < lo + rows)
        local = jnp.where(inside, idx_flat - lo, 0)
        crows = jnp.take(cb, local, axis=0) * inside[:, None].astype(cb.dtype)
        Wp = f32_einsum("mdc,md->dc", crows.reshape(m, d, d),
                        cf.astype(crows.dtype))
        return cb, jax.lax.psum(Wp, DATA_AXIS)

    C, W = _shard_map(
        body, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(None, None), P(None, None), P(None)),
        out_specs=(P(DATA_AXIS, None), P(None, None)))(
            _pad_to(op.X, rows * D), lm, coef, sk.indices.reshape(-1))
    return (C[:n] if rows * D != n and not padded else C), W


def sharded_matvec(
    op: KernelOperator, Z: jax.Array, mesh: Mesh, *, chunk: int | None = None,
) -> jax.Array:
    """K @ Z with the output rows sharded: each device streams kernel evals
    of its X tile against the replicated X (O(rows·n) peak per device).
    Only the Hutchinson probe precompute needs this."""
    mesh = resolve_mesh(mesh)
    D = _data_size(mesh)
    n = op.n
    rows = _padded_rows(n, D) // D
    Zm = Z[:, None] if Z.ndim == 1 else Z
    Xp = _pad_to(op.X, rows * D)
    Zp = _pad_to(Zm.astype(jnp.float32), rows * D)  # zero rows kill padded cols
    if chunk is None:
        chunk = max(8, (4 * 1024 * 1024) // max(rows * D, 1))
    kf = op.kernel_fn

    def body(xb, Xall, Zall):
        def blk(xc):
            return f32_matmul(kf(xc, Xall).astype(jnp.float32), Zall)

        out = _scan_row_chunks(xb, min(chunk, xb.shape[0]), blk)
        lo = jax.lax.axis_index(DATA_AXIS) * rows
        live = (lo + jnp.arange(rows)) < n
        return jnp.where(live[:, None], out, 0.0)

    out = _shard_map(
        body, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(None, None), P(None, None)),
        out_specs=P(DATA_AXIS, None))(Xp, Xp, Zp)
    out = out[:n] if rows * D != n else out
    return out[:, 0] if Z.ndim == 1 else out


# --------------------------------------------------------------------------- #
# progressive engine: sharded incremental slabs
# --------------------------------------------------------------------------- #

def _pad_engine(op: KernelOperator, state: AccumState, mesh: Mesh):
    """Pad X and the running C up to the mesh once per grow call (the loop
    then runs pad-free); returns (padded operator, padded state)."""
    D = _data_size(mesh)
    total = _padded_rows(op.n, D)
    if total == op.n:
        return op, state
    opp = KernelOperator(_pad_to(op.X, total), op.kernel, op.bandwidth, op.nu)
    return opp, dataclasses.replace(state, C=_pad_to(state.C, total))


def _unpad_state(state: AccumState, n: int) -> AccumState:
    if state.C.shape[0] == n:
        return state
    return dataclasses.replace(state, C=state.C[:n])


def _sharded_step(opp: KernelOperator, state: AccumState, mesh: Mesh,
                  use_kernel: bool, n_real: int) -> AccumState:
    """One m → m+1 slab on pre-padded (X, C) — the same arithmetic as
    ``apply.accum_step`` with the column block computed per-shard and the
    T̃ᵀC gather psum-reduced."""
    D = _data_size(mesh)
    rows = opp.n // D
    t = state.m
    # same normalization/recurrence as apply.accum_step, via the shared
    # helpers — only the n-sized pieces differ (per-shard tile + psum gather)
    idx_new, coef_new, a = A.slab_pieces(state)
    Ksub = opp.submatrix(idx_new, idx_new)
    lm = jnp.take(opp.X, idx_new, axis=0)
    tile = _tile_cols_fn(opp, use_kernel, None)

    def body(xb, cb, lm_, cf, idx_, a_):
        lo = jax.lax.axis_index(DATA_AXIS) * rows
        g = tile(xb, lm_, cf[None, :]).astype(jnp.float32)
        live = (lo + jnp.arange(rows)) < n_real
        g = jnp.where(live[:, None], g, 0.0)
        c_new = a_ * cb + g
        inside = (idx_ >= lo) & (idx_ < lo + rows)
        local = jnp.where(inside, idx_ - lo, 0)
        crows = jnp.take(cb, local, axis=0) * inside[:, None].astype(cb.dtype)
        return c_new, jax.lax.psum(crows, DATA_AXIS)

    C_new, Crows = _shard_map(
        body, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None), P(None, None),
                  P(None), P(None), P()),
        out_specs=(P(DATA_AXIS, None), P(None, None)))(
            opp.X, state.C, lm, coef_new, idx_new, a)

    TtC = coef_new[:, None] * Crows
    W_new = A.slab_w_update(state, TtC, Ksub, coef_new, a)
    return dataclasses.replace(state, C=C_new, W=W_new, m=t + 1)


def _sharded_batched(opp: KernelOperator, state: AccumState, B: int,
                     mesh: Mesh, use_kernel: bool, n_real: int) -> AccumState:
    """One m → m+B batch on pre-padded (X, C): the same arithmetic as
    ``apply.accum_grow_batched`` with the B-slab column block computed
    per-shard in ONE mapped launch and BOTH d×d W-piece gathers (TᵀC from
    the old C, TᵀG from the G the launch just produced) psum-reduced from
    the same pass — the sharded engine reads each X shard once per batch.
    Draws are the replicated pre-draw, so they stay bitwise-identical to the
    single-device batched (and sequential) paths."""
    D = _data_size(mesh)
    rows = opp.n // D
    idx_blk, coef_blk, a = A.batch_pieces(state, B)
    d = state.d
    lm = jnp.take(opp.X, idx_blk.reshape(-1), axis=0)
    tile = _tile_cols_fn(opp, use_kernel, None, slabwise=True)

    def body(xb, cb, lm_, cf, idx_flat, a_):
        lo = jax.lax.axis_index(DATA_AXIS) * rows
        g = tile(xb, lm_, cf).astype(jnp.float32)
        live = (lo + jnp.arange(rows)) < n_real
        g = jnp.where(live[:, None], g, 0.0)
        c_new = a_ * cb + g
        inside = (idx_flat >= lo) & (idx_flat < lo + rows)
        local = jnp.where(inside, idx_flat - lo, 0)
        mask = inside[:, None].astype(jnp.float32)
        grows = jnp.take(g, local, axis=0) * mask
        crows = jnp.take(cb, local, axis=0) * mask
        return (c_new, jax.lax.psum(grows, DATA_AXIS),
                jax.lax.psum(crows, DATA_AXIS))

    C_new, Grows, Crows = _shard_map(
        body, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None), P(None, None),
                  P(None, None), P(None), P()),
        out_specs=(P(DATA_AXIS, None), P(None, None), P(None, None)))(
            opp.X, state.C, lm, coef_blk, idx_blk.reshape(-1), a)

    TtG = f32_einsum("bdc,bd->dc", Grows.reshape(B, d, d), coef_blk)
    TtC = f32_einsum("bdc,bd->dc", Crows.reshape(B, d, d), coef_blk)
    W_new = A.batch_w_update(state, TtC, TtG, a)
    return dataclasses.replace(state, C=C_new, W=W_new, m=state.m + B)


def sharded_accum_step(K, state: AccumState, mesh, *,
                       use_kernel: bool | None = None) -> AccumState:
    """``apply.accum_step`` on a row-sharded operator (standalone form: pads
    and unpads around the step; the grow loops pad once instead)."""
    mesh = resolve_mesh(mesh)
    op = _operator_required(K)
    if use_kernel is None:
        use_kernel = A.default_use_kernel()
    opp, st = _pad_engine(op, state, mesh)
    return _unpad_state(_sharded_step(opp, st, mesh, use_kernel, op.n), op.n)


def sharded_accum_grow_batched(K, state: AccumState, B: int, mesh, *,
                               use_kernel: bool | None = None) -> AccumState:
    """``apply.accum_grow_batched`` on a row-sharded operator: all B slabs in
    one mapped sweep per shard (standalone form: pads/unpads around the
    batch; the doubling driver pads once instead)."""
    mesh = resolve_mesh(mesh)
    op = _operator_required(K)
    if use_kernel is None:
        use_kernel = A.default_use_kernel()
    opp, st = _pad_engine(op, state, mesh)
    return _unpad_state(_sharded_batched(opp, st, B, mesh, use_kernel, op.n),
                        op.n)


def sharded_accum_grow(K, state: AccumState, steps: int, mesh, *,
                       use_kernel: bool | None = None) -> AccumState:
    """``apply.accum_grow`` on a row-sharded operator: ``steps`` sequential
    slab updates, each a mapped sweep per shard (one pad/unpad around the
    whole loop)."""
    mesh = resolve_mesh(mesh)
    op = _operator_required(K)
    if use_kernel is None:
        use_kernel = A.default_use_kernel()
    opp, st = _pad_engine(op, state, mesh)

    def body(_, s):
        return _sharded_step(opp, s, mesh, use_kernel, op.n)

    return _unpad_state(jax.lax.fori_loop(0, steps, body, st), op.n)


def sharded_accum_grow_doubling(
    K, state: AccumState, mesh, *, tol: float, estimator,
    use_kernel: bool | None = None, refine=None,
) -> tuple[AccumState, jax.Array]:
    """The doubling schedule on the sharded engine: the SHARED
    ``apply.doubling_ladder`` driver (so the stopping decisions — hence the
    chosen m — cannot drift from the single-device engine run with the same
    draws and a matching estimator), with each batch ONE mapped sweep over
    the shards.  ``refine`` is the optional per-phase probability refresh
    (``apply.make_leverage_refine`` — it reads C through driver-level
    gathers, so the padded rows never enter).  Returns ``(state, passes)``."""
    mesh = resolve_mesh(mesh)
    op = _operator_required(K)
    if use_kernel is None:
        use_kernel = A.default_use_kernel()
    opp, st = _pad_engine(op, state, mesh)

    def apply_batch(s, B):
        return _sharded_batched(opp, s, B, mesh, use_kernel, op.n)

    state, passes = A.doubling_ladder(st, st.m_max, tol, apply_batch,
                                      estimator, refine=refine)
    return _unpad_state(state, op.n), passes


def sharded_accum_grow_adaptive(
    K, state: AccumState, mesh, *, tol: float, estimator,
    check_every: int = 1, use_kernel: bool | None = None,
    schedule: str = "unit",
) -> AccumState:
    """Adaptive growth with the sharded step; ``estimator`` sees states whose
    C is padded to the mesh (the shard-aware factories below handle that).
    ``schedule="doubling"`` delegates to the batched rank-B ladder."""
    if schedule == "doubling":
        state, _ = sharded_accum_grow_doubling(
            K, state, mesh, tol=tol, estimator=estimator,
            use_kernel=use_kernel)
        return state
    mesh = resolve_mesh(mesh)
    op = _operator_required(K)
    if use_kernel is None:
        use_kernel = A.default_use_kernel()
    opp, st = _pad_engine(op, state, mesh)
    m_max = st.m_max

    def cond(s):
        return jnp.logical_and(s.m < m_max, s.err > tol)

    def body(s):
        s = _sharded_step(opp, s, mesh, use_kernel, op.n)
        do_check = jnp.logical_or(s.m % check_every == 0, s.m >= m_max)
        err = jax.lax.cond(do_check, estimator, lambda x: x.err, s)
        return dataclasses.replace(s, err=err)

    return _unpad_state(jax.lax.while_loop(cond, body, st), op.n)


# --------------------------------------------------------------------------- #
# shard-aware plug-in stopping estimators
# --------------------------------------------------------------------------- #

def make_sharded_holdout_estimator(key: jax.Array, K, mesh, num: int = 64,
                                   *, jitter: float = 1e-6):
    """The holdout rule with the C row gather psum-reduced.  Same key → the
    SAME holdout draw as ``apply.make_holdout_estimator`` (replicated RNG)."""
    mesh = resolve_mesh(mesh)
    op = _operator_required(K)
    n = op.n
    hold = jax.random.choice(key, n, shape=(min(num, n),), replace=False)
    Kh = op.submatrix(hold, hold).astype(jnp.float32)
    denom = jnp.maximum(jnp.linalg.norm(Kh), 1e-30)

    def estimate(state: AccumState) -> jax.Array:
        Ch = sharded_take_rows(state.C, hold, mesh)
        Khat = f32_matmul(Ch, A._psd_apply_pinv(state.W, Ch.T, jitter))
        est = jnp.linalg.norm(Kh - Khat) / denom
        return jnp.where(jnp.isfinite(est), est, jnp.inf).astype(jnp.float32)

    return estimate


def make_sharded_hutchinson_estimator(key: jax.Array, K, mesh,
                                      num_probes: int = 8, *,
                                      jitter: float = 1e-6):
    """Hutchinson trace rule: the one-time K Z precompute streams per-shard
    (``sharded_matvec``) and each evaluation's CᵀZ reduces via psum.  Same
    key → the same Rademacher probes as the single-device factory."""
    mesh = resolve_mesh(mesh)
    op = _operator_required(K)
    n = op.n
    Z = jax.random.rademacher(key, (n, num_probes), dtype=jnp.float32)
    KZ = sharded_matvec(op, Z, mesh)
    zKz = jnp.diagonal(sharded_gram(Z, KZ, mesh))
    denom = jnp.maximum(jnp.mean(zKz), 1e-30)

    def estimate(state: AccumState) -> jax.Array:
        Zp = _pad_to(Z, state.C.shape[0])       # engine states carry padded C
        CtZ = sharded_gram(state.C, Zp, mesh)
        zKhatz = f32_einsum("dq,dq->q", CtZ,
                            A._psd_apply_pinv(state.W, CtZ, jitter))
        est = jnp.maximum(jnp.mean(zKz - zKhatz), 0.0) / denom
        return jnp.where(jnp.isfinite(est), est, jnp.inf).astype(jnp.float32)

    return estimate


# --------------------------------------------------------------------------- #
# one-call sharded driver (used by apply.grow_sketch_both)
# --------------------------------------------------------------------------- #

def sharded_grow_sketch_both(
    key: jax.Array, K, d: int, mesh, *, m_max: int = 32,
    tol: float | None = None, probs: jax.Array | None = None,
    signed: bool = True, estimator=None, check_every: int = 1,
    use_kernel: bool | None = None, schedule: str = "doubling",
    scheme: str = "uniform", scheme_lam: float | None = None,
    scheme_mix: float = 0.1,
):
    """The mesh branch of ``apply.grow_sketch_both``: identical RNG (the
    pre-draw happens replicated, before anything is sharded), sharded growth,
    same return contract (``schedule="doubling"`` by default — batched
    rank-B passes, ``info["passes"]`` counts them).

    ``scheme`` matches the single-device driver bitwise: the pre-draw and
    every leverage probability refresh run replicated at the driver level
    (``apply.make_leverage_refine`` built from the SAME key, reading C
    through driver-level gathers), so the index/sign draws are identical to
    the unsharded run."""
    from repro.core.schemes import validate_scheme

    validate_scheme(scheme)
    if scheme == "leverage" and schedule != "doubling":
        raise ValueError("scheme='leverage' refines between batches and "
                         "needs schedule='doubling'")
    mesh = resolve_mesh(mesh)
    op = _operator_required(K)
    state = A.accum_init(key, op.n, d, m_max, probs, signed=signed,
                         scheme=scheme)
    refine = None
    if scheme == "leverage":
        refine = A.make_leverage_refine(
            key, lam=1e-3 if scheme_lam is None else scheme_lam,
            mix=scheme_mix, signed=signed)
    passes = None
    if tol is None:
        if refine is None:
            # one batched mapped sweep, as in the single-device driver
            state = sharded_accum_grow_batched(op, state, m_max, mesh,
                                               use_kernel=use_kernel)
            passes = jnp.ones((), jnp.int32)
        else:
            # leverage at fixed size walks the doubling ladder with the
            # refresh between batches — same phases/keys as the single-device
            # driver, so the draws stay identical
            sched = A.doubling_schedule(0, m_max)
            for i, B in enumerate(sched):
                state = sharded_accum_grow_batched(op, state, B, mesh,
                                                   use_kernel=use_kernel)
                if i < len(sched) - 1:
                    state = refine(state, i)
            passes = jnp.full((), len(sched), jnp.int32)
    else:
        if estimator is None:
            estimator = make_sharded_holdout_estimator(
                jax.random.fold_in(key, _HOLDOUT_STREAM), op, mesh)
        if schedule == "doubling":
            state, passes = sharded_accum_grow_doubling(
                op, state, mesh, tol=tol, estimator=estimator,
                use_kernel=use_kernel, refine=refine)
        else:
            state = sharded_accum_grow_adaptive(
                op, state, mesh, tol=tol, estimator=estimator,
                check_every=check_every, use_kernel=use_kernel,
                schedule=schedule)
    return A.finish_grow(state, m_max, passes=passes)
