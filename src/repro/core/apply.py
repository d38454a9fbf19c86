"""Structural application of an AccumSketch — the paper's efficiency claim.

The identities (paper §3.3):

    K S     = Σ_i K S_(i)          — O(n·m·d) instead of O(n²·d)
    Sᵀ K S  = Σ_i S_(i)ᵀ (K S)     — O(m·d²)  instead of O(n·d²)

Because each S_(i) has one non-zero per column, K S_(i) is a signed/rescaled
column gather of K, and S_(i)ᵀ M is a signed/rescaled row gather of M.
None of these routines materializes S.

The PROGRESSIVE ACCUMULATION ENGINE (``accum_init`` / ``accum_step`` /
``accum_grow`` / ``accum_grow_adaptive`` / ``grow_sketch_both``) turns the
one-shot sketch into the paper's actual strategy: grow m step-by-step,
folding one new sub-sampling matrix into the running (C, W) with a rank-d
incremental update,

    S_{m+1} = sqrt(m/(m+1))·S_m + T̃_{m+1}
    C_{m+1} = sqrt(m/(m+1))·C_m + K T̃_{m+1}             (one column gather)
    W_{m+1} = (m/(m+1))·W_m + a·(T̃ᵀC_m + C_mᵀT̃) + T̃ᵀK T̃  (row gathers)

at O(n·d) per step instead of the O(n·m·d) from-scratch recompute — so a
cheap sampling distribution (uniform / approximate leverage) can buy accuracy
by growing m until a plug-in error estimate clears the caller's tolerance.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.analysis.streams import HOLDOUT_STREAM as _HOLDOUT_STREAM
from repro.analysis.streams import REFINE_STREAM as _REFINE_STREAM
from repro.core.kernels_math import f32_einsum, f32_matmul
from repro.core.sketch import AccumSketch, AccumState, make_accum_sketch
from repro.util import env_flag


def default_use_kernel() -> bool:
    """Route structural applications through the Pallas kernels by default on
    TPU (compiled MXU path); XLA's fused gathers win elsewhere.

    Overridable with REPRO_SKETCH_KERNEL=0/1."""
    return env_flag("REPRO_SKETCH_KERNEL", jax.default_backend() == "tpu")


def _operator(K):
    """The KernelOperator behind K, or None for a dense array.

    Every K-consuming routine here dispatches through this so callers can pass
    either a materialized (n, n) kernel matrix or the matrix-free
    ``repro.core.kernel_op.KernelOperator`` (lazy import: kernel_op imports
    this module for the structural applications)."""
    from repro.core.kernel_op import KernelOperator

    return K if isinstance(K, KernelOperator) else None


def sketch_right(K: jax.Array, sk: AccumSketch) -> jax.Array:
    """K S for K of shape (r, n) → (r, d). O(r·m·d)."""
    cols = jnp.take(K, sk.indices.reshape(-1), axis=1)          # (r, m*d)
    cols = cols.reshape(K.shape[0], sk.m, sk.d)
    return f32_einsum("rmd,md->rd", cols, sk.coef)


def sketch_left(sk: AccumSketch, M: jax.Array) -> jax.Array:
    """Sᵀ M for M of shape (n, c) → (d, c). O(m·d·c)."""
    rows = jnp.take(M, sk.indices.reshape(-1), axis=0)           # (m*d, c)
    rows = rows.reshape(sk.m, sk.d, M.shape[-1])
    return f32_einsum("mdc,md->dc", rows, sk.coef)


def sketch_vec(sk: AccumSketch, v: jax.Array) -> jax.Array:
    """Sᵀ v for v of shape (n,) → (d,)."""
    return sketch_left(sk, v[:, None])[:, 0]


def unsketch_vec(sk: AccumSketch, w: jax.Array) -> jax.Array:
    """S w for w of shape (d,) → (n,) via segment-sum (scatter-add)."""
    contrib = (sk.coef * w[None, :]).reshape(-1)                 # (m*d,)
    return jnp.zeros((sk.n,), w.dtype).at[sk.indices.reshape(-1)].add(contrib)


def unsketch_mat(sk: AccumSketch, W: jax.Array) -> jax.Array:
    """S W for W of shape (d, c) → (n, c)."""
    contrib = sk.coef[..., None] * W[None, ...]                  # (m, d, c)
    return (
        jnp.zeros((sk.n, W.shape[-1]), W.dtype)
        .at[sk.indices.reshape(-1)]
        .add(contrib.reshape(-1, W.shape[-1]))
    )


def sketch_both(
    K: jax.Array, sk: AccumSketch, *, use_kernel: bool | None = None,
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    """(K S, Sᵀ K S) sharing the K S intermediate, as in the paper.

    ``K`` may be a dense (n, n) array or a matrix-free ``KernelOperator`` —
    the operator path streams kernel evaluations in row tiles and never
    allocates the n×n matrix.  With ``use_kernel`` (auto: True on TPU) the
    dense pair is computed by the fused single-sweep Pallas kernel — one pass
    over K, W accumulated in-kernel — instead of two gather passes (the
    operator routes through the fused kernel-eval→GEMM kernel instead).

    ``mesh`` (a ``("data",)`` mesh / True / a device count — operator only)
    row-shards X and C over the devices: per-device kernel-eval tiles, W
    psum-reduced (``repro.core.distributed``)."""
    op = _operator(K)
    if mesh is not None:
        from repro.core import distributed as D

        return D.sharded_sketch_both(D._operator_required(K), sk,
                                     D.resolve_mesh(mesh),
                                     use_kernel=use_kernel)
    if op is not None:
        return op.sketch_both(sk, use_kernel=use_kernel)
    if use_kernel is None:
        use_kernel = default_use_kernel()

    def _xla():
        KS = sketch_right(K, sk)
        return KS, sketch_left(sk, KS)

    if use_kernel:
        from repro.kernels.accum_apply.ops import sketch_both_kernel
        from repro.resilience.degrade import ladder_call

        # W stays float32: it was accumulated in f32 VMEM and feeds the d×d
        # solve — downcasting to a low-precision K dtype would throw that away.
        # A failing Pallas dispatch degrades to the XLA gather pair (recorded
        # in the global HealthReport), never to a wrong answer.
        return ladder_call("kernel.dispatch", (
            ("pallas:sketch_both", lambda: sketch_both_kernel(K, sk)),
            ("xla:gather", _xla),
        ))
    return _xla()


def gram_sketch(sk: AccumSketch) -> jax.Array:
    """Sᵀ S (d, d) without materializing S.

    Scatter-add formulation: the m·d non-zero entries are grouped by their row
    index (segment-sum over the ≤ m·d *distinct* sampled rows), giving the
    compressed (md, d) row block B with B[rank(k), j] = S[k, j]; then
    SᵀS = BᵀB. O(m·d) scatter + one (d × md × d) GEMM, O(m·d²) memory —
    replaces the seed's (md)² coincidence matrix, which blew up at
    production m·d."""
    idx = sk.indices.reshape(-1)     # (md,)
    cf = sk.coef.reshape(-1)         # (md,)
    col = jnp.tile(jnp.arange(sk.d), sk.m)
    # rank of each entry among the distinct sampled rows (static size: md)
    _, ranks = jnp.unique(idx, return_inverse=True, size=idx.shape[0],
                          fill_value=-1)
    B = jnp.zeros((idx.shape[0], sk.d), cf.dtype).at[ranks, col].add(cf)
    return B.T @ B


# --------------------------------------------------------------------------- #
# Progressive accumulation engine
# --------------------------------------------------------------------------- #

def _psd_apply_pinv(W: jax.Array, B: jax.Array, jitter: float = 1e-6) -> jax.Array:
    """W⁺ B for PSD W via trace-scaled jitter + Cholesky (d×d, cheap)."""
    d = W.shape[0]
    eps = jitter * (jnp.trace(W) / d) + 1e-30
    L, lower = jax.scipy.linalg.cho_factor(
        W + eps * jnp.eye(d, dtype=W.dtype), lower=True)
    return jax.scipy.linalg.cho_solve((L, lower), B)


def accum_init(key: jax.Array, n: int, d: int, m_max: int,
               probs: jax.Array | None = None, *, signed: bool = True,
               scheme: str = "uniform") -> AccumState:
    """Draw all ``m_max`` sub-sampling matrices up front (same RNG scheme as
    ``make_accum_sketch``, so growing to m_max replays the one-shot draw at
    m_max exactly; a stop at m < m_max yields a prefix of that draw) and
    return the empty accumulation state.

    ``scheme`` threads to the constructor (``"poisson"`` pre-draws Poisson
    slabs; ``"leverage"`` starts from ``probs`` — or uniform when ``None`` —
    and lets the grow drivers refine the tail as m grows).  ``pdraw`` records
    the probabilities of the initial draw."""
    if scheme == "leverage":
        # the engine refines leverage probs itself — seed the pre-draw from
        # the caller's pilot distribution (or uniform), NOT the one-shot
        # constructor, which demands explicit leverage probs
        sk = make_accum_sketch(key, n, d, m_max, probs, signed=signed)
        sk = dataclasses.replace(sk, scheme=scheme)
    else:
        sk = make_accum_sketch(key, n, d, m_max, probs, signed=signed,
                               scheme=scheme)
    return AccumState(
        indices=sk.indices, signs=sk.signs, probs=sk.probs,
        pdraw=jnp.take(sk.probs, sk.indices, axis=0),
        C=jnp.zeros((n, d), jnp.float32), W=jnp.zeros((d, d), jnp.float32),
        m=jnp.zeros((), jnp.int32), err=jnp.full((), jnp.inf, jnp.float32),
        n=n, scheme=scheme,
    )


def slab_pieces(state: AccumState):
    """(idx_new, coef_new, a) for folding slab number ``state.m``: the new
    sub-sampling matrix's indices, its combination coefficients normalized
    for the GROWN size m = t+1 (coef = r / sqrt(d (t+1) p)), and the
    survivors' rescale a = sqrt(t/(t+1)) (t=0 → 0: C_1 = K T̃_1).

    Shared by the dense and sharded (``repro.core.distributed``) engines so
    the normalization cannot drift between them."""
    t = state.m
    tf = t.astype(jnp.float32)
    d = state.d
    idx_new = jax.lax.dynamic_index_in_dim(state.indices, t, axis=0,
                                           keepdims=False)
    sgn_new = jax.lax.dynamic_index_in_dim(state.signs, t, axis=0,
                                           keepdims=False)
    # at-draw probabilities, NOT take(probs, indices): the leverage scheme
    # refines probs while m grows and the slab keeps the distribution it was
    # actually drawn from
    p_new = jax.lax.dynamic_index_in_dim(state.pdraw, t, axis=0,
                                         keepdims=False).astype(jnp.float32)
    coef_new = sgn_new.astype(jnp.float32) / jnp.sqrt(d * (tf + 1.0) * p_new)
    a = jnp.sqrt(tf / (tf + 1.0))
    return idx_new, coef_new, a


def slab_w_update(state: AccumState, TtC: jax.Array, Ksub: jax.Array,
                  coef_new: jax.Array, a: jax.Array) -> jax.Array:
    """The W recurrence for one slab, from the d×d pieces:
    W_{t+1} = a²·W_t + a·(T̃ᵀC + (T̃ᵀC)ᵀ) + T̃ᵀK T̃, exact-arithmetic
    symmetrized.  Shared by the dense and sharded engines."""
    TtKT = coef_new[:, None] * Ksub.astype(jnp.float32) * coef_new[None, :]
    W_new = (a * a) * state.W + a * (TtC + TtC.T) + TtKT
    return 0.5 * (W_new + W_new.T)


def batch_pieces(state: AccumState, B: int):
    """(idx_blk, coef_blk, a) for folding slabs [t, t+B) in ONE batch: the
    B-row index/coefficient block normalized directly for the GROWN size
    t+B (coef = r / sqrt(d (t+B) p)) and the telescoped survivor rescale
    a = sqrt(t/(t+B)) — the per-step sqrt(k/(k+1)) rescales of B sequential
    ``slab_pieces`` steps collapse into exactly these two factors, which is
    what makes the batch one pass instead of B.

    Shared by the dense and sharded engines (same reason as ``slab_pieces``).
    ``B`` must be static; the caller guarantees t + B ≤ m_max (the slice
    would clamp and silently re-read earlier slabs otherwise)."""
    t = state.m
    tf = t.astype(jnp.float32)
    d = state.d
    idx_blk = jax.lax.dynamic_slice_in_dim(state.indices, t, B, axis=0)
    sgn_blk = jax.lax.dynamic_slice_in_dim(state.signs, t, B, axis=0)
    # at-draw probabilities (see slab_pieces) — leverage refines state.probs
    p_blk = jax.lax.dynamic_slice_in_dim(state.pdraw, t, B,
                                         axis=0).astype(jnp.float32)
    coef_blk = sgn_blk.astype(jnp.float32) / jnp.sqrt(d * (tf + B) * p_blk)
    a = jnp.sqrt(tf / (tf + B))
    return idx_blk, coef_blk, a


def block_left(idx_blk: jax.Array, coef_blk: jax.Array, M: jax.Array) -> jax.Array:
    """Tᵀ M (d, c) for the batch block T described by idx/coef (B, d): a
    B·d-row gather of M contracted with the coefficients — the d×d W pieces
    of the batched update (TᵀC from the running C, TᵀKT = Tᵀ(KT) from the
    same G the C update produced; no second pass over anything n-sized)."""
    B, d = idx_blk.shape
    rows = jnp.take(M, idx_blk.reshape(-1), axis=0).reshape(B, d, M.shape[-1])
    return f32_einsum("bdc,bd->dc", rows.astype(jnp.float32), coef_blk)


def batch_w_update(state: AccumState, TtC: jax.Array, TtG: jax.Array,
                   a: jax.Array) -> jax.Array:
    """The batched W recurrence: W_{t+B} = a²·W_t + a·(TᵀC + (TᵀC)ᵀ) + TᵀKT,
    exact-arithmetic symmetrized.  Shared by the dense and sharded engines."""
    W_new = (a * a) * state.W + a * (TtC + TtC.T) + TtG
    return 0.5 * (W_new + W_new.T)


def finish_grow(state: AccumState, m_max: int, passes: jax.Array | None = None):
    """The grow drivers' shared return contract: (sketch, C, W, info) with
    jax-scalar info and the trace-safe masked sketch under a tracer.
    ``passes`` is the number of data sweeps the growth took (== m on the
    unit schedule, O(log m) on the doubling schedule)."""
    info = {"m": state.m, "m_max": m_max, "err": state.err,
            "passes": state.m if passes is None else passes}
    if isinstance(state.m, jax.core.Tracer):
        return state.masked_sketch(), state.C, state.W, info
    return state.sketch(), state.C, state.W, info


def _concrete_args(*trees) -> bool:
    """True iff no leaf is a tracer — the condition for routing through the
    buffer-donating jitted wrappers (nested jit would silently drop the
    donation and warn)."""
    return not any(isinstance(leaf, jax.core.Tracer)
                   for t in trees for leaf in jax.tree_util.tree_leaves(t))


def accum_step(K: jax.Array, state: AccumState, *,
               use_kernel: bool | None = None, mesh=None) -> AccumState:
    """Fold ONE new sub-sampling matrix into (C, W): the rank-d incremental
    update, O(n·d) per step.

    ``K`` may be dense or a ``KernelOperator`` — the operator evaluates the
    slab's column block K(X, X[idx]) directly from data (O(n·d) kernel evals,
    the matrix-free analogue of the column gather) and the d×d piece from d²
    evals.  With ``use_kernel`` (auto: True on TPU) the dense C update runs
    through the single-slab Pallas entry point (``sketch_step_kernel``) and
    the operator through the fused matfree kernel; the W pieces are d×d
    gathers either way.  ``mesh`` (operator only) computes the slab's column
    block per data shard and psum-reduces the T̃ᵀC gather."""
    if mesh is not None:
        from repro.core import distributed as D

        return D.sharded_accum_step(K, state, mesh, use_kernel=use_kernel)
    op = _operator(K)
    if use_kernel is None:
        use_kernel = default_use_kernel()
    t = state.m
    idx_new, coef_new, a = slab_pieces(state)

    # W update from d×d gathers only:  T̃ᵀC_t and (T̃ᵀK T̃)[i,j] = c_i K[n_i,n_j] c_j
    TtC = coef_new[:, None] * jnp.take(state.C, idx_new, axis=0)
    if op is not None:
        Ksub = op.submatrix(idx_new, idx_new)
    else:
        Ksub = jnp.take(jnp.take(K, idx_new, axis=0), idx_new, axis=1)
    W_new = slab_w_update(state, TtC, Ksub, coef_new, a)

    if op is not None:
        G = op.weighted_cols(op.X, idx_new[None, :], coef_new[None, :],
                             use_kernel=use_kernel)
        # the loop carry C is always f32 (AccumState contract); an f64
        # operator (x64 mode) must not promote it or the while/fori carry
        # dtype check rejects the step
        C_new = a * state.C + G.astype(jnp.float32)
    elif use_kernel:
        from repro.kernels.accum_apply.ops import sketch_step_kernel
        C_new = sketch_step_kernel(K, idx_new, coef_new, state.C, a)
    else:
        G = jnp.take(K, idx_new, axis=1).astype(jnp.float32) * coef_new[None, :]
        C_new = a * state.C + G
    return dataclasses.replace(state, C=C_new, W=W_new, m=t + 1)


@functools.partial(jax.jit, static_argnames=("steps", "use_kernel"),
                   donate_argnums=(1,))
def _grow_loop_donated(K, state: AccumState, steps: int,
                       use_kernel: bool) -> AccumState:
    """The unconditional growth loop under jit with the state DONATED: the
    incoming (C, W) buffers are reused for the outputs, so an eager grow call
    keeps one n·d C resident instead of functionally rebuilding a second."""
    def body(_, s):
        return accum_step(K, s, use_kernel=use_kernel)

    return jax.lax.fori_loop(0, steps, body, state)


def accum_grow(K: jax.Array, state: AccumState, steps: int, *,
               use_kernel: bool | None = None, mesh=None,
               donate: bool = True) -> AccumState:
    """Unconditionally fold in ``steps`` more slabs (``lax.fori_loop``).

    Eager calls route through a jitted wrapper that DONATES the state — the
    caller's ``state`` buffers are consumed (its C/W must not be reused
    afterwards; pass ``donate=False`` to keep them, e.g. when timing repeated
    calls on the same state).  Traced calls inline (nested donation would be
    dropped silently)."""
    if mesh is not None:
        from repro.core import distributed as D

        return D.sharded_accum_grow(K, state, steps, mesh,
                                    use_kernel=use_kernel)
    if use_kernel is None:
        use_kernel = default_use_kernel()
    if donate and _concrete_args(K, state):
        return _grow_loop_donated(K, state, steps, use_kernel)

    def body(_, s):
        return accum_step(K, s, use_kernel=use_kernel)

    return jax.lax.fori_loop(0, steps, body, state)


def _accum_grow_batched_impl(K, state: AccumState, B: int,
                             use_kernel: bool) -> AccumState:
    op = _operator(K)
    idx_blk, coef_blk, a = batch_pieces(state, B)
    if op is not None:
        # ONE kernel-evaluation sweep for all B slabs: the fused Pallas
        # kernel-eval→GEMM kernel takes the (B, d) block whole (the MXU wants
        # the wide GEMM); the streaming path accumulates slab-by-slab at the
        # narrow GEMM shape (``stream_cols_slabs`` — XLA's wide-output CPU
        # tiling degrades ~2× by B·d ≈ 1024).  TᵀKT reuses G, no extra evals
        if use_kernel:
            G = op.weighted_cols(op.X, idx_blk, coef_blk,
                                 use_kernel=True).astype(jnp.float32)
        else:
            from repro.core.kernel_op import stream_cols_slabs

            lm = jnp.take(op.X, idx_blk.reshape(-1), axis=0)
            G = stream_cols_slabs(op.X, lm, coef_blk,
                                  op.kernel_fn).astype(jnp.float32)
        C_new = a * state.C + G
        TtG = block_left(idx_blk, coef_blk, G)
        TtC = block_left(idx_blk, coef_blk, state.C)
    elif use_kernel:
        from repro.kernels.accum_apply.ops import accum_grow_kernel
        C_new, TtG, TtC = accum_grow_kernel(K, idx_blk, coef_blk, state.C, a)
    else:
        n = K.shape[0]
        cols = jnp.take(K, idx_blk.reshape(-1), axis=1).astype(jnp.float32)
        G = f32_einsum("nbd,bd->nd", cols.reshape(n, B, state.d), coef_blk)
        C_new = a * state.C + G
        TtG = block_left(idx_blk, coef_blk, G)
        TtC = block_left(idx_blk, coef_blk, state.C)
    W_new = batch_w_update(state, TtC, TtG, a)
    return dataclasses.replace(state, C=C_new, W=W_new, m=state.m + B)


@functools.partial(jax.jit, static_argnames=("B", "use_kernel"),
                   donate_argnums=(1,))
def _grow_batched_donated(K, state: AccumState, B: int,
                          use_kernel: bool) -> AccumState:
    return _accum_grow_batched_impl(K, state, B, use_kernel)


def accum_grow_batched(K: jax.Array, state: AccumState, B: int, *,
                       use_kernel: bool | None = None, mesh=None,
                       donate: bool = True) -> AccumState:
    """Fold the next ``B`` pre-drawn slabs into (C, W) in ONE pass over the
    data — the batched rank-B counterpart of ``accum_step``.

    The per-step survivor rescales telescope (``batch_pieces``), so the whole
    batch is: one column-block application G = K·T (a single fused Pallas
    launch / kernel-eval sweep / gather, read of K or X exactly once), the
    C update a·C + G, and two d×d gathers for W — bitwise-identical in draws
    to B sequential ``accum_step`` calls (same pre-drawn indices/signs) and
    ≤ 1e-5-rel-equivalent in (C, W) values (summation order only).

    Eager calls donate the state buffers as in ``accum_grow``
    (``donate=False`` opts out).  ``B`` must be static, with
    state.m + B ≤ m_max."""
    # validate BEFORE the mesh dispatch: an overrun would make batch_pieces'
    # dynamic_slice clamp and silently re-fold earlier slabs on either path
    if not 1 <= B <= state.m_max:
        raise ValueError(f"batch size B={B} outside [1, m_max={state.m_max}]")
    if not isinstance(state.m, jax.core.Tracer) and int(state.m) + B > state.m_max:
        raise ValueError(
            f"batch of {B} slabs from m={int(state.m)} overruns the "
            f"pre-drawn m_max={state.m_max}")
    if mesh is not None:
        from repro.core import distributed as D

        return D.sharded_accum_grow_batched(K, state, B, mesh,
                                            use_kernel=use_kernel)
    if use_kernel is None:
        use_kernel = default_use_kernel()
    if donate and _concrete_args(K, state):
        return _grow_batched_donated(K, state, B, use_kernel)
    return _accum_grow_batched_impl(K, state, B, use_kernel)


def doubling_schedule(m_start: int, m_max: int) -> list[int]:
    """Static batch sizes 1, 2, 4, … (clamped into the remaining budget) that
    grow ``m_start`` → ``m_max``: O(log m_max) batches, each ONE data pass,
    instead of m_max unit steps."""
    out, t, B = [], m_start, 1
    while t < m_max:
        b = min(B, m_max - t)
        out.append(b)
        t += b
        B *= 2
    return out


def make_holdout_estimator(key: jax.Array, K: jax.Array, num: int = 64,
                           *, jitter: float = 1e-6, mesh=None):
    """Plug-in stopping rule: relative Nyström-reconstruction error of the
    sketched operator K̂ = C W⁺ Cᵀ on a fixed random holdout principal
    submatrix — O(h²·d + d³) per evaluation, independent of n.  With a
    ``KernelOperator`` the h×h holdout block comes from h² kernel evals;
    with ``mesh`` the C row gather additionally psum-reduces over the data
    shards (same key → the same holdout draw)."""
    if mesh is not None:
        from repro.core import distributed as D

        return D.make_sharded_holdout_estimator(key, K, mesh, num,
                                                jitter=jitter)
    op = _operator(K)
    n = K.shape[0]
    hold = jax.random.choice(key, n, shape=(min(num, n),), replace=False)
    if op is not None:
        Kh = op.submatrix(hold, hold).astype(jnp.float32)
    else:
        Kh = jnp.take(jnp.take(K, hold, axis=0), hold, axis=1).astype(jnp.float32)
    denom = jnp.maximum(jnp.linalg.norm(Kh), 1e-30)

    def estimate(state: AccumState) -> jax.Array:
        Ch = jnp.take(state.C, hold, axis=0)
        Khat = f32_matmul(Ch, _psd_apply_pinv(state.W, Ch.T, jitter))
        est = jnp.linalg.norm(Kh - Khat) / denom
        return jnp.where(jnp.isfinite(est), est, jnp.inf).astype(jnp.float32)

    return estimate


def make_hutchinson_estimator(key: jax.Array, K: jax.Array, num_probes: int = 8,
                              *, jitter: float = 1e-6, mesh=None):
    """Plug-in stopping rule: Hutchinson estimate of the relative trace
    residual tr(K − K̂)/tr̂(K) with Rademacher probes.  K Z is precomputed once
    (K is fixed while m grows), so each evaluation costs O(n·d·q + d³).  The
    Nyström residual of a PSD K is PSD, so the estimate is a true error.
    With a ``KernelOperator`` the one-time K Z is a streamed matvec —
    O(n²·p·q) kernel-eval compute but O(chunk·n) memory, never n²; with
    ``mesh`` the matvec rows and every CᵀZ contraction stay per-shard."""
    if mesh is not None:
        from repro.core import distributed as D

        return D.make_sharded_hutchinson_estimator(key, K, mesh, num_probes,
                                                   jitter=jitter)
    op = _operator(K)
    n = K.shape[0]
    Z = jax.random.rademacher(key, (n, num_probes), dtype=jnp.float32)
    if op is not None:
        KZ = op.matvec(Z)                              # streamed, O(chunk·n) mem
    else:
        KZ = f32_matmul(K.astype(jnp.float32), Z)     # one-time O(n²·q)
    zKz = jnp.einsum("nq,nq->q", Z, KZ)
    denom = jnp.maximum(jnp.mean(zKz), 1e-30)

    def estimate(state: AccumState) -> jax.Array:
        CtZ = f32_matmul(state.C.T, Z)  # (d, q) — O(n·d·q)
        zKhatz = f32_einsum("dq,dq->q", CtZ,
                            _psd_apply_pinv(state.W, CtZ, jitter))
        est = jnp.maximum(jnp.mean(zKz - zKhatz), 0.0) / denom
        return jnp.where(jnp.isfinite(est), est, jnp.inf).astype(jnp.float32)

    return estimate


def doubling_ladder(state: AccumState, m_max: int, tol: float, apply_batch,
                    estimator, refine=None) -> tuple[AccumState, jax.Array]:
    """The shared doubling-schedule driver: static batch ladder, one
    ``lax.cond`` phase guard per batch (only the taken branch executes), the
    estimator once per batch.  ``apply_batch(state, B)`` is the backend —
    the dense/matfree ``accum_grow_batched`` or the sharded mapped sweep —
    so the stopping decisions cannot drift between engines.  Returns
    ``(state, passes)``.

    ``refine(state, phase) -> state`` (optional) runs after each executed
    batch — the leverage scheme's probability refresh + tail redraw
    (``schemes.refresh_tail``); it must preserve the state's pytree
    structure (pure masking, no shape changes) so it composes with the
    ``lax.cond`` phases.

    The schedule is laid out from the state's current m (assumed 0 under a
    tracer — the grow drivers always pass a fresh state); the per-phase
    guard ``m + B ≤ m_max`` makes overrunning the pre-drawn slabs impossible
    either way."""
    m0 = 0 if isinstance(state.m, jax.core.Tracer) else int(state.m)
    carry = (state, jnp.zeros((), jnp.int32))
    for i, B in enumerate(doubling_schedule(m0, m_max)):
        def do_batch(sp, B=B, i=i):
            s, p = sp
            s = apply_batch(s, B)
            s = dataclasses.replace(s, err=estimator(s))
            if refine is not None:
                s = refine(s, i)
            return s, p + 1

        s, _ = carry
        pred = jnp.logical_and(s.err > tol, s.m + B <= m_max)
        carry = jax.lax.cond(pred, do_batch, lambda sp: sp, carry)
    return carry


def make_leverage_refine(key: jax.Array, *, lam: float, mix: float = 0.1,
                         signed: bool = True):
    """Build the leverage scheme's per-phase refine callback for the grow
    drivers: estimate ridge-leverage probabilities from the state's own
    (C, SᵀC) via the Nyström lift and redraw the not-yet-accumulated slabs
    from them.

    SHARED by the single-device and sharded drivers (both construct it from
    the same key), so the refreshed draws cannot drift between them.

    Args:
        key: base PRNG key; phase ``i`` folds in ``0x11E7 + i``.
        lam: ridge level λ for the leverage scores.
        mix: uniform mixing weight for the probabilities.
        signed: draw Rademacher signs for redrawn slabs.

    Returns:
        ``refine(state, phase) -> state`` suitable for ``doubling_ladder``.
    """
    from repro.core import schemes as SCH

    def refine(state: AccumState, phase: int) -> AccumState:
        p_new = SCH.state_leverage_probs(state, lam, mix=mix)
        return SCH.refresh_tail(state,
                                jax.random.fold_in(key, _REFINE_STREAM + phase),
                                p_new, signed=signed)

    return refine


def accum_grow_doubling(K: jax.Array, state: AccumState, *, tol: float,
                        estimator, use_kernel: bool | None = None,
                        mesh=None, refine=None) -> tuple[AccumState, jax.Array]:
    """Adaptive growth on the DOUBLING schedule: draw B slabs, fold them in
    with ONE data pass (``accum_grow_batched``), check the estimator, B ← 2B
    — O(log m_final) passes over K (or X) instead of O(m_final).

    The batch sizes are static (1, 2, 4, …, clamped to m_max — the shared
    ``doubling_ladder``), so the whole driver stays jittable: each phase is
    a ``lax.cond`` that either applies the batch or passes the state through
    untouched once the tolerance is met — only the taken branch executes, so
    a converged state pays nothing for the remaining phases.  The estimator
    runs once per BATCH (its probe/holdout contractions read the C the same
    pass just produced), not once per slab.  Returns ``(state, passes)``
    with ``passes`` the number of batches actually applied.  ``refine`` is
    the optional per-phase probability refresh (``make_leverage_refine``),
    forwarded to the shared ladder."""
    if mesh is not None:
        from repro.core import distributed as D

        return D.sharded_accum_grow_doubling(
            K, state, mesh, tol=tol, estimator=estimator,
            use_kernel=use_kernel, refine=refine)
    if use_kernel is None:
        use_kernel = default_use_kernel()

    def apply_batch(s, B):
        return accum_grow_batched(K, s, B, use_kernel=use_kernel,
                                  donate=False)

    return doubling_ladder(state, state.m_max, tol, apply_batch, estimator,
                           refine=refine)


def accum_grow_adaptive(K: jax.Array, state: AccumState, *, tol: float,
                        estimator, check_every: int = 1,
                        use_kernel: bool | None = None,
                        mesh=None, schedule: str = "unit") -> AccumState:
    """Grow until ``estimator(state) ≤ tol`` or the pre-drawn ``m_max`` slabs
    are exhausted.  ``estimator`` maps AccumState → scalar error.

    ``schedule="unit"`` (default here; the ``grow_sketch_both`` driver
    defaults to doubling) folds one slab per pass in a ``lax.while_loop``;
    ``check_every > 1`` amortizes the estimator over several growth steps.
    ``schedule="doubling"`` delegates to ``accum_grow_doubling`` — batched
    rank-B passes, O(log m) sweeps over the data, estimator once per batch
    (``check_every`` does not apply there).  With ``mesh`` pass a shard-aware
    estimator (``make_*_estimator(mesh=…)``) — the loop states carry C padded
    up to the mesh."""
    if schedule not in ("unit", "doubling"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if schedule == "doubling":
        state, _ = accum_grow_doubling(K, state, tol=tol, estimator=estimator,
                                       use_kernel=use_kernel, mesh=mesh)
        return state
    if mesh is not None:
        from repro.core import distributed as D

        return D.sharded_accum_grow_adaptive(
            K, state, mesh, tol=tol, estimator=estimator,
            check_every=check_every, use_kernel=use_kernel)
    if use_kernel is None:
        use_kernel = default_use_kernel()
    m_max = state.m_max

    def cond(s):
        return jnp.logical_and(s.m < m_max, s.err > tol)

    def body(s):
        s = accum_step(K, s, use_kernel=use_kernel)
        do_check = jnp.logical_or(s.m % check_every == 0, s.m >= m_max)
        err = jax.lax.cond(do_check, estimator, lambda st: st.err, s)
        return dataclasses.replace(s, err=err)

    return jax.lax.while_loop(cond, body, state)


def grow_sketch_both(
    key: jax.Array, K: jax.Array, d: int, *, m_max: int = 32,
    tol: float | None = None, probs: jax.Array | None = None,
    signed: bool = True, estimator=None, check_every: int = 1,
    use_kernel: bool | None = None, mesh=None, schedule: str = "doubling",
    scheme: str = "uniform", scheme_lam: float | None = None,
    scheme_mix: float = 0.1,
) -> tuple[AccumSketch, jax.Array, jax.Array, dict]:
    """One-call driver: grow a sketch on K — a precomputed matrix OR a
    matrix-free ``KernelOperator`` — until the error target is met (or to
    m_max when ``tol`` is None) and return ``(sketch, C, W, info)`` with
    C = K S, W = SᵀKS at the final m.

    Callers specify an error target instead of m — the paper's rescue of
    suboptimal (uniform / approximate-leverage) sampling schemes: grow m,
    keep the effective d×d size fixed.  ``estimator`` defaults to the holdout
    rule; pass ``make_hutchinson_estimator(...)`` (or any AccumState → scalar
    callable) to swap the plug-in rule.

    The whole driver is jittable: ``info``'s ``m``/``err`` are jax scalars
    (NOT host ints — converting here would force a device sync on every call
    and break tracing; examples/benchmarks convert at the printing edge), and
    under a trace the returned sketch is the state's ``masked_sketch()`` —
    static (m_max, d) shapes, zero-coefficient slabs beyond m, applies
    identically to the truncation eager callers get.

    Adaptive growth defaults to ``schedule="doubling"``: batched rank-B
    passes (draw B, one sweep, check the estimator, B ← 2B), O(log m) data
    passes instead of O(m) — ``info["passes"]`` reports the count.  Pass
    ``schedule="unit"`` for the one-slab-per-pass while_loop (there
    ``check_every`` amortizes the estimator).

    ``scheme`` selects the sampling scheme: ``"uniform"`` (default),
    ``"poisson"`` (fixed Horvitz–Thompson draws, π from ``probs`` or
    uniform), ``"leverage"`` — start from ``probs`` (or uniform), and after
    every executed batch re-estimate ridge-leverage probabilities FROM THE
    SKETCH ITSELF (``schemes.state_leverage_probs`` at ridge level
    ``scheme_lam``, uniform-mixed by ``scheme_mix``) and redraw the
    not-yet-accumulated slabs from them.  Leverage requires the doubling
    schedule (refinement happens between batches; a unit-step refresh would
    re-randomize every slab).  ``scheme_lam`` defaults to 1e-3; the KRR
    adaptive drivers forward their own λ.

    ``mesh`` (operator only) runs the whole growth data-parallel: identical
    index/holdout/probe draws (the RNG happens replicated, before anything is
    sharded), per-shard slab kernel evals, psum reductions."""
    from repro.core.schemes import validate_scheme

    validate_scheme(scheme)
    if scheme == "leverage" and schedule != "doubling":
        raise ValueError("scheme='leverage' refines between batches and "
                         "needs schedule='doubling'")
    if mesh is not None:
        from repro.core import distributed as D

        return D.sharded_grow_sketch_both(
            key, K, d, mesh, m_max=m_max, tol=tol, probs=probs, signed=signed,
            estimator=estimator, check_every=check_every,
            use_kernel=use_kernel, schedule=schedule, scheme=scheme,
            scheme_lam=scheme_lam, scheme_mix=scheme_mix)
    n = K.shape[0]
    state = accum_init(key, n, d, m_max, probs, signed=signed, scheme=scheme)
    refine = None
    if scheme == "leverage":
        refine = make_leverage_refine(
            key, lam=1e-3 if scheme_lam is None else scheme_lam,
            mix=scheme_mix, signed=signed)
    passes = None
    if tol is None:
        if refine is None:
            # fixed-size growth is ONE batch: t=0 makes the survivor rescale 0
            # and the m_max-slab block IS the one-shot sketch — a single data
            # pass where the unit loop paid m_max
            state = accum_grow_batched(K, state, m_max, use_kernel=use_kernel)
            passes = jnp.ones((), jnp.int32)
        else:
            # leverage at fixed size still walks the doubling ladder so the
            # probabilities refine between batches — O(log m) passes
            sched = doubling_schedule(0, m_max)
            for i, B in enumerate(sched):
                state = accum_grow_batched(K, state, B, use_kernel=use_kernel,
                                           donate=False)
                if i < len(sched) - 1:
                    state = refine(state, i)
            passes = jnp.full((), len(sched), jnp.int32)
    else:
        if estimator is None:
            estimator = make_holdout_estimator(
                jax.random.fold_in(key, _HOLDOUT_STREAM), K)
        if schedule == "doubling":
            state, passes = accum_grow_doubling(
                K, state, tol=tol, estimator=estimator, use_kernel=use_kernel,
                refine=refine)
        else:
            state = accum_grow_adaptive(K, state, tol=tol, estimator=estimator,
                                        check_every=check_every,
                                        use_kernel=use_kernel,
                                        schedule=schedule)
    return finish_grow(state, m_max, passes=passes)


def sketch_kernel_cols(
    X: jax.Array, sk: AccumSketch, kernel_fn, *, chunk: int | None = None
) -> jax.Array:
    """C = K S without ever forming K:  O(n·m·d) kernel evaluations.

    kernel_fn(A, B) -> (|A|, |B|) kernel matrix. Gathers the m·d landmark
    points, evaluates the (chunk, m·d) slab per row chunk, and contracts with
    the combination coefficients (``kernel_op.stream_cols`` — a ``lax.scan``
    streaming sweep).  Thin ad-hoc-callable wrapper; prefer a
    ``KernelOperator`` for named kernels (Pallas routing, engine support)."""
    from repro.core.kernel_op import stream_cols

    landmarks = jnp.take(X, sk.indices.reshape(-1), axis=0)      # (m*d, d_X)
    return stream_cols(X, landmarks, sk.coef, kernel_fn, chunk=chunk)
