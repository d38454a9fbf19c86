"""Kernel ridge regression: exact (paper eq. 2) and sketched (paper eq. 3).

Exact:     f̂(x)   = K(x, X) (K + nλ I)⁻¹ Y
Sketched:  f̂_S(x) = K(x, X) S (SᵀK²S + nλ SᵀKS)⁻¹ SᵀK Y        (Woodbury form)

Four application paths:
  * dense sketch S (Gaussian / sparse RP baselines)          — O(n²d)
  * structural AccumSketch on a precomputed K                — O(n·m·d)
  * matrix-free AccumSketch straight from X (never forms K)  — O(n·m·d) kernel evals
  * adaptive (``*_adaptive``): the progressive accumulation engine grows m
    one O(n·d) incremental slab at a time until a plug-in error estimate
    clears the caller's tolerance, and the solve reuses the incrementally
    accumulated (C, W)

Every SKETCHED K-taking entry point (``krr_sketched_fit*``) also accepts a
matrix-free ``repro.core.kernel_op.KernelOperator`` (dataset + kernel name)
in place of the dense matrix — the production configuration at n beyond ~10⁴,
where the n×n Gram matrix must never exist.  The exact solvers
(``krr_exact_fit*``) genuinely need the materialized matrix.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import apply as A
from repro.core.kernels_math import f32_gram, f32_matmul
from repro.core.sketch import AccumSketch
from repro.spans import phase


def _solve_psd(M: jax.Array, b: jax.Array) -> jax.Array:
    """Solve M x = b for PSD M through the resilience solve ladder: trace-scaled
    jitter + Cholesky, escalating ×10 jitter retries on non-finite results,
    terminal lstsq — all in-graph (``lax.while_loop`` / ``lax.cond``, no host
    syncs; pinned by the ``solve_psd_ladder`` trace contract).

    On a healthy PSD input this is bitwise the old single-attempt solve (the
    level-0 jitter is unchanged); the extra rungs trace but never execute."""
    from repro.resilience.degrade import solve_psd_ladder

    return solve_psd_ladder(M, b)[0]


# --------------------------------------------------------------------------- #
# Exact KRR
# --------------------------------------------------------------------------- #

def krr_exact_fit(K: jax.Array, y: jax.Array, lam: float) -> jax.Array:
    """α = (K + nλI)⁻¹ y; fitted values are K @ α."""
    n = K.shape[0]
    return _solve_psd(K + n * lam * jnp.eye(n, dtype=K.dtype), y)


def krr_exact_fitted(K: jax.Array, y: jax.Array, lam: float) -> jax.Array:
    """Fitted values f̂ = K α of the exact KRR solve — the O(n³) reference
    every sketched error is measured against."""
    return K @ krr_exact_fit(K, y, lam)


# --------------------------------------------------------------------------- #
# Sketched KRR
# --------------------------------------------------------------------------- #

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SketchedKRR:
    """Fitted sketched-KRR model. predict() is O(n_test · m · d).

    ``op`` carries the matrix-free ``KernelOperator`` when the model was fit
    through one; predict then routes K(X_test, landmarks)·θ through the
    operator (fused Pallas path on TPU) — test rows never meet an n×n
    matrix.

    Registered as a pytree (array-bearing fields are leaves, ``kernel_fn`` is
    aux) so models pass through ``jax.jit``/``vmap``/``shard_map`` boundaries:
    ``jax.jit(SketchedKRR.predict)(model, X)`` traces instead of failing on
    the unregistered dataclass, and fitted models can be batched or carried
    through scans.  ``info`` rides as a leaf subtree, not aux — its ``m``/
    ``err`` values are jax scalars (traced under jit on the adaptive paths)."""

    theta: jax.Array                   # (d,) dual coefficients in sketch space
    sk: AccumSketch | None             # structural sketch (None for dense S)
    S_dense: jax.Array | None          # dense sketch (baselines)
    X_train: jax.Array | None
    kernel_fn: Callable | None
    fitted: jax.Array                  # in-sample f̂_S(X) (n,)
    info: dict | None = None           # adaptive-fit stats {"m", "err", ...}
    op: "KernelOperator | None" = None  # matrix-free operator (predict routing)

    def tree_flatten(self):
        """Pytree leaves = arrays/submodels; the kernel callable is aux."""
        children = (self.theta, self.sk, self.S_dense, self.X_train,
                    self.fitted, self.info, self.op)
        return children, (self.kernel_fn,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Inverse of ``tree_flatten`` (jax pytree protocol)."""
        theta, sk, S_dense, X_train, fitted, info, op = children
        return cls(theta=theta, sk=sk, S_dense=S_dense, X_train=X_train,
                   kernel_fn=aux[0], fitted=fitted, info=info, op=op)

    @phase("krr.predict")
    def predict(self, X_test: jax.Array, *, mesh=None) -> jax.Array:
        """Out-of-sample prediction K(X_test, landmarks) θ — O(n_test·m·d)
        kernel evaluations, never an n_test × n matrix.  ``mesh`` shards the
        test rows (operator-fitted models only)."""
        if self.op is not None and self.sk is not None:
            return f32_matmul(self.op.cross_cols(X_test, self.sk, mesh=mesh), self.theta)
        if mesh is not None:
            # every other mesh entry point raises for non-operator inputs;
            # silently running single-device here would be a lie
            raise ValueError("mesh= predict requires a model fitted through "
                             "a KernelOperator")
        assert self.X_train is not None and self.kernel_fn is not None
        if self.sk is not None:
            # landmarks come from the TRAINING rows (the sketch indexes X_train;
            # gathering from X_test — as the seed did via sketch_kernel_cols —
            # read out-of-bounds whenever n_test < n_train and filled NaN)
            from repro.core.kernel_op import stream_cols

            lm = jnp.take(self.X_train, self.sk.indices.reshape(-1), axis=0)
            C_test = stream_cols(X_test, lm, self.sk.coef, self.kernel_fn)
        else:
            K_test = self.kernel_fn(X_test, self.X_train)
            C_test = f32_matmul(K_test, self.S_dense)
        return f32_matmul(C_test, self.theta)


def _fit_from_C(C: jax.Array, W: jax.Array, y: jax.Array, lam: float,
                mesh=None):
    """Given C = K S (n,d) and W = SᵀKS (d,d), solve the Woodbury system.

    With ``mesh`` (row-sharded C) the two n-contractions reduce via psum —
    the d×d solve and the row-wise fitted values need no communication.  C
    may carry zero rows past the n of y (a padded sharded C); the fitted
    values are those of y's rows.
    Returns (theta, fitted, solve-health) — the health dict carries the solve
    ladder's traced scalars and is threaded into ``SketchedKRR.info``."""
    n = y.shape[0]
    with phase("krr.gram"):
        if mesh is not None:
            from repro.core import distributed as D

            CtC = D.sharded_gram(C, C, mesh)
            rhs = D.sharded_gram(C, D._pad_to(y[:, None], C.shape[0]), mesh)[:, 0]
        else:
            CtC = f32_gram(C, C)
            rhs = f32_gram(C, y[:, None])[:, 0]           # SᵀK Y  (K symmetric)
    from repro.resilience.degrade import solve_psd_ladder

    with phase("krr.solve"):
        M = CtC + n * lam * W                      # SᵀK²S + nλ SᵀKS
        theta, health = solve_psd_ladder(M, rhs.astype(M.dtype))
        return theta, f32_matmul(C, theta)[:n], health


@phase("krr.fit")
def krr_sketched_fit(
    K: jax.Array, y: jax.Array, lam: float, sk: AccumSketch,
    X_train: jax.Array | None = None, kernel_fn: Callable | None = None,
    *, use_kernel: bool | None = None, mesh=None,
) -> SketchedKRR:
    """Structural path on K — a precomputed matrix or a matrix-free
    ``KernelOperator``: C and W in one pass, O(n·m·d).

    ``use_kernel`` (auto: True on TPU) routes dense (C, W) through the fused
    single-sweep Pallas kernel instead of two XLA gather passes; an operator
    routes through the fused kernel-eval→GEMM kernel and never forms K.
    With an operator, predict() is wired up automatically (no X_train /
    kernel_fn needed).

    ``mesh`` (operator only) row-shards X and C over a ``("data",)`` device
    mesh: per-device kernel-eval tiles, with W = SᵀC, CᵀC, and Cᵀy reducing
    across shards — only d-vectors and d×d blocks cross devices, so the
    Woodbury solve and predict are unchanged."""
    op = A._operator(K)
    with phase("krr.sketch"):
        if mesh is not None:
            from repro.core import distributed as D

            # C stays padded: its (n, d) slice would sit whole on every device
            C, W = D.sharded_sketch_both(D._operator_required(K), sk,
                                         D.resolve_mesh(mesh),
                                         use_kernel=use_kernel, padded=True)
        else:
            C, W = A.sketch_both(K, sk, use_kernel=use_kernel)
    theta, fitted, health = _fit_from_C(C, W, y, lam, mesh=mesh)
    if op is not None:
        return SketchedKRR(theta, sk, None, op.X, op.kernel_fn, fitted,
                           info=health, op=op)
    return SketchedKRR(theta, sk, None, X_train, kernel_fn, fitted, info=health)


def krr_sketched_fit_dense(
    K: jax.Array, y: jax.Array, lam: float, S: jax.Array,
    X_train: jax.Array | None = None, kernel_fn: Callable | None = None,
) -> SketchedKRR:
    """Dense-sketch baseline path (Gaussian sketching, sparse RP): O(n²d)."""
    C = f32_matmul(K, S)
    W = f32_matmul(S.T, C)
    theta, fitted, health = _fit_from_C(C, W, y, lam)
    return SketchedKRR(theta, None, S, X_train, kernel_fn, fitted, info=health)


def _sketch_left_routed(sk, C, use_kernel: bool | None):
    """W = Sᵀ C through the Pallas left-apply kernel (auto on TPU) or XLA
    gathers (the mesh paths get W from the fused ``sharded_sketch_both``
    launch instead — no second pass over C)."""
    if use_kernel is None:
        use_kernel = A.default_use_kernel()
    if use_kernel:
        from repro.kernels.accum_apply.ops import sketch_left_kernel
        return sketch_left_kernel(sk, C).astype(C.dtype)
    return A.sketch_left(sk, C)


@phase("krr.fit")
def krr_sketched_fit_matfree(
    X, y: jax.Array, lam: float, sk: AccumSketch,
    kernel_fn: Callable | None = None, *, chunk: int | None = None,
    use_kernel: bool | None = None, mesh=None,
) -> SketchedKRR:
    """Matrix-free path: never forms K. C = K S from O(n·m·d) kernel evals;
    W = Sᵀ C is a row gather of C (routed through the Pallas kernel on TPU).
    This is the production configuration.

    ``X`` may be the raw (n, p) data with an explicit ``kernel_fn`` callable,
    or a ``KernelOperator`` (kernel_fn omitted) — the operator additionally
    unlocks the fused Pallas kernel-eval→GEMM path for C, and ``mesh``
    (operator only) shards the whole fit over a data mesh."""
    op = A._operator(X)
    if mesh is not None and op is None:
        raise ValueError("mesh= sharding requires a KernelOperator input")
    with phase("krr.sketch"):
        if op is not None:
            if mesh is not None:
                # fused single launch: W gathered in-body, no second pass over C
                C, W = op.sketch_both(sk, chunk=chunk, use_kernel=use_kernel,
                                      mesh=mesh)
            else:
                C = op.sketch_cols(sk, chunk=chunk, use_kernel=use_kernel)
                W = _sketch_left_routed(sk, C, use_kernel)
            X, kernel_fn = op.X, op.kernel_fn
        else:
            C = A.sketch_kernel_cols(X, sk, kernel_fn, chunk=chunk)
            W = _sketch_left_routed(sk, C, use_kernel)
        # symmetrize W: SᵀKS is symmetric in exact arithmetic
        W = 0.5 * (W + W.T)
    theta, fitted, health = _fit_from_C(C, W, y, lam, mesh=mesh)
    return SketchedKRR(theta, sk, None, X, kernel_fn, fitted, info=health, op=op)


def _pcg_solve(C: jax.Array, W: jax.Array, y: jax.Array, lam: float,
               iters: int, mesh=None) -> jax.Array:
    """Preconditioned CG on the Woodbury system (CᵀC + nλ W) θ = Cᵀy with the
    Cholesky of (W + jitter) as preconditioner.  Never materializes CᵀC.

    With ``mesh`` (row-sharded C) each CG iteration stays communication-thin:
    C@t is a per-shard matvec, Cᵀ(·) a psum of d-vectors — the preconditioner
    solve and every other CG vector is d-sized and replicated."""
    n, d = C.shape
    if mesh is not None:
        from repro.core import distributed as D

        def _ct(v):
            return D.sharded_gram(C, v[:, None], mesh)[:, 0]
    else:
        def _ct(v):
            return f32_matmul(C.T, v)
    jitter = 1e-8 * (jnp.trace(W) / d + 1e-30)
    L, lower = jax.scipy.linalg.cho_factor(
        W + jitter * jnp.eye(d, dtype=W.dtype), lower=True)

    def matvec(t):
        return _ct(f32_matmul(C, t)) + n * lam * f32_matmul(W, t)

    def precond(r):
        # (nλ W)⁻¹ ≈ the dominant small-eigenvalue part of the operator
        return jax.scipy.linalg.cho_solve((L, lower), r) / (n * lam)

    rhs = _ct(y)
    # tol below f32 CG's stagnation floor: iterate to maxiter (or stagnation)
    # rather than parking at cg's loose 1e-5 default — the solutions two
    # reduction orders converge to must agree to ≤ 1e-5, not just their
    # residual norms
    theta, _ = jax.scipy.sparse.linalg.cg(matvec, rhs, M=precond,
                                          maxiter=iters, tol=1e-7)
    return theta


def krr_sketched_fit_pcg(
    X, y: jax.Array, lam: float, sk: AccumSketch,
    kernel_fn: Callable | None = None, *, iters: int = 30,
    chunk: int | None = None, use_kernel: bool | None = None, mesh=None,
) -> SketchedKRR:
    """Falkon-flavoured solver (Rudi et al. 2017) on the accumulation sketch:
    preconditioned CG on the Woodbury system

        (CᵀC + nλ W) θ = Cᵀy,   C = K S (matrix-free),  W = SᵀKS

    with the Cholesky of (W + nλ-scaled jitter) as preconditioner — the
    paper's point in §3.3: accumulation keeps the preconditioner d×d (one
    Cholesky of the SMALL matrix) where a vanilla md-landmark Nyström solve
    would factor an (md)×(md) system. O(n·m·d·iters), never forms K, and never
    materializes CᵀC (CG touches it only through matvecs).

    ``X``: raw data + ``kernel_fn`` callable, or a ``KernelOperator``
    (required for ``mesh`` sharding)."""
    op = A._operator(X)
    if mesh is not None and op is None:
        raise ValueError("mesh= sharding requires a KernelOperator input")
    if op is not None:
        if mesh is not None:
            # fused single launch: W gathered in-body, no second pass over C
            C, W = op.sketch_both(sk, chunk=chunk, use_kernel=use_kernel,
                                  mesh=mesh)
        else:
            C = op.sketch_cols(sk, chunk=chunk, use_kernel=use_kernel)
            W = _sketch_left_routed(sk, C, use_kernel)
        X, kernel_fn = op.X, op.kernel_fn
    else:
        C = A.sketch_kernel_cols(X, sk, kernel_fn, chunk=chunk)
        W = _sketch_left_routed(sk, C, use_kernel)
    W = 0.5 * (W + W.T)
    theta = _pcg_solve(C, W, y, lam, iters, mesh=mesh)
    return SketchedKRR(theta, sk, None, X, kernel_fn, f32_matmul(C, theta), op=op)


# --------------------------------------------------------------------------- #
# Adaptive (progressive-accumulation) variants
# --------------------------------------------------------------------------- #

def krr_sketched_fit_adaptive(
    K: jax.Array, y: jax.Array, lam: float, key: jax.Array, d: int, *,
    tol: float = 1e-2, m_max: int = 32, probs: jax.Array | None = None,
    estimator=None, check_every: int = 1,
    X_train: jax.Array | None = None, kernel_fn: Callable | None = None,
    use_kernel: bool | None = None, mesh=None, schedule: str = "doubling",
    scheme: str = "uniform", scheme_lam: float | None = None,
) -> SketchedKRR:
    """Sketched KRR with the sketch size chosen by the progressive engine:
    grow m one slab at a time (O(n·d) incremental (C, W) updates) until the
    plug-in error estimate clears ``tol`` or ``m_max`` is reached, then solve
    the Woodbury system with the (C, W) already accumulated — no recompute.

    This is the paper's rescue of suboptimal sampling: callers specify an
    error target, not m, and cheap uniform / approximate-leverage
    probabilities simply buy more slabs.  Growth runs on the DOUBLING
    schedule by default — batched rank-B slabs, O(log m) data passes
    (``info["passes"]``); pass ``schedule="unit"`` for one-slab-per-pass.
    ``K`` may be dense or a ``KernelOperator`` (the engine then grows
    matrix-free: each batch is ONE kernel-eval column-block sweep), and
    ``mesh`` (operator only) runs the whole growth data-parallel with
    identical index draws.

    ``scheme`` selects the sampling scheme (``"uniform"`` / ``"leverage"`` /
    ``"poisson"``).  ``scheme_lam`` is the ridge level at which the leverage
    refinement estimates ridge-leverage scores; it is deliberately decoupled
    from the fit's λ (default: the engine's 1e-3) — scores estimated at a
    coarse ridge whose statistical dimension is O(d) resolve exactly the
    directions a d-column sketch can capture, whereas a tiny fit λ flattens
    the score profile toward rank indicators."""
    op = A._operator(K)
    sk, C, W, info = A.grow_sketch_both(
        key, K, d, m_max=m_max, tol=tol, probs=probs, estimator=estimator,
        check_every=check_every, use_kernel=use_kernel, mesh=mesh,
        schedule=schedule, scheme=scheme, scheme_lam=scheme_lam)
    theta, fitted, health = _fit_from_C(C, W, y, lam, mesh=mesh)
    info = {**info, **health}
    if op is not None:
        return SketchedKRR(theta, sk, None, op.X, op.kernel_fn, fitted,
                           info=info, op=op)
    return SketchedKRR(theta, sk, None, X_train, kernel_fn, fitted, info=info)


def krr_sketched_fit_pcg_adaptive(
    K: jax.Array, y: jax.Array, lam: float, key: jax.Array, d: int, *,
    tol: float = 1e-2, m_max: int = 32, iters: int = 30,
    probs: jax.Array | None = None, estimator=None, check_every: int = 1,
    X_train: jax.Array | None = None, kernel_fn: Callable | None = None,
    use_kernel: bool | None = None, mesh=None, schedule: str = "doubling",
    scheme: str = "uniform", scheme_lam: float | None = None,
) -> SketchedKRR:
    """Adaptive-m Falkon-style PCG: the progressive engine grows (C, W) to the
    error target (doubling schedule by default — O(log m) data passes), then
    CG reuses the incremental pair directly — the d×d preconditioner never
    changes size while m grows (paper §3.3).  ``K`` may be dense or a
    matrix-free ``KernelOperator`` (required for ``mesh``).  ``scheme``
    selects the sampling scheme; ``scheme_lam`` the leverage-estimation ridge
    (default: the engine's 1e-3, decoupled from the fit's λ — see
    ``krr_sketched_fit_adaptive``)."""
    op = A._operator(K)
    sk, C, W, info = A.grow_sketch_both(
        key, K, d, m_max=m_max, tol=tol, probs=probs, estimator=estimator,
        check_every=check_every, use_kernel=use_kernel, mesh=mesh,
        schedule=schedule, scheme=scheme, scheme_lam=scheme_lam)
    theta = _pcg_solve(C, W, y, lam, iters, mesh=mesh)
    if op is not None:
        return SketchedKRR(theta, sk, None, op.X, op.kernel_fn, f32_matmul(C, theta),
                           info=info, op=op)
    return SketchedKRR(theta, sk, None, X_train, kernel_fn, f32_matmul(C, theta),
                       info=info)


def insample_error(f_a: jax.Array, f_b: jax.Array) -> jax.Array:
    """‖f_a − f_b‖_n² = (1/n) Σ_i (f_a(x_i) − f_b(x_i))²  (empirical L2 norm)."""
    d = f_a - f_b
    return jnp.mean(d * d)
