"""Plain reference of sketched Gaussian KRR (paper eq. 3), blocked over rows.

    f̂(x) = K(x, X) S θ,   (SᵀK²S + nλ SᵀKS + j·I) θ = SᵀK y

with S the accumulation of m uniform sub-sampling matrices given by the
draws (indices (m, d), signs (m, d)): column j of S holds
r_ij · sqrt(n / (d·m)) at row n_ij for each i.  The solve adds the jitter
j = 1e-8 · tr(M) / d that the system under test documents for its
Cholesky rung.  K is never formed: C = K S is built row block by row
block, and only its Grams CᵀC and Cᵀy are kept.

It imports nothing of the system under test.  ``fit_f64`` runs in float64
NumPy on the host, its row blocks spread over threads.  ``fit_xp`` is the
same arithmetic for any array namespace and matmul, which the control
(``fit_device``) runs on the device at a lower precision.

A fit is judged by three numbers: whether its θ solves the reference's
equations (``solve_residual``), and whether its C θ on the training rows
(``fitted_error``) and its predictions on the test rows
(``predict_error``) are what that θ gives with the reference's kernel
columns.  Predictions are not compared with the reference's own: M is
ill-conditioned at small λ (cond ~1e5), so a float32 solve lands some 1e-5
from the float64 θ at any contraction precision, and that distance cannot
tell a sound float32 fit from a less precise one.

On a TPU v5e, the float32 kernel columns themselves (the distance
formula and exp in float32) put a floor of some 2e-6 – 4e-6 under
``fitted_error`` and ``predict_error``, which a fit at three bfloat16
passes exceeds only about threefold.  ``excess`` measures an error against
that floor, read from ``fit_device`` at the stated precision on the same
draws.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import math
import os

import numpy as np

JITTER = 1e-8
BLOCK = 2048


def coefficients(signs, n: int):
    """r_ij / sqrt(d·m·p) with p = 1/n (uniform draws)."""
    m, d = signs.shape
    return signs * math.sqrt(n / (d * m))


def _cols(xp, mm, Xb, L, l2, coef, bandwidth):
    """K(Xb, landmarks)·S for one row block: (b, d)."""
    m, d = coef.shape
    d2 = xp.sum(Xb * Xb, axis=1)[:, None] + l2[None, :] - 2.0 * mm(Xb, L.T)
    Kb = xp.exp(-xp.maximum(d2, 0.0) / (2.0 * bandwidth**2))
    return xp.sum(Kb.reshape(Xb.shape[0], m, d) * coef[None], axis=1)


def _cols_f64(Xb, L, l2, coef, bandwidth):
    """``_cols`` in float64 NumPy, in place: one (b, m·d) buffer."""
    m, d = coef.shape
    K = Xb @ L.T
    K *= -2.0
    K += np.sum(Xb * Xb, axis=1)[:, None]
    K += l2[None, :]
    np.maximum(K, 0.0, out=K)
    K *= -1.0 / (2.0 * bandwidth**2)
    np.exp(K, out=K)
    K = K.reshape(Xb.shape[0], m, d)
    K *= coef[None]
    return K.sum(axis=1)


@dataclasses.dataclass
class Solution:
    """A fit: the system M θ = b, θ, the training rows' C θ (``fitted``),
    the test rows' columns Cte = K(X_test, X)·S and ``pred`` = Cte θ.  The
    float64 reference also keeps C·probes for θs handed to it."""

    M: object
    b: object
    theta: object
    fitted: object
    Cte: object
    pred: object
    C_probes: object = None


def fit_xp(Xtr, ytr, Xte, indices, signs, lam, bandwidth, *, xp, mm, solve,
           block: int = BLOCK) -> Solution:
    """The whole fit and predict with every step in namespace ``xp``."""
    n = Xtr.shape[0]
    coef = coefficients(signs, n)
    m, d = coef.shape
    L = Xtr[indices.reshape(-1)]
    l2 = xp.sum(L * L, axis=1)

    def cols(A, s):
        return _cols(xp, mm, A[s:s + block], L, l2, coef, bandwidth)

    CtC = xp.zeros((d, d), Xtr.dtype)
    Cty = xp.zeros((d,), Xtr.dtype)
    for s in range(0, n, block):
        Cb = cols(Xtr, s)
        CtC = CtC + mm(Cb.T, Cb)
        Cty = Cty + mm(Cb.T, ytr[s:s + block])
    # W = SᵀKS: the rows of C at the landmarks, combined by S
    CL = _cols(xp, mm, L, L, l2, coef, bandwidth)
    W = xp.sum((coef.reshape(-1, 1) * CL).reshape(m, d, d), axis=0)
    M = CtC + n * lam * W
    M = M + JITTER * xp.trace(M) / d * xp.eye(d, dtype=M.dtype)
    theta = solve(M, Cty)
    fitted = xp.concatenate([mm(cols(Xtr, s), theta) for s in range(0, n, block)])
    Cte = xp.concatenate([cols(Xte, s) for s in range(0, Xte.shape[0], block)])
    return Solution(M, Cty, theta, fitted, Cte, mm(Cte, theta))


def fit_f64(Xtr, ytr, Xte, indices, signs, lam, bandwidth, probes=None, *,
            block: int = BLOCK, workers: int | None = None) -> Solution:
    """``fit_xp`` in float64 NumPy, row blocks on ``workers`` threads (each
    running single-threaded BLAS).  ``probes`` (k, d): θs whose C θ on the
    training rows is wanted too (``C_probes``, (k, n))."""
    from threadpoolctl import threadpool_limits

    Xtr = np.asarray(Xtr, np.float64)
    ytr = np.asarray(ytr, np.float64)
    Xte = np.asarray(Xte, np.float64)
    indices = np.asarray(indices)
    n = Xtr.shape[0]
    if indices.min() < 0 or indices.max() >= n:
        raise ValueError("sketch indices out of range")
    coef = coefficients(np.asarray(signs, np.float64), n)
    m, d = coef.shape
    probes = np.zeros((0, d)) if probes is None else np.asarray(probes, np.float64)
    L = Xtr[indices.reshape(-1)]
    l2 = np.sum(L * L, axis=1)
    workers = workers or os.cpu_count() or 1

    def grams(s):
        Cb = _cols_f64(Xtr[s:s + block], L, l2, coef, bandwidth)
        return Cb.T @ Cb, Cb.T @ ytr[s:s + block], Cb @ probes.T

    def cols(A):
        return lambda s: _cols_f64(A[s:s + block], L, l2, coef, bandwidth)

    with threadpool_limits(limits=1), cf.ThreadPoolExecutor(workers) as pool:
        CtC = np.zeros((d, d))
        Cty = np.zeros(d)
        C_probes = []
        for g, r, Cp in pool.map(grams, range(0, n, block)):
            CtC += g
            Cty += r
            C_probes.append(Cp)
        CL = np.concatenate(list(pool.map(cols(L), range(0, L.shape[0], block))))
        Cte = np.concatenate(list(pool.map(cols(Xte),
                                           range(0, Xte.shape[0], block))))
    W = np.sum((coef.reshape(-1, 1) * CL).reshape(m, d, d), axis=0)
    M = CtC + n * lam * W
    M = M + JITTER * np.trace(M) / d * np.eye(d)
    import scipy.linalg

    theta = scipy.linalg.solve(M, Cty, assume_a="pos")
    return Solution(M, Cty, theta, None, Cte, Cte @ theta,
                    np.concatenate(C_probes).T)


def _mm_highest(a, b):
    import jax
    import jax.numpy as jnp

    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def matmul_high(a, b):
    """a @ b as the TPU's HIGH (three-pass bfloat16) precision computes it,
    on any backend: each float32 operand split into a bfloat16 head and
    tail, the tail·tail product dropped, float32 accumulation."""
    import jax.numpy as jnp

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)

    (ah, al), (bh, bl) = split(a), split(b)
    return _mm_highest(ah, bh) + (_mm_highest(ah, bl) + _mm_highest(al, bh))


def matmul_bf16(a, b):
    """a @ b with bfloat16 operands and float32 accumulation (one pass)."""
    import jax.numpy as jnp

    return _mm_highest(a.astype(jnp.bfloat16).astype(jnp.float32),
                       b.astype(jnp.bfloat16).astype(jnp.float32))


MATMULS = {"highest": _mm_highest, "high": matmul_high, "bf16": matmul_bf16}


def fit_device(Xtr, ytr, Xte, indices, signs, lam, bandwidth,
               precision: str) -> Solution:
    """``fit_xp`` in float32 on the default device, every contraction at
    ``precision`` (``MATMULS``).  The control runs it one step below the
    precision that the configuration states."""
    import jax.numpy as jnp
    import jax.scipy.linalg as jsl

    f32 = [jnp.asarray(a, jnp.float32) for a in (Xtr, ytr, Xte, signs)]
    return fit_xp(f32[0], f32[1], f32[2], jnp.asarray(indices), f32[3], lam,
                  bandwidth, xp=jnp, mm=MATMULS[precision],
                  solve=lambda M, b: jsl.cho_solve(jsl.cho_factor(M), b))


def _norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64)))


def _rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    if got.shape != np.shape(want) or not np.all(np.isfinite(got)):
        return math.inf
    return _norm(got - want) / _norm(want)


def solve_residual(ref: Solution, theta) -> float:
    """‖M θ − b‖ / ‖b‖ of a θ in the reference's float64 system."""
    theta = np.asarray(theta, np.float64)
    if theta.shape != np.shape(ref.b):
        return math.inf
    return _rel(np.asarray(ref.M) @ theta, ref.b)


def fitted_error(ref: Solution, probe: int, fitted) -> float:
    """‖fitted − C θ‖ / ‖C θ‖ on the training rows, for the θ that was
    ``probes[probe]`` of the reference's fit."""
    return _rel(fitted, ref.C_probes[probe])


def excess(err: float, floor: float) -> float:
    """How far an error exceeds the floor of the plain float32 reference
    on the same draws, as a share of that floor: sqrt(err² − floor²) /
    floor, 0 at or below the floor.  Errors from independent sources add
    in squares, so this is the size of what a fit adds to the float32
    floor: about 0.2 for a fit as precise as the reference, about 3 for one
    whose contractions lose what three bfloat16 passes lose."""
    if not (math.isfinite(err) and math.isfinite(floor)) or floor <= 0:
        return math.inf
    return math.sqrt(max(err * err - floor * floor, 0.0)) / floor


def predict_error(ref: Solution, theta, pred) -> float:
    """‖pred − Cte θ‖ / ‖Cte θ‖ on the test rows."""
    theta = np.asarray(theta, np.float64)
    if theta.shape != np.shape(ref.b):
        return math.inf
    return _rel(pred, np.asarray(ref.Cte) @ theta)
