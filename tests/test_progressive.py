"""Tests for the progressive accumulation engine: incremental m → m+1 updates
of (C, W), the adaptive stopping rule, and the grow/append sketch API.

The load-bearing guarantees (ISSUE 2 acceptance criteria):
  * growing step-by-step to m matches the one-shot ``make_accum_sketch`` +
    ``sketch_both`` at that m to ≤ 1e-5 relative error (f32, same keys);
  * one step is asymptotically O(n·d) — no O(n²·d) recompute and no n²-sized
    intermediate in the jaxpr.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply as A
from repro.core.kernels_math import gaussian_kernel, laplacian_kernel
from repro.core.sketch import (
    AccumSketch,
    append_subsample,
    make_accum_sketch,
    make_accum_sketch_jit,
)

KEY = jax.random.PRNGKey(0)


def _psd_kernel(n: int, p: int = 3, bandwidth: float = 0.6, seed: int = 0):
    X = jax.random.uniform(jax.random.fold_in(KEY, seed), (n, p))
    return gaussian_kernel(X, X, bandwidth=bandwidth)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


# --------------------------------------------------------------------------- #
# incremental update ≡ one-shot construction
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("m", [1, 3, 6])
def test_incremental_matches_one_shot(m):
    """Growing to m slab-by-slab equals make_accum_sketch + sketch_both at the
    final m, given the same key (engine pre-draws with the same RNG scheme)."""
    n, d = 300, 16
    K = _psd_kernel(n)
    sk = make_accum_sketch(KEY, n, d, m)
    C_ref, W_ref = A.sketch_both(K, sk, use_kernel=False)

    state = A.accum_init(KEY, n, d, m)
    state = A.accum_grow(K, state, m, use_kernel=False)
    assert bool(jnp.all(state.indices == sk.indices))
    assert _rel(state.C, C_ref.astype(jnp.float32)) < 1e-5
    assert _rel(state.W, W_ref.astype(jnp.float32)) < 1e-5
    assert int(state.m) == m


def test_incremental_kernel_path_matches_xla_path():
    """The single-slab Pallas entry point (interpret on CPU) and the XLA
    gather path produce the same trajectory."""
    n, d, m = 256, 16, 4
    K = _psd_kernel(n, seed=1)
    s_xla = A.accum_grow(K, A.accum_init(KEY, n, d, m), m, use_kernel=False)
    s_krn = A.accum_grow(K, A.accum_init(KEY, n, d, m), m, use_kernel=True)
    np.testing.assert_allclose(np.asarray(s_krn.C), np.asarray(s_xla.C),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_krn.W), np.asarray(s_xla.W),
                               rtol=1e-5, atol=1e-5)


def test_truncated_state_sketch_consistent_with_from_scratch():
    """grow_sketch_both's (sk, C, W) is self-consistent: re-applying the
    returned (truncated, renormalized) sketch from scratch reproduces C, W."""
    n, d = 200, 12
    K = _psd_kernel(n, seed=2)
    sk, C, W, info = A.grow_sketch_both(KEY, K, d, m_max=8, tol=0.15)
    assert 1 <= info["m"] <= 8 and sk.m == info["m"]
    C_ref, W_ref = A.sketch_both(K, sk, use_kernel=False)
    assert _rel(C, C_ref.astype(jnp.float32)) < 1e-5
    assert _rel(W, W_ref.astype(jnp.float32)) < 1e-5


# --------------------------------------------------------------------------- #
# O(n·d) per step — jaxpr / FLOP regression
# --------------------------------------------------------------------------- #

def _iter_eqns(jaxpr):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subjaxprs(val):
        if isinstance(val, ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, Jaxpr):
            yield val
        elif isinstance(val, (list, tuple)):
            for v in val:
                yield from subjaxprs(v)

    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in subjaxprs(val):
                yield from _iter_eqns(sub)


def test_step_has_no_quadratic_intermediate():
    """jaxpr regression: every intermediate of one engine step is O(n·d) —
    the O(n²·d) (or even n²) from-scratch recompute never appears."""
    n, d, m = 256, 8, 4
    K = _psd_kernel(n, seed=3)
    state = A.accum_init(KEY, n, d, m)
    jaxpr = jax.make_jaxpr(
        lambda K, s: A.accum_step(K, s, use_kernel=False))(K, state)
    budget = 6 * n * d                      # generous O(n·d); n² = 65536 ≫ this
    for eqn in _iter_eqns(jaxpr.jaxpr):
        for v in eqn.outvars:
            size = int(np.prod(v.aval.shape)) if v.aval.shape else 1
            assert size <= budget, (eqn.primitive.name, v.aval.shape)


def test_step_flops_scale_linearly_in_n():
    """FLOP regression via XLA cost analysis: doubling n must ~double (not
    quadruple) the cost of one incremental step."""

    def flops_at(n):
        d, m = 16, 4
        K = _psd_kernel(n, seed=4)
        state = A.accum_init(KEY, n, d, m)
        step = jax.jit(lambda K, s: A.accum_step(K, s, use_kernel=False))
        cost = step.lower(K, state).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        if not cost or "flops" not in cost:
            pytest.skip("XLA cost analysis unavailable on this backend")
        return float(cost["flops"])

    f1, f2 = flops_at(512), flops_at(1024)
    assert f2 / f1 < 3.0, f"step cost superlinear in n: {f1} -> {f2}"


# --------------------------------------------------------------------------- #
# adaptive stopping rule
# --------------------------------------------------------------------------- #

def test_adaptive_stops_early_on_easy_kernel():
    """A fast-decaying spectrum clears a loose tolerance at small m."""
    n, d = 300, 24
    K = _psd_kernel(n, bandwidth=0.8, seed=5)
    sk, C, W, info = A.grow_sketch_both(KEY, K, d, m_max=16, tol=0.2)
    assert info["m"] < 16 and info["err"] <= 0.2


def test_adaptive_exhausts_budget_on_unreachable_tol():
    n, d = 200, 8
    X = jax.random.uniform(jax.random.fold_in(KEY, 6), (n, 3))
    K = laplacian_kernel(X, X, bandwidth=0.5)      # heavy spectral tail
    sk, C, W, info = A.grow_sketch_both(KEY, K, d, m_max=6, tol=1e-6)
    assert info["m"] == 6                          # ran out of slabs
    assert np.isfinite(info["err"]) and info["err"] > 1e-6


def test_estimators_agree_on_scale():
    """Holdout and Hutchinson rules both report a small error for a sketch
    that reconstructs K well, and both are plain AccumState → scalar."""
    n, d = 300, 64
    K = _psd_kernel(n, bandwidth=0.8, seed=7)
    state = A.accum_grow(K, A.accum_init(KEY, n, d, 8), 8, use_kernel=False)
    e_hold = A.make_holdout_estimator(jax.random.fold_in(KEY, 1), K)(state)
    e_hutch = A.make_hutchinson_estimator(jax.random.fold_in(KEY, 2), K)(state)
    assert float(e_hold) < 0.05 and float(e_hutch) < 0.05


def test_adaptive_check_every_amortization():
    """check_every > 1 evaluates the estimator on a stride but still stops."""
    n, d = 250, 16
    K = _psd_kernel(n, bandwidth=0.7, seed=8)
    est = A.make_holdout_estimator(jax.random.fold_in(KEY, 3), K)
    state = A.accum_init(KEY, n, d, 12)
    out = A.accum_grow_adaptive(K, state, tol=0.25, estimator=est,
                                check_every=3, use_kernel=False)
    assert int(out.m) % 3 == 0 or int(out.m) == 12
    assert float(out.err) <= 0.25 or int(out.m) == 12


# --------------------------------------------------------------------------- #
# grow/append sketch API + constructor bugfixes
# --------------------------------------------------------------------------- #

def test_append_subsample_rescales_survivors():
    sk = make_accum_sketch(KEY, 100, 8, 4)
    sk2 = append_subsample(sk, jax.random.fold_in(KEY, 9))
    assert sk2.m == 5 and bool(jnp.all(sk2.indices[:4] == sk.indices))
    np.testing.assert_allclose(np.asarray(sk2.coef[:4]),
                               np.asarray(sk.coef) * np.sqrt(4 / 5), rtol=1e-6)
    # dense identity: S_5 = sqrt(4/5) S_4 + T̃_5
    T = AccumSketch(indices=sk2.indices[4:], signs=sk2.signs[4:],
                    probs=sk2.probs, n=sk2.n)
    T5 = np.asarray(T.dense()) * np.sqrt(1 / 5)    # renormalize m=1 → slab-of-5
    np.testing.assert_allclose(np.asarray(sk2.dense()),
                               np.sqrt(4 / 5) * np.asarray(sk.dense()) + T5,
                               rtol=1e-5, atol=1e-6)


def test_truncated_renormalizes():
    sk = make_accum_sketch(KEY, 80, 6, 5)
    tr = sk.truncated(3)
    ref = AccumSketch(indices=sk.indices[:3], signs=sk.signs[:3],
                      probs=sk.probs, n=sk.n)
    np.testing.assert_allclose(np.asarray(tr.coef), np.asarray(ref.coef),
                               rtol=1e-6)


def test_make_accum_sketch_jit_propagates_dtype():
    """Seed bug: make_accum_sketch_jit ignored dtype (always f32)."""
    sk16 = make_accum_sketch_jit(KEY, 64, 8, 2, dtype=jnp.bfloat16)
    assert sk16.signs.dtype == jnp.bfloat16
    assert sk16.probs.dtype == jnp.bfloat16
    assert sk16.coef.dtype == jnp.bfloat16
    sk32 = make_accum_sketch_jit(KEY, 64, 8, 2)
    assert sk32.signs.dtype == jnp.float32


def test_coef_is_cached_and_correct():
    """Constructors populate coef_ so hot loops skip the probs gather; the
    cache matches the recomputed value and survives pytree round-trips."""
    sk = make_accum_sketch(KEY, 64, 8, 3)
    assert sk.coef_ is not None
    uncached = dataclasses.replace(sk, coef_=None)
    np.testing.assert_allclose(np.asarray(sk.coef), np.asarray(uncached.coef),
                               rtol=1e-7)
    leaves, treedef = jax.tree_util.tree_flatten(sk)
    sk2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert sk2.coef_ is not None
    np.testing.assert_allclose(np.asarray(sk2.coef), np.asarray(sk.coef))


# --------------------------------------------------------------------------- #
# jittable driver (traced info scalars + masked sketch)
# --------------------------------------------------------------------------- #

def test_grow_sketch_both_is_jittable():
    """The one-call driver must trace: ``info``'s m/err come back as traced
    scalars (the seed's int()/float() forced a host sync per call) and the
    sketch degrades to the masked full-size form, which applies identically
    to the eager truncation."""
    n, d = 200, 12
    K = _psd_kernel(n, seed=3)

    sk_e, C_e, W_e, info_e = A.grow_sketch_both(KEY, K, d, m_max=8, tol=0.15,
                                                use_kernel=False)

    @jax.jit
    def driver(key, K):
        sk, C, W, info = A.grow_sketch_both(key, K, d, m_max=8, tol=0.15,
                                            use_kernel=False)
        # applying the masked sketch INSIDE the trace must work too
        C2 = A.sketch_right(K, sk)
        return sk, C, W, info, C2

    sk_j, C_j, W_j, info_j, C2 = driver(KEY, K)
    assert int(info_j["m"]) == int(info_e["m"])
    np.testing.assert_allclose(float(info_j["err"]), float(info_e["err"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(C_j), np.asarray(C_e),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(W_j), np.asarray(W_e),
                               rtol=1e-5, atol=1e-6)
    # masked sketch ≡ truncated sketch under every bilinear application
    assert sk_j.m == 8 and sk_e.m == int(info_e["m"])   # static vs truncated
    np.testing.assert_allclose(np.asarray(C2), np.asarray(A.sketch_right(K, sk_e)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sk_j.dense()),
                               np.asarray(sk_e.dense()), rtol=1e-5, atol=1e-6)


def test_adaptive_krr_driver_jits_end_to_end():
    """The adaptive KRR caller can stay inside jit: fit + predict traced."""
    from repro.core.krr import krr_sketched_fit_adaptive

    n, d = 200, 12
    X = jax.random.uniform(jax.random.fold_in(KEY, 9), (n, 3))
    K = gaussian_kernel(X, X, bandwidth=0.6)
    y = jnp.sin(3.0 * X[:, 0])

    eager = krr_sketched_fit_adaptive(K, y, 1e-2, KEY, d, tol=0.1, m_max=8,
                                      use_kernel=False)

    @jax.jit
    def fit(K, y):
        mdl = krr_sketched_fit_adaptive(K, y, 1e-2, KEY, d, tol=0.1, m_max=8,
                                        use_kernel=False)
        return mdl.fitted, mdl.info["m"], mdl.info["err"]

    fitted, m, err = fit(K, y)
    assert int(m) == int(eager.info["m"])
    np.testing.assert_allclose(np.asarray(fitted), np.asarray(eager.fitted),
                               rtol=1e-4, atol=1e-5)
