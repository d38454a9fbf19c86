"""Sketched KRR tests: the paper's estimator, error-vs-m monotonicity (Thm 8
empirics), leverage scores, incoherence, and K-satisfiability."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    get_kernel,
    incoherence,
    insample_error,
    krr_exact_fitted,
    krr_sketched_fit,
    krr_sketched_fit_dense,
    krr_sketched_fit_matfree,
    ksat_check,
    leverage_probs,
    leverage_scores,
    make_accum_sketch,
    make_gaussian_sketch,
    spectrum,
    statistical_dimension,
    d_delta,
    approx_leverage_probs,
)

KEY = jax.random.PRNGKey(7)


def _toy(n=400, noise=0.5):
    """The paper's bimodal distribution over R^3 (appendix D.2, scaled down)."""
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    gamma = 0.6
    n2 = max(int(n**gamma * n / (n + n**gamma)), 8)
    x1 = jax.random.uniform(k1, (n - n2, 3))
    x2 = 2.0 + 0.5 * jax.random.beta(k2, 1.0, 2.0, (n2, 3))
    X = jnp.concatenate([x1, x2])
    g = lambda x: 1.6 * jnp.abs((x - 0.4) * (x - 0.6)) - x * (x - 1) * (x - 2) - 0.5
    f = g(jnp.linalg.norm(X, axis=1) / 3.0)
    y = f + noise * jax.random.normal(k3, (n,))
    return X, y, f


def test_exact_krr_recovers_signal():
    X, y, f = _toy()
    kern = get_kernel("gaussian", bandwidth=0.75)
    K = kern(X, X)
    fitted = krr_exact_fitted(K, y, lam=1e-3)
    assert insample_error(fitted, f) < insample_error(y, f)


def test_error_decreases_with_m():
    """The paper's central empirical claim (Fig. 2): at fixed d, increasing m
    drives ‖f̂_S − f̂_n‖²_n down toward the Gaussian-sketch level."""
    n = 400
    X, y, f = _toy(n)
    # the paper's own hyper-parameters (appendix D.2): σ = 1.5 n^{-1/7},
    # λ = 0.5 n^{-4/7}, d = 1.5 n^{3/7} — the regime where uniform Nyström
    # fails on the bimodal data (high incoherence) and accumulation repairs it
    kern = get_kernel("gaussian", bandwidth=1.5 * n ** (-1 / 7))
    K = kern(X, X)
    lam = 0.5 * n ** (-4 / 7)
    fn = krr_exact_fitted(K, y, lam)
    d = int(1.5 * n ** (3 / 7))
    errs = {}
    for m in [1, 4, 16]:
        e = []
        for rep in range(5):
            sk = make_accum_sketch(jax.random.fold_in(KEY, 100 * m + rep), X.shape[0], d, m)
            mod = krr_sketched_fit(K, y, lam, sk)
            e.append(float(insample_error(mod.fitted, fn)))
        errs[m] = float(np.mean(e))
    assert errs[4] < errs[1] * 0.1, errs     # orders-of-magnitude repair
    assert errs[16] < errs[1] * 0.1, errs
    # Gaussian sketch benchmark: m=16 should be within ~4x of it
    eg = []
    for rep in range(5):
        S = make_gaussian_sketch(jax.random.fold_in(KEY, rep), X.shape[0], d)
        eg.append(float(insample_error(krr_sketched_fit_dense(K, y, lam, S).fitted, fn)))
    assert errs[16] < 4.0 * float(np.mean(eg)) + 1e-6


def test_matfree_equals_structural():
    X, y, _ = _toy(n=200)
    kern = get_kernel("matern", bandwidth=1.0, nu=1.5)
    K = kern(X, X)
    sk = make_accum_sketch(KEY, 200, 24, 4)
    a = krr_sketched_fit(K, y, 1e-3, sk, X, kern)
    b = krr_sketched_fit_matfree(X, y, 1e-3, sk, kern)
    np.testing.assert_allclose(a.fitted, b.fitted, rtol=2e-3, atol=2e-3)
    Xt = X[:16] + 0.01
    np.testing.assert_allclose(a.predict(Xt), b.predict(Xt), rtol=2e-3, atol=2e-3)


def test_matfree_chunked_equals_unchunked():
    X, y, _ = _toy(n=192)
    kern = get_kernel("gaussian", bandwidth=0.75)
    sk = make_accum_sketch(KEY, 192, 16, 2)
    a = krr_sketched_fit_matfree(X, y, 1e-3, sk, kern)
    b = krr_sketched_fit_matfree(X, y, 1e-3, sk, kern, chunk=64)
    # the chunked C itself is tight; the solve amplifies the f32 reorder noise
    # by cond(SᵀK²S + nλSᵀKS), so the fitted values get a looser bound
    from repro.core import sketch_kernel_cols
    np.testing.assert_allclose(
        sketch_kernel_cols(X, sk, kern),
        sketch_kernel_cols(X, sk, kern, chunk=64), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(a.fitted, b.fitted, rtol=2e-2, atol=2e-2)


def test_fused_kernel_routing_matches_seed_path():
    """The Pallas-routed fits (use_kernel=True: fused sketch_both / GEMM
    sketch_left) reproduce the XLA-gather path within 1e-4 on the paper's
    bimodal fixtures."""
    from repro.core import krr_sketched_fit_pcg

    X, y, _ = _toy(n=256)
    kern = get_kernel("gaussian", bandwidth=0.75)
    K = kern(X, X)
    sk = make_accum_sketch(KEY, 256, 16, 4)

    # structural fit: C and W both come out of the fused kernel with blocked
    # reduction order; the d×d solve amplifies the f32 noise by cond(M), so
    # this path gets a looser (still tight) bound than matfree/pcg below
    a = krr_sketched_fit(K, y, 1e-3, sk, use_kernel=False)
    b = krr_sketched_fit(K, y, 1e-3, sk, use_kernel=True)
    np.testing.assert_allclose(np.asarray(b.fitted), np.asarray(a.fitted),
                               rtol=1e-3, atol=1e-3)

    c = krr_sketched_fit_matfree(X, y, 1e-3, sk, kern, use_kernel=False)
    d = krr_sketched_fit_matfree(X, y, 1e-3, sk, kern, use_kernel=True)
    np.testing.assert_allclose(np.asarray(d.fitted), np.asarray(c.fitted),
                               rtol=1e-4, atol=1e-4)

    e = krr_sketched_fit_pcg(X, y, 1e-3, sk, kern, iters=40, use_kernel=False)
    f = krr_sketched_fit_pcg(X, y, 1e-3, sk, kern, iters=40, use_kernel=True)
    np.testing.assert_allclose(np.asarray(f.fitted), np.asarray(e.fitted),
                               rtol=1e-4, atol=1e-4)


def test_leverage_scores_sum_to_dstat():
    X, _, _ = _toy(n=150)
    K = get_kernel("gaussian", bandwidth=0.75)(X, X)
    lam = 1e-3
    spec = spectrum(K)
    l = leverage_scores(K, lam, spec)
    ds = statistical_dimension(K, lam, spec)
    np.testing.assert_allclose(float(jnp.sum(l)), float(ds), rtol=1e-4)
    assert (np.asarray(l) >= -1e-6).all() and (np.asarray(l) <= 1 + 1e-6).all()


def test_leverage_sampling_reduces_incoherence():
    """Thm 8 remark: leverage-proportional P gives M ≤ d_stat."""
    X, _, _ = _toy(n=200)
    K = get_kernel("gaussian", bandwidth=0.75)(X, X)
    lam = delta = 1e-3
    spec = spectrum(K)
    M_unif = float(incoherence(K, delta, None, spec))
    p_lev = leverage_probs(K, lam, spec)
    M_lev = float(incoherence(K, delta, p_lev, spec))
    ds = float(statistical_dimension(K, delta, spec))
    assert M_lev <= M_unif
    assert M_lev <= 1.5 * ds          # M ≤ d_stat (constant slack for fp)


def test_bimodal_data_has_high_incoherence():
    """The paper's hard case: unbalanced bimodal data → M = Ω(n) under uniform P."""
    X, _, _ = _toy(n=300)
    K = get_kernel("gaussian", bandwidth=0.3)(X, X)
    spec = spectrum(K)
    M = float(incoherence(K, 1e-4, None, spec))
    ds = float(statistical_dimension(K, 1e-4, spec))
    # M = Ω(n): the isolated mode forces near-maximal incoherence (M ≈ 0.84·n
    # here), far above the statistical dimension (M ≈ 2.9·ds on this fixture)
    assert M > 0.7 * K.shape[0]
    assert M > 2.5 * ds               # incoherence ≫ statistical dimension


def test_ksat_improves_with_m():
    """K-satisfiability (Def. 3): accumulation shrinks ‖U₁ᵀSSᵀU₁ − I‖."""
    X, _, _ = _toy(n=250)
    K = get_kernel("gaussian", bandwidth=0.75)(X, X)
    spec = spectrum(K)
    delta = 1e-3
    d = 4 * max(d_delta(spec, delta), 1)
    devs = {}
    for m in [1, 16]:
        vals = [
            float(ksat_check(K, make_accum_sketch(jax.random.fold_in(KEY, 31 * m + r),
                                                  250, d, m), delta, spec).top_deviation)
            for r in range(5)
        ]
        devs[m] = np.mean(vals)
    assert devs[16] < devs[1]


def test_approx_leverage_close_to_exact():
    X, _, _ = _toy(n=200)
    K = get_kernel("gaussian", bandwidth=0.75)(X, X)
    # λ large enough that ℓ_i(λ) varies across points (at λ→0 every score
    # saturates at 1 and rank correlation is undefined)
    lam = 0.05
    p_exact = np.asarray(leverage_probs(K, lam))
    p_hat = np.asarray(approx_leverage_probs(KEY, K, lam, sketch_dim=80))
    # rank correlation is what sampling quality needs
    from scipy.stats import spearmanr
    rho = spearmanr(p_exact, p_hat).statistic
    assert rho > 0.5, rho


def test_pcg_falkon_matches_direct_solve():
    """Falkon-flavoured PCG (paper §3.3) reaches the Woodbury solution up to
    f32 normal-equation conditioning (cond(CᵀC) squares cond(C), so fitted
    values agree to ~1e-2 absolute), and is statistically AS GOOD an
    estimator of the exact-KRR fit as the direct solve."""
    from repro.core import krr_sketched_fit_pcg

    X, y, _ = _toy(n=300)
    kern = get_kernel("gaussian", bandwidth=0.75)
    K = kern(X, X)
    fn = krr_exact_fitted(K, y, 1e-3)
    sk = make_accum_sketch(KEY, 300, 24, 4)
    direct = krr_sketched_fit_matfree(X, y, 1e-3, sk, kern)
    pcg = krr_sketched_fit_pcg(X, y, 1e-3, sk, kern, iters=60)
    np.testing.assert_allclose(np.asarray(pcg.fitted), np.asarray(direct.fitted),
                               rtol=3e-2, atol=3e-2)
    assert float(insample_error(pcg.fitted, fn)) < 2.0 * float(
        insample_error(direct.fitted, fn)) + 1e-6


def test_sketched_krr_is_a_pytree():
    """The fitted model must trace through jit/vmap boundaries: pass it AS AN
    ARGUMENT (the unregistered dataclass failed here), roundtrip its leaves,
    and pin jit(predict) ≡ eager on both the structural and operator paths."""
    from repro.core.kernel_op import KernelOperator
    from repro.core.krr import SketchedKRR

    X, y, _ = _toy(n=200)
    kern = get_kernel("gaussian", bandwidth=0.75)
    sk = make_accum_sketch(KEY, 200, 12, 3)
    Xt = X[:31] + 0.01

    for model in (
        krr_sketched_fit(kern(X, X), y, 1e-3, sk, X, kern),
        krr_sketched_fit(KernelOperator(X, "gaussian", bandwidth=0.75),
                         y, 1e-3, sk),
    ):
        leaves, treedef = jax.tree_util.tree_flatten(model)
        assert any(l.shape == model.theta.shape for l in leaves)
        model2 = jax.tree_util.tree_unflatten(treedef, leaves)
        np.testing.assert_array_equal(np.asarray(model2.theta),
                                      np.asarray(model.theta))
        # jit with the model as a traced argument, not a closure constant
        jitted = jax.jit(SketchedKRR.predict)(model, Xt)
        np.testing.assert_allclose(np.asarray(jitted),
                                   np.asarray(model.predict(Xt)),
                                   rtol=1e-6, atol=1e-6)


def test_sketched_krr_vmap_over_models():
    """vmap over a stacked batch of fitted models (shared treedef)."""
    X, y, _ = _toy(n=160)
    kern = get_kernel("gaussian", bandwidth=0.75)
    sk = make_accum_sketch(KEY, 160, 10, 2)
    m1 = krr_sketched_fit(kern(X, X), y, 1e-3, sk, X, kern)
    m2 = krr_sketched_fit(kern(X, X), 2.0 * y, 1e-3, sk, X, kern)
    stacked = jax.tree_util.tree_map(lambda a, b: jnp.stack([a, b]), m1, m2)
    Xt = X[:17] + 0.01
    out = jax.vmap(lambda m: m.predict(Xt))(stacked)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(m1.predict(Xt)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(m2.predict(Xt)),
                               rtol=1e-5, atol=1e-5)


def test_sketched_krr_operator_models_share_treedef():
    """Two models fitted through EQUAL (but distinct) operators must carry
    equal treedefs: ``kernel_fn`` rides in pytree aux and compares by
    identity, so ``get_kernel`` must hand back the cached callable — a fresh
    partial per fit made operator-path models un-stackable."""
    from repro.core.kernel_op import KernelOperator

    X, y, _ = _toy(n=160)
    sk = make_accum_sketch(KEY, 160, 10, 2)
    m1 = krr_sketched_fit(KernelOperator(X, "gaussian", bandwidth=0.75),
                          y, 1e-3, sk)
    m2 = krr_sketched_fit(KernelOperator(X, "gaussian", bandwidth=0.75),
                          2.0 * y, 1e-3, sk)
    assert jax.tree_util.tree_structure(m1) == jax.tree_util.tree_structure(m2)
    stacked = jax.tree_util.tree_map(lambda a, b: jnp.stack([a, b]), m1, m2)
    Xt = X[:17] + 0.01
    out = jax.vmap(lambda m: m.predict(Xt))(stacked)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(m2.predict(Xt)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [5, 64, 65, 200, 1000])
def test_f32_gram_blocks_and_tail(n):
    """The fit's blocked, compensated Gram equals AᵀB at every row count:
    one block, an exact multiple of the block, and a clamped last block
    whose shared rows must not be summed twice."""
    from repro.core.kernels_math import f32_gram

    rng = np.random.default_rng(n)
    a = rng.standard_normal((n, 7)).astype(np.float32) + 3.0
    b = rng.standard_normal((n, 3)).astype(np.float32)
    ref = a.astype(np.float64).T @ b.astype(np.float64)
    scale = np.abs(a).T.astype(np.float64) @ np.abs(b)
    got = np.asarray(f32_gram(jnp.asarray(a), jnp.asarray(b), rows=64))
    assert np.max(np.abs(got - ref) / scale) < 1e-6
    hi, lo = f32_gram(jnp.asarray(a), jnp.asarray(b), rows=64, parts=True)
    np.testing.assert_array_equal(np.asarray(hi + lo), got)
