"""Matrix-free kernel-operator layer: dense ≡ matrix-free golden equivalence,
the fused kernel-eval→GEMM Pallas kernel vs its oracle, engine routing, and
the jaxpr regression proving the matrix-free path never allocates an n×n
intermediate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.trace import max_intermediate_elems
from repro.core import apply as A
from repro.core.kernel_op import DENSE_GUARD_N, KernelOperator
from repro.core.kernels_math import get_kernel
from repro.core.krr import (
    krr_sketched_fit,
    krr_sketched_fit_adaptive,
    krr_sketched_fit_matfree,
    krr_sketched_fit_pcg,
)
from repro.core.sketch import make_accum_sketch
from repro.core.spectral import sketched_spectral_embedding, spectral_cluster
from repro.kernels.accum_apply.ops import matfree_cols_kernel
from repro.kernels.accum_apply.ref import matfree_cols_ref

KEY = jax.random.PRNGKey(0)

KERNELS = [("gaussian", 0.6, 1.5), ("laplacian", 1.0, 1.5), ("matern", 0.8, 1.5)]


def _data(n=300, p=3, dtype=jnp.float32):
    X = jax.random.uniform(KEY, (n, p), dtype)
    y = (jnp.sin(3.0 * X[:, 0]) + X[:, 1] ** 2
         + 0.2 * jax.random.normal(jax.random.fold_in(KEY, 1), (n,), dtype))
    return X, y


# --------------------------------------------------------------------------- #
# fused Pallas kernel vs ref oracle (required sweep for every Pallas kernel)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kernel,bw,nu", KERNELS + [("matern", 0.8, 2.5)])
@pytest.mark.parametrize("n,p,d,m", [(200, 3, 10, 3), (256, 8, 16, 4), (100, 5, 7, 1)])
def test_matfree_kernel_sweep(n, p, d, m, kernel, bw, nu, dtype):
    X = jax.random.normal(jax.random.fold_in(KEY, n + d), (n, p), dtype)
    sk = make_accum_sketch(jax.random.fold_in(KEY, m), n, d, m)
    kf = get_kernel(kernel, bw, nu)
    ref = matfree_cols_ref(X.astype(jnp.float32), sk.indices, sk.coef, kf)
    L = jnp.take(X, sk.indices.reshape(-1), axis=0)
    out = matfree_cols_kernel(X, L, sk.coef, kernel=kernel, bandwidth=bw, nu=nu)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol, atol=tol)


def test_matfree_kernel_odd_shapes_and_blocks():
    """Row counts that do not tile by bm: the ops wrapper pads and slices."""
    X = jax.random.normal(KEY, (173, 4))
    sk = make_accum_sketch(jax.random.fold_in(KEY, 3), 173, 9, 3)
    kf = get_kernel("gaussian", 0.7)
    ref = matfree_cols_ref(X, sk.indices, sk.coef, kf)
    L = jnp.take(X, sk.indices.reshape(-1), axis=0)
    out = matfree_cols_kernel(X, L, sk.coef, kernel="gaussian", bandwidth=0.7, bm=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# golden dense ≡ matrix-free equivalence
# --------------------------------------------------------------------------- #

def _golden_case(kernel, bw, nu, dtype):
    """(C, W), KRR predictions, spectral embeddings: operator vs dense ≤ 1e-5."""
    n, p, d, m, lam = 300, 3, 16, 4, 1e-2
    X, y = _data(n, p, dtype)
    op = KernelOperator(X, kernel, bandwidth=bw, nu=nu)
    K = op.dense()
    assert K.dtype == dtype
    sk = make_accum_sketch(KEY, n, d, m, dtype=dtype)

    # (C, W)
    C_d, W_d = A.sketch_both(K, sk, use_kernel=False)
    C_o, W_o = A.sketch_both(op, sk, use_kernel=False)
    np.testing.assert_allclose(np.asarray(C_o), np.asarray(C_d), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(W_o), np.asarray(W_d), rtol=1e-5, atol=1e-6)

    # KRR: in-sample fit and out-of-sample predictions
    fit_d = krr_sketched_fit(K, y, lam, sk, X, op.kernel_fn, use_kernel=False)
    fit_o = krr_sketched_fit(op, y, lam, sk, use_kernel=False)
    np.testing.assert_allclose(np.asarray(fit_o.fitted), np.asarray(fit_d.fitted),
                               rtol=1e-5, atol=1e-5)
    Xt = X[:32] + jnp.asarray(0.01, dtype)
    np.testing.assert_allclose(np.asarray(fit_o.predict(Xt)),
                               np.asarray(fit_d.predict(Xt)), rtol=1e-5, atol=1e-5)

    # spectral embedding (sign-aligned: eigenvectors are sign-ambiguous)
    k = 3
    ev_d, U_d = sketched_spectral_embedding(C_d.astype(jnp.float32),
                                            W_d.astype(jnp.float32), k)
    ev_o, U_o = sketched_spectral_embedding(C_o.astype(jnp.float32),
                                            W_o.astype(jnp.float32), k)
    np.testing.assert_allclose(np.asarray(ev_o), np.asarray(ev_d), rtol=1e-5, atol=1e-6)
    sign = np.sign(np.sum(np.asarray(U_d) * np.asarray(U_o), axis=0))
    np.testing.assert_allclose(np.asarray(U_o) * sign, np.asarray(U_d),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel,bw,nu", KERNELS)
def test_golden_dense_equals_matfree_f32(kernel, bw, nu):
    _golden_case(kernel, bw, nu, jnp.float32)


@pytest.mark.parametrize("kernel,bw,nu", KERNELS)
def test_golden_dense_equals_matfree_f64_cpu(kernel, bw, nu):
    with jax.enable_x64(True):
        _golden_case(kernel, bw, nu, jnp.float64)


# --------------------------------------------------------------------------- #
# jaxpr regression: no n×n intermediate on the matrix-free path
# --------------------------------------------------------------------------- #

# the hand-rolled walker this file used to carry now lives in
# repro.analysis.trace — the dense-path n² assertion below stays as the
# positive control proving the shared detector still sees the big buffer
_max_intermediate_elems = max_intermediate_elems


def test_matfree_path_has_no_nxn_intermediate():
    """The acceptance claim: tracing the matrix-free KRR fit (chunked scan
    streaming path) binds NO buffer within an order of magnitude of n² —
    while the dense path provably does (positive control)."""
    n, p, d, m, chunk = 4096, 4, 16, 4, 512
    X = jax.random.uniform(KEY, (n, p))
    y = jnp.zeros((n,))
    sk = make_accum_sketch(KEY, n, d, m)

    def matfree_fit(X, y):
        op = KernelOperator(X, "gaussian", bandwidth=0.6)
        C = op.sketch_cols(sk, chunk=chunk, use_kernel=False)
        W = A.sketch_left(sk, C)
        mdl = krr_sketched_fit_matfree(
            KernelOperator(X, "gaussian", bandwidth=0.6), y, 1e-2, sk, chunk=chunk)
        return C, W, mdl.fitted

    mf = _max_intermediate_elems(jax.make_jaxpr(matfree_fit)(X, y).jaxpr)
    assert mf < n * n // 8, f"matrix-free path binds a {mf}-element buffer"
    # every buffer is O(n·(m·d + p)): C/X rows and the chunked kernel slab
    assert mf <= n * (m * d + p), mf

    def dense_fit(X, y):
        K = get_kernel("gaussian", 0.6)(X, X)
        return krr_sketched_fit(K, y, 1e-2, sk, use_kernel=False).fitted

    dn = _max_intermediate_elems(jax.make_jaxpr(dense_fit)(X, y).jaxpr)
    assert dn >= n * n       # positive control: the detector sees the n² buffer


def test_auto_chunk_respects_slab_budget_at_large_md():
    """Regression for the ``max(256, …)`` floor in ``_auto_chunk``: at large
    m·d a 256-row floor made the (chunk, m·d) streaming slab 64 MiB (the exact
    failure ``matvec``'s chunk comment warns about).  The budget is ~16 MiB =
    4M f32 elements; the traced program must never bind a bigger buffer."""
    n, p, d, m = 8192, 4, 128, 512                 # m·d = 65536
    budget_elems = 4 * 1024 * 1024
    X = jax.random.uniform(KEY, (n, p))
    sk = make_accum_sketch(KEY, n, d, m)
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    assert op._auto_chunk(m * d) * m * d <= budget_elems

    jaxpr = jax.make_jaxpr(
        lambda X: KernelOperator(X, "gaussian", bandwidth=0.6).sketch_cols(
            sk, use_kernel=False))(X)
    peak = _max_intermediate_elems(jaxpr.jaxpr)
    # the old floor binds a 256·65536 ≈ 16.8M-element slab here
    assert peak <= budget_elems + n * p, peak

    # and the gate must key on SLAB size, not row count: at n = 4096 the old
    # `rows > 4096` gate skipped chunking entirely and bound the full
    # (4096, 65536) ≈ 1 GiB slab in one block
    n_small = 4096
    Xs = jax.random.uniform(KEY, (n_small, p))
    sks = make_accum_sketch(KEY, n_small, d, m)
    jaxpr_s = jax.make_jaxpr(
        lambda X: KernelOperator(X, "gaussian", bandwidth=0.6).sketch_cols(
            sks, use_kernel=False))(Xs)
    peak_s = _max_intermediate_elems(jaxpr_s.jaxpr)
    assert peak_s <= budget_elems + n_small * p, peak_s


def test_engine_step_matfree_no_nxn_intermediate():
    """The progressive engine's slab increment on an operator is O(n·d) too."""
    n, d = 2048, 16
    X = jax.random.uniform(KEY, (n, 4))
    state = A.accum_init(KEY, n, d, 4)

    jaxpr = jax.make_jaxpr(
        lambda X, s: A.accum_step(KernelOperator(X, "gaussian", bandwidth=0.6),
                                  s, use_kernel=False))(X, state)
    mf = _max_intermediate_elems(jaxpr.jaxpr)
    assert mf < n * n // 8, mf


# --------------------------------------------------------------------------- #
# engine + pipelines routed through the operator
# --------------------------------------------------------------------------- #

def test_engine_grow_operator_equals_dense():
    n, p, d, m_max = 300, 3, 16, 6
    X, _ = _data(n, p)
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    K = op.dense()
    st_o = A.accum_grow(op, A.accum_init(KEY, n, d, m_max), m_max, use_kernel=False)
    st_d = A.accum_grow(K, A.accum_init(KEY, n, d, m_max), m_max, use_kernel=False)
    np.testing.assert_allclose(np.asarray(st_o.C), np.asarray(st_d.C),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st_o.W), np.asarray(st_d.W),
                               rtol=1e-5, atol=1e-6)


def test_engine_grow_operator_f64_mode():
    """x64 regression: an f64 operator must not promote the engine's f32 loop
    carry (the fori/while carry dtype check rejects the step otherwise)."""
    with jax.enable_x64(True):
        n, d = 96, 8
        X = jax.random.uniform(KEY, (n, 3), jnp.float64)
        op = KernelOperator(X, "gaussian", bandwidth=0.6)
        st_o = A.accum_grow(op, A.accum_init(KEY, n, d, 3), 3, use_kernel=False)
        assert st_o.C.dtype == jnp.float32
        st_d = A.accum_grow(op.dense(), A.accum_init(KEY, n, d, 3), 3,
                            use_kernel=False)
        np.testing.assert_allclose(np.asarray(st_o.C), np.asarray(st_d.C),
                                   rtol=1e-5, atol=1e-6)


def test_adaptive_krr_operator_equals_dense():
    n, d = 300, 16
    X, y = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.5)
    K = op.dense()
    a = krr_sketched_fit_adaptive(op, y, 1e-2, KEY, d, tol=0.05, m_max=8,
                                  use_kernel=False)
    b = krr_sketched_fit_adaptive(K, y, 1e-2, KEY, d, tol=0.05, m_max=8,
                                  use_kernel=False)
    assert a.info["m"] == b.info["m"]
    np.testing.assert_allclose(np.asarray(a.fitted), np.asarray(b.fitted),
                               rtol=1e-4, atol=1e-4)
    # operator predict is wired automatically
    Xt = X[:16] + 0.01
    assert a.predict(Xt).shape == (16,)


def test_hutchinson_estimator_operator_matches_dense():
    n, d = 256, 12
    X, _ = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    K = op.dense()
    st = A.accum_grow(K, A.accum_init(KEY, n, d, 4), 4, use_kernel=False)
    e_d = A.make_hutchinson_estimator(KEY, K, 4)(st)
    e_o = A.make_hutchinson_estimator(KEY, op, 4)(st)
    np.testing.assert_allclose(float(e_o), float(e_d), rtol=1e-4, atol=1e-5)


def test_operator_matvec_streams_and_matches_dense():
    n = 300
    X, _ = _data(n)
    op = KernelOperator(X, "laplacian", bandwidth=0.9)
    K = op.dense()
    Z = jax.random.normal(jax.random.fold_in(KEY, 2), (n, 5))
    np.testing.assert_allclose(np.asarray(op.matvec(Z, chunk=64)),
                               np.asarray(K.astype(jnp.float32) @ Z),
                               rtol=1e-4, atol=1e-4)
    v = Z[:, 0]
    assert op.matvec(v, chunk=64).shape == (n,)


def test_spectral_cluster_operator_matches_dense_labels():
    """Planted two-cluster mixture: operator pipeline ≡ dense pipeline."""
    k1, k2 = jax.random.split(KEY)
    Xa = 0.25 * jax.random.normal(k1, (80, 2))
    Xb = 0.25 * jax.random.normal(k2, (80, 2)) + jnp.asarray([3.0, 0.0])
    X = jnp.concatenate([Xa, Xb])
    op = KernelOperator(X, "gaussian", bandwidth=0.8)
    res_o = spectral_cluster(KEY, op, 2, d=24, m=4, use_kernel=False)
    res_d = spectral_cluster(KEY, op.dense(), 2, d=24, m=4, use_kernel=False)
    lo, ld = np.asarray(res_o.labels), np.asarray(res_d.labels)
    agree = max(np.mean(lo == ld), np.mean(lo == 1 - ld))   # label-swap invariant
    assert agree == 1.0
    truth = np.asarray([0] * 80 + [1] * 80)
    acc = max(np.mean(lo == truth), np.mean(lo == 1 - truth))
    assert acc >= 0.95


def test_pcg_operator_close_to_direct():
    n, d = 300, 16
    X, y = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    sk = make_accum_sketch(KEY, n, d, 4)
    direct = krr_sketched_fit_matfree(op, y, 1e-2, sk)
    pcg = krr_sketched_fit_pcg(op, y, 1e-2, sk, iters=60)
    np.testing.assert_allclose(np.asarray(pcg.fitted), np.asarray(direct.fitted),
                               rtol=3e-2, atol=3e-2)


def test_dense_guard_refuses_large_n():
    op = KernelOperator(jnp.zeros((DENSE_GUARD_N + 1, 2)), "gaussian")
    with pytest.raises(ValueError, match="refusing to materialize"):
        op.dense()


def test_operator_is_a_pytree():
    X, _ = _data(64)
    op = KernelOperator(X, "matern", bandwidth=0.9, nu=2.5)
    leaves, treedef = jax.tree_util.tree_flatten(op)
    assert len(leaves) == 1
    op2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert op2.kernel == "matern" and op2.nu == 2.5
    sk = make_accum_sketch(KEY, 64, 8, 2)
    out = jax.jit(lambda o: o.sketch_cols(sk, use_kernel=False))(op)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(op.sketch_cols(sk, use_kernel=False)),
                               rtol=1e-6, atol=1e-6)
