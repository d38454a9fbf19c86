"""Dense ≡ sharded equivalence for the multi-device data-parallel layer.

Two tiers:

  * single-device-mesh tests (always run): the shard_map plumbing — padding,
    masked gathers, psum reductions — must be exact on a trivial mesh;
  * 8-device tests (CI leg with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; skipped when the
    devices are absent so the plain tier-1 run is unaffected): (C, W), KRR
    predictions, spectral embeddings, and engine growth at tol must match the
    single-device path to ≤ 1e-5 rel, with BITWISE-identical index draws.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply as A
from repro.core import distributed as D
from repro.core.kernel_op import KernelOperator
from repro.core.krr import (
    krr_sketched_fit,
    krr_sketched_fit_adaptive,
    krr_sketched_fit_matfree,
    krr_sketched_fit_pcg,
)
from repro.core.sketch import make_accum_sketch
from repro.core.spectral import sketched_spectral_embedding, spectral_cluster

KEY = jax.random.PRNGKey(0)

needs_8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the distributed CI leg sets it)")


def _data(n=320, p=3):
    X = jax.random.uniform(KEY, (n, p))
    y = (jnp.sin(3.0 * X[:, 0]) + X[:, 1] ** 2
         + 0.2 * jax.random.normal(jax.random.fold_in(KEY, 1), (n,)))
    return X, y


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def _mesh(num):
    return D.make_data_mesh(num)


# --------------------------------------------------------------------------- #
# reduction primitives (any device count — exercised on a 1-device mesh too)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("num", [1])
def test_primitives_single_device_mesh(num):
    mesh = _mesh(num)
    M = jax.random.normal(KEY, (300, 7))          # 300 pads to any mesh
    idx = jax.random.randint(jax.random.fold_in(KEY, 2), (13,), 0, 300)
    np.testing.assert_allclose(np.asarray(D.sharded_take_rows(M, idx, mesh)),
                               np.asarray(jnp.take(M, idx, axis=0)),
                               rtol=1e-6, atol=1e-6)
    B = jax.random.normal(jax.random.fold_in(KEY, 3), (300, 5))
    np.testing.assert_allclose(np.asarray(D.sharded_gram(M, B, mesh)),
                               np.asarray(M.T @ B), rtol=1e-5, atol=1e-5)


def test_sharded_paths_on_single_device_mesh():
    """The whole pipeline on a 1-device mesh — plumbing-only equivalence that
    runs in every environment (no forced device count needed)."""
    mesh = _mesh(1)
    n, d, m = 300, 16, 4
    X, y = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    sk = make_accum_sketch(KEY, n, d, m)
    C0, W0 = A.sketch_both(op, sk, use_kernel=False)
    C1, W1 = A.sketch_both(op, sk, mesh=mesh)
    assert _rel(C1, C0) < 1e-6 and _rel(W1, W0) < 1e-6
    f0 = krr_sketched_fit(op, y, 5e-2, sk, use_kernel=False)
    f1 = krr_sketched_fit(op, y, 5e-2, sk, mesh=mesh)
    assert _rel(f1.fitted, f0.fitted) < 1e-5


def test_resolve_mesh_forms():
    assert D.resolve_mesh(True).shape[D.DATA_AXIS] == jax.device_count()
    assert D.resolve_mesh(1).shape[D.DATA_AXIS] == 1
    with pytest.raises(TypeError):
        D.resolve_mesh("data")
    with pytest.raises(ValueError):
        D.resolve_mesh(jax.device_count() + 1)
    # bool is an int subclass: False/0 must fail LOUDLY, not build an empty
    # mesh and die with a division error deep in the padding
    with pytest.raises(ValueError, match="mesh=None"):
        D.resolve_mesh(False)
    with pytest.raises(ValueError, match="≥ 1"):
        D.resolve_mesh(0)


def test_mesh_requires_operator():
    K = jnp.eye(32)
    sk = make_accum_sketch(KEY, 32, 4, 2)
    with pytest.raises(ValueError, match="KernelOperator"):
        A.sketch_both(K, sk, mesh=_mesh(1))


# --------------------------------------------------------------------------- #
# the acceptance tier: 8-device host-platform mesh
# --------------------------------------------------------------------------- #

@needs_8
@pytest.mark.parametrize("n", [320, 300])     # divisible and padded rows
def test_sharded_sketch_both_matches_single_device(n):
    mesh = _mesh(8)
    d, m = 16, 4
    X, _ = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    sk = make_accum_sketch(KEY, n, d, m)
    C0, W0 = A.sketch_both(op, sk, use_kernel=False)
    C1, W1 = A.sketch_both(op, sk, mesh=mesh)
    assert _rel(C1, C0) < 1e-5
    assert _rel(W1, W0) < 1e-5
    if n % 8 == 0:
        # per-device peak: each shard holds exactly n/8 rows of C
        shapes = {s.data.shape for s in C1.addressable_shards}
        assert shapes == {(n // 8, d)}
    # the padded C the sharded fit reduces: one ⌈n/8⌉-row tile per device,
    # zero rows past n
    Cp, _ = D.sharded_sketch_both(op, sk, mesh, padded=True)
    rows = -(-n // 8)
    assert Cp.shape == (8 * rows, d)
    assert sorted((s.device.id, s.data.shape) for s in Cp.addressable_shards) == [
        (dev.id, (rows, d)) for dev in sorted(mesh.devices.flat, key=lambda x: x.id)]
    assert _rel(Cp[:n], C0) < 1e-5 and not np.asarray(Cp[n:]).any()


@needs_8
def test_sharded_pallas_backend_matches():
    """use_kernel=True routes the per-device tiles through the fused Pallas
    kernel-eval→GEMM kernel (interpret mode on CPU) inside shard_map."""
    mesh = _mesh(8)
    n, d, m = 320, 16, 4
    X, _ = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    sk = make_accum_sketch(KEY, n, d, m)
    C0, W0 = A.sketch_both(op, sk, use_kernel=False)
    C1, W1 = A.sketch_both(op, sk, mesh=mesh, use_kernel=True)
    assert _rel(C1, C0) < 1e-5 and _rel(W1, W0) < 1e-5


@needs_8
def test_sharded_krr_predictions_match(krr_lam=5e-2):
    mesh = _mesh(8)
    n, d, m = 320, 16, 4
    X, y = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    sk = make_accum_sketch(KEY, n, d, m)
    f0 = krr_sketched_fit(op, y, krr_lam, sk, use_kernel=False)
    f1 = krr_sketched_fit(op, y, krr_lam, sk, mesh=mesh)
    assert _rel(f1.fitted, f0.fitted) < 1e-5
    Xt = X[:48] + 0.01
    assert _rel(f1.predict(Xt), f0.predict(Xt)) < 1e-5
    # sharded predict (test rows sharded too)
    assert _rel(f1.predict(Xt, mesh=mesh), f0.predict(Xt)) < 1e-5
    # matfree + PCG variants
    fm = krr_sketched_fit_matfree(op, y, krr_lam, sk, mesh=mesh)
    assert _rel(fm.fitted, f0.fitted) < 1e-5
    p0 = krr_sketched_fit_pcg(op, y, krr_lam, sk, iters=40, use_kernel=False)
    p1 = krr_sketched_fit_pcg(op, y, krr_lam, sk, iters=40, mesh=mesh)
    assert _rel(p1.fitted, p0.fitted) < 1e-5


@needs_8
def test_sharded_spectral_embedding_matches():
    mesh = _mesh(8)
    k1, k2 = jax.random.split(KEY)
    Xa = 0.25 * jax.random.normal(k1, (80, 2))
    Xb = 0.25 * jax.random.normal(k2, (80, 2)) + jnp.asarray([3.0, 0.0])
    X = jnp.concatenate([Xa, Xb])
    op = KernelOperator(X, "gaussian", bandwidth=0.8)
    sk = make_accum_sketch(KEY, 160, 24, 4)
    C0, W0 = A.sketch_both(op, sk, use_kernel=False)
    C1, W1 = A.sketch_both(op, sk, mesh=mesh)
    k = 2
    ev0, U0 = sketched_spectral_embedding(C0.astype(jnp.float32),
                                          W0.astype(jnp.float32), k)
    ev1, U1 = sketched_spectral_embedding(C1.astype(jnp.float32),
                                          W1.astype(jnp.float32), k)
    np.testing.assert_allclose(np.asarray(ev1), np.asarray(ev0),
                               rtol=1e-5, atol=1e-6)
    sign = np.sign(np.sum(np.asarray(U0) * np.asarray(U1), axis=0))
    np.testing.assert_allclose(np.asarray(U1) * sign, np.asarray(U0),
                               rtol=1e-5, atol=1e-5)
    # end-to-end pipeline: identical labels (up to the label-swap symmetry)
    r0 = spectral_cluster(KEY, op, 2, d=24, m=4, use_kernel=False)
    r1 = spectral_cluster(KEY, op, 2, d=24, m=4, mesh=mesh)
    l0, l1 = np.asarray(r0.labels), np.asarray(r1.labels)
    assert max(np.mean(l0 == l1), np.mean(l0 == 1 - l1)) == 1.0


@needs_8
def test_sharded_engine_growth_matches_and_draws_identical():
    """Engine growth at tol: the sharded engine must stop at the same m with
    BITWISE identical pre-drawn indices/signs and the same holdout draw."""
    mesh = _mesh(8)
    n, d, m_max = 300, 16, 8
    X, _ = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.5)
    sk0, C0, W0, info0 = A.grow_sketch_both(KEY, op, d, m_max=m_max, tol=0.1,
                                            use_kernel=False)
    sk1, C1, W1, info1 = A.grow_sketch_both(KEY, op, d, m_max=m_max, tol=0.1,
                                            mesh=mesh)
    assert int(info0["m"]) == int(info1["m"])
    assert bool(jnp.all(sk0.indices == sk1.indices))       # bitwise draws
    assert bool(jnp.all(sk0.signs == sk1.signs))
    np.testing.assert_allclose(float(info1["err"]), float(info0["err"]),
                               rtol=1e-4, atol=1e-6)
    assert _rel(C1, C0) < 1e-5 and _rel(W1, W0) < 1e-5


@needs_8
def test_sharded_unconditional_grow_matches():
    mesh = _mesh(8)
    n, d, steps = 320, 16, 5
    X, _ = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    st0 = A.accum_grow(op, A.accum_init(KEY, n, d, steps), steps,
                       use_kernel=False)
    st1 = A.accum_grow(op, A.accum_init(KEY, n, d, steps), steps, mesh=mesh)
    assert bool(jnp.all(st0.indices == st1.indices))
    assert _rel(st1.C, st0.C) < 1e-5 and _rel(st1.W, st0.W) < 1e-5


@needs_8
def test_sharded_estimators_match_single_device():
    mesh = _mesh(8)
    n, d = 300, 12
    X, _ = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    st = A.accum_grow(op, A.accum_init(KEY, n, d, 4), 4, use_kernel=False)
    h0 = A.make_holdout_estimator(KEY, op)(st)
    h1 = A.make_holdout_estimator(KEY, op, mesh=mesh)(st)
    np.testing.assert_allclose(float(h1), float(h0), rtol=1e-4, atol=1e-6)
    e0 = A.make_hutchinson_estimator(KEY, op, 4)(st)
    e1 = A.make_hutchinson_estimator(KEY, op, 4, mesh=mesh)(st)
    np.testing.assert_allclose(float(e1), float(e0), rtol=1e-4, atol=1e-6)


@needs_8
def test_sharded_adaptive_krr_matches():
    mesh = _mesh(8)
    n, d = 320, 16
    X, y = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.5)
    a0 = krr_sketched_fit_adaptive(op, y, 5e-2, KEY, d, tol=0.05, m_max=8,
                                   use_kernel=False)
    a1 = krr_sketched_fit_adaptive(op, y, 5e-2, KEY, d, tol=0.05, m_max=8,
                                   mesh=mesh)
    assert int(a0.info["m"]) == int(a1.info["m"])
    assert _rel(a1.fitted, a0.fitted) < 1e-5


@needs_8
def test_sharded_fit_is_jittable():
    """The whole sharded fit traces — shard_map composes with jit."""
    mesh = _mesh(8)
    n, d, m = 320, 16, 4
    X, y = _data(n)
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    sk = make_accum_sketch(KEY, n, d, m)
    f0 = krr_sketched_fit(op, y, 5e-2, sk, use_kernel=False)
    fitted = jax.jit(
        lambda o, yy: krr_sketched_fit(o, yy, 5e-2, sk, mesh=mesh).fitted
    )(op, y)
    assert _rel(fitted, f0.fitted) < 1e-5
