"""Batched rank-B accumulation: one data sweep per m → m+B batch.

The load-bearing guarantees (ISSUE 5 acceptance criteria):

  * ``accum_grow_batched`` ≡ B sequential ``accum_step`` calls on every
    backend ({dense-XLA, dense-Pallas, matfree, sharded} × {f32, f64-on-CPU}):
    IDENTICAL index draws (both fold the same pre-drawn slabs) and (C, W)
    equal to ≤ 1e-5 relative (summation order only);
  * the doubling schedule stops in both directions (early on a loose tol,
    budget-exhausted on an unreachable one) in O(log m) passes;
  * one K-pass per batch — jaxpr regressions: a single pallas_call where the
    sequential loop launches B, and no B×(n·d) slab on the streaming path;
  * the measured autotune cache round-trips, and a corrupt/missing cache
    falls back to the heuristic blocks;
  * the engine's donated growth wrappers really alias their loop carries.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.trace import count_pallas_calls, max_intermediate_elems
from repro.core import apply as A
from repro.core import distributed as D
from repro.core.kernel_op import KernelOperator
from repro.core.kernels_math import gaussian_kernel, laplacian_kernel
from repro.core.krr import krr_sketched_fit_adaptive
from repro.kernels.accum_apply import autotune
from repro.kernels.accum_apply.kernel import accum_grow_slabs
from repro.kernels.accum_apply.ops import (
    accum_grow_kernel,
    autotune_blocks,
    sketch_right_kernel,
)
from repro.kernels.accum_apply.ref import accum_grow_ref
from repro.core.sketch import make_accum_sketch

KEY = jax.random.PRNGKey(0)

needs_8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the distributed CI leg sets it)")


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def _problem(n=300, p=3, bandwidth=0.6, dtype=jnp.float32):
    X = jax.random.uniform(KEY, (n, p), dtype)
    op = KernelOperator(X, "gaussian", bandwidth=bandwidth)
    return X, op


# --------------------------------------------------------------------------- #
# fused kernel vs ref oracle (required sweep for every Pallas kernel)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,B", [(256, 16, 4), (300, 8, 8), (128, 64, 1),
                                   (173, 9, 3), (256, 300, 3)])
def test_grow_kernel_sweep(n, d, B, dtype):
    """d = 300 spans three 128-lane output column blocks (the last padded)."""
    K = jax.random.normal(jax.random.fold_in(KEY, n + d), (n, n), dtype)
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (B, d), 0, n)
    coef = jax.random.normal(jax.random.fold_in(KEY, 2), (B, d))
    C = jax.random.normal(jax.random.fold_in(KEY, 3), (n, d), jnp.float32)
    a = jnp.float32(0.77)
    Cn, TtG, TtC = accum_grow_kernel(K, idx, coef, C, a)
    Cr, TtGr, TtCr = accum_grow_ref(K, idx, coef, C, a)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(Cn), np.asarray(Cr), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(TtG), np.asarray(TtGr), rtol=tol,
                               atol=max(tol, 1e-3 * float(jnp.abs(TtGr).max())))
    np.testing.assert_allclose(np.asarray(TtC), np.asarray(TtCr), rtol=tol, atol=tol)


def test_grow_kernel_multi_tile_accumulation():
    """Grid with several row tiles AND column chunks: the W pieces accumulate
    across every grid step, not just the last."""
    n, d, B = 512, 16, 4
    K = jax.random.normal(KEY, (n, n))
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (B, d), 0, n)
    coef = jax.random.normal(jax.random.fold_in(KEY, 2), (B, d))
    C = jax.random.normal(jax.random.fold_in(KEY, 3), (n, d))
    a = jnp.float32(0.5)
    out = accum_grow_slabs(K, idx, coef.astype(jnp.float32), C,
                           jnp.asarray([0.5], jnp.float32), bm=128, bn=128)
    ref = accum_grow_ref(K, idx, coef, C, a)
    for x, y in zip(out, ref):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-4)


# --------------------------------------------------------------------------- #
# batched ≡ sequential: {dense-XLA, dense-Pallas, matfree × both backends}
# --------------------------------------------------------------------------- #

def _seq_and_batched(K_in, B, *, n, d, m_max, use_kernel, mesh=None):
    seq = A.accum_grow(K_in, A.accum_init(KEY, n, d, m_max), B,
                       use_kernel=False, donate=False)
    bat = A.accum_grow_batched(K_in, A.accum_init(KEY, n, d, m_max), B,
                               use_kernel=use_kernel, mesh=mesh, donate=False)
    return seq, bat


@pytest.mark.parametrize("B", [1, 3, 6])
@pytest.mark.parametrize("path,use_kernel", [
    ("dense", False), ("dense", True), ("matfree", False), ("matfree", True),
])
def test_batched_equals_sequential_f32(path, use_kernel, B):
    n, d, m_max = 300, 16, 8
    _, op = _problem(n)
    K_in = op.dense() if path == "dense" else op
    seq, bat = _seq_and_batched(K_in, B, n=n, d=d, m_max=m_max,
                                use_kernel=use_kernel)
    assert bool(jnp.all(bat.indices == seq.indices))     # identical draws
    assert int(bat.m) == int(seq.m) == B
    assert _rel(bat.C, seq.C) < 1e-5
    assert _rel(bat.W, seq.W) < 1e-5


@pytest.mark.parametrize("path", ["dense", "matfree"])
def test_batched_equals_sequential_f64_cpu(path):
    with jax.enable_x64(True):
        n, d, B = 200, 12, 4
        X = jax.random.uniform(KEY, (n, 3), jnp.float64)
        op = KernelOperator(X, "gaussian", bandwidth=0.6)
        K_in = op.dense() if path == "dense" else op
        seq, bat = _seq_and_batched(K_in, B, n=n, d=d, m_max=8,
                                    use_kernel=False)
        assert bat.C.dtype == jnp.float32                # engine carry contract
        assert _rel(bat.C, seq.C) < 1e-5
        assert _rel(bat.W, seq.W) < 1e-5


def test_batched_from_nonzero_start_matches_sequential():
    """A batch folded mid-trajectory continues the SAME trajectory: grow 3
    sequentially, batch 4 more ≡ 7 sequential steps."""
    n, d = 300, 16
    _, op = _problem(n)
    K = op.dense()
    seq7 = A.accum_grow(K, A.accum_init(KEY, n, d, 8), 7, use_kernel=False,
                        donate=False)
    st3 = A.accum_grow(K, A.accum_init(KEY, n, d, 8), 3, use_kernel=False,
                       donate=False)
    st7 = st3.grow_batched(K, 4, use_kernel=False, donate=False)
    assert int(st7.m) == 7
    assert _rel(st7.C, seq7.C) < 1e-5
    assert _rel(st7.W, seq7.W) < 1e-5


def test_batched_overrun_raises():
    n, d = 100, 8
    _, op = _problem(n)
    state = A.accum_grow(op.dense(), A.accum_init(KEY, n, d, 4), 3,
                         use_kernel=False, donate=False)
    with pytest.raises(ValueError, match="overruns"):
        A.accum_grow_batched(op.dense(), state, 2, use_kernel=False)
    with pytest.raises(ValueError, match="batch size"):
        A.accum_grow_batched(op.dense(), state, 0, use_kernel=False)
    # the mesh path must validate too — an overrun there would silently
    # clamp the slice and re-fold earlier slabs into corrupted (C, W)
    st_op = A.accum_grow(op, A.accum_init(KEY, n, d, 4), 3,
                         use_kernel=False, donate=False)
    with pytest.raises(ValueError, match="overruns"):
        A.accum_grow_batched(op, st_op, 2, mesh=D.make_data_mesh(1))


def test_grow_sketch_both_fixed_size_is_one_pass():
    """tol=None (fixed m = m_max) rides the batched entry point: ONE data
    pass, and the result equals the one-shot sketch_both at m_max."""
    n, d, m_max = 300, 16, 8
    _, op = _problem(n)
    K = op.dense()
    sk, C, W, info = A.grow_sketch_both(KEY, K, d, m_max=m_max,
                                        use_kernel=False)
    assert int(info["m"]) == m_max and int(info["passes"]) == 1
    C_ref, W_ref = A.sketch_both(K, sk, use_kernel=False)
    assert _rel(C, C_ref.astype(jnp.float32)) < 1e-5
    assert _rel(W, W_ref.astype(jnp.float32)) < 1e-5
    jaxpr = jax.make_jaxpr(
        lambda K: A.grow_sketch_both(KEY, K, d, m_max=m_max,
                                     use_kernel=True)[1])(K)
    assert _count_pallas_calls(jaxpr.jaxpr) == 1


@pytest.mark.parametrize("num", [1])
def test_batched_sharded_single_device_mesh(num):
    """The shard_map plumbing of the batched step must be exact on a trivial
    mesh (n chosen to NOT divide the mesh padding away on larger ones)."""
    n, d, B = 300, 16, 5
    _, op = _problem(n)
    mesh = D.make_data_mesh(num)
    seq = A.accum_grow(op, A.accum_init(KEY, n, d, 8), B, use_kernel=False,
                       donate=False)
    bat = A.accum_grow_batched(op, A.accum_init(KEY, n, d, 8), B, mesh=mesh)
    assert bool(jnp.all(bat.indices == seq.indices))
    assert _rel(bat.C, seq.C) < 1e-5
    assert _rel(bat.W, seq.W) < 1e-5


@needs_8
def test_batched_sharded_8_devices_matches():
    n, d, B = 330, 16, 6                  # 330 % 8 != 0: pad path exercised
    X = jax.random.uniform(KEY, (n, 3))
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    mesh = D.make_data_mesh(8)
    seq = A.accum_grow(op, A.accum_init(KEY, n, d, 8), B, use_kernel=False,
                       donate=False)
    bat = A.accum_grow_batched(op, A.accum_init(KEY, n, d, 8), B, mesh=mesh)
    assert bool(jnp.all(bat.indices == seq.indices))
    assert _rel(bat.C, seq.C) < 1e-5
    assert _rel(bat.W, seq.W) < 1e-5


@needs_8
def test_doubling_sharded_matches_single_device():
    n, d = 320, 16
    X = jax.random.uniform(KEY, (n, 3))
    op = KernelOperator(X, "gaussian", bandwidth=0.6)
    mesh = D.make_data_mesh(8)
    s0 = A.grow_sketch_both(KEY, op, d, m_max=8, tol=0.1, use_kernel=False)
    s1 = A.grow_sketch_both(KEY, op, d, m_max=8, tol=0.1, use_kernel=False,
                            mesh=mesh)
    assert int(s0[3]["m"]) == int(s1[3]["m"])
    assert int(s0[3]["passes"]) == int(s1[3]["passes"])
    assert bool(jnp.all(s0[0].indices == s1[0].indices))
    assert _rel(s1[1], s0[1]) < 1e-5
    assert _rel(s1[2], s0[2]) < 1e-5


# --------------------------------------------------------------------------- #
# doubling schedule: stopping both directions, O(log m) passes
# --------------------------------------------------------------------------- #

def test_doubling_schedule_shape():
    assert A.doubling_schedule(0, 1) == [1]
    assert A.doubling_schedule(0, 6) == [1, 2, 3]
    assert A.doubling_schedule(0, 32) == [1, 2, 4, 8, 16, 1]
    assert A.doubling_schedule(3, 8) == [1, 2, 2]
    assert sum(A.doubling_schedule(0, 100)) == 100
    # O(log m): the ladder length is ≤ 2·log2(m_max) + 2 for any m_max
    for m_max in (1, 2, 5, 7, 31, 32, 100, 1000):
        assert len(A.doubling_schedule(0, m_max)) <= 2 * int(np.log2(m_max) + 1) + 2


def test_doubling_stops_early_on_easy_kernel():
    n, d = 300, 24
    X = jax.random.uniform(jax.random.fold_in(KEY, 5), (n, 3))
    K = gaussian_kernel(X, X, bandwidth=0.8)
    sk, C, W, info = A.grow_sketch_both(KEY, K, d, m_max=16, tol=0.2,
                                        use_kernel=False)
    assert int(info["m"]) < 16 and float(info["err"]) <= 0.2
    # O(log m) passes, and strictly fewer than the unit schedule's m passes
    # whenever more than one batch was applied
    assert int(info["passes"]) <= len(A.doubling_schedule(0, 16))


def test_doubling_exhausts_budget_on_unreachable_tol():
    n, d = 200, 8
    X = jax.random.uniform(jax.random.fold_in(KEY, 6), (n, 3))
    K = laplacian_kernel(X, X, bandwidth=0.5)      # heavy spectral tail
    sk, C, W, info = A.grow_sketch_both(KEY, K, d, m_max=6, tol=1e-6,
                                        use_kernel=False)
    assert int(info["m"]) == 6                     # ran out of slabs
    assert np.isfinite(float(info["err"])) and float(info["err"]) > 1e-6
    # every phase of the ladder ran: 6 slabs in 3 passes, not 6
    assert int(info["passes"]) == len(A.doubling_schedule(0, 6)) == 3


def test_doubling_result_self_consistent_and_unit_available():
    """The doubling driver's (sk, C, W) re-applies from scratch (same contract
    as the unit schedule), and schedule="unit" still routes the old loop."""
    n, d = 200, 12
    _, op = _problem(n, bandwidth=0.5)
    K = op.dense()
    sk, C, W, info = A.grow_sketch_both(KEY, K, d, m_max=8, tol=0.15,
                                        use_kernel=False)
    C_ref, W_ref = A.sketch_both(K, sk, use_kernel=False)
    assert _rel(C, C_ref.astype(jnp.float32)) < 1e-5
    assert _rel(W, W_ref.astype(jnp.float32)) < 1e-5
    sku, Cu, Wu, infou = A.grow_sketch_both(KEY, K, d, m_max=8, tol=0.15,
                                            use_kernel=False, schedule="unit")
    assert int(infou["passes"]) == int(infou["m"])   # unit: one pass per slab
    with pytest.raises(ValueError, match="schedule"):
        A.accum_grow_adaptive(K, A.accum_init(KEY, n, d, 8), tol=0.1,
                              estimator=lambda s: s.err, schedule="bogus")


def test_doubling_driver_jits_and_matches_eager():
    n, d = 200, 12
    _, op = _problem(n, bandwidth=0.5)
    K = op.dense()
    eager = A.grow_sketch_both(KEY, K, d, m_max=8, tol=0.15, use_kernel=False)

    @jax.jit
    def driver(key, K):
        return A.grow_sketch_both(key, K, d, m_max=8, tol=0.15,
                                  use_kernel=False)

    sk_j, C_j, W_j, info_j = driver(KEY, K)
    assert int(info_j["m"]) == int(eager[3]["m"])
    assert int(info_j["passes"]) == int(eager[3]["passes"])
    np.testing.assert_allclose(np.asarray(C_j), np.asarray(eager[1]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(W_j), np.asarray(eager[2]),
                               rtol=1e-5, atol=1e-6)


def test_adaptive_krr_doubling_vs_unit_quality():
    """Both schedules clear the same error target; doubling reports its pass
    count in the model info."""
    n, d = 250, 16
    X = jax.random.uniform(KEY, (n, 3))
    K = gaussian_kernel(X, X, bandwidth=0.5)
    y = jnp.sin(3.0 * X[:, 0])
    md = krr_sketched_fit_adaptive(K, y, 1e-2, KEY, d, tol=0.1, m_max=8,
                                   use_kernel=False)
    mu = krr_sketched_fit_adaptive(K, y, 1e-2, KEY, d, tol=0.1, m_max=8,
                                   use_kernel=False, schedule="unit")
    assert float(md.info["err"]) <= 0.1 or int(md.info["m"]) == 8
    assert float(mu.info["err"]) <= 0.1 or int(mu.info["m"]) == 8
    assert int(md.info["passes"]) <= int(mu.info["passes"])


# --------------------------------------------------------------------------- #
# jaxpr regressions: one K-pass per batch, no B×(n·d) slab, donated carries
# --------------------------------------------------------------------------- #

# the hand-rolled walkers this file used to carry now live in
# repro.analysis.trace — the sequential-launch and B×(n·d)-slab positive
# controls below keep proving the shared library still catches both classes
_count_pallas_calls = count_pallas_calls
_max_intermediate_elems = max_intermediate_elems


def test_one_pallas_launch_per_batch():
    """The Pallas path reads K through ONE pallas_call per batch; B sequential
    steps launch B (the positive control)."""
    n, d, B = 256, 16, 8
    _, op = _problem(n)
    K = op.dense()
    state = A.accum_init(KEY, n, d, B)

    batched = jax.make_jaxpr(
        lambda K, s: A.accum_grow_batched(K, s, B, use_kernel=True))(K, state)
    assert _count_pallas_calls(batched.jaxpr) == 1

    def seq(K, s):
        for _ in range(B):
            s = A.accum_step(K, s, use_kernel=True)
        return s

    sequential = jax.make_jaxpr(seq)(K, state)
    assert _count_pallas_calls(sequential.jaxpr) == B


def test_batched_matfree_no_Bnd_slab():
    """Streaming path: the batch's kernel-eval slab stays chunk-bounded — no
    (n, B·d) buffer even though all B slabs ride one pass.  (The B×(n·d)
    object WOULD appear if the batch were evaluated as one unchunked slab —
    the positive control.)"""
    n, p, d, B = 32768, 4, 64, 8                  # m·d = 512 → chunk < n
    X = jax.random.uniform(KEY, (n, p))
    state = A.accum_init(KEY, n, d, B)
    budget = 4 * 1024 * 1024                      # the ~16 MiB f32 slab budget

    jaxpr = jax.make_jaxpr(
        lambda X, s: A.accum_grow_batched(
            KernelOperator(X, "gaussian", bandwidth=0.6), s, B,
            use_kernel=False))(X, state)
    peak = _max_intermediate_elems(jaxpr.jaxpr)
    assert peak < n * B * d, f"B×(n·d) slab materialized: {peak}"
    assert peak <= budget + n * (p + d), peak


def test_grow_wrappers_donate_loop_carries():
    """Peak-buffer regression for the donation satellite: the jitted growth
    wrappers advertise input-output aliasing on the state (so XLA reuses the
    n·d C buffer instead of holding 2×), and an eager call really consumes
    the caller's buffers."""
    n, d = 256, 16
    _, op = _problem(n)
    K = op.dense()

    from repro.analysis.trace import verify_donation

    low = A._grow_loop_donated.lower(K, A.accum_init(KEY, n, d, 4), 4, False)
    assert verify_donation(low)
    lowb = A._grow_batched_donated.lower(K, A.accum_init(KEY, n, d, 4), 4, False)
    assert verify_donation(lowb)

    st0 = A.accum_init(KEY, n, d, 4)
    out = A.accum_grow(K, st0, 4, use_kernel=False)
    assert int(out.m) == 4
    assert st0.C.is_deleted()                     # buffers really moved
    st1 = A.accum_init(KEY, n, d, 4)
    keep = A.accum_grow(K, st1, 4, use_kernel=False, donate=False)
    assert not st1.C.is_deleted()                 # opt-out for benchmarks

    # donation must NOT fire under an outer trace (it would be dropped with
    # a warning); the traced path still works
    @jax.jit
    def traced(K, s):
        return A.accum_grow(K, s, 4, use_kernel=False).C

    np.testing.assert_allclose(np.asarray(traced(K, A.accum_init(KEY, n, d, 4))),
                               np.asarray(out.C), rtol=1e-6, atol=1e-7)


# --------------------------------------------------------------------------- #
# measured autotune cache
# --------------------------------------------------------------------------- #

def test_autotune_cache_round_trip(tmp_path, monkeypatch):
    """First eligible eager call measures once and persists; the persisted
    winner is served afterwards (including to trace-time lookups)."""
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.ENV_CACHE, str(cache))
    monkeypatch.setenv(autotune.ENV_GATE, "1")

    n, d, m = 128, 16, 3
    K = jax.random.normal(KEY, (n, n))
    sk = make_accum_sketch(KEY, n, d, m)
    out = sketch_right_kernel(K, sk)
    assert cache.exists()
    entries = json.loads(cache.read_text())
    assert entries, "measurement did not persist a winner"
    blocks = autotune.lookup("accum_apply", (n, n, d, m), K.dtype, True)
    assert blocks is not None
    # the table lookup now serves the measured winner (e.g. under jit)
    assert autotune_blocks(n, n, d, m, K.dtype, interpret=True) == blocks
    # and the result is still the oracle's
    from repro.kernels.accum_apply.ref import accum_apply_ref
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(accum_apply_ref(K, sk.indices, sk.coef)),
                               rtol=1e-5, atol=1e-5)


def test_autotune_corrupt_and_missing_cache_fall_back(tmp_path, monkeypatch):
    """A corrupt cache file (or garbage entries) must degrade to the static
    table / heuristic — never crash, never return garbage blocks."""
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.ENV_CACHE, str(cache))
    monkeypatch.setenv(autotune.ENV_GATE, "0")         # no measuring

    # missing file → heuristic blocks at the anchor shape
    assert autotune_blocks(4096, 8192, 64, 4, jnp.float32, interpret=True) == (256, 64)

    # corrupt JSON → same fallback, no exception
    cache.write_text("{not json at all")
    autotune._MEM.clear()
    assert autotune.lookup("accum_apply", (4096, 8192, 64, 4), jnp.float32,
                           True) is None
    assert autotune_blocks(4096, 8192, 64, 4, jnp.float32, interpret=True) == (256, 64)

    # valid JSON with garbage values → entries rejected, fallback again
    cache.write_text(json.dumps({"accum_apply|4096|8192|64|4|float32|cpu/interpret":
                                 ["huge", -3]}))
    autotune._MEM.clear()
    assert autotune.lookup("accum_apply", (4096, 8192, 64, 4), jnp.float32,
                           True) is None

    # schema-valid entry with the WRONG arity (hand-edited / stale schema)
    # must be rejected by the arity check, not crash the caller's unpack
    autotune.record("accum_apply", (4096, 8192, 64, 4), jnp.float32, True,
                    (8, 8, 8))
    assert autotune.lookup("accum_apply", (4096, 8192, 64, 4), jnp.float32,
                           True, arity=2) is None
    assert autotune_blocks(4096, 8192, 64, 4, jnp.float32, interpret=True) == (256, 64)

    # heuristic fallback for unknown shapes stays sane
    bm, bd = autotune_blocks(1000, 5000, 48, 3, jnp.float32, interpret=True)
    assert bm >= 8 and 1 <= bd <= 48


def test_autotune_never_measures_under_trace(tmp_path, monkeypatch):
    """Tracers cannot be timed: a jitted caller must fall back to the table
    even with measuring enabled, leaving the cache untouched."""
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.ENV_CACHE, str(cache))
    monkeypatch.setenv(autotune.ENV_GATE, "1")

    n, d, m = 96, 8, 2
    K = jax.random.normal(KEY, (n, n))
    sk = make_accum_sketch(KEY, n, d, m)
    jitted = jax.jit(lambda K: sketch_right_kernel(K, sk))
    _ = jitted(K)
    assert not cache.exists()


def test_autotune_record_lookup_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "a.json"))
    autotune.record("sketch_both", (512, 16, 4), jnp.float32, True, (128, 512))
    assert autotune.lookup("sketch_both", (512, 16, 4), jnp.float32, True) == (128, 512)
    # a fresh in-memory state re-reads the file
    autotune._MEM.clear()
    assert autotune.lookup("sketch_both", (512, 16, 4), jnp.float32, True) == (128, 512)
    # and the fused-kernel table consults it
    from repro.kernels.accum_apply.ops import autotune_both_blocks
    assert autotune_both_blocks(512, True, 16, 4) == (128, 512)


def test_autotune_skips_memory_refusals_only(tmp_path, monkeypatch):
    """A candidate tiling the compiler refuses for memory is skipped and
    kept in ``refusals()``; any other failure propagates; a measurement in
    which every candidate is refused raises instead of returning the
    fallback tiling."""
    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "a.json"))
    monkeypatch.setenv(autotune.ENV_GATE, "1")
    oom = "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem"

    def bench(c):
        if c == (512,):
            raise RuntimeError(oom)
        return jnp.zeros(())

    n0 = len(autotune.refusals())
    got = autotune.measured_blocks("probe", (1,), jnp.float32, True,
                                   [(512,), (256,)], bench, (8,), concrete=True)
    assert got == (256,)
    assert autotune.refusals()[n0:] == [("probe", (1,), (512,), oom)]

    def misaligned(c):
        raise ValueError("block shape (253, 90) not divisible by 8")

    with pytest.raises(ValueError, match="divisible"):
        autotune.measured_blocks("probe", (2,), jnp.float32, True,
                                 [(253,), (256,)], misaligned, (8,), concrete=True)

    def refuse(c):
        raise RuntimeError(oom)

    with pytest.raises(RuntimeError, match="refused every candidate"):
        autotune.measured_blocks("probe", (3,), jnp.float32, True,
                                 [(512,), (1024,)], refuse, (8,), concrete=True)
    assert autotune.lookup("probe", (3,), jnp.float32, True) is None
