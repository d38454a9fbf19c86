"""Per-kernel allclose sweeps vs the ref.py oracles (shapes × dtypes),
as required for every Pallas kernel. interpret=True executes on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.trace import all_shapes
from repro.core.sketch import make_accum_sketch
from repro.core.sketched_attention import accum_attention, make_seq_sketch
from repro.kernels.accum_apply.ops import (
    autotune_blocks,
    default_interpret,
    sketch_both_kernel,
    sketch_left_kernel,
    sketch_right_kernel,
)
from repro.kernels.accum_apply.ref import accum_apply_ref, sketch_both_ref
from repro.kernels.landmark_attention.kernel import landmark_attention
from repro.kernels.landmark_attention.ops import (
    accum_attention_kernel,
    landmark_attend,
    landmark_stats_fused,
)
from repro.kernels.landmark_attention.ref import (
    landmark_attention_ref,
    landmark_stats_ref,
)

KEY = jax.random.PRNGKey(0)
WIDE = 8192   # wider than one K column tile at the rows these tests use


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "R,N,d,m", [(128, 256, 8, 1), (256, 512, 32, 4), (128, 1024, 16, 8), (256, 256, 64, 2)]
)
def test_accum_apply_sweep(R, N, d, m, dtype):
    K = jax.random.normal(KEY, (R, N), dtype)
    sk = make_accum_sketch(jax.random.fold_in(KEY, d * m), N, d, m)
    ref = accum_apply_ref(K, sk.indices, sk.coef.astype(jnp.float32))
    out = sketch_right_kernel(K, sk, bm=128, bd=min(8, d))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_accum_apply_wide_K_chunked():
    """K wider than one column tile: the kernel's contraction grid sums the
    chunk partial products exactly."""
    K = jax.random.normal(KEY, (128, 3 * 8192 // 2), jnp.float32)
    sk = make_accum_sketch(KEY, K.shape[1], 16, 4)
    ref = accum_apply_ref(K, sk.indices, sk.coef)
    out = sketch_right_kernel(K, sk, bm=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_accum_apply_wide_K_non_multiple_chunk():
    """N neither a multiple of the column tile nor of the block: padding."""
    N = 2 * WIDE + 777
    K = jax.random.normal(KEY, (96, N), jnp.float32)
    sk = make_accum_sketch(jax.random.fold_in(KEY, 5), N, 12, 3)
    ref = accum_apply_ref(K, sk.indices, sk.coef)
    out = sketch_right_kernel(K, sk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_accum_apply_odd_shapes_padded():
    """Shapes that do not tile (R=100, d=10): the ops wrapper pads and slices."""
    K = jax.random.normal(KEY, (100, 300), jnp.float32)
    sk = make_accum_sketch(jax.random.fold_in(KEY, 9), 300, 10, 3)
    ref = accum_apply_ref(K, sk.indices, sk.coef)
    out = sketch_right_kernel(K, sk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_wide_K_chunking_does_not_unroll():
    """Jaxpr-size regression: the kernel's grid tiles the columns, so the
    traced program is O(1) in the number of column chunks (the seed's Python
    loop emitted one pallas_call per chunk, exploding compile time)."""

    def n_eqns(N):
        sk = make_accum_sketch(KEY, N, 16, 2)
        jaxpr = jax.make_jaxpr(lambda K: sketch_right_kernel(K, sk))(
            jnp.zeros((64, N), jnp.float32)
        )
        return len(jaxpr.jaxpr.eqns)

    assert n_eqns(2 * WIDE) == n_eqns(4 * WIDE)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "n,d,m", [(128, 8, 1), (256, 32, 4), (128, 16, 8), (256, 64, 2), (256, 300, 3)]
)
def test_sketch_both_fused_sweep(n, d, m, dtype):
    """Fused (C, W) kernel vs the two-pass oracle across shapes × dtypes;
    d = 300 spans three 128-lane output column blocks (the last padded)."""
    K = jax.random.normal(KEY, (n, n), dtype)
    K = (0.5 * (K.astype(jnp.float32) + K.astype(jnp.float32).T)).astype(dtype)
    sk = make_accum_sketch(jax.random.fold_in(KEY, n + d * m), n, d, m)
    C_ref, W_ref = sketch_both_ref(K, sk.indices, sk.coef.astype(jnp.float32))
    C, W = sketch_both_kernel(K, sk, bm=64, bn=128)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(C, np.float32), np.asarray(C_ref, np.float32), rtol=tol, atol=tol
    )
    np.testing.assert_allclose(
        np.asarray(W, np.float32), np.asarray(W_ref, np.float32), rtol=tol, atol=tol
    )


def test_sketch_both_fused_odd_shapes():
    """n=400, d=19 (nothing tiles): padded fused kernel stays exact."""
    n, d, m = 400, 19, 4
    K = jax.random.normal(KEY, (n, n), jnp.float32)
    sk = make_accum_sketch(jax.random.fold_in(KEY, 41), n, d, m)
    C_ref, W_ref = sketch_both_ref(K, sk.indices, sk.coef)
    C, W = sketch_both_kernel(K, sk)
    np.testing.assert_allclose(np.asarray(C), np.asarray(C_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(W), np.asarray(W_ref), rtol=1e-4, atol=1e-4)


def test_sketch_left_kernel_matches_dense():
    sk = make_accum_sketch(jax.random.fold_in(KEY, 77), 300, 12, 3)
    M = jax.random.normal(KEY, (300, 7), jnp.float32)
    S = sk.dense()
    out = sketch_left_kernel(sk, M)
    np.testing.assert_allclose(np.asarray(out), np.asarray(S.T @ M),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,d,m,c", [(256, 8, 1, 16), (512, 32, 4, 32),
                                     (300, 12, 3, 7), (128, 19, 2, 5)])
def test_sketch_left_kernel_sweep(N, d, m, c, dtype):
    """True left-apply vs the ref oracle across shapes × dtypes (incl. shapes
    where nothing tiles — the ops wrapper pads rows and sketch columns)."""
    from repro.kernels.accum_apply.ref import sketch_left_ref

    sk = make_accum_sketch(jax.random.fold_in(KEY, N + d + m), N, d, m)
    M = jax.random.normal(jax.random.fold_in(KEY, c), (N, c), dtype)
    ref = sketch_left_ref(sk.indices, sk.coef, M)
    out = sketch_left_kernel(sk, M)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_sketch_left_kernel_multi_tile_accumulation():
    """N larger than the row tile: partial products accumulate across grid
    steps (the out block is revisited, as in the fused kernel's W)."""
    from repro.kernels.accum_apply.ref import sketch_left_ref

    N, d, m, c = 5000, 16, 4, 24
    sk = make_accum_sketch(jax.random.fold_in(KEY, 91), N, d, m)
    M = jax.random.normal(KEY, (N, c), jnp.float32)
    ref = sketch_left_ref(sk.indices, sk.coef, M)
    out = sketch_left_kernel(sk, M, bn=512)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_sketch_left_kernel_never_transposes_M():
    """The regression that motivated the rewrite: the old path computed
    (Mᵀ S)ᵀ, binding an O(n·c) transposed copy of M.  The traced program must
    contain no (c, N)-shaped buffer."""
    N, c = 300, 7
    sk = make_accum_sketch(jax.random.fold_in(KEY, 77), N, 12, 3)
    M = jax.random.normal(KEY, (N, c), jnp.float32)

    # shape walker now shared via repro.analysis.trace; the (c, N) assertion
    # is this file's planted positive-control target — M itself is (N, c), so
    # the detector must prove the transposed layout is ABSENT, not just small
    shapes = all_shapes(jax.make_jaxpr(
        lambda M: sketch_left_kernel(sk, M))(M).jaxpr)
    assert (N, c) in {s[:2] for s in shapes if len(s) >= 2}  # detector sees M
    assert not any(s[:2] == (c, N) for s in shapes if len(s) >= 2), shapes


def test_interpret_autodetect_and_autotune():
    """Backend autodetection (no TPU in CI → interpreter) and the block table
    covering the benchmark anchor shape."""
    if jax.default_backend() != "tpu":
        assert default_interpret() is True
    bm, bd = autotune_blocks(4096, 8192, 64, 4, jnp.float32)
    assert (bm, bd) == (256, 64)
    # heuristic fallback stays within the VMEM budget and divides nothing
    bm, bd = autotune_blocks(1000, 5000, 48, 3, jnp.float32)
    assert bm >= 8 and 1 <= bd <= 48


@pytest.mark.parametrize("d", [16, 64, 128, 200, 1024])
def test_gemm_autotune_candidates_are_lane_legal(d):
    """Every K·S tiling autotune may try has an output block Mosaic accepts:
    bd is d itself or a multiple of the 128-lane tile (a (bm, 64) block of
    a d = 128 output is refused at lowering, and autotune re-raises that)."""
    from repro.kernels.accum_apply.ops import _gemm_candidates

    fb = autotune_blocks(4096, 4096, d, 4, jnp.float32, interpret=True)
    for bm, bd in _gemm_candidates(4096, d, fb):
        assert bm % 8 == 0 and (bd == d or bd % 128 == 0), (bm, bd)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,Dh,L,Dv", [(128, 32, 16, 32), (256, 64, 64, 64), (128, 128, 256, 128)])
def test_landmark_attention_sweep(S, Dh, L, Dv, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (S, Dh), dtype)
    kt = jax.random.normal(ks[1], (L, Dh), dtype)
    M = jax.random.normal(ks[2], (L, Dv), dtype)
    ref = landmark_attention_ref(q, kt, M)
    out = landmark_attention(q, kt, M, bq=64)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_full_sketched_attention_kernel_vs_core():
    B, H, S, Dh = 2, 3, 128, 32
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, S, Dh))
    k = jax.random.normal(ks[1], (B, H, S, Dh))
    v = jax.random.normal(ks[2], (B, H, S, Dh))
    sk = make_seq_sketch(ks[3], S, 32, 4)
    core = accum_attention(q, k, v, sk)
    kern = accum_attention_kernel(q, k, v, sk, bq=64)
    np.testing.assert_allclose(np.asarray(core), np.asarray(kern), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------- #
# landmark kernels: padding, bias lane, fused stats, autotune registration
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("S,L,Dv", [(100, 13, 24), (7, 3, 5), (256, 64, 64)])
def test_landmark_attend_padded_bias_vs_oracle(S, L, Dv):
    """The ops-level entry pads arbitrary (S, L) to the block grid; padded
    landmarks get −inf bias so they carry exactly zero softmax weight, and the
    caller-supplied bias lane (the decode log-mass correction) is honored."""
    Dh = 16
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (S, Dh))
    kt = jax.random.normal(ks[1], (L, Dh))
    M = jax.random.normal(ks[2], (L, Dv))
    bias = jax.random.normal(ks[3], (L,))
    ref = landmark_attention_ref(q, kt, M, bias)
    out = landmark_attend(q, kt, M, bias, bq=64, interpret=True)
    assert out.shape == (S, Dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("S,L,Dv", [(130, 10, 24), (512, 32, 16), (9, 5, 8)])
def test_landmark_stats_fused_vs_ref(S, L, Dv):
    """ONE fused sweep over S must reproduce both the landmark-row softmax W
    and the online-softmax Bm·V of the two-pass oracle, on odd (padded)
    shapes."""
    Dh = 16
    ks = jax.random.split(KEY, 4)
    qt = jax.random.normal(ks[0], (L, Dh))
    kt = jax.random.normal(ks[1], (L, Dh))
    k = jax.random.normal(ks[2], (S, Dh))
    v = jax.random.normal(ks[3], (S, Dv))
    W_ref, BmV_ref = landmark_stats_ref(qt, kt, k, v)
    W, BmV = landmark_stats_fused(qt, kt, k, v, bs=64, interpret=True)
    assert W.shape == (L, L) and BmV.shape == (L, Dv)
    np.testing.assert_allclose(np.asarray(W), np.asarray(W_ref), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(BmV), np.asarray(BmV_ref), rtol=1e-5, atol=1e-5)


def test_landmark_autotune_round_trip(tmp_path, monkeypatch):
    """Both landmark kernels register in the SAME measured cache as the KRR
    kernels: a gated eager call measures + persists under its own kind, and
    the persisted winner is served to later (e.g. traced) lookups."""
    import json as _json

    from repro.kernels.accum_apply import autotune

    cache = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.ENV_CACHE, str(cache))
    monkeypatch.setenv(autotune.ENV_GATE, "1")

    S, Dh, L, Dv = 128, 16, 8, 8
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (S, Dh))
    kt = jax.random.normal(ks[1], (L, Dh))
    M = jax.random.normal(ks[2], (L, Dv))
    out = landmark_attend(q, kt, M, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(landmark_attention_ref(q, kt, M)),
        rtol=1e-5, atol=1e-5,
    )
    k_seq = jax.random.normal(ks[3], (S, Dh))
    landmark_stats_fused(kt, kt, k_seq, q[:, :Dv], interpret=True)

    entries = _json.loads(cache.read_text())
    kinds = {e.split("|")[0] for e in entries}
    assert {"landmark_attention", "landmark_stats"} <= kinds
    blocks = autotune.lookup("landmark_attention", (S, Dh, L, Dv), q.dtype, True)
    assert blocks is not None and len(blocks) == 1


def test_accum_attention_use_kernel_routing():
    """core.accum_attention(use_kernel=True) routes through the Pallas
    pipeline and matches the plain-XLA path."""
    B, H, S, Dh = 1, 2, 96, 16
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, S, Dh))
    k = jax.random.normal(ks[1], (B, H, S, Dh))
    v = jax.random.normal(ks[2], (B, H, S, Dh))
    sk = make_seq_sketch(ks[3], S, 16, 4)
    plain = accum_attention(q, k, v, sk, use_kernel=False)
    kern = accum_attention(q, k, v, sk, use_kernel=True)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(kern), rtol=1e-4, atol=1e-4)


def test_sketch_decode_attend_kernel_routing():
    """The decode-path kernel (log-mass correction in the bias lane) matches
    the plain jnp decode attend, including empty-slot masking."""
    from repro.core.sketched_attention import (
        decode_slots,
        init_sketch_cache,
        sketch_decode_attend,
        update_sketch_cache,
    )

    B, Hkv, G, d_slots, m_r, Dh = 2, 2, 2, 16, 2, 8
    cache = init_sketch_cache(B, Hkv, d_slots, Dh)
    for t in range(10):    # 10 tokens → some slots stay empty (mass 0)
        kk = jax.random.fold_in(KEY, t)
        k_t = jax.random.normal(kk, (B, Hkv, Dh))
        v_t = jax.random.normal(jax.random.fold_in(kk, 1), (B, Hkv, Dh))
        cache = update_sketch_cache(
            cache, k_t, v_t, decode_slots(KEY, t, d_slots, m_r)
        )
    q_t = jax.random.normal(jax.random.fold_in(KEY, 99), (B, G * Hkv, Dh))
    plain = sketch_decode_attend(q_t, cache, use_kernel=False)
    kern = sketch_decode_attend(q_t, cache, use_kernel=True)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(kern), rtol=1e-5, atol=1e-6)
