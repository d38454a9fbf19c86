"""Each count against a hand count at small sizes, and the model sizes at
stablelm-3b's published widths."""
import pytest

from bench.counts import krr_job, lm, matfree_apply
from bench.gen.lm_weights import sizes

TINY = {"L": 2, "D": 4, "H": 2, "Hkv": 1, "Dh": 2, "F": 6, "V": 10}


def test_matfree_apply_by_hand():
    # 10 rows of width 3 against m·d = 2·4 landmarks
    w = matfree_apply.work(rows=10, p=3, d=4, m=2)
    assert w["flops"] == 2 * 10 * 3 * 8 + 2 * 10 * 8          # 640
    assert w["bytes"] == 4 * (10 * 3 + 8 * 3 + 8 + 10 * 4)    # 408


def test_krr_job_by_hand():
    f = krr_job.flops(n=10, n_test=5, p=3, d=4, m=2)
    # C 640, W 64, CᵀC 320, Cᵀy 80, Cholesky 64/3 + 32, predict 320 + 40
    assert f == pytest.approx(640 + 64 + 320 + 80 + 64 / 3 + 32 + 320 + 40)


def test_lm_by_hand():
    assert lm.layer_params(TINY) == 2 * 4 * 4 + 2 * 4 * 2 + 3 * 4 * 6   # 120
    # two layers, a head of 10 x 4, and 4 keys at position 3
    assert lm.decode_flops(TINY, 3) == 2 * (2 * 120 + 40) + 4 * 2 * 2 * 2 * 4
    assert lm.prefill_flops(TINY, 3) == 2 * 3 * 240 + 2 * 40 + 4 * 2 * 2 * 2 * 6
    assert lm.request_flops(TINY, 2, 3, 3) == 2 * (
        lm.prefill_flops(TINY, 3) + lm.decode_flops(TINY, 3) + lm.decode_flops(TINY, 4))
    # weights: 2 layers of 120 bf16 + 2 f32 norms of 4, head, final norm,
    # one embedding row; cache: K and V, 2 layers, 5 positions of 1 x 2
    assert lm.decode_bytes(TINY, 1, 3) == 2 * (240 + 32) + 80 + 16 + 8 + 2 * 2 * 5 * 2 * 2


def test_stablelm_3b_published_sizes():
    s = sizes({"n_layers": 32, "d_model": 2560, "n_heads": 32, "n_kv_heads": 32,
               "d_ff": 6912, "vocab_size": 50304})
    assert s["Dh"] == 80
    assert lm.layer_params(s) == 79_298_560
    total = 32 * lm.layer_params(s) + 2 * 50304 * 2560
    assert 2.79e9 < total < 2.80e9          # the published 2.8 B parameters
