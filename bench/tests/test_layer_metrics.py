"""Each per-layer reader on a hand-made trace, against its formula worked
out from the counts."""
import pytest

from bench import harness
from bench import trace_reduce as tr
from bench.counts import lm, matfree_apply
from bench.gen.lm_weights import sizes
from bench.tests import tiny

PEAKS = harness.read_json(harness.BENCH / "peaks.json")["TPU v5 lite"]
MS = 1e6    # ns


def ctx(cell, modules, ops, window_ms=1000.0, items=1, **counters):
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 0.0, window_ms * MS]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [[n, s * MS, d * MS] for n, s, d in modules]},
            {"name": "XLA Ops", "events": [[n, s * MS, d * MS] for n, s, d in ops]}]}]
    w = harness.Window(window_ms / 1000, {"kind": "backlog"})
    w.items = [harness.Item(0, 0, 0, 0) for _ in range(items)]
    w.t_end = window_ms / 1000
    w.counters.update(counters)
    return harness.Ctx(cell=cell, window=w, peaks=PEAKS, setup_s=1.0,
                       trace=tr.reduce(planes))


def read(name, c):
    return harness.load_module(harness.BENCH / "layer_metrics" / f"{name}.py").read(c)


def test_idle():
    c = ctx(tiny.cell("krr-msd.fit", tiny.KRR_CONFIG, tiny.KRR_TRAFFIC), [],
            [["a", 100.0, 300.0], ["b", 450.0, 100.0], ["c", 460.0, 10.0]])
    assert read("idle.fit", c) == pytest.approx(60.0)


def test_matfree_apply_roofline():
    cfg = tiny.KRR_CONFIG
    c = ctx(tiny.cell("krr-msd.fit", cfg, tiny.KRR_TRAFFIC), [],
            [["%matfree_apply.1 = f32[…] custom-call(…)", 0.0, 2.0],
             ["%matfree_apply.1 = f32[…] custom-call(…)", 5.0, 1.0],
             ["%fusion.3 = …", 7.0, 4.0]], jobs=1)
    least = 0.0
    for rows in (cfg["n_train"], cfg["n_test"]):
        w = matfree_apply.work(rows, cfg["p"], cfg["sketch_d"], cfg["sketch_m"])
        least += max(w["flops"] / PEAKS["bf16_flops_per_s"],
                     w["bytes"] / PEAKS["hbm_bytes_per_s"])
    assert read("matfree_apply_roofline", c) == pytest.approx(100 * least / 3e-3)
    # no kernel in the trace: nothing to read
    c.trace = tr.reduce([{"name": "/host:CPU", "lines": [{"name": "p", "events": [
        ["bench.window", 0.0, 1e9]]}]}])
    assert read("matfree_apply_roofline", c) is None


def test_hbm_share_decode():
    t = dict(tiny.LM_TRAFFIC, batch=2, prompt_len=3, new_tokens=3)
    c = ctx(tiny.cell("stablelm-3b.decode", tiny.LM_CONFIG, t),
            [["jit__lambda(1)", 0.0, 100.0], ["jit__unknown(2)", 100.0, 500.0]],
            [["x", 0.0, 600.0]], decode_steps=2)
    s = sizes(tiny.LM_CONFIG["model"])
    per_step = (lm.decode_bytes(s, 2, 3) + lm.decode_bytes(s, 2, 4)) / 2
    assert read("hbm_share.decode", c) == pytest.approx(
        100 * per_step / PEAKS["hbm_bytes_per_s"] / 0.25)


def test_mfu_prefill_and_decode():
    t = dict(tiny.LM_TRAFFIC, batch=1, prompt_len=24, new_tokens=1)
    c = ctx(tiny.cell("stablelm-3b.prefill", tiny.LM_CONFIG, t),
            [["jit__lambda(7)", 0.0, 10.0], ["jit__lambda(7)", 20.0, 10.0],
             ["jit__argmax(3)", 31.0, 1.0]], [["x", 0.0, 32.0]], items=2)
    f = lm.prefill_flops(sizes(tiny.LM_CONFIG["model"]), 24)
    assert read("mfu.prefill", c) == pytest.approx(
        100 * 2 * f / (0.02 * PEAKS["bf16_flops_per_s"]))
    t = dict(tiny.LM_TRAFFIC, batch=2, prompt_len=24, new_tokens=5)
    c = ctx(tiny.cell("stablelm-3b.decode", tiny.LM_CONFIG, t), [], [], items=3)
    f = lm.request_flops(sizes(tiny.LM_CONFIG["model"]), 2, 24, 5)
    assert read("mfu.decode", c) == pytest.approx(
        100 * 3 * f / (1.0 * PEAKS["bf16_flops_per_s"]))
