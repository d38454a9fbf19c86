"""``trace_reduce`` on a hand-made trace whose numbers are worked out by
hand (nesting, clipping to the window, gaps labelled by host span)."""
import pytest

from bench import trace_reduce as tr


def planes():
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.window", 0, 100], ["bench.fit", 10, 50],
        ["bench.predict", 60, 30]]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["E", -10, 13],          # starts before the window: clipped to 0..3
            ["A", 5, 15], ["B", 8, 4],   # B nested in A
            ["C", 30, 20], ["D", 70, 5],
            ["late", 120, 5]]},      # after the window: left out
        {"name": "XLA Modules", "events": [
            ["jit_fit(1)", 5, 45], ["jit_predict(2)", 70, 5]]}]}
    return [host, dev]


def test_busy_window_and_self_times():
    r = tr.reduce(planes())
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx((3 + 15 + 20 + 5) * 1e-9)
    assert r.op_seconds("A") == pytest.approx(11e-9)      # 15 less B's 4
    assert r.op_seconds("B", "C") == pytest.approx(24e-9)
    assert r.op_seconds("late") == 0
    assert r.module_seconds("fit") == pytest.approx(45e-9)
    assert r.module_count("jit_") == 2


def test_gaps_by_host_span():
    r = tr.reduce(planes())
    # holes 3..5 (no inner span), 20..30 (fit), 50..70 (mid 60: predict),
    # 75..100 (predict)
    assert r.gaps() == [("window", pytest.approx(2e-9)), ("fit", pytest.approx(10e-9)),
                        ("predict", pytest.approx(20e-9)),
                        ("predict", pytest.approx(25e-9))]
    b = r.breakdown()
    assert b["idle_gaps"][0] == ["predict", pytest.approx(45e-9)]
    assert b["device_ops"][0] == ["C", pytest.approx(20e-9)]


def test_no_device_plane_reads_nothing():
    r = tr.reduce(planes()[:1])
    assert r.devices == [] and r.busy_s == 0 and r.op_seconds("A") == 0


def recorded():
    """250 ms of a real ``krr-msd.fit`` window on one TPU v5e (three jobs),
    cut by ``bench/tools/record_trace.py``."""
    import gzip
    import json
    import pathlib

    path = pathlib.Path(__file__).with_name("data") / "krr_trace_v5e.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _grid(planes, us=1.0):
    """Busy and idle-by-span on a 1 µs grid: a second way to the same sums."""
    import numpy as np

    host = [e for p in planes if p["name"].startswith("/host") for ln in p["lines"]
            for e in ln["events"]]
    t0, dur = next((s, d) for n, s, d in host if n == "bench.window")
    nb = int(dur / (us * 1e3))
    busy = np.zeros(nb, bool)
    dev = next(p for p in planes if p["name"] == "/device:TPU:0")
    for n, s, d in next(ln for ln in dev["lines"] if ln["name"] == "XLA Ops")["events"]:
        a, b = int((s - t0) / (us * 1e3)), int(np.ceil((s + d - t0) / (us * 1e3)))
        busy[max(a, 0):min(b, nb)] = True
    label = np.full(nb, "window", object)
    for n, s, d in sorted(host, key=lambda e: -e[2]):     # inner spans last
        if n != "bench.window":
            a, b = int((s - t0) / (us * 1e3)), int((s + d - t0) / (us * 1e3))
            label[max(a, 0):min(b, nb)] = n[len("bench."):]
    idle = {k: float(np.sum(~busy & (label == k))) * us * 1e-6 for k in set(label)}
    return float(busy.sum()) * us * 1e-6, idle


def test_recorded_trace_by_hand():
    r = tr.reduce(recorded())
    assert [d.name for d in r.devices] == ["/device:TPU:0"]
    assert r.window_s == pytest.approx(0.25)
    assert [s[0] for s in r.spans] == ["draw", "fit", "predict"] * 3
    # read off the trace: three kernels on the 463,872 padded training rows
    # at 33.52 ms each, two on the 51,712 padded test rows at 3.73 ms each
    assert r.op_seconds("matfree_apply") == pytest.approx(
        3 * 0.033524 + 2 * 0.0037325, rel=1e-4)
    assert r.module_count("jit_matfree_apply") == 5
    assert r.module_count("jit__solve_psd_ladder") == 2


def test_recorded_trace_against_a_grid():
    planes = recorded()
    r = tr.reduce(planes)
    busy, idle = _grid(planes)
    assert r.busy_s == pytest.approx(busy, abs=2e-4)       # grid rounding
    got = dict(map(tuple, r.breakdown()["idle_gaps"]))
    assert sum(got.values()) == pytest.approx(r.window_s - r.busy_s, rel=1e-9)
    for k, v in idle.items():
        assert got.get(k, 0.0) == pytest.approx(v, abs=1e-3), (k, got, idle)
