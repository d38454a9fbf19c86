"""The readers of the program's spans and scopes (``bench/program_trace.py``
and the five metrics on it): hand-made traces against each formula, the
loader on an ``XSpace`` that keeps the name stack among an op's metadata
stats, and the recorded chip traces, with program spans and without."""
import gzip
import json
import pathlib

import pytest

from bench import harness
from bench import program_trace as pt
from bench import trace_reduce as tr
from bench.tests import tiny

MS = 1e6    # ns
DATA = pathlib.Path(__file__).with_name("data")
METRICS = ("gram_ms.fit", "draw_idle_ms.fit", "attn_ms.decode", "attn_ms.prefill",
           "engine_idle_ms.prefill")


def ctx(cell, planes, items=1, **counters):
    """A reader's context over plain-data ``planes`` (``program_trace.load``
    form)."""
    w = harness.Window(1.0, {"kind": "backlog"})
    w.items = [harness.Item(0, 0) for _ in range(items)]
    w.t_end = 1.0
    w.counters.update(counters)
    plain = [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [e[:3] for e in ln["events"]]}
        for ln in p["lines"]]} for p in planes]
    c = harness.Ctx(cell=cell, window=w, peaks={}, setup_s=1.0,
                    trace=tr.reduce(plain))
    c.program_trace = pt.reduce(planes, c.trace.window)
    return c


def planes(spans, ops, modules=(), window_ms=100.0):
    """Host spans (name, start ms, length ms, stats), device ops (name stack,
    start ms, length ms) and programs (name, start ms, length ms)."""
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 0.0, window_ms * MS, {}],
            *[["repro." + n, s * MS, d * MS, st] for n, s, d, st in spans]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [[f"op{i}", s * MS, d * MS, stack]
                                           for i, (stack, s, d) in enumerate(ops)]},
            {"name": "XLA Modules", "events": [[n, s * MS, d * MS]
                                               for n, s, d in modules]}]}]


def read(name, c):
    return harness.load_module(harness.BENCH / "layer_metrics" / f"{name}.py").read(c)


KRR = tiny.cell("krr-msd.fit", tiny.KRR_CONFIG, tiny.KRR_TRAFFIC)
DECODE = tiny.cell("stablelm-3b.decode", tiny.LM_CONFIG, tiny.LM_TRAFFIC)
PREFILL = tiny.cell("stablelm-3b.prefill", tiny.LM_CONFIG, tiny.LM_TRAFFIC)


def test_gram_ms_fit():
    spans = [("krr.fit", 0, 90, {}), ("krr.gram", 10, 40, {})]
    ops = [("jit(f32_gram)/while/body/dot_general", 10, 30),   # the Grams' program
           ("jit(f32_gram)/while/body/add", 15, 5),             # nested: 25 + 5
           ("jit(fit)/krr.gram/dot_general", 50, 4),            # the scope elsewhere
           ("jit(solve)/cholesky", 60, 20)]
    modules = [("jit_f32_gram(3)", 10, 30), ("jit_fit(4)", 50, 4),
               ("jit__solve_psd_ladder(5)", 60, 20)]
    c = ctx(KRR, planes(spans, ops, modules), jobs=2)
    assert read("gram_ms.fit", c) == pytest.approx((30 + 4) / 2)
    # no repro.krr.gram span: a program without spans reads nothing
    c = ctx(KRR, planes(spans[:1], ops, modules), jobs=2)
    assert read("gram_ms.fit", c) is None


def test_draw_idle_ms_fit():
    spans = [("krr.draw", 5, 30, {}), ("krr.fit", 35, 40, {}), ("krr.gram", 38, 30, {})]
    ops = [("a", 0, 10), ("b", 30, 10), ("c", 60, 40)]
    # gaps 10..30 (mid 20: draw), 40..60 (mid 50: gram inside fit)
    c = ctx(KRR, planes(spans, ops), jobs=4)
    assert read("draw_idle_ms.fit", c) == pytest.approx(20 / 4)
    assert c.program_trace.idle_by_span() == {"krr.draw": pytest.approx(0.02),
                                              "krr.gram": pytest.approx(0.02)}
    c = ctx(KRR, planes(spans[1:], ops), jobs=4)
    assert read("draw_idle_ms.fit", c) is None


def test_attn_ms_decode():
    ops = [("jit(_decode_scan)/while", 0, 50),
           ("jit(_decode_scan)/while/body/closed_call/attention/dot_general", 10, 12),
           ("jit(_decode_scan)/while/body/closed_call/attention/add", 12, 2),
           ("jit(_decode_scan)/while/body/closed_call/mlp/dot_general", 25, 10),
           ("jit(prefill_with_cache)/attention/dot_general", 60, 30)]
    modules = [("jit__decode_scan(9)", 0, 50), ("jit_prefill_with_cache(8)", 60, 30)]
    c = ctx(DECODE, planes([], ops, modules), decode_steps=4)
    assert read("attn_ms.decode", c) == pytest.approx(12 / 4)
    c = ctx(DECODE, planes([], [(s.replace("attention", "x"), a, b) for s, a, b in ops],
                           modules), decode_steps=4)
    assert read("attn_ms.decode", c) is None


def test_attn_ms_prefill():
    ops = [("jit(prefill_with_cache)/while/body/attention/fusion", 0, 30),
           ("jit(prefill_with_cache)/while/body/mlp/fusion", 30, 40),
           ("jit(prefill_with_cache)/while/body/attention/fusion", 100, 30),
           ("jit(argmax)/sample/argmax", 140, 1)]
    modules = [("jit_prefill_with_cache(8)", 0, 70),
               ("jit_prefill_with_cache(8)", 100, 30), ("jit_argmax(2)", 140, 1)]
    c = ctx(PREFILL, planes([], ops, modules, window_ms=150), items=2)
    assert read("attn_ms.prefill", c) == pytest.approx(60 / 2)


def test_engine_idle_ms_prefill():
    spans = [("engine.generate", 0, 100, {"request": 1}),
             ("engine.prefill", 0, 20, {"request": 1}),
             ("engine.first_token", 40, 10, {"request": 1})]
    ops = [("p", 20, 20), ("s", 50, 40), ("x", 100, 10)]
    # gaps 0..20 prefill, 40..50 first_token, 90..100 generate, 110..120 none
    c = ctx(PREFILL, planes(spans, ops, window_ms=120), items=2)
    assert read("engine_idle_ms.prefill", c) == pytest.approx((20 + 10 + 10) / 2)
    assert c.program_trace.idle_by_span()[None] == pytest.approx(0.01)
    c = ctx(PREFILL, planes([], ops, window_ms=120), items=2)
    assert read("engine_idle_ms.prefill", c) is None


XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 90000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 5000000
             stats { metadata_id: 10 int64_value: 3 } }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "repro.engine.generate" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(f)" } }
  stat_metadata { key: 10 value { id: 10 name: "request" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 2000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 4000000
             stats { metadata_id: 21 str_value: "jit_f" } } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 2000
    events { metadata_id: 6 offset_ps: 0 duration_ps: 4000000 } }
  lines { id: 3 name: "Steps" timestamp_ns: 2000
    events { metadata_id: 6 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.1 = f32[] fusion()"
    stats { metadata_id: 20 ref_value: 30 } } }
  event_metadata { key: 6 value { id: 6 name: "jit_f(1)" } }
  stat_metadata { key: 20 value { id: 20 name: "tf_op" } }
  stat_metadata { key: 21 value { id: 21 name: "hlo_module" } }
  stat_metadata { key: 30 value { id: 30 name: "jit(f)/attention/dot_general" } }
}
"""


def test_load_keeps_spans_stats_and_metadata_name_stacks(tmp_path):
    """Spans keep their stats; an op's name stack comes from its metadata's
    ``tf_op`` stat (an interned string here); other lines and host events
    are dropped; times are those ``ProfileData`` gives."""
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    got = pt.load(path)
    assert got == [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 1000.0, 90000.0, {}],
            ["repro.engine.generate", 2000.0, 5000.0, {"request": 3}]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%fusion.1 = f32[] fusion()", 2000.0, 4000.0,
                 "jit(f)/attention/dot_general"]]},
            {"name": "XLA Modules", "events": [["jit_f(1)", 2000.0, 4000.0]]}]}]
    ref = {e.name: e.start_ns for p in ProfileData.from_file(str(path)).planes
           for ln in p.lines for e in ln.events}
    assert ref["repro.engine.generate"] == 2000.0
    p = pt.reduce(got)
    assert p.spans == [("engine.generate", 2000.0, 7000.0, {"request": 3})]
    assert p.op_seconds("attention", "jit_f") == (pytest.approx(4e-6), 1)
    # a trace taken outside the benchmark (no bench.window) is read whole
    del got[0]["lines"][0]["events"][0]
    p = pt.reduce(got)
    assert p.window == (2000.0, 7000.0) and p.idle_by_span() == {
        "engine.generate": pytest.approx(1e-6)}


def _recorded(name):
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


# short traced runs on one TPU v5e (bench/tools/record_program_trace.py), and
# what each run printed: (cell, file, items, counters, metrics)
RECORDED = [
    (KRR, "krr_program_trace_v5e.json.gz", 4, {"jobs": 4},
     {"gram_ms.fit": 36.575093456500014, "draw_idle_ms.fit": 4.690441679999977}),
    (PREFILL, "prefill_program_trace_v5e.json.gz", 3, {},
     {"attn_ms.prefill": 99.3931784333336, "engine_idle_ms.prefill": 5.47110784800002}),
]


@pytest.mark.parametrize("cell,name,items,counters,printed", RECORDED,
                         ids=["krr-msd.fit", "stablelm-3b.prefill"])
def test_recorded_program_trace_reads_what_the_chip_printed(cell, name, items,
                                                             counters, printed):
    c = ctx(cell, _recorded(name), items=items, **counters)
    for m in METRICS:
        want = printed.get(m)
        got = read(m, c)
        assert got == (None if want is None else pytest.approx(want, rel=1e-9)), m


def test_recorded_program_trace_spans_and_scopes():
    """What the recorded serving trace holds: three requests, each one
    generate span over its prefill and first-token spans with one request
    id, and a prefill program split into the model's scopes."""
    p = pt.reduce(_recorded("prefill_program_trace_v5e.json.gz"))
    by_request = {}
    for name, s, e, st in p.spans:
        by_request.setdefault(st["request"], []).append(name)
    assert len(by_request) == 3 and all(
        v == ["engine.generate", "engine.prefill", "engine.first_token"]
        for v in by_request.values())
    t = {s: p.op_seconds(s, "prefill_with_cache")[0] for s in ("attention", "mlp", "head")}
    whole = p.op_seconds("", "prefill_with_cache")[0]
    assert t["attention"] > t["mlp"] > t["head"] > 0 and sum(t.values()) < whole


def test_old_recorded_trace_reads_nothing():
    """The recorded trace of a program without spans or scopes (the first
    benchmark's ``krr-msd.fit``) reads None for all five."""
    planes = _recorded("krr_trace_v5e.json.gz")
    for cell in (KRR, DECODE, PREFILL):
        c = ctx(cell, planes, items=3, jobs=3, decode_steps=189)
        assert c.program_trace.spans == []
        for m in METRICS:
            assert read(m, c) is None, m
