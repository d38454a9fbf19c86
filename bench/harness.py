"""One run of one benchmark cell, driven by ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Everything that belongs to one of them sits in files of its own, found by
name, so that a later cell, configuration or metric is added as new files
and entries, with no edit here:

- ``bench/configs/<config>.json``: the sizes as run; its ``runner`` names a
  module ``bench/runners/<runner>.py`` with ``setup(cell)``,
  ``measure(state, window)`` and ``check(state, window)``;
- ``bench/traffic/<cell>.json``: the traffic mix's parameters;
- ``bench/end_to_end/<metric>.py``: ``read(ctx)`` of an end-to-end metric
  from the window's completed work;
- ``bench/layer_metrics/<metric>.py``: ``read(ctx)`` of a per-layer metric
  from the reduced trace and the window's counters, or ``None`` when it
  finds nothing to read;
- ``bench/counts/<name>.py``: closed-form operation and byte counts.

The harness checks the device, times the runner's set-up as ``setup_s``,
measures a window, reads the memory peak, has the runner free its state and
compare its answers with the plain reference, and prints one JSON line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
from collections import Counter
from typing import Any

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoDevice(RuntimeError):
    """No accelerator of the kind the cell needs: the run prints no result."""


def load_module(path: pathlib.Path):
    """Import a benchmark file by path (its name may hold '.' or '-')."""
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    name = name.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """One workload entry with its configuration and traffic loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    seed: int
    end_to_end: list[dict]        # the manifest's metrics this cell reports
    per_layer: list[dict]
    root: pathlib.Path = ROOT


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(manifest: dict, name: str, seed: int,
              bench: pathlib.Path = BENCH) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = read_json(bench.parent / configs[w["config"]]["file"])
    traffic = read_json(bench / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        seed=seed,
        end_to_end=[m for m in manifest["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _reports(m, name)],
        root=bench.parent)


# --------------------------------------------------------------------------- #
# seeds
# --------------------------------------------------------------------------- #

def seed_key(seed: int, *tags: int):
    """A JAX key from any whole number: ``PRNGKey`` keeps only 32 bits of a
    larger seed, so the low and high words are folded in one by one."""
    import jax
    import numpy as np

    key = jax.random.PRNGKey(0)
    s = int(seed)
    for word in (s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF, s >> 64):
        key = jax.random.fold_in(key, np.uint32(word & 0xFFFFFFFF))
    for t in tags:
        key = jax.random.fold_in(key, np.uint32(t))
    return key


def seed_rng(seed: int, *tags: int):
    """A NumPy generator from the seed and tags."""
    import numpy as np

    return np.random.default_rng([int(seed) & (2**64 - 1), int(seed) >> 64,
                                  *tags])


# --------------------------------------------------------------------------- #
# the measured window
# --------------------------------------------------------------------------- #

def span(name: str):
    """A host span in the profiler's trace (free when no trace is taken)."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


class CompileCounter:
    """Counts programs lowered and compiled while it is armed."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.armed = False
        self.counts = Counter()
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if self.armed and event in (self.LOWER, self.COMPILE):
            self.counts["lowered" if event == self.LOWER else "compiled"] += 1


@dataclasses.dataclass
class Item:
    """One completed job or request of the window (host-clock seconds from
    the window's start), with what the host did while it ran
    (``host_counters`` deltas)."""

    start: float
    end: float
    tokens: int = 0
    ok: bool = True
    host: dict = dataclasses.field(default_factory=dict)


class Window:
    """The measured window over a backlog: work queued at the start and
    offered above capacity, so a new item starts as soon as the last ends,
    until ``seconds`` have passed.  The traffic file's ``arrival`` is
    ``{"kind": "backlog"}``."""

    def __init__(self, seconds: float, arrival: dict):
        if arrival.get("kind") != "backlog":
            raise ValueError(f"unknown arrival kind {arrival.get('kind')!r}")
        self.seconds = float(seconds)
        self.items: list[Item] = []
        self.counters: Counter = Counter()
        self.t0 = None
        self.t_end = None

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def arrivals(self):
        """Yield each item's index as the last one ends, while the window
        is open."""
        self.t0 = time.perf_counter()
        i = 0
        while (start := self.now()) < self.seconds:
            self._start, self._host = start, host_counters()
            yield i
            i += 1
        self.t_end = self.now() if self.items else self.seconds

    def record(self, *, tokens: int = 0, ok: bool = True) -> None:
        """Close the item the last ``arrivals()`` step yielded."""
        end = self.now()
        host = {k: v - self._host[k] for k, v in host_counters().items()}
        self.items.append(Item(start=self._start, end=end, tokens=int(tokens),
                               ok=bool(ok), host=host))

    @property
    def elapsed(self) -> float:
        """The window's length: from its start to the end of its last item
        (all the time of all the work)."""
        return self.t_end


# --------------------------------------------------------------------------- #
# results
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class Check:
    """One number compared for ``correct``: it passes at or below ``limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Ctx:
    """What a metric reader gets."""

    cell: Cell
    window: Window
    peaks: dict
    setup_s: float
    trace: Any = None            # trace_reduce.Reduced, with --trace 1

    def count(self, name: str):
        """The count module ``bench/counts/<name>.py``."""
        return importlib.import_module(f"bench.counts.{name}")


def peaks_for(kind: str) -> dict:
    table = read_json(BENCH / "peaks.json")
    if kind not in table:
        raise NoDevice(f"device kind {kind!r} has no row in bench/peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def find_devices(chips: int):
    """The cell's devices: TPUs only, at least ``chips`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU found (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


_GC = {"s": 0.0, "t": 0.0}


def _gc_clock(phase: str, info: dict) -> None:
    """Adds up the seconds Python's garbage collector runs (``gc.callbacks``)."""
    if phase == "start":
        _GC["t"] = time.perf_counter()
    else:
        _GC["s"] += time.perf_counter() - _GC["t"]


gc.callbacks.append(_gc_clock)


def host_counters() -> dict:
    """Cumulative counters of what this process did, for telling where a
    slow item's time went: CPU seconds (``user``, ``sys``), page faults
    that read from disk (``majflt``) and seconds in Python's garbage
    collector (``gc``)."""
    import resource

    r = resource.getrusage(resource.RUSAGE_SELF)
    return {"user": r.ru_utime, "sys": r.ru_stime, "majflt": r.ru_majflt,
            "gc": _GC["s"]}


def _host_line(h: dict) -> str:
    return ", ".join(f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
                     for k, v in h.items())


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _trace_dir(cell: Cell) -> pathlib.Path:
    return cell.root / ".bench_trace" / cell.name


def run_cell(cell: Cell, seconds: float, trace: bool, *, devices=None,
             peaks: dict | None = None, log=print) -> dict:
    """Set up, measure, check; return the result line's object.  Without
    ``devices`` the cell's chips are looked up, and a run without them
    raises ``NoDevice``; tests pass the devices and peaks themselves."""
    import jax

    if devices is None:
        devices = find_devices(cell.chips)
        peaks = peaks_for(devices[0].device_kind)
    kind = devices[0].device_kind
    runner = load_module(BENCH / "runners" / f"{cell.config['runner']}.py")
    compiles = CompileCounter()

    t = time.perf_counter()
    with span("setup"):
        state = runner.setup(cell)
    setup_s = time.perf_counter() - t

    if trace:
        seconds = min(seconds, float(cell.traffic.get("trace_seconds", seconds)))
        tdir = _trace_dir(cell)
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
        jax.profiler.start_trace(str(tdir))
    window = Window(seconds, cell.traffic["arrival"])
    host0 = host_counters()
    compiles.armed = True
    try:
        with span("window"):
            runner.measure(state, window)
    finally:
        compiles.armed = False
        if trace:
            jax.profiler.stop_trace()
    host = {k: v - host0[k] for k, v in host_counters().items()}
    dropped = window.counters["dropped"]
    mem = memory_peak(devices)
    slow = max(window.items, default=None, key=lambda it: it.end - it.start)
    log(f"[window] {len(window.items)} items in {window.elapsed:.3f} s; "
        f"programs lowered {compiles.counts['lowered']}, compiled "
        f"{compiles.counts['compiled']}; dropped rungs {dropped}; "
        f"memory peak {mem} B; host: {_host_line(host)}")
    if slow is not None:
        log(f"[slowest] item {window.items.index(slow)} took "
            f"{slow.end - slow.start:.4f} s from {slow.start:.3f} s; host: "
            f"{_host_line(slow.host)}")

    ctx = Ctx(cell=cell, window=window, peaks=peaks, setup_s=setup_s)
    out_metrics: dict[str, dict] = {}
    if trace:
        from bench import trace_reduce

        ctx.trace = trace_reduce.reduce_dir(_trace_dir(cell), cell.chips)
        for m in cell.per_layer:
            v = load_module(BENCH / "layer_metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        out_metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                continue
            v = load_module(BENCH / "end_to_end" / f"{m['name']}.py").read(ctx)
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    t = time.perf_counter()
    checks = runner.check(state, window)
    log(f"[check] reference and comparison took {time.perf_counter() - t:.3f} s")
    failed = sum(not it.ok for it in window.items)
    correct = bool(checks) and all(c.ok for c in checks)
    result = {
        "correct": correct,
        "attempted": len(window.items),
        "failed": failed,
        "metrics": out_metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": mem},
    }
    if trace:
        result["device"]["busy_s"] = ctx.trace.busy_s
        result["device"]["window_s"] = ctx.trace.window_s
        result["breakdown"] = ctx.trace.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result
