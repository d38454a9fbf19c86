"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

A trace is read into plain data (``load``): a list of planes, each
``{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns]]}]}``,
keeping the device planes (``/device:TPU:<i>``) and the host plane.  The
recorded trace in ``bench/tests/`` is kept in the same form.

``reduce`` takes the window from the benchmark's host span ``bench.window``
and computes, per device plane and then averaged over the chips used:

- busy time: the union of the intervals of the ``XLA Ops`` line's events
  inside the window, so nested events count once;
- time by operation: each op's self time (its duration less the events it
  encloses on the same line), summed by name;
- time by program: the ``XLA Modules`` line's events, by name;
- idle gaps: the holes in the busy union, each labelled by the innermost
  benchmark host span (``bench.<label>``) covering its midpoint, or
  ``window`` where no span inside the window covers it.
"""
from __future__ import annotations

import dataclasses
import pathlib
from collections import defaultdict

SPAN_PREFIX = "bench."
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def load(path) -> list[dict]:
    """Planes of an ``.xplane.pb`` file as plain data."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        if not (device or plane.name.startswith("/host:CPU")):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def newest_xplane(directory) -> pathlib.Path:
    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """{name: summed self time} of possibly nested (name, start, end): each
    event's duration less that of the events directly inside it."""
    totals = defaultdict(float)
    stack: list[list] = []          # [name, end] of the enclosing events
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][1]:
            stack.pop()
        if stack:
            totals[stack[-1][0]] -= e - s
        stack.append([name, e])
        totals[name] += e - s
    return dict(totals)


@dataclasses.dataclass
class Device:
    name: str
    ops: list          # (name, start, end) inside the window
    modules: list      # (name, start, end) inside the window
    busy: list         # union of op intervals, clipped to the window


@dataclasses.dataclass
class Reduced:
    window: tuple      # (start_ns, end_ns)
    devices: list
    spans: list        # (label, start, end) of the benchmark's host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        if not self.devices:
            return 0.0
        return sum(e - s for d in self.devices for s, e in d.busy) * 1e-9 / len(
            self.devices)

    def op_seconds(self, *patterns: str) -> float:
        """Self time of ops whose name holds any of ``patterns``, averaged
        over the chips used."""
        return self._avg(lambda d: sum(
            t for n, t in _self_times(d.ops).items()
            if any(p in n for p in patterns)))

    def module_seconds(self, *patterns: str) -> float:
        """Time of the programs whose name holds any of ``patterns``."""
        return self._avg(lambda d: sum(
            e - s for n, s, e in d.modules if any(p in n for p in patterns)))

    def module_count(self, *patterns: str) -> float:
        """Runs of the programs whose name holds any of ``patterns``,
        averaged over the chips used."""
        if not self.devices:
            return 0.0
        return sum(sum(1 for n, _, _ in d.modules if any(p in n for p in patterns))
                   for d in self.devices) / len(self.devices)

    def _avg(self, f) -> float:
        if not self.devices:
            return 0.0
        return sum(f(d) for d in self.devices) * 1e-9 / len(self.devices)

    def gaps(self) -> list:
        """(label, seconds) of each hole in the first chip's busy union."""
        if not self.devices:
            return []
        t0, t1 = self.window
        edges = [t0] + [x for iv in self.devices[0].busy for x in iv] + [t1]
        out = []
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = 0.5 * (s + e)
                inner = [sp for sp in self.spans if sp[1] <= mid < sp[2]]
                label = min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner else "window"
                out.append((label, (e - s) * 1e-9))
        return out

    def breakdown(self, top: int = 10, width: int = 120) -> dict:
        """The ``top`` ops by self time (names cut to ``width`` characters)
        and idle time by host span."""
        ops = defaultdict(float)
        for d in self.devices:
            for n, t in _self_times(d.ops).items():
                ops[n] += t * 1e-9 / len(self.devices)
        idle = defaultdict(float)
        for label, s in self.gaps():
            idle[label] += s
        return {
            "device_ops": [[n[:width], t] for n, t in
                           sorted(ops.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": sorted(([n, t] for n, t in idle.items()),
                                key=lambda x: -x[1])[:top],
        }


def reduce(planes: list[dict], chips: int | None = None) -> Reduced:
    spans, window = [], None
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    label = name[len(SPAN_PREFIX):]
                    if label == "window":
                        window = (s, s + d)
                    else:
                        spans.append((label, s, s + d))
    if window is None:
        raise ValueError("the trace has no bench.window span")
    t0, t1 = window

    def inside(events):
        return [(n, max(s, t0), min(s + d, t1)) for n, s, d in events
                if s < t1 and s + d > t0]

    devices = []
    for plane in sorted(planes, key=lambda p: p["name"]):
        if not plane["name"].startswith("/device:TPU:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = inside(lines.get(OPS_LINE, []))
        if not ops:
            continue
        devices.append(Device(
            name=plane["name"], ops=ops, modules=inside(lines.get(MODULES_LINE, [])),
            busy=_union([(s, e) for _, s, e in ops])))
    if chips is not None:
        devices = devices[:chips]
    return Reduced(window=window, devices=devices,
                   spans=[sp for sp in spans if sp[1] < t1 and sp[2] > t0])


def reduce_dir(directory, chips: int | None = None) -> Reduced:
    return reduce(load(newest_xplane(directory)), chips)
