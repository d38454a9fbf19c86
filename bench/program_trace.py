"""Read the program's own spans and scopes out of a run's profiler trace.

The program marks its phases with host spans ``repro.<name>`` (their
stats, such as ``request``, ride along) and its device work with
``jax.named_scope`` scopes, which XLA keeps in each op's metadata and the
TPU's trace gives as the op's name stack (the ``tf_op`` stat).  This module
loads the same ``.xplane.pb`` as ``trace_reduce`` once per run, keeping
what that reduction drops: the ``repro.*`` spans with their stats, and
each XLA op's name stack.  ``jax.profiler.ProfileData`` shows an event's
own stats only, while the TPU keeps the name stack among the stats of the
op's metadata, so the file is parsed with the protobuf runtime against the
few fields of the ``XSpace`` message read here.

``reduce`` clips to the run's window and gives, for the first chip:

- op self times (as ``trace_reduce``: nested events count once), each op
  under its name stack and the program (``XLA Modules`` event) it ran in;
- idle gaps (holes in the union of op intervals), each labelled by the
  innermost program span covering its midpoint, or none.

A trace taken from a program without spans or scopes reads as empty: the
readers under ``bench/layer_metrics/`` then return ``None``.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
from collections import defaultdict

from bench import trace_reduce as tr

SPAN_PREFIX = "repro."
WINDOW = tr.SPAN_PREFIX + "window"
NAME_STACK = "tf_op"        # the XLA Ops stat that holds the op's name stack


# --------------------------------------------------------------------------- #
# loading
# --------------------------------------------------------------------------- #

@functools.cache
def _xspace():
    """The ``XSpace`` message class, built from the fields read here (field
    numbers of ``tsl/profiler/protobuf/xplane.proto``; a map is a repeated
    key/value entry on the wire; fields not declared are skipped)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                           package="bench_xplane", syntax="proto3")

    def message(name, *fields, oneof=None):
        m = f.message_type.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, kind, repeated in fields:
            fd = m.field.add(name=fname, number=number,
                             label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if isinstance(kind, str):
                fd.type, fd.type_name = F.TYPE_MESSAGE, ".bench_xplane." + kind
            else:
                fd.type = kind
                if oneof and fname.endswith("_value"):
                    fd.oneof_index = 0

    i64, u64, dbl, s = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE, F.TYPE_STRING
    message("XStat", ("metadata_id", 1, i64, False), ("double_value", 2, dbl, False),
            ("uint64_value", 3, u64, False), ("int64_value", 4, i64, False),
            ("str_value", 5, s, False), ("ref_value", 7, u64, False), oneof="value")
    message("XEvent", ("metadata_id", 1, i64, False), ("offset_ps", 2, i64, False),
            ("duration_ps", 3, i64, False), ("stats", 4, "XStat", True))
    message("XLine", ("name", 2, s, False), ("timestamp_ns", 3, i64, False),
            ("events", 4, "XEvent", True))
    message("XEventMetadata", ("id", 1, i64, False), ("name", 2, s, False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("id", 1, i64, False), ("name", 2, s, False))
    message("EventMetadataEntry", ("key", 1, i64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, i64, False),
            ("value", 2, "XStatMetadata", False))
    message("XPlane", ("name", 2, s, False), ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stats(stats, names: dict) -> dict:
    out = {}
    for st in stats:
        kind = st.WhichOneof("value")
        if kind is None:
            continue
        v = getattr(st, kind)
        out[names.get(st.metadata_id, "")] = names.get(v, "") if kind == "ref_value" else v
    return out


def load(path) -> list[dict]:
    """The planes of an ``.xplane.pb`` file as plain data, in the form of
    ``trace_reduce.load`` with one more field per event: host events
    ``[name, start_ns, dur_ns, stats]`` (the program's spans and the
    benchmark's window), device ``XLA Ops`` events ``[name, start_ns,
    dur_ns, name_stack]``, ``XLA Modules`` events ``[name, start_ns,
    dur_ns]``."""
    space = _xspace()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    planes = []
    for plane in space.planes:
        device = plane.name.startswith("/device:")
        if not (device or plane.name.startswith("/host:CPU")):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: (e.value.name, _stats(e.value.stats, stat_names))
                for e in plane.event_metadata}
        lines = []
        for line in plane.lines:
            if device and line.name not in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            ops, events = line.name == tr.OPS_LINE, []
            for ev in line.events:
                name, md_stats = meta.get(ev.metadata_id, ("", {}))
                if not device and not (name.startswith(SPAN_PREFIX) or name == WINDOW):
                    continue
                e = [name, line.timestamp_ns + ev.offset_ps / 1000, ev.duration_ps / 1000]
                if not device or ops:
                    st = {**md_stats, **_stats(ev.stats, stat_names)}
                    e.append(st if not device else str(st.get(NAME_STACK, "")))
                events.append(e)
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


# --------------------------------------------------------------------------- #
# the reduction
# --------------------------------------------------------------------------- #

def in_scope(stack: str, scope: str) -> bool:
    """Whether ``scope`` is one of the components of a name stack."""
    return scope in stack.split("/")


@dataclasses.dataclass
class Program:
    window: tuple      # (start_ns, end_ns)
    spans: list        # (name less "repro.", start, end, stats) inside the window
    ops: list          # (name_stack, program, self_ns) of the first chip's ops
    busy: list         # union of the first chip's op intervals, clipped

    def has_span(self, prefix: str) -> bool:
        """Whether a program span named ``prefix``… ran in the window."""
        return any(n.startswith(prefix) for n, *_ in self.spans)

    def op_seconds(self, scope: str, program: str = "") -> tuple[float, int]:
        """(self seconds, op events) of the ops in ``scope`` inside programs
        whose name holds ``program``; a scope of "" takes every op there."""
        t = n = 0
        for stack, prog, self_ns in self.ops:
            if program in prog and (not scope or in_scope(stack, scope)):
                t += self_ns
                n += 1
        return t * 1e-9, n

    def gaps(self) -> list:
        """(label, seconds) of each hole in the busy union: the innermost
        program span covering its midpoint, or None."""
        t0, t1 = self.window
        edges = [t0] + [x for iv in self.busy for x in iv] + [t1]
        out = []
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = 0.5 * (s + e)
                inner = [sp for sp in self.spans if sp[1] <= mid < sp[2]]
                label = min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner else None
                out.append((label, (e - s) * 1e-9))
        return out

    def idle_seconds(self, prefix: str) -> float:
        """Idle seconds in gaps whose innermost program span is named
        ``prefix``…"""
        return sum(s for label, s in self.gaps()
                   if label is not None and label.startswith(prefix))

    def idle_by_span(self) -> dict:
        out = defaultdict(float)
        for label, s in self.gaps():
            out[label] += s
        return dict(out)


def reduce(planes: list[dict], window: tuple | None = None) -> Program:
    """The program's spans and the first chip's ops inside ``window`` (by
    default the benchmark's ``bench.window`` span in the trace, or where it
    has none, all of it)."""
    host = [e for p in planes if not p["name"].startswith("/device:")
            for ln in p["lines"] for e in ln["events"]]
    if window is None:
        window = next(((s, s + d) for n, s, d, *_ in host if n == WINDOW), None)
    if window is None:
        every = [e for p in planes for ln in p["lines"] for e in ln["events"]]
        window = (min(e[1] for e in every), max(e[1] + e[2] for e in every))
    t0, t1 = window
    spans = [(n[len(SPAN_PREFIX):], s, s + d, st[0] if st else {})
             for n, s, d, *st in host
             if n.startswith(SPAN_PREFIX) and s < t1 and s + d > t0]
    ops, busy = [], []
    devices = sorted((p for p in planes if p["name"].startswith("/device:TPU:")),
                     key=lambda p: p["name"])
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        raw = [(e[3] if len(e) > 3 else "", max(e[1], t0), min(e[1] + e[2], t1))
               for e in lines.get(tr.OPS_LINE, []) if e[1] < t1 and e[1] + e[2] > t0]
        if not raw:
            continue
        mods = sorted((s, s + d, n) for n, s, d in lines.get(tr.MODULES_LINE, []))
        starts = [m[0] for m in mods]
        selfs = tr._self_times([(i, s, e) for i, (_, s, e) in enumerate(raw)])
        for i, (stack, s, e) in enumerate(raw):
            j = bisect.bisect_right(starts, s) - 1
            prog = mods[j][2] if j >= 0 and s < mods[j][1] else ""
            ops.append((stack, prog, selfs[i]))
        busy = tr._union([(s, e) for _, s, e in raw])
        break
    return Program(window=window, spans=spans, ops=ops, busy=busy)


def of(ctx) -> Program | None:
    """The run's program trace, loaded once and kept on ``ctx``; None
    without a trace or without a device op in its window."""
    if not hasattr(ctx, "program_trace"):
        ctx.program_trace = None
        if ctx.trace is not None:
            from bench import harness

            try:
                path = tr.newest_xplane(harness._trace_dir(ctx.cell))
            except FileNotFoundError:
                return None
            ctx.program_trace = reduce(load(path), ctx.trace.window)
    p = ctx.program_trace
    return p if p is not None and p.ops else None
