"""Device idle time while the host draws a job's sketch, in ms per job: the
gaps in the device's busy time whose innermost program span is
``repro.krr.draw`` (``make_accum_sketch``, eager: each of its operations
is dispatched as a program of its own).  Reads nothing unless the trace
holds such spans."""
from bench import program_trace

SPAN = "krr.draw"


def read(ctx):
    p = program_trace.of(ctx)
    jobs = ctx.window.counters["jobs"]
    if p is None or not jobs or not p.has_span(SPAN):
        return None
    return 1000.0 * p.idle_seconds(SPAN) / jobs
