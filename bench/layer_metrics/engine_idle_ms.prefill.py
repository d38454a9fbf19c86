"""Device idle time inside the serving engine's host phases, in ms per
request: the gaps in the device's busy time whose innermost program span
is one of the engine's (``repro.engine.*``: new cache and prefill
dispatch, token 0 to the host, health check, decode dispatch).  Reads
nothing unless the trace holds such spans."""
from bench import program_trace

SPAN = "engine."


def read(ctx):
    p = program_trace.of(ctx)
    items = len(ctx.window.items)
    if p is None or not items or not p.has_span(SPAN):
        return None
    return 1000.0 * p.idle_seconds(SPAN) / items
