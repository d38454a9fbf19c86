"""Device time of attention in a prefill, in ms per request: the self time
of the ops in the scope ``attention`` (projections, chunked causal
attention, the bulk cache write) inside the prefill program
(``jit_prefill_with_cache``) ÷ requests.  Reads nothing unless some op
there carries the scope."""
from bench import program_trace

SCOPE, PROGRAM = "attention", "prefill_with_cache"


def read(ctx):
    p = program_trace.of(ctx)
    items = len(ctx.window.items)
    if p is None or not items:
        return None
    t, n = p.op_seconds(SCOPE, PROGRAM)
    return 1000.0 * t / items if n else None
