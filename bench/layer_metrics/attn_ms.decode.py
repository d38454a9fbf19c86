"""Device time of attention in a decode step, in ms: the self time of the
ops in the scope ``attention`` (projections, cache write, scores, softmax,
weighted sum) inside the decode-scan program (``jit__decode_scan``) ÷ the
decode steps it ran.  Reads nothing unless some op there carries the
scope."""
from bench import program_trace

SCOPE, PROGRAM = "attention", "_decode_scan"


def read(ctx):
    p = program_trace.of(ctx)
    steps = ctx.window.counters["decode_steps"]
    if p is None or not steps:
        return None
    t, n = p.op_seconds(SCOPE, PROGRAM)
    return 1000.0 * t / steps if n else None
