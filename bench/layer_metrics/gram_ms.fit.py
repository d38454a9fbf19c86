"""Device time of the KRR fit's compensated Grams (CᵀC and Cᵀy), in ms per
job: the self time of the ops in the device scope ``krr.gram``.  The fit
runs eagerly, so the scope reaches no op there and the Grams are their
own program, ``jit_f32_gram``, which only that phase runs in the KRR cell;
its ops are taken too.  Reads nothing unless the trace holds the
program's ``repro.krr.gram`` spans."""
from bench import program_trace

SCOPE, PROGRAM = "krr.gram", "jit_f32_gram"


def read(ctx):
    p = program_trace.of(ctx)
    jobs = ctx.window.counters["jobs"]
    if p is None or not jobs or not p.has_span(SCOPE):
        return None
    ns = sum(t for stack, prog, t in p.ops
             if program_trace.in_scope(stack, SCOPE) or PROGRAM in prog)
    return 1e-6 * ns / jobs
