"""A prefill's share of the chip's bf16 peak: its counted operations
(``bench/counts/lm.py``) ÷ (its device time × peak).  The device time is
that of the prefill programs in the trace, per request.  The engine jits its
prefill as a lambda, which JAX names ``jit__lambda``; in the prefill cell no
other program carries the name."""

PROGRAM = ("jit__lambda", "prefill_with_cache")


def read(ctx):
    w = ctx.window
    t = ctx.trace.module_seconds(*PROGRAM)
    if t <= 0 or not w.items:
        return None
    from bench.gen.lm_weights import sizes

    tr = ctx.cell.traffic
    f = tr["batch"] * ctx.count("lm").prefill_flops(
        sizes(ctx.cell.config["model"]), tr["prompt_len"])
    return 100.0 * len(w.items) * f / (t * ctx.peaks["bf16_flops_per_s"])
