"""Share of the HBM peak that a decode step reaches: the bytes a step must
read and write (every weight, and the keys and values of the valid
positions, at the configured types; ``bench/counts/lm.py``), averaged over a
request's steps, ÷ HBM peak ÷ the device time per step.  The device time is
that of the decode-scan program in the trace ÷ the steps it ran.  The
engine jits that scan through a ``functools.partial``, which JAX names
``jit__unknown``; no other program of the decode cell carries the name."""

PROGRAM = ("jit__unknown", "_decode_scan")


def read(ctx):
    steps = ctx.window.counters["decode_steps"]
    t = ctx.trace.module_seconds(*PROGRAM)
    if t <= 0 or not steps:
        return None
    import jax.numpy as jnp

    from bench.gen.lm_weights import sizes

    c, tr = ctx.cell.config, ctx.cell.traffic
    s = sizes(c["model"])
    L, n = tr["prompt_len"], tr["new_tokens"]
    wb, cb = jnp.dtype(c["dtype"]).itemsize, jnp.dtype(c["cache_dtype"]).itemsize
    per_step = sum(ctx.count("lm").decode_bytes(s, tr["batch"], L + k - 1, wb, cb)
                   for k in range(1, n)) / (n - 1)
    return 100.0 * per_step / ctx.peaks["hbm_bytes_per_s"] / (t / steps)
