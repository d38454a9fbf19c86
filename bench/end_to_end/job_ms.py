"""Window ÷ jobs completed in it, in milliseconds: a job is one call of the
cell's statistical entry point, run until its result is on the host."""


def read(ctx):
    w = ctx.window
    return 1000.0 * w.elapsed / len(w.items) if w.items else None
