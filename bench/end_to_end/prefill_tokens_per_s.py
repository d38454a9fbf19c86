"""All prompt tokens prefilled in the window ÷ the window's length (its
start to the end of its last request)."""


def read(ctx):
    w = ctx.window
    return w.counters["prefill_tokens"] / w.elapsed if w.items else None
