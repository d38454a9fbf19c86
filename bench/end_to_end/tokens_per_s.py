"""All tokens generated in the window ÷ the window's length (its start to
the end of its last request)."""


def read(ctx):
    w = ctx.window
    return sum(it.tokens for it in w.items) / w.elapsed if w.items else None
