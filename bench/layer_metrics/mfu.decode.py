"""The whole request's share of the chip's bf16 peak in a decode cell: the
model operations of every finished request (its prefill and its decode
steps, ``bench/counts/lm.py``) ÷ (window × peak).  This is the model
operations per generated token × ``tokens_per_s`` ÷ peak."""


def read(ctx):
    w = ctx.window
    if not w.items:
        return None
    from bench.gen.lm_weights import sizes

    t = ctx.cell.traffic
    f = ctx.count("lm").request_flops(sizes(ctx.cell.config["model"]), t["batch"],
                                      t["prompt_len"], t["new_tokens"])
    return 100.0 * len(w.items) * f / (w.elapsed * ctx.peaks["bf16_flops_per_s"])
