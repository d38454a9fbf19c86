"""Share of its roofline that the ``matfree_apply`` kernel reaches: the least
time its counted work needs on this chip (the larger of counted operations ÷
bf16 peak and counted bytes ÷ HBM peak, ``bench/counts/matfree_apply.py``)
÷ its summed device time in the trace.  Each KRR job calls it on the
training rows (C) and on the holdout rows (predict)."""

KERNEL = ("matfree_apply", "_matfree_kernel")


def read(ctx):
    t = ctx.trace.op_seconds(*KERNEL)
    jobs = ctx.window.counters["jobs"]
    if t <= 0 or not jobs:
        return None
    c = ctx.cell.config
    count = ctx.count("matfree_apply")
    least = 0.0
    for rows in (c["n_train"], c["n_test"]):
        w = count.work(rows, c["p"], c["sketch_d"], c["sketch_m"])
        least += max(w["flops"] / ctx.peaks["bf16_flops_per_s"],
                     w["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * jobs * least / t
