"""The whole KRR job's share of the chip's bf16 peak: counted operations per
job (``bench/counts/krr_job.py``: distance GEMM, W, the C Gram, the solve
and predict) × jobs ÷ (window × peak)."""


def read(ctx):
    w = ctx.window
    jobs = w.counters["jobs"]
    if not jobs:
        return None
    c = ctx.cell.config
    f = ctx.count("krr_job").flops(c["n_train"], c["n_test"], c["p"],
                                   c["sketch_d"], c["sketch_m"])
    return 100.0 * jobs * f / (w.elapsed * ctx.peaks["bf16_flops_per_s"])
