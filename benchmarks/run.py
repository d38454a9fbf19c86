"""Benchmark harness: one module per paper table/figure + system benches.
Prints ``name,us_per_call,derived`` CSV rows. Suites that track a perf
trajectory (``kernels``, ``matfree``, ``grow``, ``distributed``) also write a
BENCH_*.json at the repo root — old-vs-new kernel and structural-vs-dense
timings live in ``BENCH_kernels.json``; the matrix-free operator's
past-the-n²-wall numbers (KRR at n = 131072, dense refused) live in
``BENCH_matfree.json``; batched-vs-sequential growth and the autotune
cold/warm timings live in ``BENCH_grow.json``; the sharded weak/strong
scaling table (per-device C ∝ 1/D) lives in ``BENCH_distributed.json`` (run
that suite under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
the sampling-scheme zoo's error-vs-m curves (uniform / leverage / poisson on
the KRR anchor) live in ``BENCH_schemes.json``; the serving-layer numbers —
batched-vs-sequential prefill at the 4k anchor plus exact-vs-sketched decode
tokens/s and cache bytes across a 4k → 512k context ladder — live in
``BENCH_attention.json``; the resilience-layer numbers — fault-guard /
degradation-ladder overhead on the kernel hot path (< 5% acceptance),
checkpoint save/restore latency vs state size, and resumed-vs-cold generate —
live in ``BENCH_resilience.json``.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig2 amm   # subset
  PYTHONPATH=src python -m benchmarks.run kernels    # refresh BENCH_kernels.json
  PYTHONPATH=src python -m benchmarks.run grow       # refresh BENCH_grow.json

``--smoke`` runs suites that honor it (``kernels``, ``matfree``, ``grow``,
``distributed``, ``schemes``, ``attention``) at tiny
shapes with a single rep — CI uses it to regenerate the JSONs on every PR
without timing out; they are tagged ``"smoke": true`` so real trajectory
numbers are never overwritten by CI artifacts.
"""
from __future__ import annotations

import os
import sys
import traceback

from benchmarks import amm_bench, attention_bench, distributed_bench
from benchmarks import falkon_bench, fig1_toy
from benchmarks import fig2_approx_error, fig3_tradeoff, grow_bench
from benchmarks import kernel_bench, matfree_bench, resilience_bench
from benchmarks import roofline, schemes_bench, train_bench
from repro.util import use_compile_cache

SUITES = {
    "fig1": fig1_toy.main,          # paper Fig. 1 (toy tradeoff)
    "fig2": fig2_approx_error.main, # paper Fig. 2 (approx error vs m)
    "fig3": fig3_tradeoff.main,     # paper Fig. 3/4 (accuracy–efficiency)
    "falkon": falkon_bench.main,    # paper appendix D.3 (Falkon-style PCG)
    "amm": amm_bench.main,          # paper §5 extension
    "kernels": kernel_bench.main,   # Pallas kernels + O(nmd) claim
    "matfree": matfree_bench.main,  # matrix-free operator: past the n² wall
    "grow": grow_bench.main,        # batched rank-B growth + autotune cache
    "schemes": schemes_bench.main,  # sampling-scheme zoo: error vs m
    "attention": attention_bench.main,  # serving: prefill speedup + decode ladder
    "distributed": distributed_bench.main,  # sharded (C, W): weak/strong scaling
    "resilience": resilience_bench.main,  # guard overhead + ckpt/resume latency
    "train": train_bench.main,      # end-to-end step throughput
    "roofline": roofline.main,      # dry-run roofline table
}


def main() -> None:
    argv = sys.argv[1:]
    if "--smoke" in argv:
        # must be set before any suite builds its shapes (they read it lazily)
        os.environ["REPRO_BENCH_SMOKE"] = "1"
        argv = [a for a in argv if a != "--smoke"]
    picks = argv or list(SUITES)
    use_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name in picks:
        try:
            SUITES[name]()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"# FAILED suites: {failed}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
