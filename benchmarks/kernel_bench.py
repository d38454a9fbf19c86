"""Microbenchmarks for the accum_apply kernel family.

Times the vectorized gather→GEMM K·S kernel, the fused (K S, SᵀK S)
single-sweep kernel against the two-pass
composition, the structural-vs-dense sketch application (the paper's O(nmd)
claim), and the progressive engine's O(n·d) incremental step against the
from-scratch recompute — then writes the results to ``BENCH_kernels.json`` at
the repo root so the perf trajectory is tracked across PRs.

Run:   PYTHONPATH=src python -m benchmarks.run kernels
Smoke: PYTHONPATH=src python -m benchmarks.run kernels --smoke
       (tiny shapes, 1 rep — the CI bench-smoke job's configuration; the JSON
       is tagged "smoke": true so it never masquerades as trajectory numbers)
"""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core import apply as A
from repro.core.apply import sketch_right
from repro.core.sketch import make_accum_sketch
from repro.kernels.accum_apply.ops import (
    autotune_blocks,
    sketch_both_kernel,
    sketch_left_kernel,
    sketch_right_kernel,
)
from repro.kernels.landmark_attention.ref import landmark_attention_ref
from repro.util import env_flag

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_PATH = REPO_ROOT / "BENCH_kernels.json"

# The anchor shape every PR's numbers are compared at (f32).
ANCHOR = dict(R=4096, N=8192, d=64, m=4)
SMOKE_ANCHOR = dict(R=256, N=512, d=16, m=2)


def bench_config() -> tuple[dict, int]:
    """(anchor shapes, reps) — tiny and single-rep under ``--smoke``."""
    if env_flag("REPRO_BENCH_SMOKE", False):
        return SMOKE_ANCHOR, 1
    return ANCHOR, 3


def bench_accum_apply(results: dict, anchor: dict, reps: int) -> None:
    """Vectorized gather→GEMM K·S at the anchor shape."""
    key = jax.random.PRNGKey(0)
    R, N, d, m = anchor["R"], anchor["N"], anchor["d"], anchor["m"]
    K = jax.random.normal(key, (R, N))
    sk = make_accum_sketch(key, N, d, m)
    bm, bd = autotune_blocks(R, N, d, m, jnp.float32)

    t_new = timeit(lambda: sketch_right_kernel(K, sk, bm=bm, bd=bd), reps=reps)
    tag = f"R{R}_N{N}_d{d}_m{m}_f32"
    emit(f"accum_apply_gemm_{tag}", t_new * 1e6, "")
    results[f"accum_apply_gemm_{tag}"] = {"us": t_new * 1e6, "blocks": [bm, bd]}


def bench_fused_both(results: dict, anchor: dict, reps: int) -> None:
    """Fused single-sweep (C, W) vs the two-pass kernel composition."""
    key = jax.random.PRNGKey(1)
    n, d, m = anchor["R"], anchor["d"], anchor["m"]
    K = jax.random.normal(key, (n, n))
    K = 0.5 * (K + K.T)
    sk = make_accum_sketch(key, n, d, m)

    def two_pass():
        C = sketch_right_kernel(K, sk)
        return C, sketch_left_kernel(sk, C)

    t_fused = timeit(lambda: sketch_both_kernel(K, sk), reps=reps)
    t_two = timeit(two_pass, reps=reps)
    speedup = t_two / max(t_fused, 1e-9)
    tag = f"n{n}_d{d}_m{m}_f32"
    emit(f"sketch_both_fused_{tag}", t_fused * 1e6,
         f"two_pass/fused={speedup:.2f}x")
    emit(f"sketch_both_two_pass_{tag}", t_two * 1e6, "")
    results[f"sketch_both_fused_{tag}"] = {
        "us": t_fused * 1e6, "speedup_vs_two_pass": speedup}
    results[f"sketch_both_two_pass_{tag}"] = {"us": t_two * 1e6}


def bench_structural_vs_dense(results: dict, anchor: dict, reps: int) -> None:
    """Paper claim: structural K·S is O(nmd), dense K·S is O(n²d)."""
    key = jax.random.PRNGKey(2)
    n, d, m = anchor["R"], anchor["d"], anchor["m"]
    K = jax.random.normal(key, (n, n))
    sk = make_accum_sketch(key, n, d, m)
    S = sk.dense()
    t_struct = timeit(jax.jit(lambda K, sk: sketch_right(K, sk)), K, sk,
                      reps=reps)
    t_dense = timeit(jax.jit(lambda K, S: K @ S), K, S, reps=reps)
    speedup = t_dense / max(t_struct, 1e-9)
    emit("sketch_right_structural", t_struct * 1e6,
         f"dense/structural={speedup:.1f}x n={n} d={d} m={m}")
    emit("sketch_right_dense", t_dense * 1e6, "")
    results["sketch_right_structural"] = {
        "us": t_struct * 1e6, "speedup_vs_dense": speedup}
    results["sketch_right_dense"] = {"us": t_dense * 1e6}


def bench_landmark_ref(results: dict, anchor: dict, reps: int) -> None:
    key = jax.random.PRNGKey(3)
    S_len, Dh, L = anchor["R"], 128, 256
    q = jax.random.normal(key, (S_len, Dh))
    kt = jax.random.normal(key, (L, Dh))
    M = jax.random.normal(key, (L, Dh))
    t_lm = timeit(jax.jit(landmark_attention_ref), q, kt, M, reps=reps)
    kfull = jax.random.normal(key, (S_len, Dh))
    t_full = timeit(
        jax.jit(lambda q, k: jax.nn.softmax(q @ k.T / Dh**0.5, axis=-1) @ k),
        q, kfull, reps=reps)
    emit("landmark_attention_ref", t_lm * 1e6,
         f"exact/landmark={t_full/max(t_lm,1e-9):.1f}x S={S_len} L={L}")
    results["landmark_attention_ref"] = {
        "us": t_lm * 1e6, "speedup_vs_exact": t_full / max(t_lm, 1e-9)}


def bench_progressive_step(results: dict, anchor: dict, reps: int) -> None:
    """Engine increment (O(n·d)) vs from-scratch (C, W) recompute (O(n·m·d))
    at the final m — the tentpole claim of the progressive accumulation
    engine: growing m costs one slab, not a re-sketch."""
    key = jax.random.PRNGKey(4)
    n, d, m = anchor["R"], anchor["d"], max(anchor["m"], 2)
    K = jax.random.normal(key, (n, n))
    K = 0.5 * (K + K.T)
    state = A.accum_grow(K, A.accum_init(key, n, d, m), m - 1,
                         use_kernel=False)
    step = jax.jit(lambda K, s: A.accum_step(K, s, use_kernel=False))
    sk = make_accum_sketch(key, n, d, m)
    t_step = timeit(step, K, state, reps=reps)
    t_scratch = timeit(
        jax.jit(lambda K, sk: A.sketch_both(K, sk, use_kernel=False)), K, sk,
        reps=reps)
    speedup = t_scratch / max(t_step, 1e-9)
    tag = f"n{n}_d{d}_m{m}_f32"
    emit(f"accum_step_incremental_{tag}", t_step * 1e6,
         f"scratch/step={speedup:.1f}x")
    emit(f"accum_recompute_scratch_{tag}", t_scratch * 1e6, "")
    results[f"accum_step_incremental_{tag}"] = {
        "us": t_step * 1e6, "speedup_vs_scratch": speedup}
    results[f"accum_recompute_scratch_{tag}"] = {"us": t_scratch * 1e6}


def main() -> None:
    anchor, reps = bench_config()
    results: dict = {}
    bench_accum_apply(results, anchor, reps)
    bench_fused_both(results, anchor, reps)
    bench_structural_vs_dense(results, anchor, reps)
    bench_landmark_ref(results, anchor, reps)
    bench_progressive_step(results, anchor, reps)
    payload = {
        "host": {
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "jax": jax.__version__,
        },
        "anchor": anchor,
        "smoke": env_flag("REPRO_BENCH_SMOKE", False),
        "results": results,
    }
    BENCH_PATH.write_text(json.dumps(payload, indent=2) + "\n")
    emit("bench_json", 0.0, f"wrote {BENCH_PATH.name}")


if __name__ == "__main__":
    main()
