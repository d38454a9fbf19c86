"""Long-context serving bench: exact vs AccumSketch-compressed decode.

Two claims, measured:

  * PREFILL — the batched one-dispatch prefill (`prefill_with_cache`) vs the
    seed's token-by-token loop (L jitted dispatches) at the 4k-context anchor.
    Acceptance: ≥ 5× wall-clock.
  * DECODE — tokens/s and cache bytes for exact KV vs sketched decode across
    a 4k → 512k context ladder. The sketched cache is O(d_slots) — its bytes
    are FLAT in context length while the exact cache grows linearly (the
    paper's fixed-effective-size accumulation claim, transported to serving).

Decode steps are timed against a cache of the target length (contents don't
affect cost — the masked attention reads every slot either way), so the 512k
row doesn't require a 512k prefill on the CPU bench host.

Run:   PYTHONPATH=src python -m benchmarks.run attention
Smoke: PYTHONPATH=src python -m benchmarks.run attention --smoke
       (tiny shapes, 1 rep — CI's configuration; JSON tagged "smoke": true)

Writes ``BENCH_attention.json`` at the repo root.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.configs import get_config, reduced
from repro.configs.base import SketchAttnCfg
from repro.models.model import init_params
from repro.serve.engine import Engine, ServeConfig
from repro.util import env_flag

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_PATH = REPO_ROOT / "BENCH_attention.json"

# reduced stablelm-3b (attention-only pattern) with a production-shaped slot
# budget: d_slots fixed while the context ladder grows past it
FULL = dict(prefill_ctx=4096, decode_ctxs=[4096, 32768, 131072, 524288],
            d_slots=256, m_r=2, n_new=16, batch=1)
SMOKE = dict(prefill_ctx=128, decode_ctxs=[1024, 4096],
             d_slots=64, m_r=2, n_new=4, batch=1)


def bench_config() -> tuple[dict, int]:
    """(shape dict, reps) — smoke honors REPRO_BENCH_SMOKE like every suite."""
    if env_flag("REPRO_BENCH_SMOKE", False):
        return SMOKE, 1
    return FULL, 2


def _engine(cfg_b, max_len: int, use_sketch: bool, params) -> Engine:
    sc = ServeConfig(max_len=max_len, use_sketch=use_sketch)
    return Engine(cfg_b, params, sc)


def _cache_bytes(cache) -> int:
    return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(cache)))


def bench_prefill(results: dict, cfg_b, params, shapes: dict, reps: int) -> None:
    """Batched one-dispatch prefill vs the sequential token loop (sketched
    cache — the serving configuration the tentpole targets)."""
    L, B = shapes["prefill_ctx"], shapes["batch"]
    eng = _engine(cfg_b, L + shapes["n_new"], True, params)
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, cfg_b.vocab_size)
    )
    t_batched = timeit(
        lambda: eng.prefill_tokens(eng.new_cache(B), prompts)[1],
        reps=reps, warmup=1,
    )
    # the sequential loop is L jitted dispatches; one rep is plenty (and the
    # warmup call already compiled the shared decode step)
    t_seq = timeit(
        lambda: eng.prefill_tokens_sequential(eng.new_cache(B), prompts)[1],
        reps=1, warmup=0,
    )
    speedup = t_seq / t_batched
    results["prefill"] = {
        "ctx": L, "batch": B,
        "sequential_s": t_seq, "batched_s": t_batched, "speedup": speedup,
    }
    emit("serve_prefill_sequential", t_seq * 1e6, f"ctx={L}")
    emit("serve_prefill_batched", t_batched * 1e6, f"speedup={speedup:.1f}x")


def bench_decode(results: dict, cfg_b, params, shapes: dict, reps: int) -> None:
    """tokens/s + cache bytes across the context ladder, both cache flavors."""
    B, n_new = shapes["batch"], shapes["n_new"]
    ladder: dict = {}
    for ctx in shapes["decode_ctxs"]:
        row: dict = {}
        for flavor, use_sketch in (("exact", False), ("sketched", True)):
            eng = _engine(cfg_b, ctx + n_new, use_sketch, params)
            cache = eng.new_cache(B)
            tok = jnp.zeros((B,), jnp.int32)
            # _decode donates its cache: each call gets a fresh one
            t = timeit(
                lambda e=eng, k=tok, p=ctx: e._decode(
                    e.params, e.new_cache(B), k, jnp.int32(p), n_steps=n_new
                )[0],
                reps=reps, warmup=1,
            )
            row[flavor] = {
                "tokens_per_s": B * n_new / t,
                "cache_bytes": _cache_bytes(cache),
            }
            emit(f"serve_decode_{flavor}", t / n_new * 1e6,
                 f"ctx={ctx} tok/s={row[flavor]['tokens_per_s']:.1f}")
        row["cache_ratio"] = row["exact"]["cache_bytes"] / row["sketched"]["cache_bytes"]
        ladder[str(ctx)] = row
    results["decode"] = ladder


def main() -> None:
    """Entry point for ``benchmarks.run attention``."""
    shapes, reps = bench_config()
    base = reduced(get_config("stablelm-3b"))
    cfg_b = dataclasses.replace(
        base,
        sketch_attn=SketchAttnCfg(
            d_slots=shapes["d_slots"], m=base.sketch_attn.m, m_r=shapes["m_r"]
        ),
    )
    params = init_params(jax.random.PRNGKey(0), cfg_b)
    results: dict = {}
    bench_prefill(results, cfg_b, params, shapes, reps)
    bench_decode(results, cfg_b, params, shapes, reps)
    payload = {
        "host": {
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "jax": jax.__version__,
        },
        "config": shapes,
        "smoke": env_flag("REPRO_BENCH_SMOKE", False),
        "results": results,
    }
    BENCH_PATH.write_text(json.dumps(payload, indent=2) + "\n")
    emit("bench_json", 0.0, f"wrote {BENCH_PATH.name}")


if __name__ == "__main__":
    main()
