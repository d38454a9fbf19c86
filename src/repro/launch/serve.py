"""Production serving launcher: batched requests against exact or sketched
(AccumSketch, the paper's technique) KV caches.

  PYTHONPATH=src python -m repro.launch.serve --arch zamba2-7b --reduced \
      --batch 4 --prompt-len 32 --new-tokens 32 --sketch
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.models.model import init_params
from repro.serve.engine import Engine, ServeConfig
from repro.util import use_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--sketch", action="store_true",
                    help="AccumSketch-compressed cache (O(d_slots) memory, "
                    "context-length independent)")
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    params = init_params(jax.random.PRNGKey(0), cfg)
    sc = ServeConfig(max_len=args.prompt_len + args.new_tokens,
                     use_sketch=args.sketch, temperature=args.temperature)
    eng = Engine(cfg, params, sc)

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len),
                           dtype=np.int32)
    t0 = time.perf_counter()
    out, cache = eng.generate(prompts, args.new_tokens)
    dt = time.perf_counter() - t0
    total = args.batch * args.new_tokens
    print(f"[serve] arch={cfg.name} sketch={args.sketch} "
          f"generated {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s incl. compile)")
    print(f"[serve] sample continuation: {out[0][:16].tolist()}")
    cache_bytes = sum(
        np.prod(x.shape) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(cache)
    )
    print(f"[serve] cache bytes: {cache_bytes/1e6:.2f} MB")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
