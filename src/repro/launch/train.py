"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-12b --steps 200 \
      --reduced --ckpt-dir /tmp/ckpt

On a real slice this runs the full config on the production mesh; on CPU the
--reduced flag selects the same-family tiny config so the end-to-end path
(mesh → sharded jit → fault-tolerant loop → checkpoint/resume) is exercised
identically. The loop resumes from the latest checkpoint automatically —
re-running the same command after a kill is the restart drill.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import sharding as shlib
from repro.configs import get_config, reduced
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models.model import init_params
from repro.optim.adamw import AdamWConfig
from repro.optim.compress import CompressConfig
from repro.train.loop import LoopConfig, run
from repro.train.step import TrainConfig, init_train_state, train_step
from repro.util import use_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the same-family smoke config (CPU-feasible)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress", action="store_true",
                    help="sketched gradient all-reduce compression (paper technique)")
    ap.add_argument("--mesh", choices=["debug", "pod", "multipod"], default="debug")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    mesh = {
        "debug": lambda: make_debug_mesh(),
        "pod": lambda: make_production_mesh(multi_pod=False),
        "multipod": lambda: make_production_mesh(multi_pod=True),
    }[args.mesh]()

    tc = TrainConfig(
        optimizer=AdamWConfig(lr_peak=args.lr, total_steps=args.steps),
        n_micro=args.n_micro,
        compress=CompressConfig() if args.compress else None,
    )
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.batch)
    lc = LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                    ckpt_every=args.ckpt_every)

    with mesh:
        def init():
            params = init_params(jax.random.PRNGKey(0), cfg)
            state = init_train_state(params, tc)
            sh = shlib.params_shardings(mesh, state.params)
            return jax.device_put(
                state, type(state)(sh, shlib.opt_shardings(mesh, state.opt, sh),
                                   None if state.ef is None else jax.tree_util.tree_map(
                                       lambda _: shlib.replicated(mesh), state.ef)))

        step_fn = jax.jit(
            lambda s, t, l, i: train_step(s, t, l, i, cfg, tc),
            donate_argnums=(0,),
        )
        report = run(cfg, tc, dc, lc, init_params_fn=init, step_fn=step_fn)

    print(f"[train] ran {report.steps_run} steps "
          f"(resumed_from={report.resumed_from}) final_loss={report.final_loss:.4f}")
    n = len(report.losses)
    if n >= 20:
        first = float(np.mean(report.losses[: n // 5]))
        last = float(np.mean(report.losses[-n // 5:]))
        print(f"[train] loss first-20%={first:.4f} last-20%={last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
