"""Multi-pod dry-run: .lower().compile() every (architecture × input-shape)
cell on the production meshes and extract roofline terms.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-110b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out results/

Success here proves the distribution config is coherent: sharding mismatches,
compile-time OOMs, and unsupported collectives all surface as hard failures.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# (no `from __future__` here — the env var lines above must be literally first)

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCHS
from repro.configs.base import SHAPES
from repro.analysis.hardware import TPU_V5E
from repro.launch.analysis import analyze, model_flops
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_cell
from repro.models.model import init_params
from repro.train.step import TrainConfig


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, tc: TrainConfig | None = None,
             verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = make_cell(arch, shape_name, mesh, tc=tc)
    t0 = time.perf_counter()
    with mesh:
        lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings).lower(*cell.args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    roof = analyze(compiled, hardware=TPU_V5E)   # the production meshes are v5e
    shape = SHAPES[shape_name]
    n_active = _active_params(arch)
    n_tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_chips = 512 if multi_pod else 256
    mflops = model_flops(n_active, n_tokens, shape.kind) / n_chips  # per chip
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "model_flops_per_chip": mflops,
        "useful_fraction": mflops / max(roof.flops, 1e-30),
        **roof.to_dict(),
    }
    if verbose:
        print(f"[dryrun] {cell.label} mesh={rec['mesh']}")
        print(f"  memory_analysis: {mem}")
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"  roofline: t_comp={roof.t_compute*1e3:.2f}ms "
              f"t_mem={roof.t_memory*1e3:.2f}ms t_coll={roof.t_collective*1e3:.2f}ms "
              f"dominant={roof.dominant} frac={roof.compute_fraction():.3f} "
              f"useful={rec['useful_fraction']:.3f}")
        print(f"  collectives: {roof.coll_detail['count']}")
    return rec


_ACTIVE_CACHE: dict = {}


def _active_params(arch: str) -> int:
    if arch not in _ACTIVE_CACHE:
        cfg = ARCHS[arch]
        sds = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
        total = sum(x.size for x in jax.tree_util.tree_leaves(sds))
        _ACTIVE_CACHE[arch] = _moe_active(cfg, sds, total)
    return _ACTIVE_CACHE[arch]


def _moe_active(cfg, sds, total):
    if cfg.moe is None:
        return total
    inactive = 0
    for pos in sds["blocks"].values():
        ffn = pos.get("ffn", {})
        for n in ("wi_gate", "wi_up", "wo"):
            if n in ffn and ffn[n].ndim == 4:
                inactive += ffn[n].size * (1 - cfg.moe.top_k / cfg.moe.n_experts)
    return int(total - inactive)


def skip_reason(arch: str, shape_name: str) -> str | None:
    # every assigned cell runs: long_500k uses the paper's AccumSketch cache on
    # attention archs (see DESIGN.md §Arch-applicability) — nothing is skipped.
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--out", default=None, help="append JSONL results here")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    if args.list:
        for a in ARCHS:
            for s in SHAPES:
                print(f"{a} {s}")
        return 0

    cells = []
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    if not (args.all or args.arch):
        ap.error("pass --arch/--shape or --all")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    failures = 0
    for a, s, mp in cells:
        reason = skip_reason(a, s)
        if reason:
            print(f"[dryrun] SKIP {a}/{s}: {reason}")
            continue
        try:
            rec = run_cell(a, s, multi_pod=mp)
        except Exception as e:
            failures += 1
            rec = {
                "arch": a, "shape": s, "mesh": "2x16x16" if mp else "16x16",
                "ok": False, "error": f"{type(e).__name__}: {e}",
            }
            print(f"[dryrun] FAIL {a}/{s} mesh={rec['mesh']}: {rec['error']}")
            traceback.print_exc()
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    print(f"[dryrun] done; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
