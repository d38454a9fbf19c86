"""Read the numbers that decide ``correct`` over many seeds in one process:
the program's (the lower readings a limit is set above) and, with
``--control``, the control's (the upper readings it is set below).

    python3 bench/tools/readings.py --workload <cell> --seeds 1 2 3 \
        --seconds 5 [--control]

Each seed gets the cell's own set-up and a window of ``--seconds`` at the
cell's own load, then the runner's ``readings``.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(ROOT / ".jax_cache" / "autotune.json")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench import harness

    manifest = harness.read_json(ROOT / "BENCHMARK.json")
    for seed in args.seeds:
        cell = harness.load_cell(manifest, args.workload, seed)
        harness.find_devices(cell.chips)
        runner = harness.load_module(
            harness.BENCH / "runners" / f"{cell.config['runner']}.py")
        t = time.perf_counter()
        with harness.span("setup"):
            state = runner.setup(cell)
        setup_s = time.perf_counter() - t
        win = harness.Window(args.seconds, cell.traffic["arrival"])
        runner.measure(state, win)
        t = time.perf_counter()
        got = runner.readings(state, win, control=args.control)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "items": len(win.items), "setup_s": setup_s,
                          "check_s": time.perf_counter() - t, **got}), flush=True)
        del state
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
