"""Run one cell several times, each run a process of its own, and print the
spread of every metric: the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python3 bench/tools/spread.py --workload <cell> --seeds 1 2 3 --seconds 45 \
        [--trace 0|1] [--repeat 1] [--out results.jsonl]

This process never imports JAX, so each child gets the chip.  Every child's
result line goes to ``--out`` with its seed and exit code.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def spread(values: list[float]) -> float | None:
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(q2) if q2 else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--repeat", type=int, default=1,
                    help="sets of runs over the same seeds")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    runs = []
    out = open(args.out, "a") if args.out else None
    for rep in range(args.repeat):
        for seed in args.seeds:
            t = time.perf_counter()
            p = subprocess.run(
                [sys.executable, "bench/run.py", "--workload", args.workload,
                 "--seed", str(seed), "--seconds", str(args.seconds),
                 "--trace", str(args.trace)],
                cwd=ROOT, capture_output=True, text=True)
            wall = time.perf_counter() - t
            lines = p.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1]) if p.returncode == 0 else None
            except (IndexError, json.JSONDecodeError):
                result = None
            rec = {"workload": args.workload, "set": rep, "seed": seed,
                   "rc": p.returncode, "wall_s": wall, "result": result,
                   "log": [ln for ln in lines if ln.startswith("[")],
                   "stderr_tail": p.stderr[-1500:]}
            runs.append(rec)
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
            m = {k: round(v["value"], 6) for k, v in (result or {}).get("metrics", {}).items()}
            print(f"set {rep} seed {seed} rc {p.returncode} wall {wall:.1f}s "
                  f"correct {(result or {}).get('correct')} {m} "
                  f"{(result or {}).get('checks')}", flush=True)
            for ln in rec["log"]:
                print("   ", ln[:300], flush=True)
            if result is None:
                print(p.stdout[-3000:], p.stderr[-3000:], flush=True)
    for rep in range(args.repeat):
        ok = [r["result"] for r in runs if r["set"] == rep and r["result"]]
        names = sorted({k for r in ok for k in r["metrics"]})
        for k in names:
            vals = [r["metrics"][k]["value"] for r in ok if k in r["metrics"]]
            s = spread(vals)
            print(f"set {rep} {k}: median {statistics.median(vals):.6g} spread "
                  f"{s if s is None else round(s, 5)} over {len(vals)} runs: {vals}")
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
