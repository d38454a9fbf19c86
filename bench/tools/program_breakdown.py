"""Where a traced run's time went, by the program's own spans and scopes.

    python3 bench/tools/program_breakdown.py <trace dir or .xplane.pb>
        [--per N] [--program NAME] [--scopes attention,mlp,head,sample]

Prints, inside the benchmark's window: the device's idle time by innermost
program span (``repro.*``; ``None`` where no program span covers a gap),
the device time by program (``XLA Modules`` name), and the device time of
the programs whose name holds ``--program`` by the first of ``--scopes``
in each op's name stack (``(unscoped)`` where none is).  ``--per`` divides
every number by a count of jobs, requests or steps; times are in ms.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
from collections import defaultdict

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[2])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--per", type=float, default=1.0)
    ap.add_argument("--program", default="")
    ap.add_argument("--scopes", default="attention,mlp,head,sample")
    args = ap.parse_args(argv)
    from bench import program_trace as pt
    from bench import trace_reduce as tr

    path = pathlib.Path(args.path)
    if path.is_dir():
        path = tr.newest_xplane(path)
    p = pt.reduce(pt.load(path))
    ms = 1e3 / args.per

    def show(title, d):
        rows = sorted(d.items(), key=lambda x: -x[1])
        print(title + ": " + ", ".join(f"{k} {v * ms:.3f}" for k, v in rows))

    print(f"window {(p.window[1] - p.window[0]) * 1e-6:.3f} ms, "
          f"{len(p.spans)} program spans, {len(p.ops)} device ops")
    show("idle by program span", p.idle_by_span())
    progs, scoped = defaultdict(float), defaultdict(float)
    scopes = args.scopes.split(",")
    for stack, prog, ns in p.ops:
        progs[prog.split("(")[0]] += ns * 1e-9
        if args.program and args.program in prog:
            parts = stack.split("/")
            scoped[next((s for s in scopes if s in parts), "(unscoped)")] += ns * 1e-9
    show("device by program", progs)
    if args.program:
        show(f"device in {args.program} by scope", scoped)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
