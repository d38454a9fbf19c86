"""Keep a short traced run as the recorded program trace that
``bench/tests/test_program_trace.py`` reads back to the run's printed
metrics.

    python3 bench/tools/record_program_trace.py <trace dir or .xplane.pb> <out.json.gz>

The whole window is kept in ``program_trace.load``'s form, each op's name
cut to its HLO instruction name (``%fusion.12``).
"""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import sys

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[2])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    args = ap.parse_args(argv)
    from bench import program_trace as pt
    from bench import trace_reduce as tr

    path = pathlib.Path(args.trace)
    if path.is_dir():
        path = tr.newest_xplane(path)
    planes = pt.load(path)
    for plane in planes:
        for line in plane["lines"]:
            if line["name"] == tr.OPS_LINE:
                for e in line["events"]:
                    e[0] = e[0].split(" = ", 1)[0]
    with gzip.open(args.out, "wt") as f:
        json.dump(planes, f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
