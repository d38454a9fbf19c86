"""Cut a short piece out of a real trace and keep it as the recorded trace
that ``bench/tests/test_trace_reduce.py`` checks the reduction against.

    python3 bench/tools/record_trace.py <trace dir> <out.json.gz> --ms 30

The piece is the first ``--ms`` milliseconds of the ``bench.window`` span:
the window span is cut to that length and every event that does not start
inside it is dropped.
"""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import sys

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[2])]


def cut(planes: list[dict], ms: float) -> list[dict]:
    from bench.trace_reduce import SPAN_PREFIX

    t0 = next(e[1] for p in planes for ln in p["lines"] for e in ln["events"]
              if e[0] == SPAN_PREFIX + "window")
    t1 = t0 + ms * 1e6
    out = []
    for p in planes:
        lines = []
        for ln in p["lines"]:
            ev = [[n, s, (t1 - t0) if n == SPAN_PREFIX + "window" else d]
                  for n, s, d in ln["events"] if t0 <= s < t1]
            if ev:
                lines.append({"name": ln["name"], "events": ev})
        out.append({"name": p["name"], "lines": lines})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=30.0)
    args = ap.parse_args(argv)
    from bench.trace_reduce import load, newest_xplane

    planes = cut(load(newest_xplane(args.trace)), args.ms)
    with gzip.open(args.out, "wt") as f:
        json.dump(planes, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
