"""Print what a profiler trace holds, to look at one by hand: its planes,
their lines, and each line's busiest event names with their stats.

    python3 bench/tools/trace_dump.py <dir or .xplane.pb> [--top 25]
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
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    from bench.trace_reduce import newest_xplane

    path = pathlib.Path(args.path)
    if path.is_dir():
        path = newest_xplane(path)
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, {t0:.0f}..{t1:.0f} ns")
            tot, cnt, ex = defaultdict(float), defaultdict(int), {}
            for e in evs:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
                ex.setdefault(e.name, e)
            for name in sorted(tot, key=lambda n: -tot[n])[:args.top]:
                stats = {k: (str(v)[:100]) for k, v in ex[name].stats}
                print(f"    {tot[name] / 1e6:10.3f} ms x{cnt[name]:<6} {name[:90]!r} {stats}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
