"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration and traffic
come from ``BENCHMARK.json`` and the files it names under ``bench/``.  The
run sets up (weights and data from ``--seed``, every shape warmed up), then
measures for ``--seconds`` seconds, then compares what the timed path
produced with the plain reference.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace.  Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  The last lines of standard error are the
numbers compared for ``correct``, each beside its limit; the last line of
standard output is the result.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

# settings that would take the system off the path under test
REFUSED_ENV = {
    "REPRO_PALLAS_INTERPRET": "the Pallas kernels must run compiled",
    "REPRO_FAULT_PLAN": "fault injection is armed",
    "REPRO_AUTOTUNE": "the autotune gate is the program's default",
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not in this checkout",
              file=sys.stderr)
        return 1
    for var, why in REFUSED_ENV.items():
        if var in os.environ:
            print(f"bench: refusing to run: {var} is set ({why})", file=sys.stderr)
            return 2
    if os.environ.get("REPRO_SKETCH_KERNEL", "1") in ("0", "false", "off", "no"):
        print("bench: refusing to run: REPRO_SKETCH_KERNEL turns the kernels off",
              file=sys.stderr)
        return 2
    # Compiled programs and measured kernel tilings are kept inside the
    # checkout, at fixed paths, so that only a cell's first run compiles.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(ROOT / ".jax_cache" / "autotune.json")
    # the TPU runtime's own logs would go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    # cache every program, however quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import harness

    manifest = harness.read_json(ROOT / "BENCHMARK.json")
    cell = harness.load_cell(manifest, args.workload, args.seed)
    try:
        result = harness.run_cell(cell, args.seconds, bool(args.trace),
                                  log=lambda m: print(m, flush=True))
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"[correct] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"[correct] {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
