"""How far each route of one KRR fit lies from the float64 reference, at
the cell's own size: the system's Pallas route, its XLA route, and the
reference itself in float32 on the device at each contraction precision.

    python3 bench/tools/krr_routes.py --workload krr-msd.fit --seed 1 [--lam 1e-4]

Prints ``solve_residual``, ``fitted_error`` and ``predict_error``
(``bench/reference/krr.py``) for each route: the precision the program
actually delivers on the chip is the one a configuration may state.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="krr-msd.fit")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--lam", type=float, default=1e-4)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(ROOT / ".jax_cache" / "autotune.json")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from bench import harness
    from bench.gen.regression import regression_data
    from bench.reference import krr as ref
    from repro.core.kernel_op import KernelOperator
    from repro.core.krr import krr_sketched_fit
    from repro.core.sketch import make_accum_sketch

    cell = harness.load_cell(harness.read_json(ROOT / "BENCHMARK.json"),
                             args.workload, args.seed)
    harness.find_devices(cell.chips)
    c = cell.config
    bw = float(c["bandwidth"])
    Xtr, ytr, Xte, _ = regression_data(harness.seed_key(args.seed, 1), c["n_train"],
                                       c["n_test"], c["p"], bw)
    sk = make_accum_sketch(harness.seed_key(args.seed, 2), c["n_train"],
                           c["sketch_d"], c["sketch_m"])
    op = KernelOperator(Xtr, c["kernel"], bw)
    fits = {}
    for name, use_kernel in (("pallas", True), ("xla", False)):
        m = krr_sketched_fit(op, ytr, args.lam, sk, use_kernel=use_kernel)
        fits[name] = (np.asarray(m.theta), np.asarray(m.fitted),
                      np.asarray(m.predict(Xte) if use_kernel else
                                 np.asarray(op.cross_cols(Xte, sk, use_kernel=False))
                                 @ np.asarray(m.theta)))
        del m
    for prec in ("highest", "high", "bf16"):
        q = ref.fit_device(Xtr, ytr, Xte, sk.indices, sk.signs, args.lam, bw, prec)
        fits["reference@" + prec] = (np.asarray(q.theta), np.asarray(q.fitted),
                                     np.asarray(q.pred))
    r = ref.fit_f64(Xtr, ytr, Xte, np.asarray(sk.indices), np.asarray(sk.signs),
                    args.lam, bw, probes=np.stack([v[0] for v in fits.values()]))
    for i, (name, (th, fit, pred)) in enumerate(fits.items()):
        print(f"{name:20s} solve_residual {ref.solve_residual(r, th):.3e} "
              f"fitted_error {ref.fitted_error(r, i, fit):.3e} "
              f"predict_error {ref.predict_error(r, th, pred):.3e}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
