"""KRR runner: sketched kernel ridge regression jobs through the system.

Configuration: ``n_train``, ``n_test``, ``p``, ``kernel`` (Gaussian),
``bandwidth``, ``sketch_d``, ``sketch_m``, the ``contractions`` precision
it states and the ``controls`` below it.  Traffic: ``lams`` (a job's λ
cycles over them), the ``arrival`` schedule, and ``check``: how many jobs
the float64 reference re-computes (``jobs``) and the ``limits`` on the
largest numbers among them (``readings``; ``bench/reference/krr.py``).

A job is what an analyst tuning λ runs: draw a fresh sketch
(``make_accum_sketch``, keyed by the seed and the job's index), fit
``krr_sketched_fit`` on the matrix-free ``KernelOperator``, predict the
holdout rows with ``SketchedKRR.predict``, and bring the holdout MSE to the
host.  Data come from ``bench/gen/regression.py``, on the device, from the
seed.  Set-up runs one job per λ, which compiles every program the window
uses and measures the kernels' tilings on a checkout's first run.
"""
from __future__ import annotations

import dataclasses
import gc
from typing import Any

import numpy as np

from bench import harness
from bench.gen.regression import regression_data
from bench.reference import krr as ref

DATA, DRAWS, SAMPLE, WARM = 1, 2, 3, 4
# the answers of every KEEP-th job (from an offset drawn from the seed) and
# of the first are kept for the check; the others are dropped as they come,
# so the window does not pile up device buffers
KEEP = 8


@dataclasses.dataclass
class State:
    cell: harness.Cell
    data: tuple
    op: Any = None
    jobs: list = dataclasses.field(default_factory=list)


def _job(state: State, key, lam: float):
    import jax.numpy as jnp

    from repro.core.krr import krr_sketched_fit
    from repro.core.sketch import make_accum_sketch

    c = state.cell.config
    _, ytr, Xte, yte = state.data
    with harness.span("draw"):
        sk = make_accum_sketch(key, c["n_train"], c["sketch_d"], c["sketch_m"])
    with harness.span("fit"):
        model = krr_sketched_fit(state.op, ytr, lam, sk)
    with harness.span("predict"):
        pred = model.predict(Xte)
        mse = float(jnp.mean((pred - yte) ** 2))
    return sk, model, pred, mse


def setup(cell: harness.Cell) -> State:
    import jax

    from repro.core.kernel_op import KernelOperator

    c = cell.config
    data = jax.block_until_ready(regression_data(
        harness.seed_key(cell.seed, DATA), c["n_train"], c["n_test"], c["p"],
        float(c["bandwidth"])))
    state = State(cell=cell, data=data,
                  op=KernelOperator(data[0], c["kernel"], float(c["bandwidth"])))
    warm = harness.seed_key(cell.seed, WARM)
    for j, lam in enumerate(cell.traffic["lams"]):
        _job(state, jax.random.fold_in(warm, j), float(lam))
    return state


def measure(state: State, win: harness.Window) -> None:
    import jax

    from repro.resilience.degrade import global_health

    lams = state.cell.traffic["lams"]
    draws = harness.seed_key(state.cell.seed, DRAWS)
    offset = int(harness.seed_rng(state.cell.seed, SAMPLE).integers(KEEP))
    for i in win.arrivals():
        before = global_health().count()
        lam = float(lams[i % len(lams)])
        sk, model, pred, mse = _job(state, jax.random.fold_in(draws, i), lam)
        dropped = global_health().count() - before
        win.counters["dropped"] += dropped
        win.counters["jobs"] += 1
        win.record(ok=dropped == 0 and np.isfinite(mse))
        keep = i == 0 or i % KEEP == offset
        state.jobs.append((lam, sk.indices, sk.signs, model.theta, model.fitted,
                           pred, model.info) if keep else model.info)


def _solve_rungs(state: State, win: harness.Window) -> None:
    """A job whose solve left its first rung (jitter escalated, or lstsq)
    ran on a fallback: it counts as failed."""
    for it, job in zip(win.items, state.jobs):
        info = job[-1] if isinstance(job, tuple) else job
        if int(info["solve_escalations"]) or bool(info["solve_used_lstsq"]):
            it.ok = False


def readings(state: State, win: harness.Window, control: bool = False) -> dict:
    """The sampled jobs' largest numbers against the float64 reference:
    ``solve_residual``, ``fitted_error`` and ``predict_error``, and how far
    the last two exceed those of the plain reference run on the device at
    the configuration's ``contractions`` precision (``fitted_excess``,
    ``predict_excess``; ``ref.excess``).  That device run's own errors are
    given as ``floor.*``.  With ``control`` the same numbers for the
    reference run on the device at each of the configuration's
    ``controls`` precisions, as ``control.<precision>.*``."""
    _solve_rungs(state, win)
    c, t = state.cell.config, state.cell.traffic
    state.op = None
    kept = [job[:6] for job in state.jobs if isinstance(job, tuple)]
    if not kept:
        return {k: float("inf") for k in t["check"]["limits"]}
    k = min(int(t["check"]["jobs"]), len(kept))
    sample = harness.seed_rng(state.cell.seed, SAMPLE, 1).choice(len(kept), k,
                                                                 replace=False)
    picked = [kept[int(i)] for i in sorted(sample)]
    state.jobs = []
    gc.collect()
    Xtr, ytr, Xte, _ = (np.asarray(a) for a in state.data)
    bw = float(c["bandwidth"])
    out = {}
    for lam, idx, signs, theta, fitted, pred in picked:
        idx, signs = np.asarray(idx), np.asarray(signs)
        runs = {"": (theta, fitted, pred)}
        precs = [("floor.", c["contractions"])]
        if control:
            precs += [(f"control.{p}.", p) for p in c["controls"]]
        for tag, prec in precs:
            q = ref.fit_device(*state.data[:3], idx, signs, lam, bw, prec)
            runs[tag] = (q.theta, q.fitted, q.pred)
        r = ref.fit_f64(Xtr, ytr, Xte, idx, signs, lam, bw,
                        probes=np.stack([np.asarray(v[0]) for v in runs.values()]))
        got = {tag: {"solve_residual": ref.solve_residual(r, th),
                     "fitted_error": ref.fitted_error(r, i, fit),
                     "predict_error": ref.predict_error(r, th, pr)}
               for i, (tag, (th, fit, pr)) in enumerate(runs.items())}
        floor = got["floor."]
        for tag, g in got.items():
            if tag != "floor.":
                g["fitted_excess"] = ref.excess(g["fitted_error"], floor["fitted_error"])
                g["predict_excess"] = ref.excess(g["predict_error"],
                                                 floor["predict_error"])
            for name, v in g.items():
                out[tag + name] = max(out.get(tag + name, 0.0), v)
    return out


def check(state: State, win: harness.Window) -> list[harness.Check]:
    r = readings(state, win)
    limits = state.cell.traffic["check"]["limits"]
    return [harness.Check(k, r[k], float(limits[k])) for k in sorted(limits)]
