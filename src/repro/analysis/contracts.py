"""Trace contracts: machine-checked memory/dispatch budgets per entry point.

A *contract* pins the invariants one public entry point must keep when traced
at a small probe shape:

  * ``budget`` — max peak intermediate bytes as an expression of the probe
    variables (``"4*n*(m*d + p) + 16*MiB"``), the no-quadratic-buffer rule;
  * ``measured_peak_bytes`` — a ratchet: the peak the trace actually binds
    today.  ``check`` fails if a PR regresses it upward;
    ``check --update`` re-measures and only ever ratchets it DOWN (like the
    coverage gate);
  * ``pallas_calls`` — EXACT static dispatch count (one K-pass per batch);
  * ``forbid`` — primitive names that must not appear (host callbacks on
    serving paths, …);
  * ``donation = true`` — the entry point's donated wrapper must really lower
    with buffer-donation attrs (`verify_donation`);
  * ``rng = true`` — the RNG-lineage checker must find no reused keys
    (`repro.analysis.rng`), the PR 8 bug class;
  * ``devices`` — minimum device count (8 for the sharded twins: those
    contracts only run under the forced-8-device CI leg).

The manifest lives in ``contracts.toml`` next to this file; the probe
builders (how to construct the traced call per entry point) live in
``ENTRY_POINTS`` below.  ``python -m repro.analysis check`` evaluates
everything plus the source-level `fold_in` sweep.
"""
from __future__ import annotations

import ast
import dataclasses
import pathlib
import tomllib

import jax
import jax.numpy as jnp

from repro.analysis import rng as rng_mod
from repro.analysis import trace as trace_mod

CONTRACTS_PATH = pathlib.Path(__file__).with_name("contracts.toml")

_EXPR_GLOBALS = {"KiB": 1024, "MiB": 1024 * 1024, "min": min, "max": max}


def eval_budget(expr: str, probe: dict) -> int:
    """Evaluate a budget expression over the probe variables (restricted eval:
    names resolve to probe params plus KiB/MiB/min/max only)."""
    tree = ast.parse(expr, mode="eval")
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            if node.id not in probe and node.id not in _EXPR_GLOBALS:
                raise ValueError(
                    f"budget expression {expr!r} uses unknown name {node.id!r}")
        elif isinstance(node, (ast.Call,)):
            if not (isinstance(node.func, ast.Name)
                    and node.func.id in ("min", "max")):
                raise ValueError(f"budget expression {expr!r}: only min/max calls")
    return int(eval(compile(tree, "<budget>", "eval"),
                    {"__builtins__": {}}, {**_EXPR_GLOBALS, **probe}))


# --------------------------------------------------------------------------- #
# manifest io — tomllib reads, ``dump_manifest`` writes the flat subset
# --------------------------------------------------------------------------- #

def load_manifest(path: pathlib.Path | str = CONTRACTS_PATH) -> dict:
    """Read contracts.toml into {name: {key: value}}."""
    return tomllib.loads(pathlib.Path(path).read_text())


def _emit_value(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return str(v)
    if isinstance(v, (list, tuple)):
        return "[" + ", ".join(_emit_value(x) for x in v) + "]"
    return '"' + str(v).replace('"', '\\"') + '"'


def dump_manifest(manifest: dict, path: pathlib.Path | str = CONTRACTS_PATH):
    """Write the manifest back out (``check --update``'s ratchet writer)."""
    lines = [
        "# Trace-contract manifest — evaluated by `python -m repro.analysis "
        "check`.",
        "# `budget` is the analytic ceiling f(probe vars); "
        "`measured_peak_bytes` is the",
        "# ratchet (today's trace, update with `check --update` — it only "
        "goes DOWN).",
        "",
    ]
    for name in sorted(manifest):
        lines.append(f"[{name}]")
        entry = manifest[name]
        for key in sorted(entry, key=lambda k: (k.startswith("probe_"), k)):
            lines.append(f"{key} = {_emit_value(entry[key])}")
        lines.append("")
    pathlib.Path(path).write_text("\n".join(lines))


# --------------------------------------------------------------------------- #
# probe builders — how to trace each public entry point
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class Target:
    """One traceable probe: the callable + args (and optionally a lowering
    whose donation attrs the contract verifies)."""

    fn: object
    args: tuple
    lowered: object = None     # () -> jax.stages.Lowered, for donation checks


_KEY = jax.random.PRNGKey(0)


def _dense_K(n: int):
    from repro.core.kernel_op import KernelOperator

    X = jax.random.uniform(jax.random.PRNGKey(1), (n, 4))
    return KernelOperator(X, "gaussian", bandwidth=0.6).dense()


def _operator_X(n: int, p: int):
    return jax.random.uniform(jax.random.PRNGKey(1), (n, p))


def _build_sketch_both(probe):
    from repro.core import apply as A
    from repro.core.sketch import make_accum_sketch

    n, d, m = probe["n"], probe["d"], probe["m"]
    K = _dense_K(n)
    sk = make_accum_sketch(_KEY, n, d, m)
    return Target(lambda K: A.sketch_both(K, sk, use_kernel=True), (K,))


def _build_accum_grow_batched(probe):
    from repro.core import apply as A

    n, d, B = probe["n"], probe["d"], probe["B"]
    K = _dense_K(n)
    state = A.accum_init(_KEY, n, d, B)
    return Target(
        lambda K, s: A.accum_grow_batched(K, s, B, use_kernel=True),
        (K, state),
        lowered=lambda: A._grow_batched_donated.lower(K, state, B, False),
    )


def _build_grow_sketch_both(probe):
    from repro.core import apply as A
    from repro.core.kernel_op import KernelOperator

    n, p, d, m_max = probe["n"], probe["p"], probe["d"], probe["m_max"]
    X = _operator_X(n, p)
    return Target(
        lambda X: A.grow_sketch_both(
            _KEY, KernelOperator(X, "gaussian", bandwidth=0.6), d,
            m_max=m_max, tol=0.5, use_kernel=False),
        (X,),
    )


def _build_krr_fit(probe):
    from repro.core.krr import krr_sketched_fit
    from repro.core.sketch import make_accum_sketch

    n, d, m = probe["n"], probe["d"], probe["m"]
    K = _dense_K(n)
    y = jnp.zeros((n,))
    sk = make_accum_sketch(_KEY, n, d, m)
    return Target(
        lambda K, y: krr_sketched_fit(K, y, 1e-2, sk, use_kernel=True).fitted,
        (K, y),
    )


def _build_krr_fit_matfree(probe):
    from repro.core.kernel_op import KernelOperator
    from repro.core.krr import krr_sketched_fit_matfree
    from repro.core.sketch import make_accum_sketch

    n, p, d, m = probe["n"], probe["p"], probe["d"], probe["m"]
    X = _operator_X(n, p)
    y = jnp.zeros((n,))
    sk = make_accum_sketch(_KEY, n, d, m)
    return Target(
        lambda X, y: krr_sketched_fit_matfree(
            KernelOperator(X, "gaussian", bandwidth=0.6), y, 1e-2, sk,
            use_kernel=False).fitted,
        (X, y),
    )


def _build_krr_fit_pcg(probe):
    from repro.core.kernel_op import KernelOperator
    from repro.core.krr import krr_sketched_fit_pcg
    from repro.core.sketch import make_accum_sketch

    n, p, d, m = probe["n"], probe["p"], probe["d"], probe["m"]
    X = _operator_X(n, p)
    y = jnp.zeros((n,))
    sk = make_accum_sketch(_KEY, n, d, m)
    return Target(
        lambda X, y: krr_sketched_fit_pcg(
            KernelOperator(X, "gaussian", bandwidth=0.6), y, 1e-2, sk,
            iters=8, use_kernel=False).fitted,
        (X, y),
    )


def _build_krr_fit_adaptive(probe):
    from repro.core.krr import krr_sketched_fit_adaptive

    n, d, m_max = probe["n"], probe["d"], probe["m_max"]
    K = _dense_K(n)
    y = jnp.zeros((n,))
    return Target(
        lambda K, y: krr_sketched_fit_adaptive(
            K, y, 1e-2, _KEY, d, tol=0.5, m_max=m_max,
            use_kernel=False).fitted,
        (K, y),
    )


def _build_spectral_cluster(probe):
    from repro.core.spectral import spectral_cluster

    n, d, k = probe["n"], probe["d"], probe["k"]
    K = _dense_K(n)
    return Target(
        lambda K: spectral_cluster(_KEY, K, k, d=d, m=probe["m"],
                                   use_kernel=False).labels,
        (K,),
    )


def _serve_setup(probe, use_sketch: bool):
    from repro.configs import ARCHS, reduced
    from repro.models.model import init_params
    from repro.serve.engine import Engine, ServeConfig

    cfg = reduced(ARCHS[probe.get("arch", "stablelm-3b")])
    params = init_params(_KEY, cfg)
    sc = ServeConfig(max_len=probe["L"] + probe.get("steps", 4) + 1,
                     use_sketch=use_sketch, temperature=0.7, seed=0)
    return cfg, params, Engine(cfg, params, sc)


def _build_prefill(probe):
    from repro.models.model import prefill_with_cache

    cfg, params, eng = _serve_setup(probe, use_sketch=True)
    B, L = probe["B"], probe["L"]
    cache = eng.new_cache(B)
    tokens = jnp.zeros((B, L), jnp.int32)
    table = eng._slot_table(L)
    return Target(
        lambda p, c, t: prefill_with_cache(p, t, cfg, c, slot_table=table),
        (params, cache, tokens),
    )


def _build_engine_decode(probe):
    cfg, params, eng = _serve_setup(probe, use_sketch=True)
    B, L, steps = probe["B"], probe["L"], probe["steps"]
    cache = eng.new_cache(B)
    tok0 = jnp.zeros((B,), jnp.int32)
    return Target(
        lambda p, c, t: eng._decode_scan(p, c, t, jnp.int32(L),
                                         n_steps=steps),
        (params, cache, tok0),
    )


def _build_engine_decode_degraded(probe):
    # the exact-attention rung a sketched engine degrades to after its health
    # screen trips: same engine, use_sketch=False override + an exact cache.
    # The contract pins that the degraded path is as clean as the primary one
    # (no host syncs, no pallas, straight RNG lineage).
    cfg, params, eng = _serve_setup(probe, use_sketch=True)
    B, L, steps = probe["B"], probe["L"], probe["steps"]
    cache = eng.new_cache(B, use_sketch=False)
    tok0 = jnp.zeros((B,), jnp.int32)
    return Target(
        lambda p, c, t: eng._decode_scan(p, c, t, jnp.int32(L),
                                         n_steps=steps, use_sketch=False),
        (params, cache, tok0),
    )


def _build_solve_psd_ladder(probe):
    from repro.resilience.degrade import solve_psd_ladder

    d = probe["d"]
    A = jax.random.uniform(jax.random.PRNGKey(1), (d, d))
    M = A @ A.T / d + jnp.eye(d)
    b = jnp.ones((d,))
    return Target(lambda M, b: solve_psd_ladder(M, b), (M, b))


def _build_sharded_sketch_both(probe):
    from repro.core import apply as A
    from repro.core import distributed as D
    from repro.core.kernel_op import KernelOperator
    from repro.core.sketch import make_accum_sketch

    n, p, d, m = probe["n"], probe["p"], probe["d"], probe["m"]
    X = _operator_X(n, p)
    sk = make_accum_sketch(_KEY, n, d, m)
    mesh = D.resolve_mesh(True)
    return Target(
        lambda X: A.sketch_both(
            KernelOperator(X, "gaussian", bandwidth=0.6), sk, mesh=mesh,
            use_kernel=False),
        (X,),
    )


def _build_sharded_grow_sketch_both(probe):
    from repro.core import apply as A
    from repro.core import distributed as D
    from repro.core.kernel_op import KernelOperator

    n, p, d, m_max = probe["n"], probe["p"], probe["d"], probe["m_max"]
    X = _operator_X(n, p)
    mesh = D.resolve_mesh(True)
    return Target(
        lambda X: A.grow_sketch_both(
            _KEY, KernelOperator(X, "gaussian", bandwidth=0.6), d,
            m_max=m_max, tol=None, mesh=mesh, use_kernel=False),
        (X,),
    )


ENTRY_POINTS = {
    "sketch_both": _build_sketch_both,
    "accum_grow_batched": _build_accum_grow_batched,
    "grow_sketch_both": _build_grow_sketch_both,
    "krr_sketched_fit": _build_krr_fit,
    "krr_sketched_fit_matfree": _build_krr_fit_matfree,
    "krr_sketched_fit_pcg": _build_krr_fit_pcg,
    "krr_sketched_fit_adaptive": _build_krr_fit_adaptive,
    "spectral_cluster": _build_spectral_cluster,
    "prefill_with_cache": _build_prefill,
    "engine_decode": _build_engine_decode,
    "engine_decode_degraded": _build_engine_decode_degraded,
    "solve_psd_ladder": _build_solve_psd_ladder,
    "sharded_sketch_both": _build_sharded_sketch_both,
    "sharded_grow_sketch_both": _build_sharded_grow_sketch_both,
}


# --------------------------------------------------------------------------- #
# evaluation
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class ContractResult:
    """Outcome of evaluating one contract at its probe shape."""

    name: str
    status: str                   # "pass" | "fail" | "skipped"
    violations: list = dataclasses.field(default_factory=list)
    report: dict = dataclasses.field(default_factory=dict)
    measured_peak_bytes: int | None = None

    def to_dict(self) -> dict:
        """JSON-ready form for the CI artifact."""
        return dataclasses.asdict(self)


def _probe_vars(entry: dict) -> dict:
    return {k[len("probe_"):]: v for k, v in entry.items()
            if k.startswith("probe_")}


def evaluate_contract(name: str, entry: dict) -> ContractResult:
    """Trace one entry point at its probe shape and check every budget."""
    devices = int(entry.get("devices", 1))
    if jax.device_count() < devices:
        return ContractResult(
            name, "skipped",
            report={"reason": f"needs {devices} devices, "
                              f"have {jax.device_count()}"})
    builder = ENTRY_POINTS.get(name)
    if builder is None:
        return ContractResult(
            name, "fail",
            violations=[f"no probe builder registered for {name!r} "
                        "(add one to repro.analysis.contracts.ENTRY_POINTS)"])
    probe = _probe_vars(entry)
    target = builder(probe)
    closed = jax.make_jaxpr(target.fn)(*target.args)
    rep = trace_mod.report_from_jaxpr(closed)

    violations: list[str] = []
    # 1) analytic peak-bytes budget
    budget = entry.get("budget")
    if budget is not None:
        limit = eval_budget(str(budget), probe)
        if rep.peak_bytes > limit:
            violations.append(
                f"peak intermediate {rep.peak_bytes} B (shape "
                f"{rep.peak_shape}, {rep.peak_dtype}) exceeds budget "
                f"{limit} B = {budget!r}")
    # 2) measured ratchet
    ratchet = entry.get("measured_peak_bytes")
    if ratchet is not None and rep.peak_bytes > int(ratchet):
        violations.append(
            f"peak intermediate {rep.peak_bytes} B regressed above the "
            f"ratchet {ratchet} B (shape {rep.peak_shape}; if intentional, "
            "rerun `python -m repro.analysis check --update` and justify "
            "the increase in the PR)")
    # 3) exact pallas dispatch count
    expected_pallas = entry.get("pallas_calls")
    if expected_pallas is not None and rep.pallas_calls != int(expected_pallas):
        violations.append(
            f"pallas_call count {rep.pallas_calls} != contracted "
            f"{expected_pallas}")
    # 4) forbidden primitives (host syncs by default)
    forbid = entry.get("forbid")
    if forbid is None:
        forbid = sorted(trace_mod.HOST_CALLBACK_PRIMITIVES)
    found = rep.forbidden(forbid)
    if found:
        violations.append(f"forbidden primitives in trace: {found}")
    # 5) donation really lowered
    if entry.get("donation"):
        if target.lowered is None:
            violations.append("contract sets donation=true but the probe "
                              "builder provides no lowering")
        elif not trace_mod.verify_donation(target.lowered()):
            violations.append(
                "declared donation did not lower: no "
                "jax.buffer_donor/tf.aliasing_output attr in the lowered "
                "module (dropped donate_argnums?)")
    # 6) RNG lineage
    rng_issues: list[str] = []
    if entry.get("rng"):
        rng_rep = rng_mod.report_from_jaxpr(closed)
        rng_issues = [str(i) for i in rng_rep.issues]
        violations.extend(rng_issues)

    return ContractResult(
        name,
        "fail" if violations else "pass",
        violations=violations,
        report={**rep.to_dict(), "rng_issues": rng_issues, "probe": probe},
        measured_peak_bytes=rep.peak_bytes,
    )


def run_check(manifest: dict | None = None, *, only: str | None = None,
              update: bool = False,
              path: pathlib.Path | str = CONTRACTS_PATH):
    """Evaluate every contract (plus the fold_in sweep); returns
    (results, sweep_violations, manifest).  With ``update=True`` the
    measured peaks are ratcheted DOWN into the manifest and written back."""
    if manifest is None:
        manifest = load_manifest(path)
    results = []
    for name, entry in sorted(manifest.items()):
        if only is not None and name != only:
            continue
        res = evaluate_contract(name, entry)
        results.append(res)
        measured = res.measured_peak_bytes
        if update and res.status != "skipped" and measured is not None:
            prev = entry.get("measured_peak_bytes")
            if prev is None or measured < int(prev):
                entry["measured_peak_bytes"] = measured
            # an upward move is NOT written — the ratchet only descends;
            # raising a budget is a reviewed manifest edit, not an --update
    sweep = rng_mod.check_fold_in_sites() if only is None else []
    if update:
        dump_manifest(manifest, path)
    return results, sweep, manifest
