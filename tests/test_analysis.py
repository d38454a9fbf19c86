"""Trace-contract analyzer tests: every detector is proven on a planted bug.

A static-analysis gate that never fires is indistinguishable from one that is
broken — each test here pairs the clean case with a positive control:

  * peak-bytes: a quadratic outer product trips the detector, the streamed
    form does not;
  * RNG lineage: the PR 8 bug shape (two independent draws off the same
    `fold_in(key, pos)`) is flagged; the tagged two-stream form is clean;
  * donation: a jit WITHOUT `donate_argnums` fails `verify_donation`, the
    donated twin passes;
  * host sync: a `pure_callback` in the trace is caught by the forbidden-
    primitive check;
  * contracts: the manifest round-trips through `--update` (check → update →
    check clean) and a planted budget violation fails.
"""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts as C
from repro.analysis import rng as R
from repro.analysis import streams as S
from repro.analysis import trace as T
from repro.analysis.hardware import TPU_V5E, get_default_hardware, hardware_for

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------- #
# trace: peak bytes / census / dispatch counts / loops
# --------------------------------------------------------------------------- #

def test_peak_bytes_trips_on_quadratic_buffer():
    """Positive control: an n×n outer product is seen at full size; the
    streamed row-sum of the same quantity stays O(n)."""
    n = 512
    x = jnp.ones((n,), jnp.float32)

    quad = T.trace_report(lambda x: (x[:, None] * x[None, :]).sum(), x)
    assert quad.peak_bytes == n * n * 4
    assert quad.peak_shape == (n, n)

    def streamed(x):
        def body(acc, xi):
            return acc + (xi * x).sum(), None
        acc, _ = jax.lax.scan(body, 0.0, x)
        return acc

    lean = T.trace_report(streamed, x)
    assert lean.peak_bytes <= n * 4


def test_scan_trip_count_multiplies_flops_and_dispatch():
    """FLOPs and pallas dispatches inside a scan are charged ×length; the
    static call count is not."""
    L, d = 7, 16
    A_ = jnp.ones((d, d))

    def stepper(x):
        def body(c, _):
            return c @ A_, None
        out, _ = jax.lax.scan(body, x, None, length=L)
        return out

    rep = T.trace_report(stepper, jnp.ones((d, d)))
    assert rep.flops == pytest.approx(L * 2 * d * d * d)


def test_while_trip_count_from_condition_literal():
    """`fori_loop` bounds are read off the condition's compare constant —
    the launch/analysis.py trick transplanted to jaxprs."""
    d, trips = 8, 13
    M = jnp.ones((d, d))

    def run(x):
        return jax.lax.fori_loop(0, trips, lambda i, c: c @ M, x)

    rep = T.trace_report(run, jnp.ones((d, d)))
    assert rep.flops == pytest.approx(trips * 2 * d * d * d)


def test_host_callback_detected():
    """Positive control for the forbidden-primitive check: a pure_callback
    in the trace is a host sync and must be reported."""
    def synced(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct((4,), jnp.float32), x)

    rep = T.trace_report(synced, jnp.ones((4,)))
    assert rep.host_callbacks == ["pure_callback"]
    assert rep.forbidden(T.HOST_CALLBACK_PRIMITIVES) == ["pure_callback"]

    clean = T.trace_report(lambda x: x * 2, jnp.ones((4,)))
    assert clean.host_callbacks == []


def test_donation_verification_catches_dropped_donation():
    """Positive control: the same function jitted WITHOUT donate_argnums
    lowers with no aliasing attr — `verify_donation` must say so."""
    x = jnp.ones((32, 32))

    donated = jax.jit(lambda x: x + 1, donate_argnums=(0,)).lower(x)
    dropped = jax.jit(lambda x: x + 1).lower(x)
    assert T.verify_donation(donated)
    assert not T.verify_donation(dropped)


def test_dtype_census_and_compat_helpers():
    """Census sees produced buffers by dtype; compat helpers mirror the
    hand-rolled test walkers they replaced."""
    def f(x):
        y = x.astype(jnp.bfloat16)
        return (y @ y).astype(jnp.float32)

    closed = jax.make_jaxpr(f)(jnp.ones((8, 8)))
    rep = T.report_from_jaxpr(closed)
    assert "bfloat16" in rep.dtype_census
    assert T.max_intermediate_elems(closed) == 64
    assert T.count_pallas_calls(closed) == 0
    assert (8, 8) in T.all_shapes(closed)


# --------------------------------------------------------------------------- #
# rng lineage
# --------------------------------------------------------------------------- #

def test_rng_checker_flags_pr8_shared_stream():
    """THE bug class: slot draws and sampling both keyed off
    fold_in(key, pos) — two independent primitives, one stream."""
    def pr8(key, pos):
        k = jax.random.fold_in(key, pos)
        slots = jax.random.randint(k, (4,), 0, 16)
        u = jax.random.uniform(k, (4,))
        return slots, u

    rep = R.rng_report(pr8, KEY, jnp.int32(3))
    assert not rep.ok
    assert any(i.kind == "reused-key" for i in rep.issues)


def test_rng_checker_accepts_tagged_streams():
    """The PR 8 fix shape: per-consumer tags make the streams disjoint."""
    def fixed(key, pos):
        ks = jax.random.fold_in(jax.random.fold_in(key, S.SLOT_STREAM), pos)
        ku = jax.random.fold_in(jax.random.fold_in(key, S.SAMPLE_STREAM), pos)
        return jax.random.randint(ks, (4,), 0, 16), jax.random.uniform(ku, (4,))

    assert R.rng_report(fixed, KEY, jnp.int32(3)).ok


def test_rng_checker_flags_loop_invariant_key():
    """A key consumed unchanged inside a scan draws the SAME bits every
    iteration; the per-step fold_in form is legitimate."""
    def bad(key):
        def body(c, _):
            return c + jax.random.uniform(key, (2,)).sum(), None
        return jax.lax.scan(body, 0.0, None, length=5)[0]

    rep = R.rng_report(bad, KEY)
    assert any(i.kind == "loop-reuse" for i in rep.issues)

    def good(key):
        def body(c, i):
            return c + jax.random.uniform(
                jax.random.fold_in(key, i), (2,)).sum(), None
        return jax.lax.scan(body, 0.0, jnp.arange(5))[0]

    assert R.rng_report(good, KEY).ok


def test_rng_checker_accepts_split():
    """jax.random.split children are distinct streams by construction."""
    def split_draws(key):
        k1, k2 = jax.random.split(key)
        return jax.random.uniform(k1, (2,)), jax.random.normal(k2, (2,))

    assert R.rng_report(split_draws, KEY).ok


def test_fold_in_sweep_is_clean_and_detects_unregistered(tmp_path):
    """The real tree must sweep clean; a synthetic file with an untagged
    fold_in is the positive control."""
    assert R.check_fold_in_sites() == []

    bad = tmp_path / "mod.py"
    bad.write_text(
        "import jax\n"
        "def f(key, step):\n"
        "    return jax.random.fold_in(key, step)\n"
    )
    sites = R.sweep_fold_in_sites(tmp_path)
    assert len(sites) == 1 and not sites[0].ok

    marked = tmp_path / "ok.py"
    marked.write_text(
        "import jax\n"
        "def f(key, step):\n"
        "    # rng-stream: kmeanspp-iter\n"
        "    return jax.random.fold_in(key, step)\n"
    )
    assert all(s.ok for s in R.sweep_fold_in_sites(tmp_path)
               if str(s.path).endswith("ok.py"))


def test_stream_registry_pins_tag_values():
    """Tag values are the seed contract — changing one is a seed break."""
    assert S.SLOT_STREAM == 0x510C
    assert S.SAMPLE_STREAM == 0x5A3E
    assert S.HOLDOUT_STREAM == 0x5E1D
    assert S.REFINE_STREAM == 0x11E7
    assert S.stream_for_tag(0x510C).name == "serve-slots"
    for name in ("slot-position", "sample-position", "kmeanspp-iter",
                 "data-step-host", "compress-step-leaf", "init-block"):
        assert name in S.REGISTRY


# --------------------------------------------------------------------------- #
# contracts: manifest io + round trip
# --------------------------------------------------------------------------- #

def test_budget_expr_eval_and_rejects_unknown_names():
    got = C.eval_budget("4*n*(p + m*d) + 1*MiB",
                        {"n": 10, "p": 2, "m": 3, "d": 4})
    assert got == 4 * 10 * (2 + 3 * 4) + 1024 * 1024
    with pytest.raises(ValueError):
        C.eval_budget("__import__('os')", {})
    with pytest.raises(ValueError):
        C.eval_budget("n + q", {"n": 1})


def test_manifest_round_trip(tmp_path):
    """dump → load is the identity for the manifest subset of TOML."""
    manifest = {
        "thing": {"budget": "4*n*n + 1*MiB", "pallas_calls": 1,
                  "donation": True, "probe_n": 256, "probe_d": 8,
                  "measured_peak_bytes": 262144},
    }
    path = tmp_path / "contracts.toml"
    C.dump_manifest(manifest, path)
    assert C.load_manifest(path) == manifest


def test_contract_check_update_round_trip(tmp_path):
    """check → --update ratchet → check clean; a planted too-small budget
    fails; --update never ratchets UP."""
    path = tmp_path / "contracts.toml"
    C.dump_manifest({
        "sketch_both": {"budget": "4*n*n + 1*MiB", "pallas_calls": 1,
                        "probe_n": 64, "probe_d": 8, "probe_m": 2},
    }, path)

    results, _, manifest = C.run_check(path=path, update=True, only="sketch_both")
    assert results[0].status == "pass"
    measured = manifest["sketch_both"]["measured_peak_bytes"]
    assert measured == 64 * 64 * 4

    # clean re-check against the written ratchet
    results, _, _ = C.run_check(path=path, only="sketch_both")
    assert results[0].status == "pass"

    # planted violation: ratchet below reality must fail loudly
    tight = C.load_manifest(path)
    tight["sketch_both"]["measured_peak_bytes"] = measured // 2
    results, _, after = C.run_check(manifest=tight, path=path,
                                    only="sketch_both", update=True)
    assert results[0].status == "fail"
    assert any("ratchet" in v for v in results[0].violations)
    # --update kept the (tighter) manifest value: ratchets never move up
    assert after["sketch_both"]["measured_peak_bytes"] == measured // 2

    # planted budget violation
    broke = C.load_manifest(path)
    broke["sketch_both"]["budget"] = "n"
    broke["sketch_both"]["measured_peak_bytes"] = measured
    results, _, _ = C.run_check(manifest=broke, path=path, only="sketch_both")
    assert results[0].status == "fail"
    assert any("exceeds budget" in v for v in results[0].violations)


def test_contract_pallas_count_violation():
    """A wrong pinned dispatch count is a contract failure."""
    entry = {"budget": "4*n*n + 1*MiB", "pallas_calls": 3,
             "probe_n": 64, "probe_d": 8, "probe_m": 2}
    res = C.evaluate_contract("sketch_both", entry)
    assert res.status == "fail"
    assert any("pallas_call count" in v for v in res.violations)


def test_full_manifest_passes_here():
    """The shipped manifest holds on this machine (sharded contracts skip
    below 8 devices — the CI leg covers them)."""
    results, sweep, _ = C.run_check()
    assert sweep == []
    bad = [r for r in results if r.status == "fail"]
    assert not bad, [(r.name, r.violations) for r in bad]


def test_contract_result_json_ready(tmp_path):
    res = C.evaluate_contract(
        "sketch_both",
        {"budget": "4*n*n + 1*MiB", "probe_n": 64, "probe_d": 8, "probe_m": 2})
    blob = json.dumps(res.to_dict())
    assert "sketch_both" in blob


# --------------------------------------------------------------------------- #
# hardware model ride-along
# --------------------------------------------------------------------------- #

def test_roofline_uses_overridable_hardware():
    from repro.launch.analysis import HBM_BW, ICI_BW, PEAK_FLOPS, Roofline

    assert (PEAK_FLOPS, HBM_BW, ICI_BW) == (
        TPU_V5E.peak_flops, TPU_V5E.hbm_bw, TPU_V5E.ici_bw)

    r = Roofline(flops=1e12, hbm_bytes=1e9, coll_bytes=0.0, coll_detail={},
                 peak_mem_bytes=0.0, hardware=TPU_V5E)
    assert r.t_compute == pytest.approx(1e12 / TPU_V5E.peak_flops)

    slow = dataclasses.replace(TPU_V5E, name="half-speed",
                               peak_flops=TPU_V5E.peak_flops / 2)
    r2 = Roofline(flops=1e12, hbm_bytes=1e9, coll_bytes=0.0, coll_detail={},
                  peak_mem_bytes=0.0, hardware=slow)
    assert r2.t_compute == pytest.approx(2 * r.t_compute)
    assert r2.to_dict()["hardware"] == "half-speed"


def test_peak_table_keyed_by_device_kind():
    """Peaks come from one table keyed by ``device_kind``; a device that is
    not in it — the CPU these tests run on — is an error, not a default."""
    assert hardware_for("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="no peak numbers"):
        hardware_for("TPU v9 imaginary")
    kind = jax.devices()[0].device_kind
    if kind not in ("TPU v5 lite",):
        with pytest.raises(ValueError, match=kind):
            get_default_hardware()
