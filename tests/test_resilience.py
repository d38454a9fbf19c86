"""Resilience-layer tests: fault plans, checkpoint crash recovery, every
degradation-ladder rung, and the kill-and-resume bitwise pin.

Each ladder rung is exercised by ARMING A FAULT PLAN through the real entry
points (kernels/*/ops.py, ckpt.py, Engine.generate) — not by unit-mocking the
rung — so the recovery paths tested here are the ones production hits.

The module is chaos-tolerant: CI's chaos job re-runs this whole file under
three canned ambient ``REPRO_FAULT_PLAN``s (tests/fault_plans/*.json). The
deterministic tests clear the ambient plan via the autouse fixture below and
arm their own; ``TestAmbientChaos`` restores the ambient plan and asserts the
invariants that must hold under ANY plan (finite results or a clean
DeviceLost — never wrong numerics, never a corrupt latest checkpoint).
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.checkpoint import ckpt
from repro.configs import ARCHS, reduced
from repro.core.kernel_op import KernelOperator
from repro.core.krr import krr_sketched_fit
from repro.core.sketch import make_accum_sketch
from repro.core import apply as A
from repro.kernels.accum_apply import autotune
from repro.models.model import init_params
from repro.resilience import faults
from repro.resilience.degrade import (
    HealthReport,
    global_health,
    ladder_call,
    solve_psd_ladder,
)
from repro.serve.engine import Engine, ServeConfig

KEY = jax.random.PRNGKey(0)
REPO = pathlib.Path(__file__).resolve().parents[1]
PLANS = pathlib.Path(__file__).parent / "fault_plans"

# the chaos job's ambient plan, captured before the autouse fixture clears it
AMBIENT_PLAN = os.environ.get(faults.ENV_PLAN)


@pytest.fixture(autouse=True)
def _isolate_faults(monkeypatch):
    """Each test starts with no ambient plan, fresh arrival counters, and an
    empty global health report (tests arm their own plans explicitly)."""
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    faults.reset()
    global_health().clear()
    yield
    faults.reset()
    global_health().clear()


def _arm(monkeypatch, plan: dict) -> None:
    monkeypatch.setenv(faults.ENV_PLAN, json.dumps(plan))
    faults.reset()


# --------------------------------------------------------------------------- #
# fault plans: parsing + deterministic triggering
# --------------------------------------------------------------------------- #

class TestFaultPlans:
    def test_inline_and_file_plans_parse(self, monkeypatch, tmp_path):
        _arm(monkeypatch, {"kernel.dispatch": {"action": "error", "at": 3}})
        assert faults.active_plan() == {
            "kernel.dispatch": {"action": "error", "at": 3}
        }
        p = tmp_path / "plan.json"
        p.write_text('{"ckpt.write": {"action": "kill", "at": 1}}')
        monkeypatch.setenv(faults.ENV_PLAN, str(p))
        assert faults.active_plan() == {
            "ckpt.write": {"action": "kill", "at": 1}
        }

    @pytest.mark.parametrize("bad", [
        '{"no.such.site": {"action": "error", "at": 1}}',
        '{"ckpt.write": {"action": "explode", "at": 1}}',
        '{"ckpt.write": "error"}',
        '["ckpt.write"]',
    ])
    def test_malformed_plans_raise(self, monkeypatch, bad):
        monkeypatch.setenv(faults.ENV_PLAN, bad)
        with pytest.raises(ValueError):
            faults.active_plan()

    def test_canned_ci_plans_are_valid(self, monkeypatch):
        for name in ("kernel_dispatch", "ckpt_kill", "nan_decode"):
            monkeypatch.setenv(faults.ENV_PLAN, str(PLANS / f"{name}.json"))
            assert faults.active_plan(), name

    def test_at_and_every_triggering(self, monkeypatch):
        _arm(monkeypatch, {"kernel.dispatch": {"action": "error", "at": 2}})
        assert faults.fault_point("kernel.dispatch") is None
        with pytest.raises(faults.FaultInjected):
            faults.fault_point("kernel.dispatch")
        assert faults.fault_point("kernel.dispatch") is None  # fires once

        _arm(monkeypatch, {
            "kernel.dispatch": {"action": "error", "every": 2, "times": 1}
        })
        hits = 0
        for _ in range(6):
            try:
                faults.fault_point("kernel.dispatch")
            except faults.FaultInjected:
                hits += 1
        assert hits == 1  # every=2 capped by times=1

    def test_unregistered_site_rejected(self):
        with pytest.raises(KeyError):
            faults.fault_point("not.a.site")

    def test_poison_refuses_tracers(self, monkeypatch):
        """A jitted function must never bake an injection into its artifact:
        on tracers the arrival is not consumed and the value is unchanged."""
        _arm(monkeypatch, {"decode.step": {"action": "nan", "at": 1}})

        @jax.jit
        def f(x):
            return faults.poison("decode.step", x)

        out = f(jnp.ones((8,)))
        assert bool(jnp.all(jnp.isfinite(out)))
        # the arrival was NOT consumed under trace: the first eager arrival
        # still fires
        poisoned = faults.poison("decode.step", jnp.ones((8,)))
        assert bool(jnp.any(jnp.isnan(poisoned)))

    def test_device_lost_is_not_fault_injected(self):
        """Retry loops catch FaultInjected but must let DeviceLost fly."""
        assert not issubclass(faults.DeviceLost, faults.FaultInjected)


# --------------------------------------------------------------------------- #
# checkpoint: crash recovery (satellites 1–3)
# --------------------------------------------------------------------------- #

def _tree(step=0):
    return {
        "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) + step,
        "b": {"m": jnp.ones((2,), jnp.bfloat16) * step, "n": jnp.int32(step)},
    }


class TestCheckpoint:
    def test_kill_leaves_prior_step_loadable(self, monkeypatch, tmp_path):
        """A write killed mid-attempt (after meta, before state) must leave
        the PRIOR committed step as latest, plus a stale tmp dir that
        sweep_stale/latest_step removes."""
        td = str(tmp_path)
        ckpt.save(td, _tree(1), step=1)
        ckpt.save(td, _tree(2), step=2)
        _arm(monkeypatch, {"ckpt.write": {"action": "kill", "at": 1}})
        with pytest.raises(faults.DeviceLost):
            ckpt.save(td, _tree(3), step=3)
        assert any(n.endswith(".tmp") for n in os.listdir(td))
        assert ckpt.latest_step(td) == 2  # sweeps the stale tmp by default
        assert not any(n.endswith(".tmp") for n in os.listdir(td))
        state, step = ckpt.restore(td, _tree())
        assert step == 2
        assert float(state["w"][0, 0]) == 2.0

    def test_corrupt_latest_falls_back_to_prior(self, monkeypatch, tmp_path):
        td = str(tmp_path)
        ckpt.save(td, _tree(1), step=1)
        _arm(monkeypatch, {"ckpt.write": {"action": "corrupt", "at": 1}})
        ckpt.save(td, _tree(2), step=2)  # commits a mangled payload
        state, step = ckpt.restore(td, _tree())
        assert step == 1
        assert float(state["w"][0, 0]) == 1.0
        assert global_health().count("ckpt.restore") == 1

    def test_truncated_latest_falls_back(self, monkeypatch, tmp_path):
        td = str(tmp_path)
        ckpt.save(td, _tree(1), step=1)
        _arm(monkeypatch, {"ckpt.write": {"action": "truncate", "at": 1}})
        ckpt.save(td, _tree(2), step=2)
        _, step = ckpt.restore(td, _tree())
        assert step == 1

    def test_transient_error_retried_with_backoff(self, monkeypatch, tmp_path):
        """An 'error' plan on the first attempt is absorbed by save()'s
        retry loop; the second attempt commits."""
        td = str(tmp_path)
        _arm(monkeypatch, {"ckpt.write": {"action": "error", "at": 1}})
        out = ckpt.save(td, _tree(5), step=5, backoff=0.001)
        assert out.endswith("step_00000005")
        assert ckpt.latest_step(td) == 5

    def test_retries_exhausted_raises(self, monkeypatch, tmp_path):
        _arm(monkeypatch, {
            "ckpt.write": {"action": "error", "at": [1, 2, 3]}
        })
        with pytest.raises(faults.FaultInjected):
            ckpt.save(str(tmp_path), _tree(), step=1, retries=3, backoff=0.001)

    def test_keep_last_retention(self, tmp_path):
        td = str(tmp_path)
        for s in range(1, 6):
            ckpt.save(td, _tree(s), step=s, keep_last=2)
        assert ckpt.committed_steps(td) == [5, 4]

    def test_sweep_stale_reports_removals(self, tmp_path):
        td = str(tmp_path)
        ckpt.save(td, _tree(1), step=1)
        (tmp_path / "step_00000009").mkdir()           # uncommitted dir
        (tmp_path / "step_00000010.tmp").mkdir()       # torn tmp
        removed = ckpt.sweep_stale(td)
        assert sorted(removed) == ["step_00000009", "step_00000010.tmp"]
        assert ckpt.committed_steps(td) == [1]

    def test_async_writer_failure_reraised(self, tmp_path):
        """Satellite 1: a writer-thread death must surface on the next
        save()/close(), never silently."""
        parent = tmp_path / "plainfile"
        parent.write_text("not a directory")
        ac = ckpt.AsyncCheckpointer(str(parent / "sub"), keep=2)
        ac.save(_tree(1), step=1)
        with pytest.raises(OSError):
            ac.close()
        # a second failure surfaces on the next save() call
        ac.save(_tree(2), step=2)
        with pytest.raises(OSError):
            ac.save(_tree(3), step=3)

    def test_async_writer_clean_path_still_works(self, tmp_path):
        ac = ckpt.AsyncCheckpointer(str(tmp_path), keep=2)
        ac.save(_tree(1), step=1)
        ac.save(_tree(2), step=2)
        ac.close()
        assert ckpt.committed_steps(str(tmp_path)) == [2, 1]


# --------------------------------------------------------------------------- #
# leaf wire-format round-trip (satellite 3)
# --------------------------------------------------------------------------- #

_DTYPES = [jnp.float32, jnp.float64, jnp.bfloat16, jnp.int8, jnp.bool_]
_SHAPES = [(), (0,), (3, 2), (1, 0, 4)]


def _roundtrip(a):
    out = ckpt._decode_leaf(ckpt._encode_leaf(a))
    assert out.shape == a.shape
    assert out.dtype == a.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(a))


class TestLeafRoundTrip:
    @pytest.mark.parametrize("dtype", _DTYPES, ids=str)
    @pytest.mark.parametrize("shape", _SHAPES, ids=str)
    def test_encode_decode_roundtrip(self, dtype, shape):
        if dtype == jnp.bool_:
            a = np.arange(int(np.prod(shape))).reshape(shape) % 2 == 0
        else:
            a = np.arange(int(np.prod(shape))).reshape(shape)
        _roundtrip(np.asarray(a, jnp.dtype(dtype)))

    @given(
        seed=st.integers(min_value=0, max_value=2**32 - 1),
        dtype_i=st.integers(min_value=0, max_value=len(_DTYPES) - 1),
        shape=st.lists(st.integers(min_value=0, max_value=4),
                       min_size=0, max_size=3),
    )
    @settings(max_examples=50, deadline=None)
    def test_roundtrip_property(self, seed, dtype_i, shape):
        """Property form: arbitrary bit patterns reinterpreted as each wire
        dtype must survive encode→decode bitwise across 0-d/empty shapes
        (including NaN payloads and non-canonical bools)."""
        dt = jnp.dtype(_DTYPES[dtype_i])
        n = int(np.prod(shape)) if shape else 1
        raw = np.random.default_rng(seed).integers(
            0, 256, size=n * dt.itemsize, dtype=np.uint8
        )
        a = raw.view(dt).reshape(tuple(shape))
        out = ckpt._decode_leaf(ckpt._encode_leaf(a))
        assert out.shape == a.shape
        assert out.dtype == a.dtype
        assert out.tobytes() == a.tobytes()


# --------------------------------------------------------------------------- #
# degradation ladders (tentpole c) — driven by fault plans, not mocks
# --------------------------------------------------------------------------- #

def _kernel_fixture(n=96, d=8, m=2):
    X = jax.random.uniform(jax.random.PRNGKey(1), (n, 5))
    op = KernelOperator(X, "gaussian", bandwidth=0.7)
    sk = make_accum_sketch(KEY, n, d, m)
    return op, sk


class TestLadders:
    def test_sketch_both_pallas_to_xla(self, monkeypatch):
        """kernel.dispatch error → the XLA gather rung, bitwise-equal to the
        use_kernel=False path, with the drop health-recorded."""
        op, sk = _kernel_fixture()
        K = op.dense()
        want = A.sketch_both(K, sk, use_kernel=False)
        _arm(monkeypatch, {"kernel.dispatch": {"action": "error", "at": 1}})
        got = A.sketch_both(K, sk, use_kernel=True)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert global_health().count("kernel.dispatch") == 1

    def test_weighted_cols_three_rungs_to_dense(self, monkeypatch):
        """Arming BOTH kernel sites drives the matfree ladder past Pallas AND
        the streaming rung, landing on the dense one-slab oracle."""
        op, sk = _kernel_fixture()
        want = op.sketch_cols(sk, use_kernel=False)
        _arm(monkeypatch, {
            "kernel.dispatch": {"action": "error", "at": 1},
            "kernel.stream": {"action": "error", "at": 1},
        })
        got = op.sketch_cols(sk, use_kernel=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )
        assert global_health().count("kernel.dispatch") == 2  # two rung drops

    def test_terminal_rung_failure_propagates(self, monkeypatch):
        """When every rung's arrival faults, the last exception escapes the
        ladder (the caller must see a real failure, not a silent None)."""
        _arm(monkeypatch, {"kernel.dispatch": {"action": "error", "at": [1, 2]}})
        rungs = [("a", lambda: faults.fault_point("kernel.dispatch")),
                 ("b", lambda: faults.fault_point("kernel.dispatch"))]
        with pytest.raises(faults.FaultInjected):
            ladder_call("kernel.dispatch", rungs, health=HealthReport())

    def test_ladder_propagates_real_errors(self):
        """A rung that fails with anything but an injected fault — a Mosaic
        compile or lowering error, a shape bug — propagates: no drop to a
        slower rung, no health record."""
        hr = HealthReport()

        def broken():
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        with pytest.raises(RuntimeError, match="Mosaic"):
            ladder_call("kernel.dispatch", [("pallas", broken), ("xla", lambda: 42)],
                        health=hr)
        assert hr.count() == 0

    def test_ladder_lets_device_lost_fly(self, monkeypatch):
        """A simulated preemption is NOT a degradation — the ladder must not
        absorb it into a slower rung."""
        _arm(monkeypatch, {"kernel.dispatch": {"action": "kill", "at": 1}})
        rungs = [("a", lambda: faults.fault_point("kernel.dispatch")),
                 ("b", lambda: 42)]
        with pytest.raises(faults.DeviceLost):
            ladder_call("kernel.dispatch", rungs, health=HealthReport())

    def test_solve_healthy_no_escalation(self):
        Am = jax.random.uniform(jax.random.PRNGKey(2), (16, 16))
        M = Am @ Am.T / 16 + jnp.eye(16)
        b = jnp.ones((16,))
        x, health = solve_psd_ladder(M, b)
        np.testing.assert_allclose(np.asarray(M @ x), np.asarray(b), atol=1e-4)
        assert int(health["solve_escalations"]) == 0
        assert not bool(health["solve_used_lstsq"])

    def test_solve_escalates_on_marginal_matrix(self, monkeypatch):
        """A barely-indefinite input (tiny negative shift past a singular
        direction) is recovered by the ×10 jitter escalation WITHOUT falling
        to lstsq: the shift 3e-7·(tr M/d) ≈ 2.8e-7 beats the base jitter
        j0 ≈ 9.4e-9 but not j0·10²."""
        _arm(monkeypatch, {
            "solve.cholesky": {"action": "indefinite", "at": 1, "scale": 3e-7}
        })
        M = jnp.diag(jnp.ones((16,)).at[0].set(0.0))
        x, health = solve_psd_ladder(M, jnp.ones((16,)))
        assert bool(jnp.all(jnp.isfinite(x)))
        assert int(health["solve_escalations"]) >= 1
        assert not bool(health["solve_used_lstsq"])

    def test_solve_lstsq_terminal_rung(self, monkeypatch):
        """A hard spectrum flip exhausts the bounded escalation and lands on
        lstsq — still finite, flagged in the health scalars."""
        _arm(monkeypatch, {
            "solve.cholesky": {"action": "indefinite", "at": 1, "scale": 2.0}
        })
        Am = jax.random.uniform(jax.random.PRNGKey(2), (16, 16))
        M = Am @ Am.T / 16 + jnp.eye(16)
        x, health = solve_psd_ladder(M, jnp.ones((16,)))
        assert bool(jnp.all(jnp.isfinite(x)))
        assert bool(health["solve_used_lstsq"])

    def test_krr_fit_survives_indefinite_fault(self, monkeypatch):
        """The fault threaded through the REAL fit entry point: the fit stays
        finite and the ladder's health scalars ride out in .info."""
        op, sk = _kernel_fixture()
        K = op.dense()
        y = jnp.sin(jnp.arange(K.shape[0], dtype=jnp.float32))
        _arm(monkeypatch, {
            "solve.cholesky": {"action": "indefinite", "at": 1, "scale": 2.0}
        })
        fit = krr_sketched_fit(K, y, 1e-2, sk, use_kernel=False)
        assert bool(jnp.all(jnp.isfinite(fit.fitted)))
        assert bool(fit.info["solve_used_lstsq"])

    def test_autotune_corrupt_cache_degrades(self, monkeypatch, tmp_path):
        """A garbage cache file must fall back to the heuristic (lookup
        returns None) and record the degradation — never crash the caller."""
        p = tmp_path / "autotune.json"
        p.write_text("{ this is not json")
        monkeypatch.setenv(autotune.ENV_CACHE, str(p))
        autotune._MEM.clear()
        assert autotune.lookup("sketch_both", (96, 8, 2), jnp.float32, True) is None
        assert global_health().count("autotune.load") == 1

    def test_autotune_fault_site_degrades(self, monkeypatch, tmp_path):
        p = tmp_path / "autotune.json"
        p.write_text('{"k": [1, 2]}')
        monkeypatch.setenv(autotune.ENV_CACHE, str(p))
        _arm(monkeypatch, {"autotune.load": {"action": "error", "at": 1}})
        autotune._MEM.clear()
        assert autotune.lookup("k", (), jnp.float32, True) is None
        assert global_health().count("autotune.load") == 1
        # missing file is a normal cold start — no health event
        global_health().clear()
        autotune._MEM.clear()
        monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "absent.json"))
        assert autotune.lookup("k", (), jnp.float32, True) is None
        assert global_health().count("autotune.load") == 0


# --------------------------------------------------------------------------- #
# engine: checkpoint/resume + health screen (tentpole b)
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def built():
    cfg = reduced(ARCHS["stablelm-3b"])
    return cfg, init_params(KEY, cfg)


B, L, N_NEW = 2, 8, 6


def _engine(built, ckdir=None, ckpt_every=2):
    cfg, params = built
    sc = ServeConfig(
        max_len=L + N_NEW + 2, use_sketch=True, temperature=0.7, seed=3,
        ckpt_dir=ckdir, ckpt_every=ckpt_every,
    )
    return Engine(cfg, params, sc)


def _prompts(built):
    cfg, _ = built
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, cfg.vocab_size)
    )


class TestEngineResilience:
    def test_checkpointed_run_matches_plain(self, built, tmp_path):
        """Chunked decode + checkpointing must not change the tokens."""
        prompts = _prompts(built)
        ref, _ = _engine(built).generate(prompts, N_NEW)
        toks, _ = _engine(built, str(tmp_path)).generate(
            prompts, N_NEW, request_id="r"
        )
        np.testing.assert_array_equal(ref, toks)

    def test_kill_and_resume_bitwise(self, built, tmp_path, monkeypatch):
        """In-process pin: kill the 2nd decode dispatch, resume with a FRESH
        engine from the surviving checkpoint → bitwise-identical tokens."""
        prompts = _prompts(built)
        ref, _ = _engine(built).generate(prompts, N_NEW)
        _arm(monkeypatch, {"decode.step": {"action": "kill", "at": 2}})
        with pytest.raises(faults.DeviceLost):
            _engine(built, str(tmp_path)).generate(
                prompts, N_NEW, request_id="r"
            )
        monkeypatch.delenv(faults.ENV_PLAN)
        faults.reset()
        eng = _engine(built, str(tmp_path))
        toks, _ = eng.generate(prompts, N_NEW, request_id="r")
        np.testing.assert_array_equal(ref, toks)
        assert eng.health.count("ckpt.resume") == 1

    def test_kill_and_resume_bitwise_cross_process(self, built, tmp_path):
        """THE pinned guarantee: a generate() killed mid-decode and resumed in
        a NEW PROCESS produces bitwise-identical tokens (tests/resume_worker
        fixes the request; three subprocess runs: ref / kill / resume)."""
        env = {k: v for k, v in os.environ.items() if k != faults.ENV_PLAN}
        env["PYTHONPATH"] = str(REPO / "src")

        def run(mode, extra_env=None):
            return subprocess.run(
                [sys.executable, str(REPO / "tests" / "resume_worker.py"),
                 mode, str(tmp_path)],
                env={**env, **(extra_env or {})},
                capture_output=True, text=True, timeout=600,
            )

        ref = run("ref")
        assert ref.returncode == 0, ref.stderr
        kill = run("kill", {
            faults.ENV_PLAN: '{"decode.step": {"action": "kill", "at": 2}}'
        })
        assert kill.returncode == 17, (kill.stdout, kill.stderr)
        assert "KILLED" in kill.stdout
        assert ckpt.committed_steps(str(tmp_path / "req"))  # progress survived
        res = run("resume")
        assert res.returncode == 0, res.stderr
        assert json.loads(res.stdout) == json.loads(ref.stdout)

    def test_nan_poison_degrades_to_exact(self, built, monkeypatch):
        """decode.step nan → the health screen catches the poisoned sketched
        cache between chunks and rebuilds exact attention; tokens stay valid
        and the degradation is recorded — never silent."""
        prompts = _prompts(built)
        _arm(monkeypatch, {"decode.step": {"action": "nan", "at": 1}})
        eng = _engine(built)
        toks, _ = eng.generate(prompts, N_NEW)
        assert toks.shape == (B, N_NEW)
        assert np.all((toks >= 0) & (toks < built[0].vocab_size))
        assert eng.health.count("decode.cache") == 1
        ev = eng.health.events[0]
        assert (ev.rung_from, ev.rung_to) == ("sketched", "exact-rebuild")

    def test_resume_refuses_mismatched_request(self, built, tmp_path):
        """Resuming different prompts against an existing request checkpoint
        must raise — silently generating different tokens would void the
        bitwise guarantee."""
        prompts = _prompts(built)
        _engine(built, str(tmp_path)).generate(prompts, N_NEW, request_id="r")
        other = (prompts + 1) % built[0].vocab_size
        with pytest.raises(ValueError, match="refusing to resume"):
            _engine(built, str(tmp_path)).generate(
                other, N_NEW, request_id="r"
            )

    def test_stats_surface_health(self, built, monkeypatch):
        prompts = _prompts(built)
        _arm(monkeypatch, {"decode.step": {"action": "nan", "at": 1}})
        eng = _engine(built)
        eng.generate(prompts, N_NEW)
        stats = eng.stats()
        assert stats["health_events"] >= 1
        assert any("decode.cache" in k for k in stats["health"])


# --------------------------------------------------------------------------- #
# chaos job: the whole module re-runs under an ambient plan; this class
# restores it and asserts only plan-agnostic invariants
# --------------------------------------------------------------------------- #

class TestAmbientChaos:
    @pytest.mark.skipif(AMBIENT_PLAN is None, reason="no ambient fault plan")
    def test_pipeline_survives_ambient_plan(self, built, monkeypatch, tmp_path):
        """Under ANY canned plan the stack must produce finite results, a
        loadable checkpoint trail, or die with a clean DeviceLost — never
        wrong numerics, never a corrupt latest checkpoint."""
        monkeypatch.setenv(faults.ENV_PLAN, AMBIENT_PLAN)
        faults.reset()
        op, sk = _kernel_fixture()
        prompts = _prompts(built)
        try:
            C = op.sketch_cols(sk, use_kernel=True)
            assert bool(jnp.all(jnp.isfinite(C)))
            eng = _engine(built, str(tmp_path))
            toks, _ = eng.generate(prompts, N_NEW, request_id="r")
            assert np.all((toks >= 0) & (toks < built[0].vocab_size))
        except faults.DeviceLost:
            pass  # a preemption plan may kill the attempt — that IS the contract
        # whatever happened, the checkpoint directory must never hold a
        # corrupt LATEST step: either nothing was committed or it restores
        faults.reset()
        monkeypatch.delenv(faults.ENV_PLAN)
        req = tmp_path / "r"
        steps = ckpt.committed_steps(str(req))
        if steps:
            eng2 = _engine(built, str(tmp_path))
            toks, _ = eng2.generate(prompts, N_NEW, request_id="r")
            assert toks.shape == (B, N_NEW)
