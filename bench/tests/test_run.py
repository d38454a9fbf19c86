"""A whole run of each runner on the CPU at a tiny size: set-up, window,
metrics and the comparison with the reference, as ``bench/run.py`` drives
them on the chip."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests import tiny


@pytest.mark.parametrize("trace", [False, True])
def test_krr_cell(trace):
    r = tiny.run(tiny.cell("krr-msd.fit", tiny.KRR_CONFIG, tiny.KRR_TRAFFIC), 1.0, trace)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["checks"]) == set(tiny.KRR_TRAFFIC["check"]["limits"])
    if trace:
        assert "busy_s" in r["device"] and "breakdown" in r
        # the CPU has no device plane: the readers of the trace find nothing
        assert "idle.fit" not in r["metrics"]
        assert r["metrics"]["mfu.fit"]["value"] > 0
    else:
        assert set(r["metrics"]) == {"setup_s", "job_ms"}
        assert r["metrics"]["job_ms"]["value"] > 0


@pytest.mark.parametrize("cell,overrides", [
    ("stablelm-3b.decode", {}),
    ("stablelm-3b.prefill", {"batch": 1, "new_tokens": 1, "max_len": 24}),
])
def test_lm_cell(cell, overrides):
    r = tiny.run(tiny.cell(cell, tiny.LM_CONFIG, tiny.LM_TRAFFIC, **overrides))
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    e2e = {"stablelm-3b.decode": "tokens_per_s",
           "stablelm-3b.prefill": "prefill_tokens_per_s"}
    assert set(r["metrics"]) == {"setup_s", e2e[cell]}
    assert r["metrics"][e2e[cell]]["value"] > 0


def test_backlog_window_starts_each_item_as_the_last_ends_until_it_closes():
    w = harness.Window(0.2, {"kind": "backlog"})
    for i in w.arrivals():
        time.sleep(0.03)
        w.record(tokens=2)
        assert w.items[i].start >= w.items[i - 1].end if i else True
    # the last item started while the window was open and ended after it
    assert len(w.items) >= 2 and w.items[-1].start < 0.2 <= w.items[-1].end
    assert w.elapsed >= w.items[-1].end
    assert w.items[0].host["user"] >= 0 and "gc" in w.items[0].host
    with pytest.raises(ValueError):
        harness.Window(1.0, {"kind": "fixed_rate"})


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "krr-msd.fit",
                        "--seed", "3", "--seconds", "1", "--trace", "0"],
                       cwd=harness.ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_run_without_the_system_under_test_exits_nonzero(tmp_path):
    import shutil

    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "krr-msd.fit",
                        "--seed", "3", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()
