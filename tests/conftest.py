import numpy as np
import pytest

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# real single CPU device; only launch/dryrun.py forces 512 placeholder devices.


@pytest.fixture(autouse=True)
def _autotune_isolation(tmp_path, monkeypatch):
    """Point the measured autotune cache at a per-test throwaway file: tests
    asserting heuristic block sizes must not read (or write) the user's
    persisted ~/.cache/repro/autotune.json.  Tests that exercise the cache
    explicitly monkeypatch their own path on top of this."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
