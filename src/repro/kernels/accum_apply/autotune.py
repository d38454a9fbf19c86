"""Measured autotune cache for the accum_apply kernel family.

Block sizes come from a MEASURED cache, with a fixed heuristic in each entry
point (``ops.py``) as the fallback:

  * the first eligible call at a (kernel, shape, dtype, backend) key times the
    candidate tilings once on the caller's real arrays and keeps the winner;
  * winners persist to a JSON cache (``REPRO_AUTOTUNE_CACHE``, default
    ``~/.cache/repro/autotune.json``) so later processes skip the measurement;
  * a corrupt, missing, or unwritable cache degrades to the heuristic (a
    corrupt file is recorded in the global ``HealthReport``);
  * a candidate tiling is skipped only when the compiler refuses it for
    memory (VMEM / RESOURCE_EXHAUSTED); the skip is kept in ``refusals()``.
    Any other failure is a bug in the kernel or its wrapper and propagates,
    and a measurement in which every candidate is refused raises — a
    failing kernel never hides behind the fallback tiling.

Measurement only happens when it can be meaningful:

  * the entry point's arrays must be CONCRETE (under ``jit`` tracing the
    inputs are tracers and nothing can be timed — the cache/heuristic answer is
    used instead, so jitted callers compile against the persisted winner);
  * ``REPRO_AUTOTUNE`` gates it (default: on for compiled TPU kernels, off in
    interpret mode, where timings measure the interpreter's dispatch, not the
    tiling — benchmarks force it on explicitly for the cold/warm numbers).

All reads go through ``os.environ`` at call time so tests can monkeypatch the
cache location and the gate without reloads.
"""
from __future__ import annotations

import json
import os
import pathlib
import time

import jax

from repro.util import env_flag

ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
ENV_GATE = "REPRO_AUTOTUNE"

# messages of a compile refusal for memory — the one failure a candidate
# tiling may be skipped for
_REFUSAL_MARKS = ("RESOURCE_EXHAUSTED", "Ran out of memory")

# in-memory mirror of the JSON file, keyed by cache path so tests that
# repoint REPRO_AUTOTUNE_CACHE never see another file's entries
_MEM: dict[str, dict[str, list[int]]] = {}
# (kind, shape_key, blocks, message) of every candidate refused for memory
_REFUSALS: list[tuple[str, tuple, tuple, str]] = []


def cache_path() -> pathlib.Path:
    env = os.environ.get(ENV_CACHE)
    if env:
        return pathlib.Path(env)
    return pathlib.Path.home() / ".cache" / "repro" / "autotune.json"


def measure_enabled() -> bool:
    """Measure by default only where timings are meaningful: compiled TPU.
    Interpret-mode timings rank interpreter dispatch, not tilings.
    Override with REPRO_AUTOTUNE=0/1."""
    return env_flag(ENV_GATE, jax.default_backend() == "tpu")


def _load(path: pathlib.Path) -> dict[str, list[int]]:
    key = str(path)
    if key in _MEM:
        return _MEM[key]
    from repro.resilience import faults

    entries: dict[str, list[int]] = {}
    try:
        faults.fault_point("autotune.load")  # simulated unreadable cache file
        raw = json.loads(path.read_text())
        # validate hard: a corrupt cache must fall back, not crash
        if isinstance(raw, dict):
            for k, v in raw.items():
                if (isinstance(k, str) and isinstance(v, list)
                        and all(isinstance(x, int) and x > 0 for x in v)):
                    entries[k] = v
    except FileNotFoundError:
        entries = {}  # a missing cache is the normal cold start, not a fault
    except faults.DeviceLost:
        raise  # simulated preemption is fatal, not a degradation
    except (OSError, ValueError, faults.FaultInjected) as e:
        # corrupt/unreadable cache: fall back to the heuristic — but
        # recorded, not silent (a fleet quietly losing its tunings is an
        # operational smell worth surfacing)
        from repro.resilience.degrade import global_health

        entries = {}
        global_health().record(
            "autotune.load", rung_from="measured-cache", rung_to="heuristic",
            detail=repr(e),
        )
    _MEM[key] = entries
    return entries


def _store(path: pathlib.Path, entries: dict[str, list[int]]) -> None:
    """Best-effort atomic persist; an unwritable cache dir is not an error."""
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(path.name + f".tmp{os.getpid()}")
        tmp.write_text(json.dumps(entries, indent=2, sort_keys=True) + "\n")
        tmp.replace(path)
    except OSError:
        pass


def _key(kind: str, shape_key: tuple, dtype, interpret: bool) -> str:
    backend = jax.default_backend() + ("/interpret" if interpret else "")
    parts = [kind, *map(str, shape_key), jax.numpy.dtype(dtype).name, backend]
    return "|".join(parts)


def lookup(kind: str, shape_key: tuple, dtype, interpret: bool,
           arity: int | None = None) -> tuple[int, ...] | None:
    """The persisted winner for this key, or None (missing/corrupt cache).
    ``arity`` rejects entries of the wrong length — a hand-edited or
    stale-schema entry must fall back, not crash the caller's unpack."""
    entry = _load(cache_path()).get(_key(kind, shape_key, dtype, interpret))
    if not entry or (arity is not None and len(entry) != arity):
        return None
    return tuple(entry)


def record(kind: str, shape_key: tuple, dtype, interpret: bool,
           blocks: tuple[int, ...]) -> None:
    path = cache_path()
    entries = dict(_load(path))
    entries[_key(kind, shape_key, dtype, interpret)] = [int(b) for b in blocks]
    _MEM[str(path)] = entries
    _store(path, entries)


def refusals() -> list[tuple[str, tuple, tuple, str]]:
    """Candidate tilings skipped because the compiler refused them for
    memory: ``(kind, shape_key, blocks, first line of the error)``."""
    return list(_REFUSALS)


def _is_memory_refusal(e: Exception) -> bool:
    return any(mark in str(e) for mark in _REFUSAL_MARKS)


def _time_once(fn) -> float:
    """One warmup (compile) + one timed rep."""
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def measured_blocks(
    kind: str, shape_key: tuple, dtype, interpret: bool,
    candidates: list[tuple[int, ...]], bench_fn, fallback: tuple[int, ...],
    concrete: bool,
) -> tuple[int, ...]:
    """The autotune decision for one kernel call site.

    Resolution order: persisted/measured cache hit → (if ``concrete`` inputs
    and the gate allows) time ``bench_fn(blocks)`` for each candidate once,
    persist and return the winner → ``fallback`` (the heuristic answer).
    ``bench_fn`` runs the caller's actual kernel on its actual arrays, so the
    measurement is of the real workload.  A candidate the compiler refuses
    for memory is skipped (``refusals()``); any other exception propagates,
    and so does the refusal when no candidate is left."""
    hit = lookup(kind, shape_key, dtype, interpret, arity=len(fallback))
    if hit is not None:
        return hit
    if not concrete or not measure_enabled() or not candidates:
        return fallback
    candidates = list(dict.fromkeys(candidates))
    timings, refusal = [], None
    for c in candidates:
        try:
            timings.append((_time_once(lambda c=c: bench_fn(c)), c))
        except Exception as e:  # noqa: BLE001 — re-raised unless a memory refusal
            if not _is_memory_refusal(e):
                raise
            refusal = e
            _REFUSALS.append((kind, tuple(shape_key), tuple(c),
                              str(e).strip().split("\n")[0]))
    if not timings:
        raise RuntimeError(
            f"autotune {kind} {shape_key}: the compiler refused every "
            f"candidate tiling {candidates}") from refusal
    _, best = min(timings, key=lambda tc: tc[0])
    record(kind, shape_key, dtype, interpret, best)
    return best


def is_concrete(*arrays) -> bool:
    """True iff no argument is a tracer — the only situation where timing the
    kernel on the caller's arrays is possible."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)
