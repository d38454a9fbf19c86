"""Graceful-degradation ladder + structured health reporting.

A *ladder* is an ordered list of implementations of the same computation,
fastest first: Pallas kernel → XLA ``lax.scan`` path → dense oracle.  When a
rung raises an injected fault (:class:`faults.FaultInjected`),
:func:`ladder_call` records the degradation in a :class:`HealthReport` and
falls to the next rung — the result stays correct, only slower, and the event
is surfaced through ``info`` / engine stats.  Every other exception — a
Mosaic compile or lowering error, a shape bug — propagates: the ladder
exists to exercise recovery from faults, never to hide a kernel that does
not run on the device.

For numerics that fail *inside* jitted code (a Cholesky on a non-PSD
matrix), :func:`solve_psd_ladder` runs the whole ladder — escalating ×10
jitter retries, then lstsq — in pure JAX under ``lax.while_loop`` /
``lax.cond``, returning its health record as traced scalars so the jitted
decode/fit path gains **no host syncs** (pinned by the ``solve_psd_ladder``
entry in ``analysis/contracts.toml``).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import cho_factor, cho_solve

from repro.resilience import faults


@dataclasses.dataclass(frozen=True)
class HealthEvent:
    """One recorded degradation: ``site`` fell from ``rung_from`` to ``rung_to``."""

    site: str
    rung_from: str
    rung_to: str
    detail: str = ""


class HealthReport:
    """Thread-safe append-only log of degradation events.

    Engines and module-level consumers record every rung drop here; tests and
    ops dashboards read ``events`` / ``summary()`` to see *that* and *why*
    numerics took a slower path."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events: list[HealthEvent] = []

    def record(self, site: str, *, rung_from: str, rung_to: str, detail: str = "") -> HealthEvent:
        """Append one degradation event and return it."""
        ev = HealthEvent(site, rung_from, rung_to, str(detail))
        with self._lock:
            self._events.append(ev)
        return ev

    @property
    def events(self) -> list[HealthEvent]:
        """Snapshot of all recorded events, oldest first."""
        with self._lock:
            return list(self._events)

    def count(self, site: str | None = None) -> int:
        """Number of events, optionally restricted to one site."""
        return len([e for e in self.events if site is None or e.site == site])

    def summary(self) -> dict[str, int]:
        """Histogram ``{"site: from->to": n}`` — the engine-stats surface."""
        out: dict[str, int] = {}
        for e in self.events:
            key = f"{e.site}: {e.rung_from}->{e.rung_to}"
            out[key] = out.get(key, 0) + 1
        return out

    def clear(self) -> None:
        """Drop all events (tests)."""
        with self._lock:
            self._events.clear()


_GLOBAL = HealthReport()


def global_health() -> HealthReport:
    """The process-wide report used by module-level ladders (apply, autotune,
    checkpoint restore).  ``Engine`` instances keep their own report too."""
    return _GLOBAL


def ladder_call(
    site: str,
    rungs: Sequence[tuple[str, Callable[[], Any]]],
    *,
    health: HealthReport | None = None,
):
    """Run ``rungs`` (``(name, thunk)`` pairs, fastest first) until one
    succeeds or fails with something other than an injected fault.

    ``site`` names the ladder for health records; fault *arrivals* happen
    inside the rungs themselves (the kernel entry points in
    ``kernels/*/ops.py`` visit ``kernel.dispatch``, the streaming rung visits
    ``kernel.stream``), so arming both sites drives a three-rung ladder all
    the way to its dense oracle.  Each drop is recorded in ``health``
    (default: the global report).  Only :class:`faults.FaultInjected` drops
    a rung; the terminal rung's fault, any :class:`faults.DeviceLost` (a
    preemption, not a degradation) and every real exception propagate."""
    hr = health if health is not None else _GLOBAL
    for i, (name, fn) in enumerate(rungs):
        try:
            return fn()
        except faults.FaultInjected as e:
            if i == len(rungs) - 1:
                raise
            hr.record(site, rung_from=name, rung_to=rungs[i + 1][0], detail=repr(e))


def solve_psd_ladder(M, b, *, escalations: int = 3):
    """Solve ``M x = b`` for PSD ``M`` with an in-graph degradation ladder.

    Rungs: Cholesky with base jitter ``j0 = 1e-8·(tr M / d)``; on non-finite
    result escalate the jitter ×10 up to ``escalations`` times under
    ``lax.while_loop``; if still non-finite fall to ``lstsq`` under
    ``lax.cond``.  Everything is traced JAX — no host syncs — and the health
    record comes back as traced scalars:

    returns ``(x, {"solve_escalations": int32, "solve_used_lstsq": bool})``.

    The ``solve.cholesky`` fault site mangles ``M`` on entry (eager calls
    only; tracers pass through), letting fault-plan tests drive both the
    escalation rung (tiny ``scale``) and the lstsq rung (large ``scale``).
    The ladder itself is one jitted program: called eagerly, its loop and
    cond closures would otherwise be traced and compiled again on every
    call (seconds on a TPU, where the lstsq rung is an SVD).
    """
    M = faults.mangle_matrix("solve.cholesky", M)
    return _solve_psd_ladder(M, b, escalations=escalations)


@functools.partial(jax.jit, static_argnames=("escalations",))
def _solve_psd_ladder(M, b, *, escalations: int):
    d = M.shape[0]
    eye = jnp.eye(d, dtype=M.dtype)
    j0 = 1e-8 * (jnp.trace(M) / d + 1e-30)

    def attempt(level):
        c, lo = cho_factor(M + (j0 * 10.0**level) * eye, lower=True)
        x = cho_solve((c, lo), b)
        return x, jnp.all(jnp.isfinite(x))

    x0, ok0 = attempt(jnp.zeros((), M.dtype))

    def cond(carry):
        lvl, _, ok = carry
        return (~ok) & (lvl < escalations)

    def body(carry):
        lvl, _, _ = carry
        lvl = lvl + 1
        x, ok = attempt(lvl.astype(M.dtype))
        return lvl, x, ok

    lvl, x, ok = lax.while_loop(cond, body, (jnp.int32(0), x0, ok0))

    def _lstsq(_):
        rhs = b if b.ndim == 2 else b[:, None]
        sol = jnp.linalg.lstsq(M + j0 * eye, rhs)[0]
        return sol if b.ndim == 2 else sol[:, 0]

    x = lax.cond(ok, lambda _: x, _lstsq, None)
    return x, {"solve_escalations": lvl, "solve_used_lstsq": ~ok}
