"""Named spans and scopes of the program in the profiler's trace.

``span(name, **ids)`` is a host span ``repro.<name>``: it costs about a
microsecond when no trace is being taken, and its keyword ids become the
event's stats, not part of its name.  ``phase(name)`` opens the host span
and a device scope ``name`` (``jax.named_scope``) together; the scope lands
in the op metadata of whatever is traced inside it under ``jit``.  The
names, and the metrics that read them: ``docs/architecture.md`` (Tracing).
"""
from __future__ import annotations

import contextlib

import jax

PREFIX = "repro."


def span(name: str, **ids):
    """Host span ``repro.<name>`` with ``ids`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


@contextlib.contextmanager
def phase(name: str, **ids):
    """Host span ``repro.<name>`` and device scope ``name``."""
    with span(name, **ids), jax.named_scope(name):
        yield
