"""Tiny shared helpers (no module-level jax import — safe to import from
anywhere)."""
from __future__ import annotations

import os
import pathlib

# the checkout root (src/repro/util.py → ../..)
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[2]
_FALSY = ("0", "false", "False", "FALSE", "off", "no")


def env_flag(name: str, default: bool) -> bool:
    """Tri-state boolean env override: unset → default, else truthiness."""
    env = os.environ.get(name)
    if env is None:
        return default
    return env not in _FALSY


def compile_cache_dir() -> str:
    """Where the entry points keep JAX's persistent compilation cache:
    ``JAX_COMPILATION_CACHE_DIR`` where it is set, else ``.jax_cache/`` at
    the checkout root — a fixed path, because the path is part of the key."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_ROOT / ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache at ``compile_cache_dir()``
    and return it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and nothing else is set.  Entry points (``chip_smoke.py``,
    ``benchmarks/run.py``, ``repro.launch.serve``/``train``) call this
    before compiling; library code and tests never do."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
