"""The benchmark's own tests run on the CPU at tiny sizes:
``python -m pytest bench/tests`` from the checkout root."""
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
