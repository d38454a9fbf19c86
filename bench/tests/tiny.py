"""Tiny cells for the CPU tests: the real manifest's metrics, small sizes."""
from __future__ import annotations

import copy
import json

from bench import harness

KRR_CONFIG = {
    "name": "krr-tiny", "runner": "krr", "n_train": 3000, "n_test": 400, "p": 8,
    "kernel": "gaussian", "bandwidth": 2.0, "sketch_d": 64, "sketch_m": 4,
    "contractions": "highest", "controls": ["high", "bf16"],
}
KRR_TRAFFIC = {
    "lams": [1e-5, 1e-4], "arrival": {"kind": "backlog"}, "trace_seconds": 1,
    "check": {"jobs": 2, "limits": {"solve_residual": 5e-5, "fitted_error": 5e-5,
                                    "predict_error": 5e-5, "fitted_excess": 1.0,
                                    "predict_excess": 1.0}},
}
LM_CONFIG = {
    "name": "lm-tiny", "runner": "lm_serve",
    "model": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab_size": 512, "rope_theta": 10000.0,
              "norm_eps": 1e-5},
    "sketch_attn": {"d_slots": 16, "m": 2, "m_r": 2},
    "dtype": "bfloat16", "cache_dtype": "bfloat16", "controls": ["fp8", "attn_bf16"],
}
LM_TRAFFIC = {
    "batch": 2, "prompt_len": 24, "new_tokens": 5, "max_len": 29, "use_sketch": False,
    "arrival": {"kind": "backlog"}, "trace_seconds": 1,
    "check": {"requests": 2, "limit": 0.005},
}


def manifest() -> dict:
    return json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def cell(name: str, config: dict, traffic: dict, seed: int = 2**33 + 7,
         **traffic_overrides) -> harness.Cell:
    """A tiny cell that reports what the manifest's cell ``name`` reports."""
    m = manifest()
    t = copy.deepcopy(traffic)
    t.update(traffic_overrides)
    return harness.Cell(
        name=name, chips=1, config=copy.deepcopy(config), traffic=t, seed=seed,
        end_to_end=[x for x in m["end_to_end"] if harness._reports(x, name)],
        per_layer=[x for x in m["per_layer"] if harness._reports(x, name)])


def run(c: harness.Cell, seconds: float = 1.0, trace: bool = False) -> dict:
    import jax

    return harness.run_cell(c, seconds, trace, devices=jax.devices()[:1],
                            peaks=harness.read_json(harness.BENCH / "peaks.json")
                            ["TPU v5 lite"], log=lambda m: None)
