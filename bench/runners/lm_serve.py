"""Serving runner: requests through the system's ``Engine.generate``.

Configuration (``bench/configs/<config>.json``): ``model`` (sizes),
``sketch_attn``, ``dtype``, ``cache_dtype`` and the ``controls`` that
``bench/reference/lm.py`` runs in the reference's place.  Traffic (``bench/traffic/<cell>.json``):
``batch`` sequences of ``prompt_len`` random tokens per request,
``new_tokens`` greedy tokens each, the cache's ``max_len``, ``use_sketch``,
the ``arrival`` schedule, and ``check``: how many finished requests the
reference re-reads (``requests``) and the ``limit`` on the widest gap by
which a served token's reference logit lies below the reference's best.

Weights come from ``bench/gen/lm_weights.py`` in one program from the seed;
request i's prompts from the seed and i.  Set-up builds the engine and
serves one request of the cell's shape, which compiles every program the
window uses.
"""
from __future__ import annotations

import dataclasses
import gc
from typing import Any

import numpy as np

from bench import harness
from bench.gen import lm_weights
from bench.reference import lm as ref

WEIGHTS, PROMPTS, SAMPLE, WARM = 1, 2, 3, 4


@dataclasses.dataclass
class State:
    cell: harness.Cell
    sizes: dict
    engine: Any = None
    served: list = dataclasses.field(default_factory=list)


def model_config(config: dict):
    """The system's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, SketchAttnCfg

    m = config["model"]
    return ModelConfig(
        name=config["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
        head_dim=m.get("head_dim", 0), d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        pattern=("attn",), n_superblocks=m["n_layers"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"],
        tie_embeddings=False, sketch_attn=SketchAttnCfg(**config["sketch_attn"]))


def program_params(w: dict) -> dict:
    """The benchmark's weight layout as the system's params pytree (the
    same arrays, not copies)."""
    lw = w["layers"]
    return {
        "embed": w["embed"], "lm_head": w["head"], "final_norm": w["final_norm"],
        "blocks": {"pos0": {
            "attn": {"wq": lw["wq"], "wk": lw["wk"], "wv": lw["wv"],
                     "wo": lw["wo"], "norm": lw["attn_norm"]},
            "ffn": {"wi_gate": lw["w_gate"], "wi_up": lw["w_up"],
                    "wo": lw["w_down"], "norm": lw["mlp_norm"]}}},
        "shared": {},
    }


def weights(cell: harness.Cell, s: dict):
    return lm_weights.make(harness.seed_key(cell.seed, WEIGHTS), s,
                           cell.config["dtype"])


def prompts(cell: harness.Cell, i: int, tag: int = PROMPTS) -> np.ndarray:
    t = cell.traffic
    return harness.seed_rng(cell.seed, tag, i).integers(
        0, cell.config["model"]["vocab_size"], (t["batch"], t["prompt_len"]),
        dtype=np.int32)


def setup(cell: harness.Cell) -> State:
    import jax

    from repro.serve.engine import Engine, ServeConfig

    t = cell.traffic
    s = lm_weights.sizes(cell.config["model"])
    params = program_params(jax.block_until_ready(weights(cell, s)))
    engine = Engine(model_config(cell.config), params, ServeConfig(
        max_len=t["max_len"], use_sketch=t["use_sketch"],
        cache_dtype=cell.config["cache_dtype"]))
    engine.generate(prompts(cell, 0, WARM), t["new_tokens"])
    return State(cell=cell, sizes=s, engine=engine)


def measure(state: State, win: harness.Window) -> None:
    from repro.resilience.degrade import global_health

    cell, eng = state.cell, state.engine
    t = cell.traffic
    for i in win.arrivals():
        with harness.span("request"):
            p = prompts(cell, i)
            before = eng.health.count() + global_health().count()
            with harness.span("generate"):
                toks, cache = eng.generate(p, t["new_tokens"])
            del cache
        dropped = eng.health.count() + global_health().count() - before
        win.counters["dropped"] += dropped
        win.counters["prefill_tokens"] += p.size
        win.counters["decode_steps"] += t["new_tokens"] - 1
        win.record(tokens=toks.size, ok=dropped == 0)
        state.served.append(toks)


def readings(state: State, win: harness.Window, control: bool = False) -> dict:
    """Widest served-token gap over the sampled requests, and with
    ``control`` the same gap for the tokens that each of the
    configuration's ``controls`` puts first (``control.<name>.*``)."""
    cell, t = state.cell, state.cell.traffic
    state.engine = None
    gc.collect()
    if not state.served:
        return {"served_logit_gap": float("inf")}
    w = weights(cell, state.sizes)
    k = min(int(t["check"]["requests"]), len(state.served))
    sample = harness.seed_rng(cell.seed, SAMPLE).choice(len(state.served), k,
                                                        replace=False)
    L, n = t["prompt_len"], t["new_tokens"]
    rows = np.arange(L - 1, L - 1 + n)
    controls = cell.config["controls"] if control else []
    out = {"served_logit_gap": 0.0,
           **{f"control.{c}.served_logit_gap": 0.0 for c in controls}}
    for i in sorted(sample):
        p, toks = prompts(cell, int(i)), state.served[int(i)]
        if toks.shape != (t["batch"], n):
            return {"served_logit_gap": float("inf")}
        for b in range(t["batch"]):
            seq = np.concatenate([p[b], toks[b, :-1]])
            lg = ref.logits(w, seq, rows, cell.config["model"])
            got = {"served_logit_gap": toks[b]}
            for c in controls:
                lq = ref.logits(w, seq, rows, cell.config["model"], control=c)
                got[f"control.{c}.served_logit_gap"] = np.asarray(lq).argmax(axis=1)
            for name, served in got.items():
                out[name] = max(out[name], float(ref.served_gaps(lg, served).max()))
    return out


def check(state: State, win: harness.Window) -> list[harness.Check]:
    r = readings(state, win)
    return [harness.Check("served_logit_gap", r["served_logit_gap"],
                          float(state.cell.traffic["check"]["limit"]))]
