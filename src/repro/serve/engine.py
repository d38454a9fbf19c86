"""Serving engine: batched prefill + decode with exact or AccumSketch caches.

The sketched cache (paper technique) makes per-request memory independent of
context length — the long_500k production shape decodes against d_slots
landmark slots instead of a 500k-entry KV cache.

Request lifecycle (each phase is ONE jitted dispatch; the programs are
named `jit_prefill_with_cache` and `jit__decode_scan`):

  prefill  — `prefill_with_cache`: all L prompt tokens in a single chunked
             forward with a bulk cache write (exact: dynamic_update_slice;
             sketched: one vectorized segment-sum scatter, bitwise-identical
             to the token-by-token loop's cache);
  decode   — a `lax.scan` of exactly n_new - 1 `decode_step`s (the first
             output token is sampled from the prefill logits, so an n-token
             request runs n - 1 steps — the seed ran n and threw the last
             away).

Slot draws and temperature sampling use independent counter-based RNG streams
(`fold_in(fold_in(key, tag), pos)`); the seed derived both from
`fold_in(key, pos)`, correlating cache placement with sampled tokens.

Resilience (see docs/resilience.md):

* With `ckpt_dir` set and a `request_id` passed to `generate()`, the decode
  loop runs in chunks of `ckpt_every` steps and checkpoints
  (cache, emitted tokens) after each chunk. Because every random draw is a
  pure function of (seed, position-counter), the snapshot plus the emitted
  count IS the full RNG-stream + slot-schedule state — a generate() killed
  mid-decode and resumed in a fresh process emits bitwise-identical tokens.
* With `health_check` on, the cache is screened for non-finite values / mass
  underflow between chunks (eager, OUTSIDE the jitted scan — the scan itself
  gains no host syncs, pinned by the `engine_decode*` trace contracts). A
  poisoned sketched cache degrades to exact attention by re-prefilling the
  emitted history; the event lands in `Engine.health`, never silently.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.streams import SAMPLE_STREAM as _SAMPLE_STREAM
from repro.analysis.streams import SLOT_STREAM as _SLOT_STREAM
from repro.checkpoint import ckpt
from repro.configs.base import ModelConfig
from repro.core.sketched_attention import SketchCache, decode_slot_table, decode_slots
from repro.models.model import (
    DecodeCache,
    decode_step,
    init_cache,
    prefill_with_cache,
)
from repro.resilience import faults
from repro.resilience.degrade import HealthReport
from repro.spans import span

PyTree = Any

# distinct fold_in tags (registered in repro.analysis.streams) so slot draws
# and sampling draws are independent streams off the same seed (both are then
# folded with the position counter)


def _prompt_digest(prompts: np.ndarray) -> str:
    a = np.ascontiguousarray(np.asarray(prompts))
    return hashlib.sha256(a.tobytes() + str(a.shape).encode()).hexdigest()[:16]


@dataclasses.dataclass
class ServeConfig:
    """Engine knobs (cache flavor, sampling, slot-draw scheme, resilience).

    `slot_scheme` selects the streaming sampling scheme for sketched-cache
    slot draws ("uniform" | "poisson" — see `decode_slots`). `cache_dtype`
    applies to both exact KV caches and the sketched k/v slot accumulators
    (mass stays f32). When `max_len <= cfg.sketch_attn.d_slots` the slot draw
    degrades to the identity and sketched decode is exact attention.

    Resilience knobs: `ckpt_dir` + a `request_id` arm per-request
    checkpoint/resume, `ckpt_every` sets the decode chunk between snapshots
    (0 → one chunk, checkpoint only at the end), `keep_last` bounds retained
    history, `health_check` screens the cache between chunks."""

    max_len: int = 2048
    use_sketch: bool = False
    temperature: float = 0.0        # 0 → greedy
    seed: int = 0
    slot_scheme: str = "uniform"
    cache_dtype: Any = jnp.bfloat16
    ckpt_dir: str | None = None
    ckpt_every: int = 0
    keep_last: int = 3
    health_check: bool = True


def _slots(cfg: ModelConfig, sc: ServeConfig, slot_key, pos) -> jax.Array:
    sa = cfg.sketch_attn
    return decode_slots(
        slot_key, pos, sa.d_slots, sa.m_r,
        scheme=sc.slot_scheme, max_len=sc.max_len,
    )


@jax.named_scope("sample")
def _sample(sc: ServeConfig, sample_key, logits: jax.Array, pos) -> jax.Array:
    if sc.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = jax.random.fold_in(sample_key, pos)  # rng-stream: sample-position
    return jax.random.categorical(k, logits / sc.temperature).astype(jnp.int32)


def _decode_scan(
    cfg: ModelConfig, sc: ServeConfig, slot_key, sample_key, params, cache,
    tok0, pos0, *, n_steps: int, use_sketch: bool | None = None,
):
    """n_steps decode steps + samples as one jitted `lax.scan` dispatch.

    `use_sketch` (static) overrides the engine default so a degraded
    request can continue on the exact-attention path."""
    if use_sketch is None:
        use_sketch = sc.use_sketch

    def _body(carry, _):
        cache, tok, pos = carry
        logits, cache = decode_step(
            params, cache, tok, pos, cfg,
            slots=_slots(cfg, sc, slot_key, pos), use_sketch=use_sketch,
        )
        nxt = _sample(sc, sample_key, logits, pos + 1)
        return (cache, nxt, pos + 1), nxt

    (cache, _, _), toks = jax.lax.scan(
        _body, (cache, tok0, pos0), None, length=n_steps
    )
    return jnp.swapaxes(toks, 0, 1), cache


def _prefill_program(cfg: ModelConfig, params, cache, tokens, slot_table):
    return prefill_with_cache(params, tokens, cfg, cache, slot_table=slot_table)


def _step_program(cfg: ModelConfig, use_sketch: bool, params, cache, tok, pos, slots):
    return decode_step(params, cache, tok, pos, cfg, slots=slots, use_sketch=use_sketch)


def _program(name: str, fn, *args):
    """``fn`` with ``args`` bound, under ``name``: the name of its jitted
    program is ``jit_<name>`` (a bare partial's is ``jit__unknown``), which
    the profiler's trace shows.  A partial, not a bound method or a closure
    over the engine: either would hold the engine, and through it the
    weights, in a reference cycle that outlives `del engine` until the next
    cyclic collection."""
    f = functools.partial(fn, *args)
    f.__name__ = name
    return f


class Engine:
    """Single-host engine; the sharded variant jits with in_shardings from
    repro.sharding (see launch/serve.py)."""

    def __init__(self, cfg: ModelConfig, params: PyTree, sc: ServeConfig):
        self.cfg, self.params, self.sc = cfg, params, sc
        self.health = HealthReport()
        self.key = jax.random.PRNGKey(sc.seed)
        self._slot_key = jax.random.fold_in(self.key, _SLOT_STREAM)
        self._sample_key = jax.random.fold_in(self.key, _SAMPLE_STREAM)
        self._requests = 0      # the `request` id of the engine's spans
        self._step = jax.jit(
            _program("decode_step", _step_program, cfg, sc.use_sketch))
        self._prefill = jax.jit(
            _program("prefill_with_cache", _prefill_program, cfg))
        # the cache is donated: the scan's carry reuses its buffers, so one
        # copy of the cache is resident, not two (at stablelm-3b width, B=4
        # and a 2k context the second copy does not fit a 16 GB chip)
        self._decode = jax.jit(
            _program("_decode_scan", _decode_scan, cfg, sc, self._slot_key,
                     self._sample_key),
            static_argnames=("n_steps", "use_sketch"),
            donate_argnums=(1,),
        )

    def new_cache(self, batch: int, use_sketch: bool | None = None) -> DecodeCache:
        """Fresh decode cache (exact KV or sketched per `sc.use_sketch`;
        `use_sketch` overrides — the degradation/resume paths build exact
        caches from a sketched engine)."""
        if use_sketch is None:
            use_sketch = self.sc.use_sketch
        return init_cache(
            self.cfg, batch, self.sc.max_len, self.sc.cache_dtype,
            use_sketch=use_sketch,
        )

    def stats(self) -> dict:
        """Engine health surface: degradation/resume events recorded so far."""
        return {"health_events": self.health.count(), "health": self.health.summary()}

    def _slots(self, pos) -> jax.Array:
        return _slots(self.cfg, self.sc, self._slot_key, pos)

    def _slot_table(self, length: int) -> jax.Array:
        sa = self.cfg.sketch_attn
        return decode_slot_table(
            self._slot_key, length, sa.d_slots, sa.m_r,
            scheme=self.sc.slot_scheme, max_len=self.sc.max_len,
        )

    def prefill_tokens(
        self, cache: DecodeCache, prompts: np.ndarray
    ) -> tuple[DecodeCache, jax.Array]:
        """Batched one-dispatch prefill of all L prompt tokens (positions
        0..L-1). prompts: (B, L). Returns (cache, last-position logits)."""
        tokens = jnp.asarray(prompts)
        table = self._slot_table(tokens.shape[1]) if self.sc.use_sketch else None
        logits, cache = self._prefill(self.params, cache, tokens, table)
        return cache, logits

    def prefill_tokens_sequential(
        self, cache: DecodeCache, prompts: np.ndarray
    ) -> tuple[DecodeCache, jax.Array]:
        """Token-by-token decode-mode prefill (L jitted dispatches) — the
        pre-batched path, kept as the equivalence oracle for tests and the
        baseline for `benchmarks/attention_bench.py`. prompts: (B, L)."""
        logits = None
        for t in range(prompts.shape[1]):
            logits, cache = self._step(
                self.params, cache, jnp.asarray(prompts[:, t]), jnp.int32(t),
                self._slots(t),
            )
        return cache, logits

    def _decode_scan(
        self, params, cache, tok0, pos0, *, n_steps: int, use_sketch: bool | None = None
    ):
        return _decode_scan(
            self.cfg, self.sc, self._slot_key, self._sample_key, params, cache,
            tok0, pos0, n_steps=n_steps, use_sketch=use_sketch,
        )

    # ---------------------------------------------------------------- resume

    def _request_extra(self, prompts, use_sketch: bool, n_emitted: int) -> dict:
        return {
            "prompt_sha": _prompt_digest(prompts),
            "seed": self.sc.seed,
            "slot_scheme": self.sc.slot_scheme,
            "max_len": self.sc.max_len,
            "temperature": self.sc.temperature,
            "use_sketch": bool(use_sketch),
            "n_emitted": int(n_emitted),
        }

    def _save_request(self, ckdir: str, cache, toks_done, use_sketch, prompts) -> None:
        ckpt.save(
            ckdir,
            {"cache": cache, "toks": np.asarray(toks_done, np.int32)},
            step=int(toks_done.shape[1]),
            extra=self._request_extra(prompts, use_sketch, toks_done.shape[1]),
            keep_last=self.sc.keep_last,
        )

    def _try_resume(self, ckdir: str, prompts: np.ndarray):
        """Load the newest usable request checkpoint, validating that it was
        written for this exact (prompts, seed, scheme, max_len, temperature)
        — anything else would break the bitwise guarantee, so a mismatch
        raises instead of silently generating different tokens. A corrupt
        newest step falls back to the prior one (health-recorded)."""
        B = prompts.shape[0]
        steps = ckpt.committed_steps(ckdir)
        digest = _prompt_digest(prompts)
        for i, s in enumerate(steps):
            try:
                extra = ckpt.read_meta(ckdir, s)["extra"]
            except Exception as e:  # noqa: BLE001 — unreadable meta == corrupt step
                self._record_skip(steps, i, e)
                continue
            fields = ("seed", "slot_scheme", "max_len", "temperature")
            want = self._request_extra(prompts, extra.get("use_sketch", False), 0)
            if extra.get("prompt_sha") != digest or any(
                extra.get(f) != want[f] for f in fields
            ):
                raise ValueError(
                    f"checkpoint {ckdir}/step_{s} was written for a different "
                    "request or engine config; refusing to resume (the bitwise "
                    "guarantee would not hold)"
                )
            use_sketch = bool(extra.get("use_sketch", self.sc.use_sketch))
            like = {
                "cache": self.new_cache(B, use_sketch=use_sketch),
                "toks": np.zeros((B, 1), np.int32),
            }
            try:
                state, _ = ckpt.restore(ckdir, like, step=s)
            except Exception as e:  # noqa: BLE001 — corrupt payload: try step N−1
                self._record_skip(steps, i, e)
                continue
            cache = jax.tree_util.tree_map(jnp.asarray, state["cache"])
            toks = np.asarray(state["toks"], np.int32)
            self.health.record(
                "ckpt.resume", rung_from="cold", rung_to=f"step_{s}",
                detail=f"resumed with {toks.shape[1]} tokens emitted",
            )
            return cache, toks, use_sketch
        return None

    def _record_skip(self, steps, i, err) -> None:
        nxt = f"step_{steps[i + 1]}" if i + 1 < len(steps) else "none"
        self.health.record(
            "ckpt.restore", rung_from=f"step_{steps[i]}", rung_to=nxt, detail=repr(err)
        )

    # ------------------------------------------------------------ health

    def _cache_bad(self, cache, use_sketch: bool) -> str:
        """Screen the cache between decode chunks (ONE host read, outside the
        jitted scan). Returns a reason string, or "" when healthy."""
        bad = jnp.zeros((), jnp.int32)
        for leaf in jax.tree_util.tree_leaves(cache):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                bad = bad + jnp.sum(~jnp.isfinite(leaf)).astype(jnp.int32)
        n_bad = int(bad)
        if n_bad:
            return f"{n_bad} non-finite cache entries"
        if use_sketch:
            nodes = jax.tree_util.tree_flatten(
                cache.blocks, is_leaf=lambda x: isinstance(x, SketchCache)
            )[0]
            mins = [
                jnp.min(jnp.sum(n.mass.astype(jnp.float32), axis=-1))
                for n in nodes
                if isinstance(n, SketchCache)
            ]
            if mins and float(jnp.min(jnp.stack(mins))) <= 0.0:
                return "sketched cache mass underflow"
        return ""

    def _rebuild_exact(self, prompts: np.ndarray, toks_done: np.ndarray) -> DecodeCache:
        """Exact-attention degrade: re-prefill prompt + emitted history into a
        fresh exact KV cache (generalizes the `max_len <= d_slots` identity
        path — correctness is preserved, only the flat-memory property is
        given up for this request)."""
        hist = np.concatenate(
            [np.asarray(prompts), np.asarray(toks_done[:, :-1])], axis=1
        )
        cache = self.new_cache(prompts.shape[0], use_sketch=False)
        _, cache = self._prefill(self.params, cache, jnp.asarray(hist), None)
        return cache

    # ---------------------------------------------------------------- serve

    def generate(
        self, prompts: np.ndarray, n_new: int, *, request_id: str | None = None
    ) -> tuple[np.ndarray, DecodeCache]:
        """Prefill `prompts` (B, L) and generate n_new tokens per sequence.

        Token 0 is sampled from the prefill logits; the scan then runs exactly
        n_new - 1 decode steps (each producing the next token), so no model
        forward's outputs are ever discarded. Returns ((B, n_new), cache).

        With `sc.ckpt_dir` set and a `request_id`, progress is checkpointed
        every `sc.ckpt_every` emitted tokens and an interrupted request
        resumes from <ckpt_dir>/<request_id> with bitwise-identical output
        (every slot draw and sample is a pure function of (seed, position),
        so cache + emitted tokens IS the complete resume state).

        In the profiler's trace the request is a `repro.engine.generate`
        span holding one span per phase, all with the same `request` stat
        (docs/architecture.md, Tracing)."""
        self._requests += 1
        rid = self._requests
        with span("engine.generate", request=rid):
            return self._generate(prompts, n_new, request_id, rid)

    def _generate(self, prompts, n_new: int, request_id, rid: int):
        B, L = prompts.shape
        use_sketch = self.sc.use_sketch
        ckdir = (
            os.path.join(self.sc.ckpt_dir, str(request_id))
            if self.sc.ckpt_dir and request_id is not None
            else None
        )
        resumed = None
        if ckdir:
            with span("engine.checkpoint", request=rid):
                resumed = self._try_resume(ckdir, prompts)
        if resumed is not None:
            cache, toks_done, use_sketch = resumed
        else:
            with span("engine.prefill", request=rid):
                cache = self.new_cache(B)
                cache, logits = self.prefill_tokens(cache, prompts)
            # token 0 on the host: the time to first token ends here
            with span("engine.first_token", request=rid):
                tok = self._sample(logits, jnp.int32(L))
                toks_done = np.asarray(tok)[:, None]
            if ckdir:
                with span("engine.checkpoint", request=rid):
                    self._save_request(ckdir, cache, toks_done, use_sketch, prompts)
        while toks_done.shape[1] < n_new:
            emitted = toks_done.shape[1]
            remaining = n_new - emitted
            chunk = (
                remaining if self.sc.ckpt_every <= 0
                else min(self.sc.ckpt_every, remaining)
            )
            # fault site: one arrival per decode dispatch ("kill" dies here;
            # "nan"/"inf"/"zero" poison the cache the health screen must catch)
            cache = faults.poison("decode.step", cache)
            if self.sc.health_check:
                with span("engine.health_check", request=rid):
                    reason = self._cache_bad(cache, use_sketch)
                if reason:
                    self.health.record(
                        "decode.cache",
                        rung_from="sketched" if use_sketch else "exact",
                        rung_to="exact-rebuild",
                        detail=reason,
                    )
                    cache = self._rebuild_exact(prompts, toks_done)
                    use_sketch = False
            with span("engine.decode", request=rid, steps=chunk):
                toks, cache = self._decode(
                    self.params, cache, jnp.asarray(toks_done[:, -1]),
                    jnp.int32(L + emitted - 1), n_steps=chunk, use_sketch=use_sketch,
                )
                toks_done = np.concatenate([toks_done, np.asarray(toks)], axis=1)
            if ckdir:
                with span("engine.checkpoint", request=rid):
                    self._save_request(ckdir, cache, toks_done, use_sketch, prompts)
        return toks_done[:, :n_new], cache

    def _sample(self, logits: jax.Array, pos) -> jax.Array:
        return _sample(self.sc, self._sample_key, logits, pos)
