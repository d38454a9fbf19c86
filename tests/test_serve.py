"""Serving-layer tests: batched prefill equivalence (bitwise at the scatter
level), the exact decode-step count, RNG stream independence, cache dtype and
memory-footprint invariants, and the identity-slot exactness degradation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serve.engine as engine_mod
from repro.configs import ARCHS, reduced
from repro.configs.base import SketchAttnCfg
from repro.core.sketched_attention import (
    SketchCache,
    decode_slot_table,
    decode_slots,
    init_sketch_cache,
    prefill_sketch_cache,
    update_sketch_cache,
)
from repro.models.attention import KVCache
from repro.models.model import init_cache, init_params
from repro.serve.engine import Engine, ServeConfig

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def built():
    cfg = reduced(ARCHS["stablelm-3b"])
    return cfg, init_params(KEY, cfg)


def _prompts(B, L, vocab, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (B, L), 0, vocab))


def _sketch_leaves(cache):
    flat = jax.tree_util.tree_flatten(
        cache.blocks, is_leaf=lambda n: isinstance(n, (SketchCache, KVCache))
    )[0]
    return [x for x in flat if isinstance(x, SketchCache)]


def _kv_leaves(cache):
    flat = jax.tree_util.tree_flatten(
        cache.blocks, is_leaf=lambda n: isinstance(n, (SketchCache, KVCache))
    )[0]
    return [x for x in flat if isinstance(x, KVCache)]


# --------------------------------------------------------------------------- #
# batched prefill ≡ sequential loop
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("scheme", ["uniform", "poisson"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefill_scatter_bitwise_matches_sequential_fold(scheme, dtype):
    """The one-dispatch vectorized scatter must produce a cache BIT-IDENTICAL
    to folding `update_sketch_cache` token by token (same contraction order:
    token-major, one rounding point per contribution)."""
    B, Hkv, d_slots, m_r, Dh, L = 2, 2, 16, 2, 8, 40
    table = decode_slot_table(KEY, L, d_slots, m_r, scheme=scheme, max_len=999)
    ks = jax.random.split(KEY, 2)
    k_seq = jax.random.normal(ks[0], (B, Hkv, L, Dh), dtype)
    v_seq = jax.random.normal(ks[1], (B, Hkv, L, Dh), dtype)

    seq = init_sketch_cache(B, Hkv, d_slots, Dh, dtype)
    for t in range(L):
        seq = update_sketch_cache(seq, k_seq[:, :, t], v_seq[:, :, t], table[t])
    bat = prefill_sketch_cache(
        init_sketch_cache(B, Hkv, d_slots, Dh, dtype), k_seq, v_seq, table
    )
    np.testing.assert_array_equal(np.asarray(bat.k_sum), np.asarray(seq.k_sum))
    np.testing.assert_array_equal(np.asarray(bat.v_sum), np.asarray(seq.v_sum))
    np.testing.assert_array_equal(np.asarray(bat.mass), np.asarray(seq.mass))


@pytest.mark.parametrize("use_sketch", [False, True])
def test_engine_batched_prefill_matches_sequential(built, use_sketch):
    """Engine-level: one-dispatch prefill ≈ the token-by-token oracle — same
    last-position logits and same cache, both cache flavors."""
    cfg, params = built
    sc = ServeConfig(max_len=48, use_sketch=use_sketch, cache_dtype=jnp.float32)
    eng = Engine(cfg, params, sc)
    prompts = _prompts(2, 33, cfg.vocab_size)
    cache_b, logits_b = eng.prefill_tokens(eng.new_cache(2), prompts)
    cache_s, logits_s = eng.prefill_tokens_sequential(eng.new_cache(2), prompts)
    np.testing.assert_allclose(
        np.asarray(logits_b), np.asarray(logits_s), rtol=1e-5, atol=1e-5
    )
    for b, s in zip(
        jax.tree_util.tree_leaves(cache_b), jax.tree_util.tree_leaves(cache_s)
    ):
        np.testing.assert_allclose(
            np.asarray(b, np.float32), np.asarray(s, np.float32),
            rtol=1e-5, atol=1e-5,
        )


@pytest.mark.parametrize("use_sketch", [False, True])
def test_generate_greedy_matches_stepwise_reference(built, use_sketch):
    """Greedy `generate` (batched prefill + scanned decode) emits the same
    token ids as the unbatched reference: sequential prefill + explicit
    decode_step/argmax loop."""
    cfg, params = built
    sc = ServeConfig(max_len=48, use_sketch=use_sketch, cache_dtype=jnp.float32)
    eng = Engine(cfg, params, sc)
    B, L, n_new = 2, 12, 6
    prompts = _prompts(B, L, cfg.vocab_size)
    out, _ = eng.generate(prompts, n_new)

    cache, logits = eng.prefill_tokens_sequential(eng.new_cache(B), prompts)
    ref = [np.asarray(jnp.argmax(logits, -1))]
    tok, pos = jnp.argmax(logits, -1).astype(jnp.int32), L
    for _ in range(n_new - 1):
        logits, cache = eng._step(
            params, cache, tok, jnp.int32(pos), eng._slots(pos)
        )
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        ref.append(np.asarray(tok))
        pos += 1
    np.testing.assert_array_equal(out, np.stack(ref, axis=1))


# --------------------------------------------------------------------------- #
# decode-step count (the seed ran one wasted forward per request)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("n_new,expect", [(5, 4), (1, 0)])
def test_generate_runs_exactly_n_minus_1_decode_steps(built, monkeypatch, n_new, expect):
    """An n-token request runs exactly n−1 decode steps: token 0 comes from
    the prefill logits; no forward pass's outputs are discarded."""
    cfg, params = built
    counter = {"n": 0}
    real = engine_mod.decode_step

    def spy(*args, **kw):
        jax.debug.callback(lambda: counter.__setitem__("n", counter["n"] + 1))
        return real(*args, **kw)

    monkeypatch.setattr(engine_mod, "decode_step", spy)
    eng = Engine(cfg, params, ServeConfig(max_len=32, use_sketch=True))
    out, _ = eng.generate(_prompts(1, 8, cfg.vocab_size), n_new)
    jax.effects_barrier()
    assert out.shape == (1, n_new)
    assert counter["n"] == expect


@pytest.mark.parametrize("use_sketch", [False, True])
def test_engine_freed_on_del_without_cyclic_gc(built, use_sketch):
    """A served engine holds no reference cycle: dropping the last reference
    frees it — and with it the weights it holds — at once, not at the next
    cyclic collection (which may come after the next model's weights were
    put on the device)."""
    import gc
    import weakref

    cfg, params = built
    eng = Engine(cfg, params, ServeConfig(max_len=32, use_sketch=use_sketch))
    eng.generate(_prompts(1, 8, cfg.vocab_size), 3)
    ref = weakref.ref(eng)
    gc.disable()
    try:
        del eng
        assert ref() is None
    finally:
        gc.enable()


# --------------------------------------------------------------------------- #
# RNG streams (regression: slot draws and sampling shared fold_in(key, pos))
# --------------------------------------------------------------------------- #

def test_rng_streams_independent(built):
    """Slot draws and temperature sampling must consume INDEPENDENT streams:
    fold_in(fold_in(key, tag), pos) with distinct tags — never the same
    fold_in(key, pos) key for both uses at a position."""
    cfg, params = built
    eng = Engine(cfg, params, ServeConfig(max_len=4096, use_sketch=True,
                                          temperature=1.0))
    base = np.asarray(jax.random.key_data(eng.key))
    slot = np.asarray(jax.random.key_data(eng._slot_key))
    samp = np.asarray(jax.random.key_data(eng._sample_key))
    assert not np.array_equal(slot, samp)
    assert not np.array_equal(slot, base) and not np.array_equal(samp, base)
    for pos in (0, 7, 1000):
        kd = lambda k: np.asarray(jax.random.key_data(jax.random.fold_in(k, pos)))
        assert not np.array_equal(kd(eng._slot_key), kd(eng._sample_key))
    # the draws stay deterministic per position (counter-based, resumable)
    np.testing.assert_array_equal(
        np.asarray(eng._slots(13)), np.asarray(eng._slots(13))
    )


# --------------------------------------------------------------------------- #
# slot schemes
# --------------------------------------------------------------------------- #

def test_decode_slots_poisson_properties():
    """Poisson draws: ≤ m_r real slots, no duplicates, padding marked with
    the out-of-bounds index d_slots (dropped by the scatter), deterministic."""
    d_slots, m_r = 16, 4
    saw_pad = saw_real = False
    for step in range(64):
        s = np.asarray(decode_slots(KEY, step, d_slots, m_r, scheme="poisson"))
        assert s.shape == (m_r,) and s.dtype == np.int32
        assert ((s >= 0) & (s <= d_slots)).all()
        real = s[s < d_slots]
        assert len(np.unique(real)) == len(real)    # coins → no replacement
        saw_pad |= bool((s == d_slots).any())
        saw_real |= len(real) > 0
        np.testing.assert_array_equal(
            s, np.asarray(decode_slots(KEY, step, d_slots, m_r, scheme="poisson"))
        )
    assert saw_pad and saw_real                     # mean m_r ⇒ both occur


def test_decode_slots_identity_and_bad_scheme():
    """max_len ≤ d_slots degrades every scheme to the identity draw (slot t
    for position t ⇒ singleton slots ⇒ exact attention); unknown schemes
    raise."""
    for scheme in ("uniform", "poisson"):
        s = decode_slots(KEY, 5, 16, 3, scheme=scheme, max_len=16)
        np.testing.assert_array_equal(np.asarray(s), np.full(3, 5, np.int32))
    with pytest.raises(ValueError, match="unknown decode slot scheme"):
        decode_slots(KEY, 0, 16, 3, scheme="bogus")


def test_sketched_decode_exact_when_slots_cover_context(built):
    """d_slots ≥ max_len ⇒ sketched generate == exact generate, token for
    token (the identity-slot degradation, end to end)."""
    cfg, params = built
    cfg = dataclasses.replace(
        cfg, sketch_attn=SketchAttnCfg(d_slots=64, m=cfg.sketch_attn.m, m_r=2)
    )
    params = init_params(KEY, cfg)
    outs = {}
    for use_sketch in (False, True):
        sc = ServeConfig(max_len=32, use_sketch=use_sketch, cache_dtype=jnp.float32)
        outs[use_sketch], _ = Engine(cfg, params, sc).generate(
            _prompts(2, 10, cfg.vocab_size), 6
        )
    np.testing.assert_array_equal(outs[False], outs[True])


# --------------------------------------------------------------------------- #
# cache dtype + memory footprint
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_cache_dtype_honored(built, dtype):
    """`ServeConfig.cache_dtype` reaches both cache flavors' k/v storage;
    sketched `mass` stays f32 regardless (count saturation in bf16)."""
    cfg, params = built
    for use_sketch in (False, True):
        eng = Engine(cfg, params, ServeConfig(
            max_len=32, use_sketch=use_sketch, cache_dtype=dtype
        ))
        cache = eng.new_cache(1)
        if use_sketch:
            leaves = _sketch_leaves(cache)
            assert leaves and not _kv_leaves(cache)
            for sc in leaves:
                assert sc.k_sum.dtype == dtype and sc.v_sum.dtype == dtype
                assert sc.mass.dtype == jnp.float32
        else:
            leaves = _kv_leaves(cache)
            assert leaves and not _sketch_leaves(cache)
            for kv in leaves:
                assert kv.k.dtype == dtype and kv.v.dtype == dtype


def test_cache_bytes_flat_vs_linear(built):
    """Sketched cache bytes are INDEPENDENT of max_len (the paper's fixed
    effective size); exact KV bytes grow linearly."""
    cfg, _ = built
    bytes_at = lambda ml, sk: sum(
        x.nbytes for x in jax.tree_util.tree_leaves(
            init_cache(cfg, 1, ml, jnp.bfloat16, use_sketch=sk)
        )
    )
    assert bytes_at(1024, True) == bytes_at(256, True)
    assert bytes_at(1024, False) == 4 * bytes_at(256, False)
