"""Exact-cache decode attention: the in-place read of the stacked bf16/f32
cache against the upcast formula it replaced, the rows `decode_step` writes,
and the shape of `decode_step`'s program (no cache-sized upcast, the layer
scan carries the cache stacks and slices none out)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.attention as att
from repro.configs import ARCHS, reduced
from repro.models.common import rope_table
from repro.models.model import decode_step, init_cache, init_params

KEY = jax.random.PRNGKey(0)
B, L_STACK, LAYER = 2, 3, 1


def _upcast_attn_decode(p, h_t, cache, pos, cfg, sin_t, cos_t, *, write_pos=None):
    """The formula decode used before the cache was read in place: one
    layer's (B, S, Hkv·Dh) cache, upcast to float32, einsum, mask, softmax."""
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if write_pos is None:
        write_pos = pos
    q, k, v = att._qkv(p, h_t, cfg, sin_t, cos_t)
    cache = att.KVCache(
        jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype).reshape(B, 1, -1),
                                     (0, write_pos, 0)),
        jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype).reshape(B, 1, -1),
                                     (0, write_pos, 0)),
    )
    o = _upcast_attend(q[:, 0], cache.k, cache.v, pos, Hkv)
    out = o.reshape(B, 1, H * Dh).astype(h_t.dtype) @ p["wo"]
    return out, cache


def _upcast_attend(q, ck, cv, pos, Hkv):
    Bq, H, Dh = q.shape
    S = ck.shape[1]
    ck = ck.reshape(Bq, S, Hkv, Dh)
    cv = cv.reshape(Bq, S, Hkv, Dh)
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, jnp.float32))
    qg = q.reshape(Bq, Hkv, H // Hkv, Dh)
    logits = jnp.einsum(
        "bhgd,bshd->bhgs", qg.astype(jnp.float32), ck.astype(jnp.float32)
    ) * scale
    logits = jnp.where((jnp.arange(S) <= pos)[None, None, None, :], logits, att.NEG_INF)
    o = jnp.einsum(
        "bhgs,bshd->bhgd", jax.nn.softmax(logits, axis=-1), cv.astype(jnp.float32)
    )
    return o.reshape(Bq, H, Dh)


def _cfg(G):
    # Dh = 10: like stablelm-3b's 80, not a multiple of the lane width
    return dataclasses.replace(reduced(ARCHS["stablelm-3b"]), n_heads=4,
                               n_kv_heads=4 // G, head_dim=10)


# cache length, and the positions "first", "mid", "last slot" for each kind
# of cache: a full one, and a ring buffer (write_pos = pos % S) that has wrapped
CACHES = {"full": (24, {"first": 0, "mid": 11, "last": 23}),
          "ring": (8, {"first": 0, "mid": 13, "last": 15})}


def _rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("at", ["first", "mid", "last"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("kind", ["full", "ring"])
@pytest.mark.parametrize("G", [1, 2])
def test_attn_decode_matches_upcast_formula(G, kind, dtype, at):
    """Same numbers as the upcast formula, to float32 rounding.  Both form
    every product of a cache value exactly in float32 and accumulate in
    float32; they differ only in the order of the sums, so the outputs agree
    to a few float32 ulps of the largest entry (bound 1e-6, against a
    bfloat16 ulp of 7.8e-3).  The stack is written at one row only."""
    cfg = _cfg(G)
    S, positions = CACHES[kind]
    pos = jnp.int32(positions[at])
    write_pos = pos % S if kind == "ring" else None
    ks = jax.random.split(KEY, 5)
    p = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                               att.init_attn(ks[0], cfg))
    HD = cfg.n_kv_heads * cfg.head_dim
    stack = att.KVCache(*(jax.random.normal(k, (L_STACK, B, S, HD)).astype(dtype)
                          for k in ks[1:3]))
    h_t = jax.random.normal(ks[3], (B, 1, cfg.d_model), jnp.float32)
    sin_t, cos_t = rope_table(pos[None], cfg.head_dim, cfg.rope_theta)

    out, new = att.attn_decode(p, h_t, stack, jnp.int32(LAYER), pos, cfg, sin_t, cos_t,
                               write_pos=write_pos)
    layer = att.KVCache(stack.k[LAYER], stack.v[LAYER])
    ref_out, ref = _upcast_attn_decode(p, h_t, layer, pos, cfg, sin_t, cos_t,
                                       write_pos=write_pos)
    assert _rel_err(out, ref_out) <= 1e-6
    for got, was, want in zip(new, stack, ref):
        np.testing.assert_array_equal(np.asarray(got[LAYER]), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(jnp.delete(got, LAYER, axis=0)),
                                      np.asarray(jnp.delete(was, LAYER, axis=0)))

    # bfloat16 queries, as the served model makes them, on the cache just written
    q = jax.random.normal(ks[4], (B, cfg.n_heads, cfg.head_dim)).astype(jnp.bfloat16)
    assert _rel_err(att._decode_attend(q, ref.k, ref.v, pos),
                    _upcast_attend(q, ref.k, ref.v, pos, cfg.n_kv_heads)) <= 1e-6


@pytest.mark.parametrize("at", ["first", "mid", "last"])
@pytest.mark.parametrize("qdtype", [jnp.bfloat16, jnp.float32], ids=["q_bf16", "q_f32"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("per_head", [False, True], ids=["stored", "per_head"])
@pytest.mark.parametrize("G", [1, 2])
def test_decode_attend_compiled_matches_upcast_formula(G, per_head, dtype, qdtype, at):
    """Both cache layouts of `_decode_attend` compiled as one program, where
    the compiler sees the float32 → bfloat16 parts of the queries and the
    probabilities whole, agree with the upcast formula to float32 rounding
    (bound 1e-6 relative, as above): the parts still sum to what they split."""
    cfg = _cfg(G)
    S, positions = CACHES["full"]
    ks = jax.random.split(jax.random.fold_in(KEY, G), 3)
    HD = cfg.n_kv_heads * cfg.head_dim
    ck, cv = (jax.random.normal(k, (B, S, HD)).astype(dtype) for k in ks[:2])
    q = jax.random.normal(ks[2], (B, cfg.n_heads, cfg.head_dim)).astype(qdtype)
    pos = jnp.int32(positions[at])
    got = jax.jit(lambda *a: att._decode_attend(*a, per_head=per_head))(q, ck, cv, pos)
    want = jax.jit(_upcast_attend, static_argnums=4)(q, ck, cv, pos, cfg.n_kv_heads)
    assert _rel_err(got, want) <= 1e-6


def test_exact_parts_sum_to_what_they_split():
    """Three bfloat16 parts hold a float32 exactly, compiled or not, over
    magnitudes whose parts all stay normal; a float32 cache takes the value
    itself at HIGHEST; a bfloat16 value is its own part; other cache types
    are refused."""
    x = jnp.concatenate([
        jax.random.normal(KEY, (4096,)) * jnp.exp2(jax.random.randint(
            jax.random.fold_in(KEY, 1), (4096,), -90, 90).astype(jnp.float32)),
        jnp.array([0.0, 1.0, -1.0, 1 + 2 ** -23, 1 - 2 ** -24, 2 ** -90 * (1 + 2 ** -23)]),
    ]).astype(jnp.float32)
    parts = jax.jit(lambda x: jnp.stack(att._exact_parts(x, jnp.bfloat16)[0]))(x)
    assert parts.shape == (3,) + x.shape and parts.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(parts, np.float64).sum(axis=0),
                                  np.asarray(x, np.float64))
    (same,), prec = att._exact_parts(x, jnp.float32)
    assert prec == jax.lax.Precision.HIGHEST and same.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(same), np.asarray(x))
    xb = x.astype(jnp.bfloat16)
    assert att._exact_parts(xb, jnp.bfloat16) == ((xb,), jax.lax.Precision.DEFAULT)
    with pytest.raises(ValueError, match="float32 or bfloat16"):
        att._exact_parts(x, jnp.float16)


def _written_rows(cfg, cache, pos):
    """{pos{i}: the cache row decode writes at position `pos`} (ring-buffer
    layers write at pos % window)."""
    return {name: pos % c.k.shape[2] if cfg.pattern[int(name[3:])] == "attn_local" else pos
            for name, c in cache.blocks.items() if isinstance(c, att.KVCache)}


def _stacked(arch):
    cfg = reduced(ARCHS[arch])
    return dataclasses.replace(cfg, n_superblocks=3, n_layers=3 * len(cfg.pattern))


DECODE_ARCHS = ["stablelm-3b", "qwen2-vl-2b", "gemma3-12b", "zamba2-7b"]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_step_writes_one_row_per_layer(arch, monkeypatch):
    """`decode_step` leaves every exact cache bit-identical to what the
    upcast formula's step leaves, except the row each layer writes; those
    rows, the logits and the other states agree with it to float32 rounding
    (float32 weights and cache, so that no bfloat16 rounding amplifies the
    order of the sums)."""
    cfg = _stacked(arch)
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                    init_params(KEY, cfg))
    cache = init_cache(cfg, B, 20, jnp.float32)
    cache = jax.tree_util.tree_map(
        lambda x: jax.random.normal(jax.random.fold_in(KEY, x.size), x.shape, x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, cache)
    tok, pos = jnp.array([3, 7], jnp.int32), jnp.int32(17)

    logits, new = decode_step(params, cache, tok, pos, cfg)

    def upcast_layer(p, h_t, stack, layer, pos, cfg, sin_t, cos_t, *, write_pos=None):
        one = att.KVCache(stack.k[layer], stack.v[layer])
        out, one = _upcast_attn_decode(p, h_t, one, pos, cfg, sin_t, cos_t,
                                       write_pos=write_pos)
        return out, att.KVCache(stack.k.at[layer].set(one.k), stack.v.at[layer].set(one.v))

    monkeypatch.setattr(att, "attn_decode", upcast_layer)
    ref_logits, ref = decode_step(params, cache, tok, pos, cfg)

    assert _rel_err(logits, ref_logits) <= 1e-5
    rows = _written_rows(cfg, cache, int(pos))
    assert rows
    for name, c in new.blocks.items():
        if name not in rows:
            for got, want in zip(jax.tree_util.tree_leaves(c),
                                 jax.tree_util.tree_leaves(ref.blocks[name])):
                assert _rel_err(got, want) <= 1e-5
            continue
        for got, want, was in zip(c, ref.blocks[name], cache.blocks[name]):
            r = rows[name]
            np.testing.assert_array_equal(np.asarray(jnp.delete(got, r, axis=2)),
                                          np.asarray(jnp.delete(want, r, axis=2)))
            np.testing.assert_array_equal(np.asarray(jnp.delete(got, r, axis=2)),
                                          np.asarray(jnp.delete(was, r, axis=2)))
            assert _rel_err(got[:, :, r], want[:, :, r]) <= 1e-5


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested ones included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_step_program_keeps_the_cache_in_place(arch, dtype):
    """No cache-sized `convert_element_type` anywhere in the step, and the
    layer scan carries the stacked `KVCache` leaves and takes none of them,
    nor a layer's slice of them, as `xs` or `ys`.  At 512 positions one
    layer's cache outsizes every weight matrix, so sizes tell them apart
    (the sliding window is widened to match)."""
    cfg = dataclasses.replace(_stacked(arch), window=512)
    params = jax.eval_shape(lambda: init_params(KEY, cfg))
    cache = jax.eval_shape(lambda: init_cache(cfg, B, 512, dtype))
    kv = [c for c in cache.blocks.values() if isinstance(c, att.KVCache)]
    stacks = {tuple(x.shape) for c in kv for x in c}
    layer_size = min(np.prod(s[1:]) for s in stacks)
    closed = jax.make_jaxpr(lambda p, c, t, i: decode_step(p, c, t, i, cfg))(
        params, cache, jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))

    eqns = list(_eqns(closed.jaxpr))
    for e in eqns:
        if e.primitive.name == "convert_element_type":
            assert e.invars[0].aval.size < layer_size, e

    scans = [e for e in eqns if e.primitive.name == "scan"
             and any(tuple(v.aval.shape) in stacks for v in e.invars)]
    assert len(scans) == 1
    e = scans[0]
    nc, ncarry = e.params["num_consts"], e.params["num_carry"]
    carry = [tuple(v.aval.shape) for v in e.invars[nc:nc + ncarry]]
    assert sorted(s for s in carry if s in stacks) == sorted(
        tuple(x.shape) for c in kv for x in c)
    xs = [v.aval for v in e.invars[nc + ncarry:]]
    ys = [v.aval for v in e.outvars[ncarry:]]
    for a in xs + ys:
        assert a.size < layer_size, a
