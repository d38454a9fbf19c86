"""Mosaic compiles of the main-path Pallas kernels for a described TPU v5e.

Interpret mode (what every other test runs on the CPU) cannot see what the
TPU's compiler refuses: tiles over the scoped VMEM, slices off the (8, 128)
tiling, unsupported ops.  These tests lower each kernel through its public
entry point with ``interpret=False`` at deployment widths for one chip of a
described ``v5e:2x2`` topology and compile it — no chip is needed, nothing
runs.  Under ``jit`` the entry points take their heuristic tilings, which are
the tilings every traced caller runs with.  The same described topology, as a
2 × 2 mesh, shows which collectives a sharded decode step compiles to.

The topology is described inside a module fixture, never at import: only one
process may hold the TPU library, and a test worker that loads it keeps it
until it exits.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.core.sketch import AccumSketch
from repro.kernels.accum_apply.ops import (
    accum_grow_kernel,
    matfree_cols_kernel,
    sketch_both_kernel,
    sketch_left_kernel,
    sketch_right_kernel,
    sketch_step_kernel,
)
from repro.kernels.landmark_attention.ops import landmark_attend, landmark_stats_fused
from repro.launch.specs import decode_cell


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip land in the persistent cache but cannot
    # be read back without one — keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sketch(idx, coef, n):
    return AccumSketch(indices=idx, signs=jnp.sign(coef),
                       probs=jnp.full((n,), 1.0 / n, jnp.float32), n=n,
                       coef_=coef)


F32, I32 = jnp.float32, jnp.int32
N = 8192          # dense K: 8192 × 8192 f32 (256 MiB)


def test_sketch_right_compiles_dense_8192(one_chip):
    """K·S (``accum_apply``) — refused for VMEM before its contraction axis
    was tiled in the grid."""
    _compile(lambda K, i, c: sketch_right_kernel(K, _sketch(i, c, N), interpret=False),
             one_chip, ((N, N), F32), ((4, 64), I32), ((4, 64), F32))


def test_sketch_step_compiles_dense_8192(one_chip):
    """a·C + K·T̃ (``accum_step_slab``) — the same VMEM refusal as K·S."""
    _compile(lambda K, i, c, C, a: sketch_step_kernel(K, i, c, C, a, interpret=False),
             one_chip, ((N, N), F32), ((64,), I32), ((64,), F32), ((N, 64), F32),
             ((), F32))


def test_sketch_both_compiles_dense_8192(one_chip):
    _compile(lambda K, i, c: sketch_both_kernel(K, _sketch(i, c, N), interpret=False),
             one_chip, ((N, N), F32), ((4, 64), I32), ((4, 64), F32))


def test_accum_grow_compiles_dense_8192(one_chip):
    _compile(lambda K, i, c, C, a: accum_grow_kernel(K, i, c, C, a, interpret=False),
             one_chip, ((N, N), F32), ((4, 64), I32), ((4, 64), F32), ((N, 64), F32),
             ((), F32))


@pytest.mark.parametrize("d", [256, 1024])
def test_sketch_both_compiles_wide_sketch(one_chip, d):
    """(C, W) at sketch widths past one 128-lane tile: the output column
    blocks tile d, and W's (d, bd) column block stays within VMEM."""
    _compile(lambda K, i, c: sketch_both_kernel(K, _sketch(i, c, N), interpret=False),
             one_chip, ((N, N), F32), ((4, d), I32), ((4, d), F32))


@pytest.mark.parametrize("d", [256, 1024])
def test_accum_grow_compiles_wide_sketch(one_chip, d):
    _compile(lambda K, i, c, C, a: accum_grow_kernel(K, i, c, C, a, interpret=False),
             one_chip, ((N, N), F32), ((4, d), I32), ((4, d), F32), ((N, d), F32),
             ((), F32))


def test_sketch_left_compiles_wide_sketch(one_chip):
    """Sᵀ C (``accum_apply_left``) with C as wide as the sketch, d = 1024."""
    d = 1024
    _compile(lambda M, i, c: sketch_left_kernel(_sketch(i, c, N), M, interpret=False),
             one_chip, ((N, d), F32), ((4, d), I32), ((4, d), F32))


def test_matfree_compiles_at_yearpredictionmsd_shape(one_chip):
    """The matrix-free kernel-eval → scale kernel at YearPredictionMSD's
    shape (n ≈ 4.6e5 rows, p = 90), m = 8."""
    n, p, m, d = 463_872, 90, 8, 256
    _compile(lambda X, L, c: matfree_cols_kernel(X, L, c, kernel="gaussian",
                                                 bandwidth=6.0, interpret=False),
             one_chip, ((n, p), F32), ((m * d, p), F32), ((m, d), F32))


def test_landmark_attention_compiles_stablelm_heads(one_chip):
    """Sketched decode attention at stablelm-3b's head dim (80) over 1024
    landmark slots, batched over (batch·heads) as the decode step vmaps it."""
    bh, Dh, L = 128, 80, 1024
    _compile(lambda q, kt, M, b: jax.vmap(
                 lambda *a: landmark_attend(*a, interpret=False))(q, kt, M, b),
             one_chip, ((bh, 1, Dh), F32), ((bh, L, Dh), F32), ((bh, L, Dh), F32),
             ((bh, L), F32))


def test_landmark_stats_compiles_stablelm_heads(one_chip):
    Dh, L, S = 80, 1024, 2048
    _compile(lambda qt, kt, k, v: landmark_stats_fused(qt, kt, k, v, interpret=False),
             one_chip, ((L, Dh), F32), ((L, Dh), F32), ((S, Dh), F32), ((S, Dh), F32))


@pytest.mark.parametrize("S", [2112, 8192], ids=["heads_split", "positions_split"])
def test_sharded_decode_step_sums_no_scores_across_chips(mesh_2x2, S):
    """One stablelm-3b layer at batch 8 on a (data 2 × model 2) mesh.
    `cache_shardings` splits the cache's largest non-batch axis over "model":
    Hkv·Dh = 2560 at 2112 positions, the positions at 8192.  Decode reads the
    cache per KV head there, so no all-reduce carries (B, S, H) scores or
    block-diagonal (…, H, Hkv·Dh) partial outputs; what is left are the
    output projection's, the softmax's and, where the positions are split,
    the weighted sum's (B, H, Dh) parts."""
    cfg = dataclasses.replace(get_config("stablelm-3b"), n_superblocks=1, n_layers=1)
    cell = decode_cell(cfg, ShapeSpec("decode", S, 8, "decode"), mesh_2x2)
    with mesh_2x2:
        text = jax.jit(cell.fn, in_shardings=cell.in_shardings).lower(
            *cell.args).compile().as_text()
    reduced = [[int(d) for d in m.split(",") if d] for m in
               re.findall(r"= [a-z0-9]+\[([0-9,]*)\]\S* all-reduce(?:-start)?\(", text)]
    assert reduced
    H, HD = cfg.n_heads, cfg.n_kv_heads * cfg.head_dim
    for dims in reduced:
        assert S not in dims and S // 2 not in dims, dims
        assert math.prod(dims) < H * HD, dims
