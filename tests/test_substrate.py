"""Substrate tests: optimizer, data pipeline determinism, checkpoint
fault-tolerance, gradient compression, training-loop resume, serving engine."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import AsyncCheckpointer, latest_step, restore, retain, save
from repro.configs import ARCHS, reduced
from repro.data.pipeline import DataConfig, global_batch, host_batch
from repro.models import init_params
from repro.optim.adamw import AdamWConfig, adamw_update, global_norm, init_adamw, schedule
from repro.optim.compress import CompressConfig, compress_grads, init_error_feedback
from repro.serve.engine import Engine, ServeConfig
from repro.train.loop import LoopConfig, run
from repro.train.step import TrainConfig, init_train_state

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------- #
# optimizer
# --------------------------------------------------------------------------- #

def test_adamw_reduces_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0], jnp.float32)}
    state = init_adamw(params)
    cfg = AdamWConfig(lr_peak=0.1, warmup_steps=0, total_steps=200, weight_decay=0.0)
    p = params
    for _ in range(150):
        g = {"w": 2 * p["w"]}
        p, state, _ = adamw_update(g, state, cfg, param_dtype=jnp.float32)
    assert float(jnp.abs(p["w"]).max()) < 0.15


def test_schedule_warmup_and_decay():
    cfg = AdamWConfig(lr_peak=1e-3, lr_min=1e-5, warmup_steps=10, total_steps=100)
    assert float(schedule(cfg, jnp.int32(5))) == pytest.approx(5e-4)
    assert float(schedule(cfg, jnp.int32(10))) == pytest.approx(1e-3, rel=0.1)
    assert float(schedule(cfg, jnp.int32(100))) == pytest.approx(1e-5, rel=0.1)


def test_grad_clip_bounds_update_norm():
    params = {"w": jnp.zeros((4,), jnp.float32)}
    state = init_adamw(params)
    cfg = AdamWConfig(lr_peak=1.0, warmup_steps=0, clip_norm=1.0, weight_decay=0.0)
    g = {"w": jnp.full((4,), 100.0)}
    _, state2, mets = adamw_update(g, state, cfg, param_dtype=jnp.float32)
    assert float(mets["grad_norm"]) == pytest.approx(200.0)
    # post-clip effective grad norm 1 → m update bounded
    assert float(global_norm(state2.m)) < 0.2


# --------------------------------------------------------------------------- #
# data pipeline
# --------------------------------------------------------------------------- #

def test_data_deterministic_and_restart_safe():
    dc = DataConfig(vocab_size=100, seq_len=16, global_batch=8)
    a1, b1 = global_batch(dc, step=7)
    a2, b2 = global_batch(dc, step=7)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(b1, b2)
    a3, _ = global_batch(dc, step=8)
    assert not np.array_equal(a1, a3)
    # labels are next-token shifted
    full = np.concatenate([a1[:, :1], b1], axis=1)
    np.testing.assert_array_equal(full[:, 1:], b1)


def test_data_host_sharding_partitions():
    parts = [host_batch(DataConfig(100, 8, 8, 0, 4, h), 3)[0] for h in range(4)]
    assert all(p.shape == (2, 8) for p in parts)
    # different hosts get different data
    assert not np.array_equal(parts[0], parts[1])


# --------------------------------------------------------------------------- #
# checkpointing / fault tolerance
# --------------------------------------------------------------------------- #

def test_checkpoint_roundtrip_bf16():
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "b": {"c": jnp.float32(1.5)}}
    with tempfile.TemporaryDirectory() as td:
        save(td, tree, step=3)
        out, step = restore(td, tree)
        assert step == 3
        np.testing.assert_array_equal(np.asarray(out["a"], np.float32),
                                      np.asarray(tree["a"], np.float32))
        assert out["a"].dtype == jnp.bfloat16


def test_checkpoint_ignores_torn_writes():
    tree = {"x": jnp.ones((2,))}
    with tempfile.TemporaryDirectory() as td:
        save(td, tree, step=1)
        # simulate a preempted write: tmp dir without COMMIT marker
        os.makedirs(os.path.join(td, "step_00000002.tmp"))
        # and a committed-looking dir without marker
        os.makedirs(os.path.join(td, "step_00000003"))
        assert latest_step(td) == 1


def test_checkpoint_retention():
    tree = {"x": jnp.ones((2,))}
    with tempfile.TemporaryDirectory() as td:
        for s in range(6):
            save(td, tree, step=s)
        retain(td, keep=2)
        assert latest_step(td) == 5
        kept = [n for n in os.listdir(td) if n.startswith("step_")]
        assert len(kept) == 2


def test_async_checkpointer():
    tree = {"x": jnp.arange(4.0)}
    with tempfile.TemporaryDirectory() as td:
        ck = AsyncCheckpointer(td, keep=2)
        ck.save(tree, step=1)
        ck.wait()
        out, step = restore(td, tree)
        np.testing.assert_array_equal(out["x"], np.arange(4.0))


# --------------------------------------------------------------------------- #
# gradient compression (paper technique in the optimizer)
# --------------------------------------------------------------------------- #

def test_compression_error_feedback_preserves_signal():
    """EF guarantees: sum of applied (compressed) grads + residual = sum of
    true grads — nothing is lost, only delayed."""
    cfg = CompressConfig(ratio=0.25, m=4, min_rows=8)
    g = {"w": jax.random.normal(KEY, (64, 16))}
    ef = init_error_feedback(g, cfg)
    applied_sum = jnp.zeros((64, 16))
    true_sum = jnp.zeros((64, 16))
    for step in range(5):
        gs = {"w": jax.random.normal(jax.random.fold_in(KEY, step), (64, 16))}
        out, ef, mets = compress_grads(gs, ef, jnp.int32(step), KEY, cfg)
        applied_sum = applied_sum + out["w"]
        true_sum = true_sum + gs["w"]
        assert float(mets["compress_ratio"]) < 1.0
    resid = jax.tree_util.tree_leaves(ef)[0]
    np.testing.assert_allclose(
        np.asarray(applied_sum + resid), np.asarray(true_sum), rtol=1e-3, atol=1e-3
    )


def test_compression_skips_small_blocks():
    cfg = CompressConfig(ratio=0.25, m=2, min_rows=1000)
    g = {"small": jnp.ones((4, 4))}
    ef = init_error_feedback(g, cfg)
    out, ef2, mets = compress_grads(g, ef, jnp.int32(0), KEY, cfg)
    np.testing.assert_array_equal(out["small"], g["small"])
    assert float(mets["compress_ratio"]) == 1.0


# --------------------------------------------------------------------------- #
# end-to-end: loop + resume + serve
# --------------------------------------------------------------------------- #

def test_loop_trains_and_resumes():
    cfg = reduced(ARCHS["qwen2-vl-2b"]).scaled(frontend=None, cond_len=0)
    tc = TrainConfig(optimizer=AdamWConfig(lr_peak=5e-3, warmup_steps=2, total_steps=40))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    mk = lambda: init_train_state(init_params(KEY, cfg), tc)
    with tempfile.TemporaryDirectory() as td:
        lc = LoopConfig(total_steps=8, ckpt_dir=td, ckpt_every=4, log_every=100)
        rep = run(cfg, tc, dc, lc, init_params_fn=mk, log=lambda *a: None)
        assert rep.final_loss < rep.losses[0]
        lc2 = LoopConfig(total_steps=10, ckpt_dir=td, ckpt_every=4, log_every=100)
        rep2 = run(cfg, tc, dc, lc2, init_params_fn=mk, log=lambda *a: None)
        assert rep2.resumed_from == 8 and rep2.steps_run == 2


def test_engine_greedy_deterministic():
    cfg = reduced(ARCHS["stablelm-3b"])
    params = init_params(KEY, cfg)
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    prompts = np.array([[1, 2, 3]], np.int32)
    out1, _ = eng.generate(prompts, 5)
    out2, _ = eng.generate(prompts, 5)
    np.testing.assert_array_equal(out1, out2)
    assert out1.shape == (1, 5)


def test_compile_cache_dir_env_or_checkout(monkeypatch):
    """Entry points keep the persistent compile cache where
    JAX_COMPILATION_CACHE_DIR says, else at a fixed, git-ignored
    ``.jax_cache/`` in the checkout (only the path is resolved here — tests
    never turn the cache on)."""
    import pathlib

    from repro.util import CHECKOUT_ROOT, compile_cache_dir

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache_dir() == "/elsewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == str(CHECKOUT_ROOT / ".jax_cache")
    assert (CHECKOUT_ROOT / "src" / "repro" / "util.py").is_file()
    ignored = (pathlib.Path(CHECKOUT_ROOT) / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
