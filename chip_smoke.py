"""Smoke run of the system's main path on one TPU chip, in one process.

    python chip_smoke.py              # five phases on one chip
    python chip_smoke.py --chips 4    # sharded KRR vs one device, on four chips

Phases, in order; each failure ends the run with a non-zero exit:

1. device check — the first device must be a TPU whose kind has a row in the
   peak table; there is no CPU fallback;
2. matrix-free KRR at YearPredictionMSD's shape (the Falkon benchmark suite:
   463,715 training rows, 51,630 test rows, p = 90), Gaussian kernel through
   ``KernelOperator``, sketch d = 1024, m = 8: the fixed-m fit and predict
   on the Pallas route checked against the XLA route on the same draws, then
   the adaptive (doubling) fit;
3. dense K·S on a 16,384 × 16,384 f32 kernel matrix (1 GiB) with a
   d = 1024 sketch: the fused (C, W) kernel, K·S, and batched growth steps,
   each against its XLA twin;
4. sketched spectral clustering at MNIST's shape (70,000 × 784, 10 clusters)
   on 10 planted Gaussian blobs;
5. serving stablelm-3b at its published width (32 layers, d_model 2560,
   vocab 50304) with random bf16 weights: batch 4, prompt 2048, 32 new
   tokens on the exact and on the sketched cache; then a short request whose
   slots cover its context, where sketched decode must reproduce exact decode
   token for token — run in f32 weights and activations at all 32 layers (in
   bf16 it is only reported, with the logit error and top-2 margin that
   decide whether rounding can flip a greedy token).

All data and weights are generated from ``--seed``.  The times printed are
one-off readings of a smoke run (first call = compile + autotune + run,
second call = run), not benchmark numbers.  The last line of standard output
is one JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.

With ``--chips 4`` only the fixed-m KRR fit and predict run, on a 4-device
``("data",)`` mesh against the same fit on one device, and the script checks
that the fit's C is split over the four devices, one (⌈n/4⌉, d) row tile
each.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# KRR phase (YearPredictionMSD shape)
N_TRAIN, N_TEST, P_MSD = 463_715, 51_630, 90
KRR_D, KRR_M, KRR_BANDWIDTH, KRR_LAM = 1024, 8, 6.0, 1e-4
# dense phase
DENSE_N, DENSE_D, DENSE_M = 16_384, 1024, 4
# spectral phase (MNIST shape)
MNIST_N, MNIST_P, MNIST_K = 70_000, 784, 10
SPECTRAL_D, SPECTRAL_M, SPECTRAL_AGREEMENT = 512, 4, 0.95
# serving phase
SERVE_ARCH, SERVE_B, SERVE_PROMPT, SERVE_NEW = "stablelm-3b", 4, 2048, 32

KRR_TOL, KERNEL_TOL, MESH_TOL = 1e-3, 1e-4, 1e-4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, non-finite or misplaced result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def log_memory(when: str) -> None:
    """Device memory in use and its peak so far, as the runtime reports it."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    gib = {k: stats.get(k, 0) / 2**30
           for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
    log(f"[mem] {when}: {gib['bytes_in_use']:.3f} GiB in use, peak "
        f"{gib['peak_bytes_in_use']:.3f} GiB of {gib['bytes_limit']:.3f} GiB")


def timed(fn):
    """(result, seconds) of ``fn()`` run to completion on the device."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def twice(name: str, fn):
    """Run ``fn`` twice — the first call compiles (and autotunes) — and print
    both wall times.  Returns the second result."""
    _, t_first = timed(fn)
    out, t_second = timed(fn)
    log(f"[time] {name}: first call {t_first:.3f} s (compile+run), "
        f"second call {t_second:.3f} s")
    return out


def once(name: str, fn):
    """Run ``fn`` once (compile + run) and print its wall time: the
    four-chip phase holds four chips, so it pays for no second call."""
    out, t = timed(fn)
    log(f"[time] {name}: one call {t:.3f} s (compile+run)")
    return out


# --------------------------------------------------------------------------- #
# data, generated on the device from the seed
# --------------------------------------------------------------------------- #

def regression_data(key, n_train: int, n_test: int, p: int, bandwidth: float):
    """Standardized features and a target drawn from the kernel's own
    function class: y = Σ_j a_j k(x, c_j) over 256 random centres, scaled to
    unit variance, plus 0.1 noise."""
    import jax
    import jax.numpy as jnp

    from repro.core.kernels_math import get_kernel

    kx, kc, ka, ke = jax.random.split(key, 4)
    X = jax.random.normal(kx, (n_train + n_test, p), jnp.float32)
    centres = jax.random.normal(kc, (256, p), jnp.float32)
    f = get_kernel("gaussian", bandwidth)(X, centres) @ jax.random.normal(ka, (256,))
    f = (f - jnp.mean(f)) / jnp.std(f)
    y = f + 0.1 * jax.random.normal(ke, f.shape)
    return X[:n_train], y[:n_train], X[n_train:], y[n_train:]


def blob_data(key, n: int, p: int, k: int):
    """``k`` Gaussian blobs of unit spread around centres 1.5·N(0, I)."""
    import jax
    import jax.numpy as jnp

    kc, kl, kx = jax.random.split(key, 3)
    centres = 1.5 * jax.random.normal(kc, (k, p), jnp.float32)
    labels = jax.random.randint(kl, (n,), 0, k)
    X = centres[labels] + jax.random.normal(kx, (n, p), jnp.float32)
    return X, labels


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #

def krr_problem(key):
    """YearPredictionMSD-shaped data, its Gaussian operator and the d, m
    sketch, all from ``key``."""
    import jax

    from repro.core.kernel_op import KernelOperator
    from repro.core.sketch import make_accum_sketch

    Xtr, ytr, Xte, yte = regression_data(jax.random.fold_in(key, 1), N_TRAIN,
                                         N_TEST, P_MSD, KRR_BANDWIDTH)
    op = KernelOperator(Xtr, "gaussian", KRR_BANDWIDTH)
    sk = make_accum_sketch(jax.random.fold_in(key, 2), N_TRAIN, KRR_D, KRR_M)
    return op, sk, ytr, Xte, yte


def phase_krr(key):
    import jax
    import jax.numpy as jnp

    from repro.core import apply as A
    from repro.core.kernels_math import f32_matmul
    from repro.core.krr import krr_sketched_fit, krr_sketched_fit_adaptive
    from repro.resilience.degrade import global_health

    log(f"[krr] YearPredictionMSD shape: n_train={N_TRAIN} n_test={N_TEST} "
        f"p={P_MSD}; gaussian bandwidth={KRR_BANDWIDTH} lam={KRR_LAM} "
        f"d={KRR_D} m={KRR_M}")
    op, sk, ytr, Xte, yte = krr_problem(key)

    preds, mses = {}, {}
    for route, use_kernel in (("pallas", True), ("xla", False)):
        twice(f"krr (C, W) = sketch_both ({route})",
              lambda: A.sketch_both(op, sk, use_kernel=use_kernel))
        model = twice(f"krr fit ({route})", lambda: krr_sketched_fit(
            op, ytr, KRR_LAM, sk, use_kernel=use_kernel))
        log(f"[krr] {route} solve: " + ", ".join(
            f"{k}={int(v)}" for k, v in sorted(model.info.items())))
        if use_kernel:
            pred = twice(f"krr predict ({route})", lambda: model.predict(Xte))
        else:
            pred = twice(f"krr predict ({route})", lambda: f32_matmul(
                model.op.cross_cols(Xte, model.sk, use_kernel=False), model.theta))
        check(pred.shape == (N_TEST,), f"prediction shape {pred.shape}")
        check(bool(jnp.all(jnp.isfinite(pred))), f"non-finite {route} predictions")
        preds[route] = pred
        mses[route] = float(jnp.mean((pred - yte) ** 2))
        del model
    err = rel_err(preds["pallas"], preds["xla"])
    base = float(jnp.var(yte))
    log(f"[krr] holdout MSE pallas={mses['pallas']:.6f} xla={mses['xla']:.6f} "
        f"(predicting the mean: {base:.6f}); pallas vs xla rel RMS {err:.3e}")
    check(err <= KRR_TOL, f"KRR predictions differ from the XLA route: {err:.3e}")
    check(mses["pallas"] < base, "the fit predicts worse than the mean")

    tol = 1e-3     # below the holdout estimate at m = 1, so growth doubles on
    model = twice("krr adaptive fit (pallas)", lambda: krr_sketched_fit_adaptive(
        op, ytr, KRR_LAM, jax.random.fold_in(key, 3), KRR_D, tol=tol,
        m_max=KRR_M))
    passes, m_chosen = int(model.info["passes"]), int(model.info["m"])
    mse = float(jnp.mean((model.predict(Xte) - yte) ** 2))
    log(f"[krr] adaptive tol={tol}: chose m={m_chosen} in {passes} data passes, "
        f"estimate {float(model.info['err']):.4f}, holdout MSE {mse:.6f}")
    check(passes >= 2, f"adaptive growth took {passes} passes")
    check(global_health().count() == 0,
          f"a rung was dropped: {global_health().summary()}")
    log("[krr] ok")


def phase_dense(key):
    import jax
    import jax.numpy as jnp

    from repro.core import apply as A
    from repro.core.kernels_math import get_kernel
    from repro.core.sketch import make_accum_sketch
    from repro.kernels.accum_apply.ops import sketch_right_kernel

    n, d, m = DENSE_N, DENSE_D, DENSE_M
    log(f"[dense] K {n}x{n} f32 ({n * n * 4 / 2**30:.2f} GiB), d={d} m={m}")
    X = jax.random.normal(jax.random.fold_in(key, 1), (n, 16), jnp.float32)
    K = jax.block_until_ready(get_kernel("gaussian", 4.0)(X, X))
    sk = make_accum_sketch(jax.random.fold_in(key, 2), n, d, m)

    C_k, W_k = twice("sketch_both (pallas accum_sketch_both)",
                     lambda: A.sketch_both(K, sk, use_kernel=True))
    C_x, W_x = twice("sketch_both (xla)", lambda: A.sketch_both(K, sk, use_kernel=False))
    R_k = twice("K·S (pallas accum_apply)", lambda: sketch_right_kernel(K, sk))
    R_x = twice("K·S (xla)", lambda: A.sketch_right(K, sk))

    state = A.accum_init(jax.random.fold_in(key, 3), n, d, 2 * m)
    grown = {}
    for route, use_kernel in (("pallas accum_grow_slabs", True), ("xla", False)):
        def grow(use_kernel=use_kernel):
            s = A.accum_grow_batched(K, state, m, use_kernel=use_kernel, donate=False)
            return A.accum_grow_batched(K, s, m, use_kernel=use_kernel, donate=False)
        grown[use_kernel] = twice(f"two batched growth steps ({route})", grow)
    errs = {
        "C (sketch_both)": rel_err(C_k, C_x), "W (sketch_both)": rel_err(W_k, W_x),
        "K·S": rel_err(R_k, R_x),
        "C (growth)": rel_err(grown[True].C, grown[False].C),
        "W (growth)": rel_err(grown[True].W, grown[False].W),
    }
    for name, e in errs.items():
        log(f"[dense] {name}: pallas vs xla rel err {e:.3e}")
        check(e <= KERNEL_TOL, f"{name} differs from its XLA twin: {e:.3e}")
    log("[dense] ok")


def phase_spectral(key):
    import jax
    import numpy as np

    from repro.core.kernel_op import KernelOperator
    from repro.core.spectral import spectral_cluster

    n, p, k = MNIST_N, MNIST_P, MNIST_K
    bandwidth = float(np.sqrt(p))
    log(f"[spectral] MNIST shape: {n}x{p}, {k} planted blobs; gaussian "
        f"bandwidth={bandwidth:.1f} d={SPECTRAL_D} m={SPECTRAL_M}")
    X, truth = blob_data(jax.random.fold_in(key, 1), n, p, k)
    op = KernelOperator(X, "gaussian", bandwidth)
    res = twice("spectral_cluster", lambda: spectral_cluster(
        jax.random.fold_in(key, 2), op, k, d=SPECTRAL_D, m=SPECTRAL_M))
    labels, truth = np.asarray(res.labels), np.asarray(truth)
    # purity: each found cluster votes for its majority planted blob
    agree = sum(np.bincount(truth[labels == c], minlength=k).max()
                for c in np.unique(labels)) / n
    log(f"[spectral] label agreement with the planted blobs {agree:.4f} "
        f"(required ≥ {SPECTRAL_AGREEMENT})")
    check(labels.shape == (n,), f"labels shape {labels.shape}")
    check(agree >= SPECTRAL_AGREEMENT, f"label agreement {agree:.4f}")
    log("[spectral] ok")


def identity_request(cfg, params, prompts, dtype):
    """Greedy decode of each row of ``prompts`` alone (batch 1), with slots
    covering the whole context (max_len ≤ d_slots: the slot draw is the
    identity), on the exact and on the sketched cache.  Returns ({sketched:
    (rows, n_new) tokens}, {sketched: (rows, vocab) prefill logits},
    max_len).  Batch 1 keeps 32 layers of f32 weights and the sketched f32
    cache within one chip's 16 GB."""
    import jax
    import numpy as np

    from repro.serve.engine import Engine, ServeConfig

    n_new = min(16, cfg.sketch_attn.d_slots - prompts.shape[1])
    max_len = prompts.shape[1] + n_new
    toks, first = {}, {}
    for use_sketch in (False, True):
        eng = Engine(cfg, params, ServeConfig(max_len=max_len,
                                              use_sketch=use_sketch,
                                              cache_dtype=dtype))
        rows = [row[None] for row in prompts]
        first[use_sketch] = np.concatenate([np.asarray(jax.block_until_ready(
            eng.prefill_tokens(eng.new_cache(1), row)[1])) for row in rows])
        toks[use_sketch] = np.concatenate(
            [eng.generate(row, n_new)[0] for row in rows])
        del eng
    return toks, first, max_len


def top2_margin(logits) -> float:
    """Smallest gap between the two largest logits over the batch."""
    import numpy as np

    top = np.sort(np.asarray(logits, np.float64), axis=-1)[..., -2:]
    return float(np.min(top[..., 1] - top[..., 0]))


def phase_serve(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models.model import init_params, param_count
    from repro.serve.engine import Engine, ServeConfig

    cfg = get_config(SERVE_ARCH)
    B, L, n_new = SERVE_B, SERVE_PROMPT, SERVE_NEW
    params = jax.block_until_ready(init_params(jax.random.PRNGKey(seed), cfg))
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, {param_count(params) / 1e9:.3f} B params "
        f"(random bf16), d_slots {cfg.sketch_attn.d_slots}")
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (B, L), dtype=np.int32)

    for use_sketch in (False, True):
        kind = "sketched" if use_sketch else "exact"
        eng = Engine(cfg, params, ServeConfig(max_len=L + n_new,
                                              use_sketch=use_sketch))
        toks = twice(f"generate {kind} B={B} L={L} +{n_new}",
                     lambda: eng.generate(prompts, n_new)[0])
        logits, t_prefill = timed(lambda: eng.prefill_tokens(
            eng.new_cache(B), prompts)[1])
        _, t_gen = timed(lambda: eng.generate(prompts, n_new)[0])
        check(toks.shape == (B, n_new), f"tokens shape {toks.shape}")
        check(bool(jnp.all(jnp.isfinite(logits))), f"non-finite {kind} logits")
        check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()), "token ids")
        rate = B * (n_new - 1) / max(t_gen - t_prefill, 1e-9)
        log(f"[serve] {kind}: TTFT (batched prefill) {t_prefill:.3f} s, decode "
            f"{rate:.1f} tok/s over {n_new - 1} steps (one-off readings)")
        del eng

    # Slots covering the context make the slot draw the identity, and
    # sketched attention is exact attention up to rounding (k̄ = k_sum /
    # mass).  Greedy tokens agree only where that rounding cannot flip an
    # argmax.  In bf16 the request is reported with the size of the rounding
    # (prefill logits' error) beside the closest top-2 margin; the check runs
    # the same 32 layers in f32 weights, caches and contractions.
    short = prompts[:2, :min(64, cfg.sketch_attn.d_slots // 2)]
    toks, first, max_len = identity_request(cfg, params, short, jnp.bfloat16)
    diff = np.flatnonzero((toks[False] != toks[True]).any(axis=0))
    log(f"[serve] identity slots, bf16 (reported, not checked): prefill logits "
        f"rel err {rel_err(first[True], first[False]):.3e}, max |Δ| "
        f"{float(np.max(np.abs(first[True] - first[False]))):.3e}, exact top-2 "
        f"margin {top2_margin(first[False]):.3e}; tokens equal "
        f"{not diff.size}" + (f", first differing step {int(diff[0])}"
                              if diff.size else ""))
    if diff.size:
        # the logits that chose the first differing token, both caches fed
        # the same (exact) tokens before it
        t = int(diff[0])
        ctx = np.concatenate([short, toks[False][:, :t]], axis=1)
        at = {}
        for use_sketch in (False, True):
            eng = Engine(cfg, params, ServeConfig(
                max_len=max_len, use_sketch=use_sketch, cache_dtype=jnp.bfloat16))
            at[use_sketch] = eng.prefill_tokens(eng.new_cache(len(ctx)), ctx)[1]
            del eng
        log(f"[serve] bf16 step {t}: logits rel err "
            f"{rel_err(at[True], at[False]):.3e}, max |Δ| "
            f"{float(jnp.max(jnp.abs(at[True] - at[False]))):.3e}, exact top-2 "
            f"margin {top2_margin(at[False]):.3e}")
        del at

    # f32 weights (11.2 GB), converted on the host: the bf16 copy leaves the
    # chip before the f32 one arrives, so the two never share it and the f32
    # leaves are not placed between holes the bf16 leaves left
    host = jax.device_get(params)
    del params
    log_memory("bf16 weights freed")
    params = jax.tree_util.tree_map(lambda x: jax.block_until_ready(
        jnp.asarray(np.asarray(x, np.float32))), host)
    del host
    log_memory("f32 weights on the chip")
    with jax.default_matmul_precision("highest"):
        toks, first, max_len = identity_request(cfg, params, short, jnp.float32)
    same = bool(np.array_equal(toks[False], toks[True]))
    log(f"[serve] identity slots, f32 ({cfg.n_layers} layers, max_len {max_len} "
        f"≤ d_slots {cfg.sketch_attn.d_slots}): prefill logits rel err "
        f"{rel_err(first[True], first[False]):.3e}, exact top-2 margin "
        f"{top2_margin(first[False]):.3e}; sketched == exact tokens: {same}")
    check(same, f"sketched {toks[True].tolist()} != exact {toks[False].tolist()}")
    log("[serve] ok")


def phase_mesh(key, n_dev: int):
    import jax
    import jax.numpy as jnp

    from repro.core import distributed as D
    from repro.core.krr import krr_sketched_fit

    mesh = D.make_data_mesh(n_dev)
    log(f"[mesh] YearPredictionMSD shape on a {n_dev}-device data mesh vs one "
        f"device: n_train={N_TRAIN} d={KRR_D} m={KRR_M}")
    op, sk, ytr, Xte, yte = krr_problem(key)

    single = once("krr fit (1 device)",
                  lambda: krr_sketched_fit(op, ytr, KRR_LAM, sk))
    p_single = once("krr predict (1 device)", lambda: single.predict(Xte))
    del single
    sharded = once(f"krr fit ({n_dev}-device mesh)",
                   lambda: krr_sketched_fit(op, ytr, KRR_LAM, sk, mesh=mesh))
    p_mesh = once(f"krr predict ({n_dev}-device mesh)",
                  lambda: sharded.predict(Xte, mesh=mesh))
    err = rel_err(p_mesh, p_single)
    log(f"[mesh] holdout MSE 1-device {float(jnp.mean((p_single - yte) ** 2)):.6f} "
        f"{n_dev}-device {float(jnp.mean((p_mesh - yte) ** 2)):.6f}; "
        f"rel err {err:.3e}")
    check(bool(jnp.all(jnp.isfinite(p_mesh))), "non-finite sharded predictions")
    check(err <= MESH_TOL, f"sharded predictions differ: {err:.3e}")

    # the C the sharded fit reduces: one (⌈n/n_dev⌉, d) row tile per device
    C, _ = D.sharded_sketch_both(op, sk, mesh, padded=True)
    rows = -(-N_TRAIN // n_dev)
    shards = sorted((s.device.id, s.data.shape) for s in C.addressable_shards)
    log(f"[mesh] fit's C {C.shape} {C.sharding.spec}, fully replicated "
        f"{C.sharding.is_fully_replicated}; shards (device, shape) {shards}")
    check(shards == [(dev.id, (rows, KRR_D))
                     for dev in sorted(mesh.devices.flat, key=lambda x: x.id)],
          f"C is not split into {n_dev} row tiles of ({rows}, {KRR_D})")
    log("[mesh] ok")


# --------------------------------------------------------------------------- #
# entry point
# --------------------------------------------------------------------------- #

def refuse_environment() -> str | None:
    """On the chip the kernels run compiled, unfaulted, on their own route."""
    from repro.util import env_flag

    if "REPRO_PALLAS_INTERPRET" in os.environ:
        return "REPRO_PALLAS_INTERPRET is set (the kernels must run compiled)"
    if not env_flag("REPRO_SKETCH_KERNEL", True):
        return "REPRO_SKETCH_KERNEL turns the Pallas kernels off"
    if "REPRO_FAULT_PLAN" in os.environ:
        return "REPRO_FAULT_PLAN arms fault injection"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-vs-one-device KRR comparison")
    args = ap.parse_args(argv)

    reason = refuse_environment()
    if reason:
        print(f"chip_smoke: refusing to run: {reason}", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.analysis.hardware import hardware_for
    from repro.kernels.accum_apply import autotune
    from repro.util import use_compile_cache

    hw = hardware_for(dev.device_kind)
    log(f"[device] peaks ({hw.name}): {hw.peak_flops / 1e12:.0f} TFLOP/s bf16, "
        f"{hw.hbm_bw / 1e9:.0f} GB/s HBM, {hw.hbm_bytes / 1e9:.0f} GB")
    log(f"[device] compile cache: {use_compile_cache()}")
    key = jax.random.PRNGKey(args.seed)

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(jax.random.fold_in(key, 5), 4)
    else:
        phase_krr(jax.random.fold_in(key, 1))
        log_memory("after the KRR phase")
        phase_dense(jax.random.fold_in(key, 2))
        log_memory("after the dense phase")
        phase_spectral(jax.random.fold_in(key, 3))
        log_memory("after the spectral phase")
        phase_serve(args.seed)
        log_memory("after the serving phase")
    for kind, shape, blocks, msg in autotune.refusals():
        log(f"[autotune] skipped {kind} {shape} blocks={blocks}: {msg[:160]}")
    log(f"[done] all phases in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
