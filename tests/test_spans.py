"""The program's spans and scopes as the profiler sees them: host spans
``repro.*`` with their nesting and stats (read back from a CPU trace with
``ProfileData``), device scopes in the op metadata of the lowered programs,
and the names of the engine's programs and of every Pallas kernel."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import ARCHS, reduced
from repro.core.kernel_op import KernelOperator
from repro.core.krr import krr_sketched_fit, krr_sketched_fit_matfree
from repro.core.sketch import AccumSketch, make_accum_sketch
from repro.models.model import init_params
from repro.serve.engine import Engine, ServeConfig

KEY = jax.random.PRNGKey(0)
SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def _spans(fn, tmp_path):
    """Run ``fn`` under the profiler; return the ``repro.*`` host spans as
    (name, start, end, stats) in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                {k: int(v) for k, v in e.stats}))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parent(spans, child):
    """The innermost span enclosing ``child`` (None at the top)."""
    outer = [s for s in spans if s is not child and s[1] <= child[1]
             and child[2] <= s[2]]
    return min(outer, key=lambda s: s[2] - s[1])[0] if outer else None


def _data(n=256, p=4):
    X = jax.random.normal(KEY, (n, p))
    return X, jnp.sin(X[:, 0]) + 0.1 * X[:, 1]


@pytest.mark.parametrize("route", ["operator", "matfree"])
def test_krr_fit_and_predict_spans_nest(tmp_path, route):
    X, y = _data()
    op = KernelOperator(X, "gaussian", 1.0)
    fit = krr_sketched_fit if route == "operator" else krr_sketched_fit_matfree

    def job():
        sk = make_accum_sketch(jax.random.PRNGKey(1), X.shape[0], 32, 2)
        jax.block_until_ready(fit(op, y, 1e-3, sk).predict(X[:16]))

    job()                                        # compile outside the trace
    spans = _spans(job, tmp_path)
    assert [s[0] for s in spans] == [
        "repro.krr.draw", "repro.krr.fit", "repro.krr.sketch", "repro.krr.gram",
        "repro.krr.solve", "repro.krr.predict"]
    assert {s[0]: _parent(spans, s) for s in spans} == {
        "repro.krr.draw": None, "repro.krr.fit": None,
        "repro.krr.sketch": "repro.krr.fit", "repro.krr.gram": "repro.krr.fit",
        "repro.krr.solve": "repro.krr.fit", "repro.krr.predict": None}


@pytest.fixture(scope="module")
def built():
    cfg = reduced(ARCHS["stablelm-3b"])
    return cfg, init_params(KEY, cfg)


def _prompts(B, L, vocab):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, vocab))


def test_generate_spans_share_one_request_id(built, tmp_path):
    cfg, params = built
    eng = Engine(cfg, params, ServeConfig(max_len=32, cache_dtype=jnp.float32))
    prompts = _prompts(2, 8, cfg.vocab_size)
    eng.generate(prompts, 3)                     # request 1, outside the trace
    spans = _spans(lambda: eng.generate(prompts, 3), tmp_path)
    assert [s[0] for s in spans] == [
        "repro.engine.generate", "repro.engine.prefill", "repro.engine.first_token",
        "repro.engine.health_check", "repro.engine.decode"]
    assert all(_parent(spans, s) == "repro.engine.generate" for s in spans[1:])
    assert {s[3]["request"] for s in spans} == {2}
    assert spans[-1][3]["steps"] == 2


def test_generate_checkpoint_spans_only_when_armed(built, tmp_path):
    cfg, params = built
    sc = ServeConfig(max_len=32, cache_dtype=jnp.float32,
                     ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=1)
    eng = Engine(cfg, params, sc)
    prompts = _prompts(1, 8, cfg.vocab_size)
    eng.generate(prompts, 3)                     # unarmed: no request_id
    spans = _spans(lambda: eng.generate(prompts, 3, request_id="r"),
                   tmp_path / "trace")
    names = [s[0] for s in spans]
    # resume attempt, save after token 0, then one save per one-step chunk
    assert names.count("repro.engine.checkpoint") == 4
    assert names.count("repro.engine.decode") == 2
    assert {s[3]["request"] for s in spans} == {2}


def _scopes(lowered) -> set:
    """Every component of the name stacks in the compiled program's op
    metadata."""
    import re

    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    return {part for n in names for part in n.split("/")}


def _dense_fit(key, K, y):
    sk = make_accum_sketch(key, K.shape[0], 16, 2)
    model = krr_sketched_fit(K, y, 1e-3, sk, use_kernel=False)
    return model.fitted


def test_fit_hlo_carries_krr_scopes():
    X, y = _data(64)
    K = jnp.exp(-jnp.sum((X[:, None] - X[None]) ** 2, -1))
    lowered = jax.jit(_dense_fit).lower(KEY, K, y)
    assert {"krr.draw", "krr.fit", "krr.sketch", "krr.gram", "krr.solve"} <= _scopes(
        lowered)
    assert "/krr.fit/krr.gram/" in lowered.as_text(debug_info=True)


def test_predict_hlo_carries_krr_predict_scope():
    X, y = _data(64)
    op = KernelOperator(X, "gaussian", 1.0)
    model = krr_sketched_fit(op, y, 1e-3,
                             make_accum_sketch(KEY, 64, 16, 2), use_kernel=False)
    assert "krr.predict" in _scopes(jax.jit(lambda m, x: m.predict(x)).lower(model, X[:8]))


@pytest.fixture(scope="module")
def engine(built):
    cfg, params = built
    eng = Engine(cfg, params, ServeConfig(max_len=32, cache_dtype=jnp.float32))
    prompts = jnp.asarray(_prompts(2, 8, cfg.vocab_size))
    return eng, params, prompts


def test_decode_step_hlo_carries_model_scopes(engine):
    eng, params, prompts = engine
    cache = eng.new_cache(2)
    assert {"attention", "mlp", "head"} <= _scopes(
        eng._step.lower(params, cache, prompts[:, 0], jnp.int32(0), None))
    assert {"attention", "mlp", "head", "sample"} <= _scopes(
        eng._decode.lower(params, cache, prompts[:, 0], jnp.int32(8), n_steps=2))
    assert {"attention", "mlp", "head"} <= _scopes(
        eng._prefill.lower(params, cache, prompts, None))


def test_engine_program_names(engine):
    eng, params, prompts = engine
    cache = eng.new_cache(2)

    def module(lowered):
        return lowered.as_text().split("module @", 1)[1].split(" ", 1)[0]

    assert module(eng._prefill.lower(params, cache, prompts, None)) == \
        "jit_prefill_with_cache"
    assert module(eng._decode.lower(params, cache, prompts[:, 0], jnp.int32(8),
                                    n_steps=2)) == "jit__decode_scan"
    assert module(eng._step.lower(params, cache, prompts[:, 0], jnp.int32(0),
                                  None)) == "jit_decode_step"


def _sk(idx, coef, n):
    return AccumSketch(indices=idx, signs=jnp.sign(coef),
                       probs=jnp.full((n,), 1.0 / n, jnp.float32), n=n, coef_=coef)


def _kernel_cases():
    from repro.kernels.accum_apply.ops import (
        accum_grow_kernel,
        matfree_cols_kernel,
        sketch_both_kernel,
        sketch_left_kernel,
        sketch_right_kernel,
        sketch_step_kernel,
    )
    from repro.kernels.landmark_attention.ops import landmark_attend, landmark_stats_fused

    n, d, F, I = 256, 128, jnp.float32, jnp.int32
    return {
        "accum_apply": (lambda K, i, c: sketch_right_kernel(K, _sk(i, c, n), interpret=True),
                        ((n, n), F), ((2, d), I), ((2, d), F)),
        "accum_sketch_both": (lambda K, i, c: sketch_both_kernel(K, _sk(i, c, n),
                                                                 interpret=True),
                              ((n, n), F), ((2, d), I), ((2, d), F)),
        "accum_apply_left": (lambda M, i, c: sketch_left_kernel(_sk(i, c, n), M,
                                                                interpret=True),
                             ((n, d), F), ((2, d), I), ((2, d), F)),
        "accum_step_slab": (lambda K, i, c, C, a: sketch_step_kernel(K, i, c, C, a,
                                                                     interpret=True),
                            ((n, n), F), ((d,), I), ((d,), F), ((n, d), F), ((), F)),
        "accum_grow_slabs": (lambda K, i, c, C, a: accum_grow_kernel(K, i, c, C, a,
                                                                     interpret=True),
                             ((n, n), F), ((2, d), I), ((2, d), F), ((n, d), F), ((), F)),
        "matfree_apply": (lambda X, L, c: matfree_cols_kernel(X, L, c, kernel="gaussian",
                                                              interpret=True),
                          ((n, 8), F), ((2 * d, 8), F), ((2, d), F)),
        "landmark_attention": (lambda q, kt, M, b: landmark_attend(q, kt, M, b,
                                                                   interpret=True),
                               ((1, 16), F), ((d, 16), F), ((d, 16), F), ((d,), F)),
        "landmark_stats": (lambda qt, kt, k, v: landmark_stats_fused(qt, kt, k, v,
                                                                     interpret=True),
                           ((d, 16), F), ((d, 16), F), ((n, 16), F), ((n, 16), F)),
    }


@pytest.mark.parametrize("name", [
    "accum_apply", "accum_sketch_both", "accum_apply_left", "accum_step_slab",
    "accum_grow_slabs", "matfree_apply", "landmark_attention", "landmark_stats"])
def test_pallas_call_named(name):
    """Each kernel's ``pallas_call`` carries its own name, which becomes a
    component of its ops' name stacks (``jit(<entry>)/<name>/…``; the
    interpreter lowers the kernel body in place) and, on the TPU, the name
    of its custom call."""
    fn, *shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    assert name in _scopes(jax.jit(fn).lower(*args))


def test_one_trace_annotation_in_the_program():
    """Every program span goes through ``repro.spans``."""
    hits = [p.relative_to(SRC) for p in SRC.rglob("*.py")
            if "TraceAnnotation" in p.read_text()]
    assert hits == [pathlib.Path("repro/spans.py")]
