"""Each plain reference against the system's own path at a tiny size on the
CPU, in float32 or better, so that a difference in the equations (not in
rounding) would show."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.gen import lm_weights
from bench.gen.regression import regression_data
from bench.reference import krr as krr_ref
from bench.reference import lm as lm_ref
from bench.runners import lm_serve
from bench.tests import tiny


def test_lm_reference_matches_the_program_in_float32():
    from repro.models.model import forward

    m = tiny.LM_CONFIG["model"]
    s = lm_weights.sizes(m)
    w = lm_weights.make(jax.random.PRNGKey(5), s, jnp.float32)
    tokens = np.random.default_rng(5).integers(0, m["vocab_size"], (1, 12), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        h, _ = forward(lm_serve.program_params(w), jnp.asarray(tokens),
                       lm_serve.model_config(tiny.LM_CONFIG), remat="none")
        prog = jnp.einsum("sd,vd->sv", h[0], w["head"])
    ref = lm_ref.logits(w, tokens[0], np.arange(12), m)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(prog), rtol=2e-5, atol=2e-5)


def test_lm_control_rounds_to_fp8():
    x = jnp.array([[1.0, 1.0 / 3.0, -0.3, 448.0]])
    q = lm_ref._q8(x, -1)
    assert q[0, 3] == 448.0 and q[0, 0] == 1.0
    assert 0 < abs(float(q[0, 1]) - 1 / 3) < 1 / 3 * 2**-3


def test_served_gaps():
    lg = np.array([[0.0, 2.0, 1.5], [3.0, 1.0, 0.0]])
    assert list(lm_ref.served_gaps(lg, [1, 2])) == [0.0, 3.0]
    assert np.isinf(lm_ref.served_gaps(lg, [1, 3])).all()


def test_krr_reference_matches_the_program():
    from repro.core.kernel_op import KernelOperator
    from repro.core.krr import krr_sketched_fit
    from repro.core.sketch import make_accum_sketch

    c = tiny.KRR_CONFIG
    Xtr, ytr, Xte, _ = regression_data(jax.random.PRNGKey(3), c["n_train"],
                                       c["n_test"], c["p"], c["bandwidth"])
    sk = make_accum_sketch(jax.random.PRNGKey(4), c["n_train"], c["sketch_d"],
                           c["sketch_m"])
    model = krr_sketched_fit(KernelOperator(Xtr, "gaussian", c["bandwidth"]), ytr,
                             1e-4, sk)
    pred = model.predict(Xte)
    # the generic form of the reference, in float64 NumPy
    generic = krr_ref.fit_xp(
        *(np.asarray(a, np.float64) for a in (Xtr, ytr, Xte)), np.asarray(sk.indices),
        np.asarray(sk.signs, np.float64), 1e-4, c["bandwidth"], xp=np, mm=np.matmul,
        solve=np.linalg.solve, block=700)
    ref = krr_ref.fit_f64(Xtr, ytr, Xte, sk.indices, sk.signs, 1e-4, c["bandwidth"],
                          probes=np.stack([np.asarray(model.theta), generic.theta]),
                          block=512)
    assert krr_ref._rel(pred, ref.pred) < 1e-4
    assert krr_ref.solve_residual(ref, model.theta) < 1e-5
    assert krr_ref.fitted_error(ref, 0, model.fitted) < 1e-6
    assert krr_ref.predict_error(ref, model.theta, pred) < 1e-6
    # both forms of the reference compute the same sums
    assert krr_ref._rel(generic.pred, ref.pred) < 1e-10
    assert krr_ref.fitted_error(ref, 1, generic.fitted) < 1e-12


def test_krr_reference_refuses_draws_out_of_range():
    X = np.zeros((10, 2))
    with pytest.raises(ValueError):
        krr_ref.fit_f64(X, np.zeros(10), X, np.array([[10]]), np.array([[1.0]]),
                        1e-3, 1.0)
