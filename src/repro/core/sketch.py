"""Algorithm 1 of the paper: sketching matrices as accumulations of m rescaled,
randomly-signed sub-sampling matrices.

The sketch is *structural*: we never materialize the n-by-d matrix S. It is fully
described by

  indices : (m, d) int32   — n_ij, the sampled row index of the single non-zero in
                             column j of the i-th sub-sampling matrix S_(i)
  signs   : (m, d) float   — r_ij, i.i.d. Rademacher
  probs   : (n,)   float   — the sampling distribution P (p_k)

so that  S = sum_i S_(i),  with  (S_(i))[:, j] = r_ij / sqrt(d * m * p_{n_ij}) e_{n_ij}.

Special cases:
  m = 1, uniform P, signs ignored  → classical Nyström sub-sampling sketch
  m → ∞                            → sub-Gaussian (Gaussian) sketch by the CLT

Grow API: ``append_subsample`` draws one more sub-sampling matrix (m → m+1,
survivors rescaled by sqrt(m/(m+1))), ``AccumSketch.truncated`` drops slabs
with the inverse renormalization, and ``AccumState`` is the pytree the
progressive accumulation engine (``repro.core.apply``) carries through
``lax.fori_loop``/``while_loop`` while growing (C, W) incrementally.

Sampling schemes: every constructor takes ``scheme=`` — ``"uniform"``
(default), ``"leverage"`` (caller-supplied or engine-refined ridge-leverage
probabilities), ``"poisson"`` (independent per-row inclusion, Horvitz–
Thompson normalized).  The draw mechanics live in ``repro.core.schemes``;
for Poisson sketches ``probs`` stores the EFFECTIVE per-row probability
π_i/d, which makes the universal coefficient r/√(d·m·p) equal the
Horvitz–Thompson r/√(m·π) with no special-casing anywhere downstream.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.spans import phase


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class AccumSketch:
    """Structural representation of an accumulation-of-sub-sampling sketch.

    ``coef_`` optionally carries the precomputed (m, d) combination
    coefficients.  The constructors populate it so hot loops (kernel entry
    points, PCG iterations, the progressive engine) never re-run the
    ``jnp.take(probs, indices)`` gather; ``coef`` falls back to computing it
    for hand-built sketches that leave it ``None``.
    """

    indices: jax.Array  # (m, d) int32
    signs: jax.Array    # (m, d) — ±1 (Poisson: {0, ±√(N/kept)})
    probs: jax.Array    # (n,) sampling distribution (Poisson: π/d)
    n: int              # ambient dimension (rows of S)
    coef_: jax.Array | None = None  # (m, d) cached r_ij / sqrt(d m p)
    scheme: str = "uniform"         # sampling scheme that drew this sketch

    # -- pytree plumbing ------------------------------------------------------
    def tree_flatten(self):
        """Flatten into (array leaves, static aux) for jax transformations."""
        return (self.indices, self.signs, self.probs, self.coef_), (
            self.n, self.scheme)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild from ``tree_flatten`` output (jax pytree protocol)."""
        indices, signs, probs, coef_ = children
        return cls(indices=indices, signs=signs, probs=probs, n=aux[0],
                   coef_=coef_, scheme=aux[1])

    # -- derived quantities ---------------------------------------------------
    @property
    def m(self) -> int:
        """Number of accumulated sub-sampling matrices (slabs)."""
        return self.indices.shape[0]

    @property
    def d(self) -> int:
        """Sketch dimension (columns of S)."""
        return self.indices.shape[1]

    @property
    def coef(self) -> jax.Array:
        """(m, d) combination coefficients r_ij / sqrt(d m p_{n_ij})."""
        if self.coef_ is not None:
            return self.coef_
        return _compute_coef(self.indices, self.signs, self.probs)

    def with_coef(self) -> "AccumSketch":
        """Copy with ``coef_`` populated (no-op if already cached)."""
        if self.coef_ is not None:
            return self
        return dataclasses.replace(self, coef_=self.coef)

    def truncated(self, m: int) -> "AccumSketch":
        """The sketch restricted to its first ``m`` sub-sampling matrices.

        The cached coefficients renormalize by sqrt(M/m) — each column's
        combination coefficient is r / sqrt(d·m·p), so dropping slabs *raises*
        the weight of the survivors (paper eq. after Alg. 1)."""
        if not 0 < m <= self.m:
            raise ValueError(f"cannot truncate m={self.m} sketch to m={m}")
        if m == self.m:
            return self
        coef_ = None
        if self.coef_ is not None:
            coef_ = self.coef_[:m] * jnp.sqrt(self.m / m).astype(self.coef_.dtype)
        return AccumSketch(indices=self.indices[:m], signs=self.signs[:m],
                           probs=self.probs, n=self.n, coef_=coef_,
                           scheme=self.scheme)

    def dense(self) -> jax.Array:
        """Materialize S (n, d) — O(n d), for tests/small problems only."""
        onehot = jax.nn.one_hot(self.indices, self.n, dtype=self.signs.dtype)  # (m,d,n)
        return jnp.einsum("mdn,md->nd", onehot, self.coef)

    def nnz_per_column(self) -> jax.Array:
        """Number of distinct non-zeros per column (≤ m); density diagnostic.

        Computed O(m²·d) from ``indices``/``coef`` directly — never the dense
        (n, d) S: for each column, group the m draws by sampled row (the m×m
        index-coincidence mask) and count the distinct rows whose summed
        coefficient is non-zero (colliding draws with cancelling signs are
        zeros in S, exactly as in the dense count)."""
        coef = self.coef
        eq = self.indices[:, None, :] == self.indices[None, :, :]   # (m, m, d)
        summed = jnp.sum(jnp.where(eq, coef[None, :, :], 0.0), axis=1)
        # entry i represents its row iff no earlier draw i' < i hit the same row
        earlier = jnp.tril(jnp.ones((self.m, self.m), bool), k=-1)
        seen = jnp.any(eq & earlier[:, :, None], axis=1)            # (m, d)
        return jnp.sum(~seen & (summed != 0), axis=0)


def _compute_coef(indices: jax.Array, signs: jax.Array, probs: jax.Array) -> jax.Array:
    m, d = indices.shape
    p = jnp.take(probs, indices, axis=0)  # (m, d)
    return signs / jnp.sqrt(d * m * p)


def _normalize_probs(probs: jax.Array | None, n: int,
                     dtype=jnp.float32) -> jax.Array:
    """The one shared probs-normalization path for EVERY sketch constructor.

    ``None`` → the uniform distribution; anything else is coerced to
    ``dtype`` and renormalized to sum 1, so unnormalized weight vectors are
    accepted identically everywhere (``make_accum_sketch``,
    ``make_accum_sketch_jit``, ``make_nystrom_sketch``, ``accum_init``, the
    Poisson inclusion map).

    Args:
        probs: (n,) nonnegative weights, or ``None`` for uniform.
        n: ambient dimension.
        dtype: dtype of the returned distribution.

    Returns:
        (n,) normalized sampling distribution.
    """
    if probs is None:
        return jnp.full((n,), 1.0 / n, dtype=dtype)
    probs = jnp.asarray(probs, dtype=dtype)
    return probs / jnp.sum(probs)


@phase("krr.draw")
def make_accum_sketch(
    key: jax.Array,
    n: int,
    d: int,
    m: int = 1,
    probs: jax.Array | None = None,
    *,
    scheme: str = "uniform",
    signed: bool = True,
    dtype=jnp.float32,
) -> AccumSketch:
    """Algorithm 1. Draw m*d indices from P with replacement + Rademacher signs.

    probs=None means the uniform distribution (classical Nyström when m=1).
    `signed=False` drops the Rademacher signs (pure Nyström; the paper notes the
    signs cancel in K S for m=1 anyway).

    ``scheme`` selects the sampling scheme (``repro.core.schemes``):
    ``"uniform"`` ignores ``probs``-as-scheme semantics (a provided ``probs``
    is still honored, as before), ``"leverage"`` requires an explicit
    ``probs`` vector here (the adaptive drivers estimate one from the sketch
    itself; this one-shot constructor cannot), and ``"poisson"`` draws each
    row independently with probability π_i = min(1, d·p_i), storing π/d as
    the per-row probability so the cached coef is the Horvitz–Thompson
    r/√(m·π).
    """
    from repro.core.schemes import poisson_inclusion, poisson_pieces, validate_scheme

    validate_scheme(scheme)
    if scheme == "poisson":
        pi = poisson_inclusion(probs, n, d, dtype=dtype)
        indices, signs = poisson_pieces(key, pi, m, d, dtype=dtype,
                                        signed=signed)
        probs_eff = (pi / d).astype(dtype)
        return AccumSketch(indices=indices, signs=signs, probs=probs_eff, n=n,
                           coef_=_compute_coef(indices, signs, probs_eff),
                           scheme=scheme)
    if scheme == "leverage" and probs is None:
        raise ValueError(
            "scheme='leverage' needs an explicit probs vector in the one-shot "
            "constructor — compute one with schemes.sketch_leverage_probs / "
            "leverage.leverage_probs, or use the adaptive drivers "
            "(grow_sketch_both / krr_sketched_fit_adaptive), which estimate "
            "and refine it from the sketch itself")
    probs = _normalize_probs(probs, n, dtype)
    kidx, ksgn = jax.random.split(key)
    indices = jax.random.choice(kidx, n, shape=(m, d), replace=True, p=probs)
    if signed:
        signs = jax.random.rademacher(ksgn, (m, d), dtype=dtype)
    else:
        signs = jnp.ones((m, d), dtype=dtype)
    indices = indices.astype(jnp.int32)
    return AccumSketch(indices=indices, signs=signs, probs=probs, n=n,
                       coef_=_compute_coef(indices, signs, probs),
                       scheme=scheme)


def append_subsample(sk: AccumSketch, key: jax.Array, *, signed: bool = True) -> AccumSketch:
    """Grow a sketch m → m+1 by drawing ONE new sub-sampling matrix from the
    same distribution P — the paper's accumulation step.

    The survivors' cached coefficients rescale by sqrt(m/(m+1)) (each column's
    normalization is 1/sqrt(d·m·p)), so S_{m+1} = sqrt(m/(m+1))·S_m + T_{m+1}.
    The grown sketch is a fresh draw, not a prefix of any single-key
    ``make_accum_sketch`` — use ``AccumState``/``accum_grow`` when the
    step-by-step trajectory must replay a one-shot construction exactly.

    Scheme-aware: a ``"poisson"`` sketch appends one more Poisson slab drawn
    with the SAME inclusion probabilities π = d·probs (the stored effective
    probabilities reconstruct π exactly); other schemes redraw with
    replacement from ``sk.probs`` as before."""
    kidx, ksgn = jax.random.split(key)
    if sk.scheme == "poisson":
        from repro.core.schemes import poisson_pieces

        pi = jnp.clip(sk.d * sk.probs, 1e-9, 1.0)   # probs stores π/d
        idx_new, sgn_new = poisson_pieces(kidx, pi, 1, sk.d,
                                          dtype=sk.signs.dtype, signed=signed)
    else:
        idx_new = jax.random.choice(kidx, sk.n, shape=(1, sk.d), replace=True,
                                    p=sk.probs).astype(jnp.int32)
        if signed:
            sgn_new = jax.random.rademacher(ksgn, (1, sk.d),
                                            dtype=sk.signs.dtype)
        else:
            sgn_new = jnp.ones((1, sk.d), dtype=sk.signs.dtype)
    indices = jnp.concatenate([sk.indices, idx_new], axis=0)
    signs = jnp.concatenate([sk.signs, sgn_new], axis=0)
    return AccumSketch(indices=indices, signs=signs, probs=sk.probs, n=sk.n,
                       coef_=_compute_coef(indices, signs, sk.probs),
                       scheme=sk.scheme)


def make_nystrom_sketch(key, n, d, probs=None, dtype=jnp.float32,
                        *, scheme: str = "uniform") -> AccumSketch:
    """m=1 special case — the classical (or leverage-weighted) Nyström sketch.

    Delegates to ``make_accum_sketch`` (m=1, unsigned), so ``probs`` gets the
    SAME normalization/dtype coercion as every other constructor —
    unnormalized weight vectors are accepted identically everywhere — and
    ``scheme`` threads through unchanged.
    """
    return make_accum_sketch(key, n, d, m=1, probs=probs, scheme=scheme,
                             signed=False, dtype=dtype)


def make_gaussian_sketch(key, n, d, dtype=jnp.float32) -> jax.Array:
    """Dense sub-Gaussian sketch (the m→∞ limit): i.i.d. N(0, 1/d)."""
    return jax.random.normal(key, (n, d), dtype=dtype) / jnp.sqrt(d)


def make_sparse_rp(key, n, d, s: float | None = None, dtype=jnp.float32) -> jax.Array:
    """Very sparse random projection (Li, Hastie, Church 2006).

    Entries are sqrt(s/d)·{+1 w.p. 1/(2s), -1 w.p. 1/(2s), 0 otherwise}.
    Default s = sqrt(n) (their recommended density). Returned dense — it is a
    *baseline*, the paper's method never materializes its sketch.
    """
    if s is None:
        s = float(jnp.sqrt(n))
    ku, ks = jax.random.split(key)
    u = jax.random.uniform(ku, (n, d))
    sgn = jax.random.rademacher(ks, (n, d), dtype=dtype)
    mask = (u < 1.0 / s).astype(dtype)
    return sgn * mask * jnp.sqrt(s / d).astype(dtype)


@partial(jax.jit, static_argnames=("n", "d", "m", "signed", "dtype", "scheme"))
def _jit_make(key, n, d, m, probs, signed, dtype, scheme):
    return make_accum_sketch(key, n, d, m, probs, scheme=scheme,
                             signed=signed, dtype=dtype)


def make_accum_sketch_jit(key, n, d, m=1, probs=None, signed=True,
                          dtype=jnp.float32, *,
                          scheme: str = "uniform") -> AccumSketch:
    """jit'd constructor (probs must be a concrete array or None).

    ``dtype`` propagates to signs/probs/coef exactly as in the eager
    constructor (the seed version silently pinned float32), ``probs`` gets
    the same normalization (``_normalize_probs`` runs inside the traced
    constructor), and ``scheme`` rides as a static argument."""
    if probs is None:
        if scheme == "leverage":
            # same contract as the eager constructor (whose message explains
            # where leverage probs come from) — filling uniform here would
            # silently change the scheme
            make_accum_sketch(key, n, d, m, None, scheme=scheme)
        probs = jnp.full((n,), 1.0 / n, dtype=dtype)
    return _jit_make(key, n, d, m, probs, signed, jnp.dtype(dtype).name,
                     scheme)


# --------------------------------------------------------------------------- #
# Progressive accumulation state
# --------------------------------------------------------------------------- #

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class AccumState:
    """State of the progressive accumulation engine after ``m`` steps.

    Carried through ``lax.fori_loop``/``lax.while_loop`` by
    ``repro.core.apply.accum_grow``/``accum_grow_adaptive``: all m_max
    sub-sampling matrices are pre-drawn (same RNG scheme as
    ``make_accum_sketch``, so growing all the way to m_max replays
    ``make_accum_sketch(key, n, d, m_max)`` bit-for-bit; intermediate m are a
    prefix of THAT draw, not of a one-shot draw at m), and each step folds
    slab ``m`` into the running, *currently normalized* accumulators

        C = K S_m   (n, d)      W = S_mᵀ K S_m   (d, d)

    in O(n·d) — one column gather of K plus a rescale — instead of the
    O(n·m·d) from-scratch recompute per candidate m.  ``err`` holds the latest
    value of the plug-in stopping estimate (+inf until first evaluated).

    ``pdraw`` records the per-entry probability AT DRAW TIME — for fixed
    distributions it equals ``take(probs, indices)``, but the leverage scheme
    refines ``probs`` while m grows (``schemes.refresh_tail``), and the
    normalization of already-accumulated slabs must keep the probabilities
    they were actually drawn with.  The engine's coefficient gathers
    (``apply.slab_pieces``/``batch_pieces``, ``masked_sketch``) read
    ``pdraw``, never ``take(probs, indices)``.
    """

    indices: jax.Array   # (m_max, d) int32 — rows ≥ m not yet accumulated
    signs: jax.Array     # (m_max, d)
    probs: jax.Array     # (n,) current sampling distribution
    pdraw: jax.Array     # (m_max, d) per-entry probability at draw time
    C: jax.Array         # (n, d) float32 running K S_m
    W: jax.Array         # (d, d) float32 running Sᵀ K S_m
    m: jax.Array         # () int32 — number of slabs folded in so far
    err: jax.Array       # () float32 — latest stopping-rule estimate
    n: int               # static ambient dimension
    scheme: str = "uniform"  # sampling scheme driving the draws

    def tree_flatten(self):
        """Flatten into (array leaves, static aux) for jax transformations."""
        return (self.indices, self.signs, self.probs, self.pdraw, self.C,
                self.W, self.m, self.err), (self.n, self.scheme)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild from ``tree_flatten`` output (jax pytree protocol)."""
        return cls(*children, n=aux[0], scheme=aux[1])

    @property
    def m_max(self) -> int:
        """Number of pre-drawn slabs (static upper bound on m)."""
        return self.indices.shape[0]

    @property
    def d(self) -> int:
        """Sketch dimension (columns of S)."""
        return self.indices.shape[1]

    def grow_batched(self, K, B: int, *, use_kernel: bool | None = None,
                     mesh=None, donate: bool = True) -> "AccumState":
        """Fold the next ``B`` pre-drawn slabs into (C, W) in ONE pass over
        the data (``repro.core.apply.accum_grow_batched`` — lazy import, the
        engine lives there): bitwise-identical draws to B sequential steps,
        one read of K (or one kernel-eval sweep over X) instead of B."""
        from repro.core.apply import accum_grow_batched

        return accum_grow_batched(K, self, B, use_kernel=use_kernel,
                                  mesh=mesh, donate=donate)

    def sketch(self) -> AccumSketch:
        """The AccumSketch accumulated so far (host-side: m must be concrete).

        Coefficients come from ``pdraw`` — the probabilities each slab was
        actually drawn with — so leverage-refined growth (where ``probs``
        has since moved on) stays correctly normalized.  ``coef_`` is the
        authoritative normalization on the result."""
        m = int(self.m)
        if m == 0:
            raise ValueError("no sub-sampling matrices accumulated yet")
        coef = self.signs[:m] / jnp.sqrt(self.d * m * self.pdraw[:m])
        return AccumSketch(indices=self.indices[:m], signs=self.signs[:m],
                           probs=self.probs, n=self.n, coef_=coef,
                           scheme=self.scheme)

    def masked_sketch(self) -> AccumSketch:
        """Trace-safe equivalent of ``sketch()``: the FULL (m_max, d) sketch
        with slabs ≥ m zero-masked and the survivors renormalized for the
        accumulated size m (coef = r/sqrt(d·m·p)).

        Every structural application is bilinear in ``coef`` (K S, Sᵀ M,
        stream_cols, dense()), so zero-coefficient slabs contribute nothing
        and the masked sketch applies EXACTLY like ``sketch()``'s truncation —
        but with static shapes, so it works when ``m`` is a tracer (jitted
        ``grow_sketch_both`` drivers).  Note ``.m`` reads m_max on the result;
        the accumulated count lives in the caller's ``info["m"]``."""
        mf = jnp.maximum(self.m.astype(jnp.float32), 1.0)
        p = self.pdraw.astype(jnp.float32)   # at-draw probs (leverage refines)
        coef = self.signs.astype(jnp.float32) / jnp.sqrt(self.d * mf * p)
        mask = jnp.arange(self.m_max)[:, None] < self.m
        return AccumSketch(
            indices=self.indices,
            signs=jnp.where(mask, self.signs, 0.0),
            probs=self.probs, n=self.n,
            coef_=jnp.where(mask, coef, 0.0),
            scheme=self.scheme)
