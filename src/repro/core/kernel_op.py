"""Matrix-free kernel operator — the accumulation sketch applied to a DATASET.

Every earlier path in the repo took a materialized n×n kernel matrix K,
capping n at ~10⁴ on a single host and contradicting the paper's point:
accumulation controls the *effective* matrix size, so the n×n object should
never exist.  ``KernelOperator`` represents K = k(X, X) by the data ``X`` and
the kernel's name/bandwidth (``core/kernels_math.py``) and computes

    C = K S           (n, d)   — row-streamed kernel-eval → contraction
    W = Sᵀ K S = SᵀC  (d, d)   — row gathers of C, no extra kernel evals

directly from X in row tiles: per tile, the (tile, m·d) kernel block against
the sketch's landmark rows is evaluated and immediately contracted with the
combination coefficients, so peak memory is O(tile · m·d) — never O(n²).
Two backends share the arithmetic:

  * a fused Pallas kernel (``kernels/accum_apply/matfree_apply``) doing the
    sqdist → kernel → GEMM pipeline per grid tile (MXU path on TPU), and
  * a ``lax.scan`` streaming jnp path for CPU/AD, chunked so the jaxpr stays
    O(1) in n.

The progressive accumulation engine, KRR solvers, and spectral clustering all
accept a ``KernelOperator`` wherever they accept a dense K (``repro.core
.apply`` dispatches), including the engine's column-slab increments, the
plug-in stopping estimators, and the matrix-free predict path
K(X_test, landmarks)·θ.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import apply as A
from repro.core.kernels_math import f32_einsum, f32_matmul, get_kernel
from repro.core.sketch import AccumSketch

# dense() materializes the n×n kernel — refuse above this n unless forced
# (at n = 32768 the f32 matrix is already 4 GiB; the sqdist intermediates
# triple that)
DENSE_GUARD_N = 32768


def _scan_row_chunks(X: jax.Array, chunk: int | None, block_fn) -> jax.Array:
    """Row-streaming scaffold: ``block_fn`` maps a (b, p) row block to a
    (b, c) result; full chunks ride a ``lax.scan`` (jaxpr O(1) in the number
    of chunks) and the ragged tail gets one extra call.  ``chunk=None`` or
    small inputs take a single unstreamed block."""
    n, p = X.shape
    if chunk is None or n <= chunk:
        return block_fn(X)
    nfull = (n // chunk) * chunk

    def body(carry, xb):
        return carry, block_fn(xb)

    _, out = jax.lax.scan(body, None, X[:nfull].reshape(-1, chunk, p))
    out = out.reshape(nfull, -1)
    if nfull < n:
        out = jnp.concatenate([out, block_fn(X[nfull:])], axis=0)
    return out


def stream_cols(
    Xq: jax.Array, landmarks: jax.Array, coef: jax.Array, kernel_fn,
    *, chunk: int | None = None,
) -> jax.Array:
    """C = K(Xq, ·)·S from raw rows: the (b, m·d) kernel slab of each row
    chunk against the landmark rows, contracted with the combination
    coefficients.  ``chunk`` streams the rows through a ``lax.scan`` (jaxpr
    stays O(1) in the number of chunks) so peak memory is O(chunk · m·d)
    regardless of how large Xq is.  Returns (nq, d), f32-accumulated (f64
    inputs stay f64)."""
    m, d = coef.shape
    # accumulate in f32 at least; keep f64 when the caller runs in x64 mode
    acc_t = jnp.promote_types(jnp.float32, jnp.result_type(Xq.dtype, coef.dtype))
    coef_a = coef.astype(acc_t)

    def _block(xb):
        slab = kernel_fn(xb, landmarks).astype(acc_t)           # (b, m·d)
        return f32_einsum("bmd,md->bd", slab.reshape(xb.shape[0], m, d), coef_a)

    return _scan_row_chunks(Xq, chunk, _block)


def stream_cols_slabs(
    Xq: jax.Array, landmarks: jax.Array, coef: jax.Array, kernel_fn,
    *, chunk: int | None = None,
) -> jax.Array:
    """Multi-slab C = K(Xq, ·)·S accumulated SLAB-BY-SLAB — the batched
    engine's streaming twin.

    A ``lax.scan`` over the m slabs evaluates each slab's (chunk, d) kernel
    blocks at the NARROW GEMM shape the row-streamed backends are fastest at
    and folds them into the (nq, d) accumulator: the (nq, m·d) wide slab of
    ``stream_cols`` never exists, and peak memory is O(nq·d + chunk·d).
    Measured on the CPU bench host, XLA's wide-output GEMM tiling degrades
    ~2× by m·d = 1024, so at batch sizes B ≥ 2 this formulation is the fast
    one (the Pallas matfree kernel keeps the wide block — the MXU wants it).
    Returns (nq, d), f32-accumulated (f64 inputs stay f64)."""
    m, d = coef.shape
    p = Xq.shape[-1]
    acc_t = jnp.promote_types(jnp.float32, jnp.result_type(Xq.dtype, coef.dtype))
    if chunk is None:
        # the (chunk, d) kernel block is the transient peak — same ~16 MiB
        # budget as everywhere else
        chunk = max(8, (4 * 1024 * 1024) // max(d, 1))
    lmr = landmarks.reshape(m, d, p)
    cf = coef.astype(acc_t)

    def body(acc, slab):
        lm_b, cf_b = slab

        def blk(xb):
            return kernel_fn(xb, lm_b).astype(acc_t)

        return acc + _scan_row_chunks(Xq, chunk, blk) * cf_b[None, :], None

    acc0 = jnp.zeros((Xq.shape[0], d), acc_t)
    acc, _ = jax.lax.scan(body, acc0, (lmr, cf))
    return acc


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class KernelOperator:
    """K = k(X, X) as an operator: data + kernel name, never the matrix.

    ``kernel``/``bandwidth``/``nu`` are static (pytree aux) so the operator
    jits like an array; ``X`` is the only leaf.  ``chunk=None`` lets each
    method pick a row-chunk bounding the kernel slab at ~16 MiB."""

    X: jax.Array                 # (n, p) dataset rows
    kernel: str = "gaussian"
    bandwidth: float = 1.0
    nu: float = 1.5              # matern only

    def tree_flatten(self):
        """Pytree leaf = X; kernel name/bandwidth/nu are static aux."""
        return (self.X,), (self.kernel, self.bandwidth, self.nu)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Inverse of ``tree_flatten`` (jax pytree protocol)."""
        return cls(X=children[0], kernel=aux[0], bandwidth=aux[1], nu=aux[2])

    # -- array-like surface (what apply/krr/spectral touch on a dense K) ------
    @property
    def n(self) -> int:
        """Number of dataset rows (= both dims of the represented K)."""
        return self.X.shape[0]

    @property
    def shape(self) -> tuple[int, int]:
        """(n, n) — the shape of the NEVER-materialized Gram matrix."""
        return (self.n, self.n)

    @property
    def dtype(self):
        """dtype of the represented K (= the dataset's dtype)."""
        return self.X.dtype

    @property
    def kernel_fn(self):
        """(a, p), (b, p) → (a, b) kernel matrix — ``core.kernels_math``."""
        return get_kernel(self.kernel, self.bandwidth, self.nu)

    def _auto_chunk(self, md: int) -> int:
        # f32 slab (chunk, md) ≤ ~16 MiB.  The floor is the same small
        # constant ``matvec`` uses — a 256-row floor would let the slab grow
        # past the budget whenever m·d is large (a (256, 65536) f32 slab is
        # 64 MiB), exactly the failure matvec's chunk comment warns about.
        return max(8, (4 * 1024 * 1024) // max(md, 1))

    # -- kernel-block primitives ----------------------------------------------
    def submatrix(self, rows: jax.Array, cols: jax.Array) -> jax.Array:
        """K[rows][:, cols] from |rows|·|cols| kernel evaluations."""
        return self.kernel_fn(jnp.take(self.X, rows, axis=0),
                              jnp.take(self.X, cols, axis=0))

    def weighted_cols(
        self, Xq: jax.Array, idx: jax.Array, coef: jax.Array, *,
        chunk: int | None = None, use_kernel: bool | None = None,
        mesh=None,
    ) -> jax.Array:
        """K(Xq, ·)·S for the sketch described by idx/coef (m, d) — the core
        primitive behind C, the engine's slab increments, and prediction.

        ``use_kernel`` (auto: True on TPU) routes through the fused Pallas
        kernel-eval→GEMM kernel; otherwise the ``lax.scan`` streaming path.
        ``mesh`` row-shards Xq over a ``("data",)`` device mesh: each device
        computes its tile through the same backend with the landmarks
        replicated (``repro.core.distributed``)."""
        if mesh is not None:
            from repro.core import distributed as D

            return D.sharded_weighted_cols(
                self, Xq, idx, coef, D.resolve_mesh(mesh), chunk=chunk,
                use_kernel=use_kernel)
        if use_kernel is None:
            use_kernel = A.default_use_kernel()
        lm = jnp.take(self.X, idx.reshape(-1), axis=0)
        if chunk is None:
            # always budget by SLAB size, not row count: an (nq, m·d) slab
            # blows the ~16 MiB budget at large m·d even when nq is small
            # (nq ≤ _auto_chunk(m·d) degrades to a single unstreamed block,
            # so small problems pay no scan overhead)
            chunk = self._auto_chunk(idx.size)

        def _stream():
            from repro.resilience import faults

            faults.fault_point("kernel.stream")
            return stream_cols(Xq, lm, coef, self.kernel_fn, chunk=chunk)

        if use_kernel:
            from repro.kernels.accum_apply.ops import matfree_cols_kernel
            from repro.resilience.degrade import ladder_call

            # three-rung ladder: fused Pallas kernel → XLA lax.scan streaming
            # → one dense unstreamed slab (only when it fits the dense guard).
            # Each rung drop is recorded in the global HealthReport.
            rungs = [
                ("pallas:matfree_cols",
                 lambda: matfree_cols_kernel(Xq, lm, coef, kernel=self.kernel,
                                             bandwidth=self.bandwidth,
                                             nu=self.nu)),
                ("xla:stream_cols", _stream),
            ]
            if Xq.shape[0] * idx.size <= DENSE_GUARD_N * 1024:
                rungs.append(
                    ("dense:one-slab",
                     lambda: stream_cols(Xq, lm, coef, self.kernel_fn,
                                         chunk=Xq.shape[0]))
                )
            return ladder_call("kernel.dispatch", rungs)
        return _stream()

    # -- sketched applications ------------------------------------------------
    def sketch_cols(self, sk: AccumSketch, *, chunk: int | None = None,
                    use_kernel: bool | None = None, mesh=None) -> jax.Array:
        """C = K S (n, d) — O(n·m·d) kernel evaluations, O(n·d) memory
        (O(n/D · d) per device under ``mesh``)."""
        return self.weighted_cols(self.X, sk.indices, sk.coef, chunk=chunk,
                                  use_kernel=use_kernel, mesh=mesh)

    def cross_cols(self, Xq: jax.Array, sk: AccumSketch, *,
                   chunk: int | None = None,
                   use_kernel: bool | None = None, mesh=None) -> jax.Array:
        """K(Xq, X)·S (nq, d) — the matrix-free predict path: test rows only
        ever meet the m·d landmark rows, never the training Gram matrix."""
        return self.weighted_cols(Xq, sk.indices, sk.coef, chunk=chunk,
                                  use_kernel=use_kernel, mesh=mesh)

    def sketch_both(
        self, sk: AccumSketch, *, chunk: int | None = None,
        use_kernel: bool | None = None, mesh=None,
    ) -> tuple[jax.Array, jax.Array]:
        """(C, W) = (K S, SᵀK S) without forming K.

        W = SᵀC is a row gather of the already-computed C (the sketch's
        non-zero rows are exactly the landmark rows), so it costs O(m·d²) on
        top of C — the same arithmetic as the dense path, which is what the
        golden dense ≡ matrix-free equivalence tests pin.  ``mesh`` computes
        both per data shard in one mapped launch (W psum-reduced)."""
        if mesh is not None:
            from repro.core import distributed as D

            return D.sharded_sketch_both(self, sk, D.resolve_mesh(mesh),
                                         chunk=chunk, use_kernel=use_kernel)
        C = self.sketch_cols(sk, chunk=chunk, use_kernel=use_kernel)
        return C, A.sketch_left(sk, C)

    def matvec(self, Z: jax.Array, *, chunk: int | None = None,
               mesh=None) -> jax.Array:
        """K @ Z streamed over row chunks — O(chunk·n) peak memory, O(n²·p)
        compute.  Only for estimators that genuinely need full matvecs
        (Hutchinson probes); sketched paths never call this.  ``mesh``
        splits the row streaming over the data shards."""
        if mesh is not None:
            from repro.core import distributed as D

            return D.sharded_matvec(self, Z, D.resolve_mesh(mesh),
                                    chunk=chunk)
        Zm = Z[:, None] if Z.ndim == 1 else Z
        n = self.n
        if chunk is None:
            # the (chunk, n) slab is the peak allocation — keep it ~16 MiB
            # even at n where a 256-row floor would let it grow to O(n)·256
            chunk = max(8, (4 * 1024 * 1024) // max(n, 1))
        kf = self.kernel_fn
        Z32 = Zm.astype(jnp.float32)

        def _block(xb):
            return f32_matmul(kf(xb, self.X).astype(jnp.float32), Z32)

        out = _scan_row_chunks(self.X, chunk, _block)
        return out[:, 0] if Z.ndim == 1 else out

    def dense(self, *, force: bool = False) -> jax.Array:
        """Materialize K (n, n) — tests and small problems ONLY.

        Refused above ``DENSE_GUARD_N`` rows unless ``force=True``: the whole
        point of this layer is that the n×n object never exists."""
        if self.n > DENSE_GUARD_N and not force:
            raise ValueError(
                f"refusing to materialize the {self.n}×{self.n} kernel matrix "
                f"(~{self.n * self.n * 4 / 2**30:.0f} GiB as f32); use the "
                "matrix-free sketched paths, or pass force=True if you really "
                "have the memory")
        return self.kernel_fn(self.X, self.X)
