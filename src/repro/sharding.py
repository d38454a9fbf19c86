"""Sharding rules: map (param path, shape) → PartitionSpec on the production mesh.

Policy (DP/FSDP/TP/EP/SP composed):
  * batch axes            → ("pod","data")  (DP; pod composes with data)
  * parameter "fsdp" dim  → ("pod","data")  (ZeRO-3-style weight sharding; XLA
                            all-gathers per scan step, overlapped by the
                            latency-hiding scheduler)
  * parameter "tensor" dim→ "model"         (TP: heads / FFN inner / vocab)
  * MoE expert dim        → "model"         (EP)
  * long-context KV cache → sequence dim on "model" when head dims don't
                            divide (SP fallback)

Every assignment is divisibility-checked against the mesh; non-divisible dims
fall back to replication (never a compile error on exotic head counts).
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def data_axes(mesh: Mesh, policy: str = "default"):
    """The combined DP/FSDP axes: ("pod","data") on multi-pod, ("data",) else.
    Under "dp_only" the model axis joins them (no TP anywhere)."""
    names = ("pod", "data", "model") if policy == "dp_only" else ("pod", "data")
    return tuple(a for a in names if a in mesh.shape)


def _fit(mesh: Mesh, dim: int, axes):
    """Return the contiguous sub-tuple of `axes` with the LARGEST device count
    whose size divides `dim`, else None (replicate). Size-1 results are
    dropped (sharding over them is replication anyway — keeping specs None on
    debug meshes keeps the HLO and tests clean). Largest-first keeps the most
    parallelism — e.g. batch=256 on the 2×16×16 multi-pod mesh under dp_only
    picks ("data","model")=256-way, not ("pod","data")=32-way; earlier
    sub-tuples win ties so the leading (outermost) axes are preferred."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    n = len(axes)
    best, best_sz = None, 1
    for k in range(n, 0, -1):
        for start in range(n - k + 1):
            sub = axes[start:start + k]
            sz = _axis_size(mesh, sub)
            if sz > best_sz and dim % sz == 0:
                best, best_sz = sub, sz
    if best is None:
        return None
    return best if len(best) > 1 else best[0]


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def param_spec(mesh: Mesh, path_str: str, shape: tuple[int, ...],
               policy: str = "default") -> P:
    fsdp = data_axes(mesh, policy)
    tp = "model" if "model" in mesh.shape and policy != "dp_only" else None
    stacked = "/blocks/" in f"/{path_str}/"  # leading superblock axis
    dims = list(shape[1:]) if stacked else list(shape)
    lead = [None] if stacked else []

    def spec(*entries):
        return P(*lead, *entries)

    name = path_str.rsplit("/", 1)[-1]
    nd = len(dims)

    if nd <= 1:
        return spec(*([None] * nd))

    # --- MoE experts: (E, D, F) / (E, F, D) — EP on E, FSDP on D ---------- #
    if nd == 3 and name in ("wi_gate", "wi_up", "wo") and "ffn" in path_str:
        e = _fit(mesh, dims[0], tp)
        if name == "wo":   # (E, F, D)
            return spec(e, None, _fit(mesh, dims[2], fsdp))
        return spec(e, _fit(mesh, dims[1], fsdp), None)

    # --- xLSTM per-head recurrent mats (H, Dh, Dh) ------------------------- #
    if nd == 3 and name.startswith("r"):
        return spec(_fit(mesh, dims[0], tp), None, None)

    # --- embeddings: (V, D) — vocab on TP, D on FSDP ----------------------- #
    if name in ("embed", "lm_head"):
        return spec(_fit(mesh, dims[0], tp), _fit(mesh, dims[1], fsdp))

    # --- 2-D projections ---------------------------------------------------- #
    if nd == 2:
        # output projections: contract dim is TP-sharded
        if name in ("wo", "out_proj"):
            return spec(_fit(mesh, dims[0], tp), _fit(mesh, dims[1], fsdp))
        if name == "conv_w":
            return spec(None, _fit(mesh, dims[1], tp))
        if name == "router":
            return spec(_fit(mesh, dims[0], fsdp), None)
        # input projections (wq/wk/wv/wi_*/in_proj/wz/wi/wf/wog/...):
        return spec(_fit(mesh, dims[0], fsdp), _fit(mesh, dims[1], tp))

    return spec(*([None] * nd))


def params_shardings(mesh: Mesh, params: PyTree, policy: str = "default") -> PyTree:
    def one(path, x):
        return NamedSharding(
            mesh, param_spec(mesh, _path_str(path), x.shape, policy))

    return jax.tree_util.tree_map_with_path(one, params)


def opt_shardings(mesh: Mesh, opt_state: PyTree, params_sh: PyTree) -> PyTree:
    """ZeRO-1: m/v/master inherit the param shardings; step is replicated."""
    from repro.optim.adamw import AdamWState

    rep = NamedSharding(mesh, P())
    return AdamWState(
        step=rep,
        m=params_sh, v=params_sh, master=params_sh,
    )


def batch_spec(mesh: Mesh, batch: int, *, extra_dims: int = 1,
               policy: str = "default") -> P:
    b = _fit(mesh, batch, data_axes(mesh, policy))
    return P(b, *([None] * extra_dims))


def cache_shardings(mesh: Mesh, cache: PyTree, batch: int,
                    policy: str = "default") -> PyTree:
    """Decode caches: batch → DP axes; if batch doesn't divide, shard the
    sequence/slot axis (SP) or heads on "model"."""
    fsdp = data_axes(mesh, policy)
    tp = "model" if "model" in mesh.shape and policy != "dp_only" else None

    def one(x):
        # leading superblock axis then (B, ...) — cache leaves are stacked
        dims = x.shape[1:]
        b = _fit(mesh, dims[0], fsdp)
        rest = [None] * (len(dims) - 1)
        # shard the largest remaining dim on model (seq for KV, slots for
        # sketch caches, heads for states) if divisible
        if len(rest) > 0 and tp is not None:
            sizes = list(dims[1:])
            order = sorted(range(len(sizes)), key=lambda i: -sizes[i])
            for i in order:
                if sizes[i] % _axis_size(mesh, tp) == 0:
                    rest[i] = tp
                    break
        return NamedSharding(mesh, P(None, b, *rest))

    return jax.tree_util.tree_map(one, cache)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# --------------------------------------------------------------------------- #
# Activation constraints (used inside model code; no-ops without a mesh)
# --------------------------------------------------------------------------- #

def _current_mesh():
    from jax._src import mesh as mesh_lib

    m = mesh_lib.thread_resources.env.physical_mesh
    return None if m.empty else m


def model_split(policy: str = "default") -> bool:
    """Whether the current mesh splits arrays over a "model" axis of more than
    one device under `policy`, as `cache_shardings` splits decode caches."""
    m = _current_mesh()
    return m is not None and policy != "dp_only" and m.shape.get("model", 1) > 1


def constrain(x: jax.Array, *entries, policy: str = "default") -> jax.Array:
    """with_sharding_constraint that (a) is a no-op outside a mesh context and
    (b) drops axes absent from the mesh / non-divisible dims. Entries use the
    logical names "dp" (pod+data; +model under dp_only) and "tp" (model), or
    None.

    This pins the scan carry: without it, SPMD propagation lets the embedding's
    FSDP sharding leak into activations (batch-replicated loop carries)."""
    m = _current_mesh()
    if m is None:
        return x
    fsdp = data_axes(m, policy)
    tp = "model" if "model" in m.shape and policy != "dp_only" else None
    spec = []
    for dim, e in zip(x.shape, entries):
        if e == "dp":
            spec.append(_fit(m, dim, fsdp))
        elif e == "tp":
            spec.append(_fit(m, dim, tp))
        elif e == "sp":
            # sequence parallelism: shard the sequence dim over the model
            # axis so per-block TP output all-reduces become reduce-scatter +
            # all-gather pairs (half the bytes) and norms/elementwise run on
            # 1/|model| of the tokens (Megatron-SP). Dropped under dp_only or
            # when the dim doesn't divide (decode: S=1 → replicated).
            spec.append(_fit(m, dim, tp))
        elif e == "tp!":
            # force model-axis sharding even when the dim doesn't divide —
            # XLA pads the trailing shards. Used to pin HEAD-ALIGNED q/k/v
            # sharding: without it, SPMD inherits the flat (H·Dh)/|model|
            # column sharding from the projection GEMM, splits head_dim, and
            # the QKᵀ contraction goes partial → a (B,Hkv,G,q,S)-sized
            # all-reduce per query chunk per layer.
            spec.append(tp if tp is not None and m.shape.get("model", 1) > 1 else None)
        else:
            spec.append(None)
    spec += [None] * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(m, P(*spec)))
