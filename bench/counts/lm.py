"""Work of a dense decoder LM (pre-norm attention + SwiGLU MLP blocks,
untied output head), from its sizes (``lm_weights.sizes``).

Operations count every multiply-add of the projections, the MLP, the causal
attention over the keys a position may see, and the output head where its
logits are needed: at every decode step, and once per sequence at the end
of a prefill.  Norms, rotary embedding and softmax are not counted.  Bytes
count what a decode step must read: every weight but the embedding table
(of which it reads one row per sequence), and the keys and values of the
valid positions, at the configured types; it writes one position.  Decode
is memory-bound, prefill compute-bound.
"""


def layer_params(s: dict) -> int:
    D, H, Hkv, Dh, F = s["D"], s["H"], s["Hkv"], s["Dh"], s["F"]
    return 2 * D * H * Dh + 2 * D * Hkv * Dh + 3 * D * F


def _attn(s: dict, keys: float) -> float:
    """Score and value products of one query over ``keys`` keys, all layers."""
    return 4.0 * s["L"] * s["H"] * s["Dh"] * keys


def decode_flops(s: dict, pos: int) -> float:
    """One sequence's step at position ``pos`` (it sees pos + 1 keys)."""
    return 2.0 * (s["L"] * layer_params(s) + s["V"] * s["D"]) + _attn(s, pos + 1)


def prefill_flops(s: dict, T: int) -> float:
    """One sequence of T prompt tokens, logits at the last position."""
    return (2.0 * T * s["L"] * layer_params(s) + 2.0 * s["V"] * s["D"]
            + _attn(s, T * (T + 1) / 2.0))


def request_flops(s: dict, B: int, T: int, n_new: int) -> float:
    """A request: B prompts of T tokens, then n_new − 1 decode steps."""
    return B * (prefill_flops(s, T)
                + sum(decode_flops(s, T + k - 1) for k in range(1, n_new)))


def decode_bytes(s: dict, B: int, pos: int, wbytes: int = 2, cbytes: int = 2) -> float:
    """HBM bytes one decode step of B sequences at position ``pos`` needs."""
    D = s["D"]
    weights = s["L"] * (layer_params(s) * wbytes + 2 * D * 4) + s["V"] * D * wbytes \
        + D * 4 + B * D * wbytes
    cache = 2.0 * s["L"] * B * (pos + 2) * s["Hkv"] * s["Dh"] * cbytes
    return weights + cache
