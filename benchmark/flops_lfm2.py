"""FLOP and byte counts of an LFM2-shaped decoder (token mixers of two kinds
read from a list, gated short convolutions and grouped-query attention with
per-head QK-norm; leading layers with a dense SwiGLU FFN, then a sigmoid
router with a selection bias and a SHARE of the routed SwiGLU experts, no
shared expert; a head tied to the embedding over a vocabulary slice), from a
configuration file's keys. Read by ``step.mfu_model`` and
``attention.roofline``, which find this module by the configuration's
``family`` (``benchmark.flops_<family>``) and call ``train_flops_per_token``
and ``kernel_least_seconds``, and by ``conv.roofline``, which calls
``short_conv_bytes``. The peak table is ``flops.PEAKS``; the chip's HBM
bandwidth, which that table does not hold, is ``flops_moe``'s, handed on here
(``hbm_bytes_per_s``) for the convolution's roofline.

Attention is counted by the query-key PAIRS the causal mask keeps, ``T (T +
1) / 2`` a head, as ``flops_laguna`` counts them: a pair a head costs ``4 D``
forward, ``8 D`` backward as the algorithm requires it and ``10 D`` as the
fused kernel runs it (it recomputes the score). The model's FLOPs
(``step.mfu_model``) count the first two, a kernel's roofline the first and
the third.

The short convolution is counted by its BYTES: its arithmetic is five
elementwise operations a channel and position (two gates, three taps), a
hundredth of what the chip could do while it moves them. Forward it reads the
three streams and writes one; backward it reads the three streams and the
output's cotangent and writes the three streams' cotangent; the taps and their
gradient are ``3 x d`` numbers."""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.flops_laguna import causal_pairs
from benchmark.flops_moe import hbm_bytes_per_s  # noqa: F401 - the bandwidth beside the bytes
from benchmark.references.lfm2 import layer_types

CONV, FULL = "conv", "full_attention"


def _dims(cfg: Dict[str, Any]):
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "d": d, "heads": heads, "kv": int(cfg["num_key_value_heads"]), "hd": d // heads,
        "d_ff": int(cfg["intermediate_size"]), "f": int(cfg["moe_intermediate_size"]),
        "held": int(cfg["num_experts"]),
        "routed": int(cfg.get("published", {}).get("num_experts", cfg["num_experts"])),
        "k": int(cfg["num_experts_per_tok"]), "v": int(cfg["vocab_size"]),
        "taps": int(cfg["conv_L_cache"]), "dense": int(cfg["num_dense_layers"]),
    }


def _layers(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Per layer the program runs: its mixer kind and whether its FFN is dense."""
    dense = int(cfg["num_dense_layers"])
    return [{"mixer": kind, "dense": i < dense} for i, kind in enumerate(layer_types(cfg))]


def mixer_matrix_params(cfg: Dict[str, Any], kind: str) -> int:
    """The matrices of one token mixer: in and out projection of a conv layer;
    q, k, v and o of an attention layer."""
    m = _dims(cfg)
    if kind == CONV:
        return m["d"] * 3 * m["d"] + m["d"] * m["d"]
    return 2 * m["d"] * m["heads"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter the program holds: per layer the mixer's matrices (and a
    conv layer's taps, an attention layer's two head norms), two norm vectors,
    the dense FFN or the router over all routed experts, its selection bias and
    the HELD experts; the embedding (which is the head) and the final norm."""
    m = _dims(cfg)
    total = m["v"] * m["d"] + m["d"]
    for layer in _layers(cfg):
        total += mixer_matrix_params(cfg, layer["mixer"]) + 2 * m["d"]
        total += m["taps"] * m["d"] if layer["mixer"] == CONV else 2 * m["hd"]
        if layer["dense"]:
            total += 3 * m["d"] * m["d_ff"]
        else:
            total += m["d"] * m["routed"] + m["routed"] + m["held"] * 3 * m["d"] * m["f"]
    return total


def active_params(cfg: Dict[str, Any]) -> float:
    """Parameters whose matrix products a token's forward pass runs ON THIS
    CHIP: every mixer's matrices, the dense FFN, the router over all routed
    experts, the held experts at their expected rows (``k x held / routed``
    experts a token: 0.5 with 8 of 64 held and top-4); the tied head over the
    slice. Embedding lookup, norms, taps and biases not counted."""
    m = _dims(cfg)
    total = float(m["d"] * m["v"])
    for layer in _layers(cfg):
        total += mixer_matrix_params(cfg, layer["mixer"])
        if layer["dense"]:
            total += 3 * m["d"] * m["d_ff"]
        else:
            total += m["d"] * m["routed"] + (m["k"] * m["held"] / m["routed"]) * 3 * m["d"] * m["f"]
    return total


def attention_pair_heads(cfg: Dict[str, Any], seq_len: int) -> int:
    """Sum over the attention layers of (heads x pairs the causal mask keeps),
    one sequence."""
    layers = sum(1 for layer in _layers(cfg) if layer["mixer"] == FULL)
    return layers * _dims(cfg)["heads"] * causal_pairs(seq_len, 0)


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """6 N_active for the matrix products (2 forward, 4 backward) plus 12 D a
    pair a head for attention (4 forward, 8 backward), over the sequence's
    tokens; recomputation (remat, the kernel's recomputed score) and the
    convolution's elementwise work are not counted."""
    return (6.0 * active_params(cfg)
            + 12.0 * _dims(cfg)["hd"] * attention_pair_heads(cfg, seq_len) / seq_len)


def kernel_flops(cfg: Dict[str, Any], seq_len: int, batch: int, sliding: bool, backward: bool) -> float:
    """One call of the attention kernel as it runs: 4 D a pair a head forward,
    10 D backward (five products). The model has no windowed layer."""
    if sliding:
        return 0.0
    m = _dims(cfg)
    return (10.0 if backward else 4.0) * m["hd"] * batch * m["heads"] * causal_pairs(seq_len, 0)


def kernel_bytes(cfg: Dict[str, Any], seq_len: int, batch: int, sliding: bool, backward: bool,
                 itemsize: int = 2) -> float:
    """The least one call moves: forward q in and o out over the query heads,
    k and v in over the key/value heads; backward q, o's cotangent in and dq
    out, k, v in and dk, dv out (per query head, as the kernel writes them)."""
    if sliding:
        return 0.0
    m = _dims(cfg)
    rows = batch * seq_len * m["hd"] * itemsize
    heads, kv = m["heads"], m["kv"]
    return float(rows * (3 * heads + 2 * kv + 2 * heads) if backward else rows * (2 * heads + 2 * kv))


def kernel_least_seconds(cfg, seq_len, batch, sliding, backward, peak_flops, hbm_bytes_per_s) -> float:
    """The roofline of one attention call: the larger of FLOPs over the peak
    and bytes over the bandwidth."""
    return max(kernel_flops(cfg, seq_len, batch, sliding, backward) / peak_flops,
               kernel_bytes(cfg, seq_len, batch, sliding, backward) / hbm_bytes_per_s)


def short_conv_bytes(cfg: Dict[str, Any], batch: int, seq_len: int, backward: bool,
                     itemsize: int = 2) -> float:
    """The least one call of the gated short convolution moves, the streams
    read and written once at the compute dtype: forward ``[B | C | u]`` in (3 d
    a position) and ``y`` out (d); backward the streams and ``y``'s cotangent
    in (4 d) and the streams' cotangent out (3 d); the float32 taps in, and
    their gradient out backward."""
    m = _dims(cfg)
    positions = batch * seq_len * m["d"] * itemsize
    taps = m["taps"] * m["d"] * 4
    return float(7 * positions + 2 * taps if backward else 4 * positions + taps)
