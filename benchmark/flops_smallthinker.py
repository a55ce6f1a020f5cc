"""FLOP and byte counts of a SmallThinker-shaped decoder (layers of one
parameter shape and two attention kinds, global and windowed, over shared
key/value heads; a router and a SHARE of the routed ReGLU experts in every
layer, no shared expert, no dense layer; an untied head over a vocabulary
slice), from a configuration file's keys. Read by ``step.mfu_model`` and
``attention.roofline``, which find this module by the configuration's
``family`` (``benchmark.flops_<family>``) and call ``train_flops_per_token``
and ``kernel_least_seconds``. The peak table is ``flops.PEAKS``, the bandwidth
``flops_moe``'s.

Attention is counted by the query-key PAIRS the mask keeps, as
``flops_laguna`` counts them: ``T (T + 1) / 2`` in a global layer,
``sum_i min(i + 1, window)`` in a sliding one; a pair a head costs ``4 D``
forward, ``8 D`` backward as the algorithm requires it and ``10 D`` as the
fused kernel runs it (it recomputes the score). The model's FLOPs
(``step.mfu_model``) count the first two, a kernel's roofline the first and
the third. Pairs a block visits and masks are not work."""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.flops_laguna import causal_pairs


def _sliding_layers(cfg: Dict[str, Any]) -> List[bool]:
    """Per layer the program runs: whether it is a sliding-window layer."""
    return [bool(w) for w in cfg["sliding_window_layout"][:int(cfg["num_hidden_layers"])]]


def _dims(cfg: Dict[str, Any]):
    return (int(cfg["hidden_size"]), int(cfg["head_dim"]), int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), int(cfg["moe_ffn_hidden_size"]),
            int(cfg["moe_num_primary_experts"]),
            int(cfg.get("published", {}).get("moe_num_primary_experts", cfg["moe_num_primary_experts"])),
            int(cfg["moe_num_active_primary_experts"]), int(cfg["vocab_size"]))


def attention_params(cfg: Dict[str, Any]) -> int:
    """q, k, v and o of one layer."""
    d, hd, heads, kv = _dims(cfg)[:4]
    return d * heads * hd + 2 * d * kv * hd + heads * hd * d


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter the program holds: per layer the attention matrices,
    two norm vectors, the router over all routed experts and the HELD
    experts; the embedding and the head over the vocabulary slice, the final
    norm."""
    d, _, _, _, f, held, routed, _, v = _dims(cfg)
    layer = attention_params(cfg) + 2 * d + d * routed + held * 3 * d * f
    return len(_sliding_layers(cfg)) * layer + 2 * v * d + d


def active_params(cfg: Dict[str, Any]) -> float:
    """Parameters whose matrix products a token's forward pass runs ON THIS
    CHIP: attention matrices, the router over all routed experts, the held
    experts at their expected rows (``k x held / routed`` experts a token:
    0.75 with 8 of 64 held and top-6); the head over the slice. Embedding
    lookup and norms not counted."""
    d, _, _, _, f, held, routed, k, v = _dims(cfg)
    layer = attention_params(cfg) + d * routed + (k * held / routed) * 3 * d * f
    return len(_sliding_layers(cfg)) * layer + float(d * v)


def kept_pairs(cfg: Dict[str, Any], seq_len: int, sliding: bool) -> int:
    """Query-key pairs one head of a layer of this kind keeps, one sequence."""
    return causal_pairs(seq_len, int(cfg["sliding_window_size"]) if sliding else 0)


def attention_pair_heads(cfg: Dict[str, Any], seq_len: int, sliding: bool) -> int:
    """Sum over the layers of one kind of (heads x pairs the mask keeps), one
    sequence."""
    layers = sum(1 for s in _sliding_layers(cfg) if s == sliding)
    return layers * _dims(cfg)[2] * kept_pairs(cfg, seq_len, sliding)


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """6 N_active for the matrix products (2 forward, 4 backward) plus 12 D a
    pair a head for attention (4 forward, 8 backward), over the sequence's
    tokens; recomputation (remat, the kernel's recomputed score) is not
    counted."""
    pairs = attention_pair_heads(cfg, seq_len, True) + attention_pair_heads(cfg, seq_len, False)
    return 6.0 * active_params(cfg) + 12.0 * _dims(cfg)[1] * pairs / seq_len


def kernel_flops(cfg: Dict[str, Any], seq_len: int, batch: int, sliding: bool, backward: bool) -> float:
    """One call of the attention kernel of one layer kind, as it runs: 4 D a
    pair a head forward, 10 D backward (five products)."""
    _, hd, heads = _dims(cfg)[:3]
    return (10.0 if backward else 4.0) * hd * batch * heads * kept_pairs(cfg, seq_len, sliding)


def kernel_bytes(cfg: Dict[str, Any], seq_len: int, batch: int, sliding: bool, backward: bool,
                 itemsize: int = 2) -> float:
    """The least one call moves, of either kind: forward q in and o out over
    the query heads, k and v in over the key/value heads; backward q, o's
    cotangent in and dq out, k, v in and dk, dv out (per query head, as the
    kernel writes them)."""
    _, hd, heads, kv = _dims(cfg)[:4]
    rows = batch * seq_len * hd * itemsize
    return float(rows * (3 * heads + 2 * kv + 2 * heads) if backward else rows * (2 * heads + 2 * kv))


def kernel_least_seconds(cfg, seq_len, batch, sliding, backward, peak_flops, hbm_bytes_per_s) -> float:
    """The roofline of one call: the larger of FLOPs over the peak and bytes
    over the bandwidth (the FLOPs, at every size the cell runs)."""
    return max(kernel_flops(cfg, seq_len, batch, sliding, backward) / peak_flops,
               kernel_bytes(cfg, seq_len, batch, sliding, backward) / hbm_bytes_per_s)
