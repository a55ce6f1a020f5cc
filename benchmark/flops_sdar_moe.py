"""FLOP and byte counts of an SDAR-shaped training step (Qwen3-MoE layers of
one shape, a router and a SHARE of the routed SwiGLU experts in every layer,
no shared expert, an untied head over a vocabulary slice; a block-diffusion
objective: every sequence of L DATA tokens runs through every layer as 2L rows,
its clean copy and its noised copy, under one three-part mask, and through the
head as the noised half's L rows), from a configuration file's keys. Read by
``step.mfu_model`` (``train_flops_per_token``), by ``attention.bd_roofline``
(``kernel_least_seconds``) and by the yardstick tests. The peak table is
``flops.PEAKS``, the bandwidth ``flops_moe``'s.

Everything is per DATA token, the token ``tok_s_chip`` counts: two rows'
matrix products in the layers, one row's in the head, and the pairs the mask
keeps, ``L^2 + L bd`` a head a sequence (clean -> clean ``L (L + bd) / 2``,
noised -> clean ``L (L - bd) / 2``, noised -> noised ``L bd``), where a causal
mask over the same 2L rows keeps ``2 L^2 + L``. A pair a head costs ``4 D``
forward, ``8 D`` backward as the algorithm requires it and ``10 D`` as the
fused kernel runs it (it recomputes the score): the model's FLOPs count the
first two, the kernels' roofline the first and the third. Pairs a tile visits
and masks are not work."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.references.sdar_moe import kept_pairs  # L^2 + L bd: (data tokens a sequence, block length)


def _dims(cfg: Dict[str, Any]):
    return (int(cfg["hidden_size"]), int(cfg["head_dim"]), int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), int(cfg["moe_intermediate_size"]), int(cfg["num_experts"]),
            int(cfg.get("published", {}).get("num_experts", cfg["num_experts"])),
            int(cfg["num_experts_per_tok"]), int(cfg["vocab_size"]), int(cfg["num_hidden_layers"]))


def block_length(cfg: Dict[str, Any]) -> int:
    return int(cfg["assumed"]["block_length"]["value"])


def causal_pairs_over_the_rows(seq_len: int) -> int:
    """What a causal mask over the same 2L rows would keep."""
    rows = 2 * seq_len
    return rows * (rows + 1) // 2


def attention_params(cfg: Dict[str, Any]) -> int:
    """q, k, v and o of one layer (the two QK-norm vectors are no product)."""
    d, hd, heads, kv = _dims(cfg)[:4]
    return d * heads * hd + 2 * d * kv * hd + heads * hd * d


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter the program holds: per layer the attention matrices,
    the q and k norm vectors of a head's width, two norm vectors, the router
    over all routed experts and the HELD experts; the embedding and the head
    over the vocabulary slice, the final norm."""
    d, hd, _, _, f, held, routed, _, v, layers = _dims(cfg)
    layer = attention_params(cfg) + 2 * hd + 2 * d + d * routed + held * 3 * d * f
    return layers * layer + 2 * v * d + d


def active_params_a_row(cfg: Dict[str, Any]) -> float:
    """Parameters whose matrix products ONE ROW's forward pass through the
    layers runs on this chip: attention matrices, the router over all routed
    experts, the held experts at their expected rows (``k x held / routed``
    experts a row: 1 with 16 of 128 held and top-8). Norms not counted."""
    d, _, _, _, f, held, routed, k, _, layers = _dims(cfg)
    return layers * (attention_params(cfg) + d * routed + (k * held / routed) * 3 * d * f)


def head_params(cfg: Dict[str, Any]) -> int:
    d, v = _dims(cfg)[0], _dims(cfg)[8]
    return d * v


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """What one DATA token's training needs: 6 N (2 forward, 4 backward) over
    TWO rows' products in the layers and ONE row's in the head, plus 12 D a kept
    pair a head a layer (4 forward, 8 backward) over the sequence's tokens;
    recomputation (remat, the kernel's recomputed score) is not counted."""
    _, hd, heads, _, _, _, _, _, _, layers = _dims(cfg)
    pairs = layers * heads * kept_pairs(seq_len, block_length(cfg))
    return 6.0 * (2.0 * active_params_a_row(cfg) + head_params(cfg)) + 12.0 * hd * pairs / seq_len


def kernel_flops(cfg: Dict[str, Any], seq_len: int, batch: int, backward: bool) -> float:
    """One call of a block-diffusion attention kernel, as it runs: 4 D a kept
    pair a head forward, 10 D backward (five products)."""
    _, hd, heads = _dims(cfg)[:3]
    return (10.0 if backward else 4.0) * hd * batch * heads * kept_pairs(seq_len, block_length(cfg))


def kernel_bytes(cfg: Dict[str, Any], seq_len: int, batch: int, backward: bool, itemsize: int = 2) -> float:
    """The least one call moves over its 2L rows: forward q in and o out over
    the query heads, k and v in over the key/value heads; backward q, o's
    cotangent in and dq out, k, v in and dk, dv out (per query head, as the
    kernel writes them)."""
    _, hd, heads, kv = _dims(cfg)[:4]
    rows = batch * 2 * seq_len * hd * itemsize
    return float(rows * (3 * heads + 2 * kv + 2 * heads) if backward else rows * (2 * heads + 2 * kv))


def kernel_least_seconds(cfg, seq_len, batch, backward, peak_flops, hbm_bytes_per_s) -> float:
    """The roofline of one call: the larger of FLOPs over the peak and bytes
    over the bandwidth (the FLOPs, at every size the cell runs)."""
    return max(kernel_flops(cfg, seq_len, batch, backward) / peak_flops,
               kernel_bytes(cfg, seq_len, batch, backward) / hbm_bytes_per_s)
