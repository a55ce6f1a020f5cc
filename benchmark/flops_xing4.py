"""FLOP and byte counts of a Xing4.0-shaped decoder (four residual streams a
token mixed around every sublayer by maps made from the token's own state;
every mixer multi-head latent attention at a key of ``nope + rot`` over a value
head of its own width; leading layers with a dense SwiGLU FFN, then a sigmoid
router with a selection bias, one shared expert and a SHARE of the routed SwiGLU
experts; an untied head over a vocabulary slice), from a configuration file's
keys. Read by ``step.mfu_model``, ``attention.roofline`` and ``hc.roofline``,
which find this module by the configuration's ``family``
(``benchmark.flops_<family>``) and call ``train_flops_per_token``,
``kernel_least_seconds`` and ``hc_least_seconds``. The peak table is
``flops.PEAKS``, the bandwidth ``flops_moe``'s.

Attention is counted by the query-key PAIRS the causal mask keeps, ``T (T +
1) / 2`` a head, as ``flops_glm4_moe_lite`` counts them, at the PUBLISHED head:
a pair a head costs ``2 Dqk + 2 Dv`` = 2 x 192 + 2 x 128 forward, twice that
backward as the algorithm requires it and ``6 Dqk + 4 Dv`` as the fused kernel
runs it. The program hands the kernels a key padded to 256 lanes through its
weights; the zero lanes are the program's way and are not counted, in FLOPs or
in bytes.

The residual path is counted by what it REQUIRES whatever implements it. Its
products: ``u Phi``, ``2 n C (n^2 + 2n)`` a token a sublayer forward (in the
model's FLOPs with the other matrix products). Its traffic (``hc_bytes``): a
sublayer a token forward reads the ``n C`` streams and writes the ``C`` input
(the maps and ``Hpre X`` from ONE read of ``X``), then reads the streams and
the sublayer's ``C`` result and writes the new streams: ``(nC + C) + (nC + C +
nC)`` elements of the compute dtype; the recomputed forward moves the same and
the backward twice that. The mixing sums' FLOPs (``n^2 + 2n`` multiply-adds a
coordinate) are far under the bytes' time at this chip's ratio and are not in
the roofline; the Sinkhorn steps are 16 numbers a token."""

from __future__ import annotations

from typing import Any, Dict

# latent attention's matrices, kept pairs and one kernel call's FLOPs, bytes and least time are GLM's counts at
# this file's keys: every one reads ``qk_nope_head_dim`` + ``qk_rope_head_dim`` for the key and ``v_head_dim`` for
# the value, so a key of 192 over a value of 128 is counted as ``2 Dqk + 2 Dv`` forward, ``6 Dqk + 4 Dv`` backward
from benchmark.flops_glm4_moe_lite import (  # noqa: F401 — ``kernel_least_seconds`` is what ``attention.roofline`` calls
    attention_matrix_params,
    attention_pair_heads,
    kernel_bytes,
    kernel_flops,
    kernel_least_seconds,
)

PASSES = ("fwd", "refwd", "bwd")


def _dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "q_rank": int(cfg["q_lora_rank"]), "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]), "rot": int(cfg["qk_rope_head_dim"]),
        "v_dim": int(cfg["v_head_dim"]), "hd": int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
        "d_ff": int(cfg["intermediate_size"]), "f": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["n_shared_experts"]), "held": int(cfg["n_routed_experts"]),
        "routed": int(cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"])),
        "k": int(cfg["num_experts_per_tok"]), "v": int(cfg["vocab_size"]),
        "layers": int(cfg["num_hidden_layers"]), "dense": int(cfg["first_k_dense_replace"]),
        "n": int(cfg["hc_mult"]),
    }


def hc_matrix_params(cfg: Dict[str, Any]) -> int:
    """``Phi`` of one sublayer: ``[n C, n + n + n^2]``."""
    m = _dims(cfg)
    return m["n"] * m["d"] * m["n"] * (m["n"] + 2)


def hc_params(cfg: Dict[str, Any]) -> int:
    """One sublayer's maps: ``Phi``, the biases, the three scalars, the stream norm's weight."""
    m = _dims(cfg)
    return hc_matrix_params(cfg) + m["n"] * (m["n"] + 2) + 3 + m["n"] * m["d"]


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter the program holds: per layer the attention's matrices
    and its two inner norms, two norm vectors, two sublayers' maps, the dense
    FFN or the router over all routed experts, its selection bias, the shared
    expert and the HELD experts; the embedding, the head and the final norm."""
    m = _dims(cfg)
    total = 2 * m["v"] * m["d"] + m["d"]
    for layer in range(m["layers"]):
        total += attention_matrix_params(cfg) + m["q_rank"] + m["kv_rank"] + 2 * m["d"] + 2 * hc_params(cfg)
        if layer < m["dense"]:
            total += 3 * m["d"] * m["d_ff"]
        else:
            total += m["d"] * m["routed"] + m["routed"] + (m["shared"] + m["held"]) * 3 * m["d"] * m["f"]
    return total


def active_params(cfg: Dict[str, Any]) -> float:
    """Parameters whose matrix products a token's forward pass runs ON THIS
    CHIP: every layer's attention matrices and both sublayers' ``Phi``, the
    dense FFN, the router, the shared expert, the held experts at their
    expected rows (``k x held / routed`` experts a token: 0.5 with 8 of 64 held
    and top-4); the head over the slice."""
    m = _dims(cfg)
    total = float(m["d"] * m["v"])
    for layer in range(m["layers"]):
        total += attention_matrix_params(cfg) + 2 * hc_matrix_params(cfg)
        if layer < m["dense"]:
            total += 3 * m["d"] * m["d_ff"]
        else:
            total += m["d"] * m["routed"] + (m["shared"] + m["k"] * m["held"] / m["routed"]) * 3 * m["d"] * m["f"]
    return total


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """6 N_active for the matrix products (2 forward, 4 backward) plus
    ``3 (2 Dqk + 2 Dv)`` a pair a head for attention, over the sequence's
    tokens; recomputation is not counted."""
    m = _dims(cfg)
    return (6.0 * active_params(cfg)
            + 3.0 * (2 * m["hd"] + 2 * m["v_dim"]) * attention_pair_heads(cfg, seq_len) / seq_len)


def hc_bytes(cfg: Dict[str, Any], tokens: int, which: str, itemsize: int = 2) -> float:
    """What the residual path of every sublayer of the model must move in one
    pass (``fwd``, ``refwd`` or ``bwd``) over ``tokens`` tokens: forward ``(nC +
    C) + (nC + C + nC)`` elements a token a sublayer, the recomputed forward
    the same, the backward twice that."""
    if which not in PASSES:
        raise ValueError(f"unknown pass {which!r}; known: {PASSES}")
    m = _dims(cfg)
    forward = (m["n"] * m["d"] + m["d"]) + (m["n"] * m["d"] + m["d"] + m["n"] * m["d"])
    sublayers = 2 * m["layers"]
    return float((2 if which == "bwd" else 1) * forward * sublayers * tokens * itemsize)


def hc_least_seconds(cfg: Dict[str, Any], tokens: int, hbm_bytes_per_s: float) -> float:
    """The least time a step's residual path takes: its three passes' bytes over the bandwidth."""
    return sum(hc_bytes(cfg, tokens, which) for which in PASSES) / hbm_bytes_per_s
