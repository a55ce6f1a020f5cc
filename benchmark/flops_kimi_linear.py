"""FLOP and byte counts of a Kimi-Linear-shaped decoder (Kimi Delta Attention
mixers, a delta rule with a decay by channel, beside latent attention without
positions whose value head is narrower than its key head; a leading dense
SwiGLU FFN, then a sigmoid router with a selection bias, one shared expert and
a SHARE of the routed SwiGLU experts; an untied head over a vocabulary slice),
from a configuration file's keys. Read by ``step.mfu_model``,
``attention.roofline``, ``conv.roofline`` and ``kda.roofline``, which find this
module by the configuration's ``family`` (``benchmark.flops_<family>``). The peak
table is ``flops.PEAKS``, the bandwidth ``flops_moe``'s.

Attention is counted by the query-key PAIRS the causal mask keeps, as
``flops_laguna`` counts them, at the widths the equations have: a pair a head
costs ``2 Dqk + 2 Dv`` forward (score over the key's 192, value product over
128), twice that backward as the algorithm requires it and ``6 Dqk + 4 Dv`` as
the fused kernel runs it (it recomputes the score). A kernel that padded either
width would show as a lower roofline share, not as more work.

The delta rule is counted by the WORK of its chunked form at the configuration's
chunk C (``assumed.chunk``), whatever implements it (``kda_flops``,
``kda_bytes``): a chunk of a head costs the two in-chunk matrices (``kb k^T``
and ``q k^T`` under their decays: 2 C C K each), the triangular system's
solution applied to the values and the output's in-chunk product (2 C C V each),
the two reads of the carried state (2 C K V each) and its update (2 C K V);
backward twice that (a product's two transposes). The products are counted
whole: at C = 64 a triangle of an MXU tile saves nothing. How the decays'
exponents are kept in range and how the system is solved (``ops/kda.py``: six
levels of masked products, an inverse by blocks) is the implementation's and not
counted. The bytes are the streams q, k, v in and o out once at the compute
dtype, the log decay (float32, a key channel) and beta (float32, a head) in,
and the chunk-boundary states (float32) written once forward and read once
backward; backward the streams and o's cotangent in and the five cotangents
out."""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.flops_laguna import causal_pairs

KDA, LATENT = "kda", "latent_attention"


def _dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    linear = cfg["linear_attn_config"]
    layers = int(cfg["num_hidden_layers"])
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "kv_rank": int(cfg["kv_lora_rank"]), "nope": int(cfg["qk_nope_head_dim"]),
        "shared_key": int(cfg["qk_rope_head_dim"]), "v_dim": int(cfg["v_head_dim"]),
        "hd": int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
        "H": int(linear["num_heads"]), "K": int(linear["head_dim"]), "taps": int(linear["short_conv_kernel_size"]),
        "rank": int(cfg["assumed"]["gate_rank"]["value"]), "C": int(cfg["assumed"]["chunk"]["value"]),
        "d_ff": int(cfg["intermediate_size"]), "f": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["num_shared_experts"]), "held": int(cfg["num_experts"]),
        "routed": int(cfg.get("published", {}).get("num_experts", cfg["num_experts"])),
        "k": int(cfg["num_experts_per_token"]), "v": int(cfg["vocab_size"]),
        "layers": layers, "dense": int(cfg["first_k_dense_replace"]),
        "mixers": tuple(KDA if n in linear["kda_layers"] else LATENT for n in range(1, layers + 1)),
    }


def kda_matrix_params(cfg: Dict[str, Any]) -> int:
    """The matrices of one KDA mixer: q, k, v, the decay gate's two factors,
    beta, the output gate's two factors, o."""
    m = _dims(cfg)
    inner = m["H"] * m["K"]
    return m["d"] * 3 * inner + 2 * (m["d"] * m["rank"] + m["rank"] * inner) + m["d"] * m["H"] + inner * m["d"]


def latent_matrix_params(cfg: Dict[str, Any]) -> int:
    """The four matrices of one layer's latent attention: the direct query, the
    joint latent's (with the shared key part's columns), the latent's expansion
    to each head's own key and value, the output."""
    m = _dims(cfg)
    return (m["d"] * m["heads"] * m["hd"] + m["d"] * (m["kv_rank"] + m["shared_key"])
            + m["kv_rank"] * m["heads"] * (m["nope"] + m["v_dim"]) + m["heads"] * m["v_dim"] * m["d"])


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter the program holds: per layer its mixer's matrices and
    vectors (a KDA mixer's taps, ``A_log``, ``dt_bias``, the output gate's bias,
    the head norm's scale; the latent's norm), two norm vectors, the dense FFN
    or the router over all routed experts, its selection bias, the shared
    expert and the HELD experts; the embedding, the head and the final norm."""
    m = _dims(cfg)
    inner = m["H"] * m["K"]
    total = 2 * m["v"] * m["d"] + m["d"]
    for layer, mixer in enumerate(m["mixers"]):
        if mixer == KDA:
            total += kda_matrix_params(cfg) + m["taps"] * 3 * inner + m["H"] + 2 * inner + m["K"]
        else:
            total += latent_matrix_params(cfg) + m["kv_rank"]
        total += 2 * m["d"]
        if layer < m["dense"]:
            total += 3 * m["d"] * m["d_ff"]
        else:
            total += m["d"] * m["routed"] + m["routed"] + (m["shared"] + m["held"]) * 3 * m["d"] * m["f"]
    return total


def active_params(cfg: Dict[str, Any]) -> float:
    """Parameters whose matrix products a token's forward pass runs ON THIS
    CHIP: every mixer's matrices, the dense FFN, the router over all routed
    experts, the shared expert, the held experts at their expected rows (``k x
    held / routed`` experts a token: 0.25 with 8 of 256 held and top-8); the
    head over the slice. Embedding lookup, norms, taps and biases not counted."""
    m = _dims(cfg)
    total = float(m["d"] * m["v"])
    for layer, mixer in enumerate(m["mixers"]):
        total += kda_matrix_params(cfg) if mixer == KDA else latent_matrix_params(cfg)
        if layer < m["dense"]:
            total += 3 * m["d"] * m["d_ff"]
        else:
            total += m["d"] * m["routed"] + (m["shared"] + m["k"] * m["held"] / m["routed"]) * 3 * m["d"] * m["f"]
    return total


def attention_pair_heads(cfg: Dict[str, Any], seq_len: int) -> int:
    """Sum over the latent layers of (heads x pairs the causal mask keeps), one sequence."""
    m = _dims(cfg)
    return m["mixers"].count(LATENT) * m["heads"] * causal_pairs(seq_len, 0)


def kda_flops(cfg: Dict[str, Any], batch: int, seq_len: int, backward: bool) -> float:
    """The chunked form's required products for ONE KDA mixer over ``batch``
    sequences (the module's docstring): forward, or backward (twice the forward's)."""
    m = _dims(cfg)
    c, k = m["C"], m["K"]
    chunks = -(-seq_len // c)
    a_chunk = m["H"] * (4 * c * c * k + 4 * c * c * k + 6 * c * k * k)   # key head = value head = K
    return float((2 if backward else 1) * batch * chunks * a_chunk)


def kda_bytes(cfg: Dict[str, Any], batch: int, seq_len: int, backward: bool, itemsize: int = 2) -> float:
    """The least one pass of the scan moves for ONE mixer: forward q, k, v in
    and o out at the compute dtype, the log decay (float32 a key channel) and
    beta (float32 a head) in, the chunk-boundary states out in float32; backward
    those inputs and o's cotangent in, the five cotangents out, the states in."""
    m = _dims(cfg)
    inner = m["H"] * m["K"]
    positions = batch * seq_len
    states = batch * -(-seq_len // m["C"]) * m["H"] * m["K"] * m["K"] * 4
    stream, decay, beta = inner * itemsize, inner * 4, m["H"] * 4
    if backward:
        return float(positions * (7 * stream + 2 * decay + 2 * beta) + states)
    return float(positions * (4 * stream + decay + beta) + states)


def kda_scan_shapes(cfg: Dict[str, Any], batch: int, seq_len: int) -> Tuple[Tuple[int, ...], Tuple[int, int]]:
    """What tells the scan's loops in a compiled step (``benchmark/kda_trace.py``):
    (the shape of the heads' states a loop carries, [batch, heads, value head, key
    head]; the leading [chunks, batch] of every array a loop holds a chunk at a time)."""
    m = _dims(cfg)
    return (batch, m["H"], m["K"], m["K"]), (-(-seq_len // m["C"]), batch)


def kda_least_seconds(cfg, batch, seq_len, backward, peak_flops, hbm_bytes_per_s) -> float:
    """The roofline of one pass of one mixer's scan."""
    return max(kda_flops(cfg, batch, seq_len, backward) / peak_flops,
               kda_bytes(cfg, batch, seq_len, backward) / hbm_bytes_per_s)


def _per_pair(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(forward, the fused kernel's backward) FLOPs of a kept pair a head."""
    m = _dims(cfg)
    return 2 * m["hd"] + 2 * m["v_dim"], 6 * m["hd"] + 4 * m["v_dim"]


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """6 N_active for the matrix products (2 forward, 4 backward), ``3 (2 Dqk +
    2 Dv)`` a pair a head for attention (forward, and twice that backward) and
    three forward scans' products a KDA mixer (one forward, two backward), over
    the sequence's tokens; recomputation (remat, the kernel's recomputed score)
    and the elementwise work (convolutions, gates, norms) are not counted."""
    m = _dims(cfg)
    scans = 3.0 * m["mixers"].count(KDA) * kda_flops(cfg, 1, seq_len, False) / seq_len
    return (6.0 * active_params(cfg) + 3.0 * _per_pair(cfg)[0] * attention_pair_heads(cfg, seq_len) / seq_len
            + scans)


def kernel_flops(cfg: Dict[str, Any], seq_len: int, batch: int, sliding: bool, backward: bool) -> float:
    """One call of the attention kernel as the equations size it: score over
    the key's 192 and value product over 128 forward, five products backward
    (the score again, dv, dp, dq, dk). The model has no windowed layer."""
    if sliding:
        return 0.0
    m = _dims(cfg)
    return float(_per_pair(cfg)[backward]) * batch * m["heads"] * causal_pairs(seq_len, 0)


def kernel_bytes(cfg: Dict[str, Any], seq_len: int, batch: int, sliding: bool, backward: bool,
                 itemsize: int = 2) -> float:
    """The least one call moves, every head its own q, k, v (the shared key part
    comes broadcast): forward q, k, v in and o out; backward q, k, v and o's
    cotangent in, dq, dk, dv out."""
    if sliding:
        return 0.0
    m = _dims(cfg)
    rows = batch * m["heads"] * seq_len * itemsize
    qk, v = m["hd"], m["v_dim"]
    return float(rows * (4 * qk + 3 * v) if backward else rows * (2 * qk + 2 * v))


def kernel_least_seconds(cfg, seq_len, batch, sliding, backward, peak_flops, hbm_bytes_per_s) -> float:
    """The roofline of one attention call: the larger of FLOPs over the peak
    and bytes over the bandwidth."""
    return max(kernel_flops(cfg, seq_len, batch, sliding, backward) / peak_flops,
               kernel_bytes(cfg, seq_len, batch, sliding, backward) / hbm_bytes_per_s)


def short_conv_bytes(cfg: Dict[str, Any], batch: int, seq_len: int, backward: bool,
                     itemsize: int = 2) -> float:
    """The least one call of THIS convolution moves (one of a mixer's three
    streams: ``H K`` channels, no gates; the program calls the kernel a stream):
    forward the stream in and out; backward the stream and the cotangent in and
    the stream's cotangent out; the float32 taps in (and the zeros the kernel
    takes as its bias), and their gradients out backward."""
    m = _dims(cfg)
    channels = m["H"] * m["K"]
    positions = batch * seq_len * channels * itemsize
    taps = (m["taps"] + 1) * channels * 4
    return float(3 * positions + 2 * taps if backward else 2 * positions + taps)
