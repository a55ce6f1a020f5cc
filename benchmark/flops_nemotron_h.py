"""FLOP and byte counts of a Nemotron-H-shaped decoder (one-mixer blocks in a
published order: Mamba-2 state-space mixers, expert blocks with a sigmoid
router, a selection bias, a shared expert and a SHARE of the routed experts,
all WITHOUT a gate, and grouped-query attention; an untied head over a
vocabulary slice), from a configuration file's keys. Read by ``step.mfu_model``,
``attention.roofline``, ``conv.roofline`` and ``ssm.roofline``, which find this
module by the configuration's ``family`` (``benchmark.flops_<family>``). The peak
table is ``flops.PEAKS``, the bandwidth ``flops_moe``'s.

Attention is counted by the query-key PAIRS the causal mask keeps, as
``flops_laguna`` counts them: 4 D a pair a head forward, 8 D backward as the
algorithm requires it and 10 D as the fused kernel runs it.

The state-space scan is counted by the WORK of its chunked form at the
configuration's ``chunk_size`` Q, whatever implements it (``ssd_flops``,
``ssd_bytes``): a chunk of a sequence costs the group's ``C B^T`` (2 Q Q N a
group) and, a head, the masked product with ``dt x'`` (2 Q Q P), the read of
the carried state (2 Q N P) and its update (2 Q P N); backward twice that (a
product's two transposes). The masked product is counted whole: at Q = 128 a
triangle of a 128-wide MXU tile saves nothing. The bytes are the streams ``x'``,
``B``, ``C``, ``dt`` in and ``y`` out once at the compute dtype (``dt`` float32)
and the chunk-boundary states (float32) written once forward and read once
backward; backward the streams and ``y``'s cotangent in and the four
cotangents out."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops_laguna import causal_pairs

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    h, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "kv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "H": h, "P": p, "G": g, "N": n, "inner": h * p, "conv": h * p + 2 * g * n,
        "taps": int(cfg["conv_kernel"]), "Q": int(cfg["chunk_size"]),
        "f": int(cfg["moe_intermediate_size"]), "f_shared": int(cfg["moe_shared_expert_intermediate_size"]),
        "held": int(cfg["n_routed_experts"]),
        "routed": int(cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"])),
        "k": int(cfg["num_experts_per_tok"]), "v": int(cfg["vocab_size"]),
        "blocks": str(cfg["hybrid_override_pattern"])[:int(cfg["num_hidden_layers"])],
    }


def mamba_matrix_params(cfg: Dict[str, Any]) -> int:
    """The two projections of a state-space block: ``[z | xBC | dt]`` in, out."""
    m = _dims(cfg)
    return m["d"] * (m["inner"] + m["conv"] + m["H"]) + m["inner"] * m["d"]


def attention_matrix_params(cfg: Dict[str, Any]) -> int:
    m = _dims(cfg)
    return 2 * m["d"] * m["heads"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]


def block_params(cfg: Dict[str, Any], kind: str) -> int:
    """Every parameter of one block of ``kind``, its norm's scale included."""
    m = _dims(cfg)
    if kind == MAMBA:   # projections, taps and their bias, A_log / D / dt_bias, the gated norm's scale
        return (mamba_matrix_params(cfg) + (m["taps"] + 1) * m["conv"] + 3 * m["H"] + m["inner"] + m["d"])
    if kind == ATTENTION:
        return attention_matrix_params(cfg) + m["d"]
    return (m["d"] * m["routed"] + m["routed"] + m["held"] * 2 * m["d"] * m["f"]
            + 2 * m["d"] * m["f_shared"] + m["d"])


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter the program holds: the blocks, the embedding, the head, the final norm."""
    m = _dims(cfg)
    return 2 * m["v"] * m["d"] + m["d"] + sum(block_params(cfg, kind) for kind in m["blocks"])


def active_params(cfg: Dict[str, Any]) -> float:
    """Parameters whose matrix products a token's forward pass runs ON THIS
    CHIP: a state-space block's two projections, an attention block's four, an
    expert block's router over all routed experts, its shared expert and the
    held experts at their expected rows (``k x held / routed`` experts a token:
    0.375 with 8 of 128 held and top-6); the head over the slice. Embedding
    lookup, norms, taps and biases not counted."""
    m = _dims(cfg)
    total = float(m["d"] * m["v"])
    for kind in m["blocks"]:
        if kind == MAMBA:
            total += mamba_matrix_params(cfg)
        elif kind == ATTENTION:
            total += attention_matrix_params(cfg)
        else:
            total += (m["d"] * m["routed"] + 2 * m["d"] * m["f_shared"]
                      + m["k"] * m["held"] / m["routed"] * 2 * m["d"] * m["f"])
    return total


def attention_pair_heads(cfg: Dict[str, Any], seq_len: int) -> int:
    """Sum over the attention blocks of (heads x pairs the causal mask keeps), one sequence."""
    m = _dims(cfg)
    return m["blocks"].count(ATTENTION) * m["heads"] * causal_pairs(seq_len, 0)


def ssd_flops(cfg: Dict[str, Any], batch: int, seq_len: int, backward: bool) -> float:
    """The chunked scan's required products for ONE state-space block over
    ``batch`` sequences (the module's docstring): forward, or backward (twice
    the forward's)."""
    m = _dims(cfg)
    q = m["Q"]
    chunks = -(-seq_len // q)
    a_chunk = 2 * q * q * m["N"] * m["G"] + m["H"] * (2 * q * q * m["P"] + 4 * q * m["N"] * m["P"])
    return float((2 if backward else 1) * batch * chunks * a_chunk)


def ssd_bytes(cfg: Dict[str, Any], batch: int, seq_len: int, backward: bool, itemsize: int = 2) -> float:
    """The least one pass of the scan moves for ONE block: forward ``x'``, ``B``,
    ``C`` in and ``y`` out at the compute dtype, ``dt`` in float32, the
    chunk-boundary states out in float32; backward those streams and ``y``'s
    cotangent in, the four cotangents out, the states in."""
    m = _dims(cfg)
    positions = batch * seq_len
    states = batch * -(-seq_len // m["Q"]) * m["H"] * m["P"] * m["N"] * 4
    rows, groups, dt = m["inner"] * itemsize, m["G"] * m["N"] * itemsize, m["H"] * 4
    if backward:
        return float(positions * (3 * rows + 4 * groups + 2 * dt) + states)
    return float(positions * (2 * rows + 2 * groups + dt) + states)


def ssd_least_seconds(cfg, batch, seq_len, backward, peak_flops, hbm_bytes_per_s) -> float:
    """The roofline of one pass of one block's scan."""
    return max(ssd_flops(cfg, batch, seq_len, backward) / peak_flops,
               ssd_bytes(cfg, batch, seq_len, backward) / hbm_bytes_per_s)


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """6 N_active for the matrix products (2 forward, 4 backward), 12 D a pair
    a head for attention (4 forward, 8 backward) and three forward scans'
    products a state-space block (one forward, two backward), over the
    sequence's tokens; recomputation (remat, the kernel's recomputed score) and
    the elementwise work (convolution, gates, norms) are not counted."""
    m = _dims(cfg)
    scans = 3.0 * m["blocks"].count(MAMBA) * ssd_flops(cfg, 1, seq_len, False) / seq_len
    return (6.0 * active_params(cfg) + 12.0 * m["hd"] * attention_pair_heads(cfg, seq_len) / seq_len + scans)


def kernel_flops(cfg: Dict[str, Any], seq_len: int, batch: int, sliding: bool, backward: bool) -> float:
    """One call of the attention kernel as it runs: 4 D a pair a head forward,
    10 D backward (five products). The model has no windowed block."""
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
    """The least one call of THIS convolution moves (one stream of ``d_inner +
    2 G N`` channels, a bias, no gates): forward the stream in and out; backward
    the stream and the cotangent in and the stream's cotangent out; the float32
    taps and bias in, and their gradients out backward."""
    m = _dims(cfg)
    positions = batch * seq_len * m["conv"] * itemsize
    taps = (m["taps"] + 1) * m["conv"] * 4
    return float(3 * positions + 2 * taps if backward else 2 * positions + taps)
