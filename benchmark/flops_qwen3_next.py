"""FLOP and byte counts of a Qwen3-Next-shaped decoder (Gated DeltaNet mixers, a
delta rule under ONE decay a head with two value heads a key head, beside
output-gated grouped-query attention at a head of 256; a softmax router over
all routed experts, one shared expert scaled by a scalar a token and a SHARE of
the routed SwiGLU experts; an untied head over a vocabulary slice), from a
configuration file's keys. Read by ``step.mfu_model``, ``attention.roofline``,
``conv.roofline`` and ``gdn.roofline``, which find this module by the
configuration's ``family`` (``benchmark.flops_<family>``). The peak table is
``flops.PEAKS``, the bandwidth ``flops_moe``'s.

Attention is counted by the query-key PAIRS the causal mask keeps: a pair a
query head costs ``2 x 256 + 2 x 256`` forward (score, value product), twice
that backward as the algorithm requires it and ``6 x 256 + 4 x 256`` as the fused
kernel runs it (it recomputes the score). The output gate is elementwise and
not counted.

The delta rule is counted by the WORK of its chunked form at the configuration's
chunk C (``assumed.chunk``), whatever implements it (``gdn_flops``,
``gdn_bytes``): a chunk costs, a KEY head, the two in-chunk matrices (``k k^T``
and ``q k^T``, 2 C C K each: both of the key head's value heads scale the same
two) and, a VALUE head, the triangular system's solution applied to the values
and the output's in-chunk product (2 C C V each), the two reads of the carried
state (2 C K V each) and its update (2 C K V); backward twice that. The
products are counted whole: at C = 64 a triangle of an MXU tile saves nothing.
How the system is solved (``ops/gdn.py``: an inverse by blocks) is the
implementation's and not counted, so a form that repeated q and k to the value
heads, or took the decay by channel, would show as a LOWER roofline share, not
as more work. The bytes are q and k in at the KEY heads' count, v in and o out at
the value heads', at the compute dtype, the log decay and beta (float32, a value
head) in, and the chunk-boundary states (float32) written once forward and read
once backward; backward the streams and o's cotangent in and the five
cotangents out."""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.flops_laguna import causal_pairs

LINEAR, FULL = "linear", "full"


def _dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    layers, period = int(cfg["num_hidden_layers"]), int(cfg["full_attention_interval"])
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "kv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "Hk": int(cfg["linear_num_key_heads"]), "Hv": int(cfg["linear_num_value_heads"]),
        "K": int(cfg["linear_key_head_dim"]), "V": int(cfg["linear_value_head_dim"]),
        "taps": int(cfg["linear_conv_kernel_dim"]), "C": int(cfg["assumed"]["chunk"]["value"]),
        "f": int(cfg["moe_intermediate_size"]), "fs": int(cfg["shared_expert_intermediate_size"]),
        "held": int(cfg["num_experts"]),
        "routed": int(cfg.get("published", {}).get("num_experts", cfg["num_experts"])),
        "k": int(cfg["num_experts_per_tok"]), "v": int(cfg["vocab_size"]), "layers": layers,
        "mixers": tuple(FULL if (i + 1) % period == 0 else LINEAR for i in range(layers)),
    }


def conv_channels(cfg: Dict[str, Any]) -> int:
    """The channels the convolution runs: q's and k's at the key heads, v's at the value heads."""
    m = _dims(cfg)
    return 2 * m["Hk"] * m["K"] + m["Hv"] * m["V"]


def delta_matrix_params(cfg: Dict[str, Any]) -> int:
    """The matrices of one Gated DeltaNet: q, k, v and z as one, b and a as one, the output."""
    m = _dims(cfg)
    inner = m["Hv"] * m["V"]
    return m["d"] * (conv_channels(cfg) + inner) + m["d"] * 2 * m["Hv"] + inner * m["d"]


def attention_matrix_params(cfg: Dict[str, Any]) -> int:
    """The four matrices of one layer's gated attention: query with its gate, key, value, output."""
    m = _dims(cfg)
    return m["d"] * m["heads"] * 2 * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"] + m["heads"] * m["hd"] * m["d"]


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter the program holds: per layer its mixer's matrices and
    vectors (a delta mixer's taps, ``A_log``, ``dt_bias``, the output norm's
    scale; the attention's two head norms), two norm vectors, the router over
    all routed experts, the shared expert with its gate and the HELD experts;
    the embedding, the head and the final norm."""
    m = _dims(cfg)
    total = 2 * m["v"] * m["d"] + m["d"]
    for mixer in m["mixers"]:
        if mixer == LINEAR:
            total += delta_matrix_params(cfg) + m["taps"] * conv_channels(cfg) + 2 * m["Hv"] + m["V"]
        else:
            total += attention_matrix_params(cfg) + 2 * m["hd"]
        total += 2 * m["d"] + m["d"] * m["routed"] + 3 * m["d"] * m["fs"] + m["d"] + m["held"] * 3 * m["d"] * m["f"]
    return total


def active_params(cfg: Dict[str, Any]) -> float:
    """Parameters whose matrix products a token's forward pass runs ON THIS
    CHIP: every mixer's matrices, the router over all routed experts, the
    shared expert and its gate, the held experts at their expected rows (``k x
    held / routed`` experts a token: 0.3125 with 16 of 512 held and top-10); the
    head over the slice. Embedding lookup, norms, taps and biases not counted."""
    m = _dims(cfg)
    total = float(m["d"] * m["v"])
    for mixer in m["mixers"]:
        total += delta_matrix_params(cfg) if mixer == LINEAR else attention_matrix_params(cfg)
        total += m["d"] * m["routed"] + 3 * m["d"] * m["fs"] + m["d"] + (m["k"] * m["held"] / m["routed"]) * 3 * m["d"] * m["f"]
    return total


def attention_pair_heads(cfg: Dict[str, Any], seq_len: int) -> int:
    """Sum over the attention layers of (query heads x pairs the causal mask keeps), one sequence."""
    m = _dims(cfg)
    return m["mixers"].count(FULL) * m["heads"] * causal_pairs(seq_len, 0)


def gdn_flops(cfg: Dict[str, Any], batch: int, seq_len: int, backward: bool) -> float:
    """The chunked form's required products for ONE delta mixer over ``batch``
    sequences (the module's docstring): forward, or backward (twice the forward's)."""
    m = _dims(cfg)
    c, k, v = m["C"], m["K"], m["V"]
    chunks = -(-seq_len // c)
    a_chunk = m["Hk"] * (2 * 2 * c * c * k) + m["Hv"] * (2 * 2 * c * c * v + 3 * 2 * c * k * v)
    return float((2 if backward else 1) * batch * chunks * a_chunk)


def gdn_bytes(cfg: Dict[str, Any], batch: int, seq_len: int, backward: bool, itemsize: int = 2) -> float:
    """The least one pass of the scan moves for ONE mixer: forward q, k (key
    heads) and v in and o out (value heads) at the compute dtype, the log decay
    and beta (float32, a value head) in, the chunk-boundary states out in
    float32; backward those inputs and o's cotangent in, the five cotangents out,
    the states in."""
    m = _dims(cfg)
    qk, vo, scalars = 2 * m["Hk"] * m["K"] * itemsize, m["Hv"] * m["V"] * itemsize, 2 * m["Hv"] * 4
    positions = batch * seq_len
    states = batch * -(-seq_len // m["C"]) * m["Hv"] * m["K"] * m["V"] * 4
    if backward:
        return float(positions * (2 * qk + 3 * vo + 2 * scalars) + states)
    return float(positions * (qk + 2 * vo + scalars) + states)


def gdn_scan_shapes(cfg: Dict[str, Any], batch: int, seq_len: int) -> Tuple[Tuple[int, ...], Tuple[int, int]]:
    """What tells the scan's loops in a compiled step (``benchmark/gdn_trace.py``):
    (the shape of the value heads' states a loop carries, [batch, value heads,
    value head, key head]; the leading [batch, T] of every WHOLE stream a loop
    carries: it reads and writes its chunks in place)."""
    m = _dims(cfg)
    chunks = -(-seq_len // m["C"])
    return (batch, m["Hv"], m["V"], m["K"]), (batch, chunks * m["C"])


def gdn_least_seconds(cfg, batch, seq_len, backward, peak_flops, hbm_bytes_per_s) -> float:
    """The roofline of one pass of one mixer's scan."""
    return max(gdn_flops(cfg, batch, seq_len, backward) / peak_flops,
               gdn_bytes(cfg, batch, seq_len, backward) / hbm_bytes_per_s)


def _per_pair(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(forward, the fused kernel's backward) FLOPs of a kept pair a query head."""
    hd = _dims(cfg)["hd"]
    return 2 * hd + 2 * hd, 6 * hd + 4 * hd


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """6 N_active for the matrix products (2 forward, 4 backward), ``3 (2 D + 2
    D)`` a pair a query head for attention (forward, and twice that backward) and
    three forward scans' products a delta mixer (one forward, two backward), over
    the sequence's tokens; recomputation (remat, the kernel's recomputed score)
    and the elementwise work (the convolution, gates, norms) are not counted."""
    m = _dims(cfg)
    scans = 3.0 * m["mixers"].count(LINEAR) * gdn_flops(cfg, 1, seq_len, False) / seq_len
    return (6.0 * active_params(cfg) + 3.0 * _per_pair(cfg)[0] * attention_pair_heads(cfg, seq_len) / seq_len
            + scans)


def kernel_flops(cfg: Dict[str, Any], seq_len: int, batch: int, sliding: bool, backward: bool) -> float:
    """One call of the attention kernel as the equations size it: score and
    value product over 256 forward, five products backward (the score again, dv,
    dp, dq, dk). The model has no windowed layer."""
    if sliding:
        return 0.0
    m = _dims(cfg)
    return float(_per_pair(cfg)[backward]) * batch * m["heads"] * causal_pairs(seq_len, 0)


def kernel_bytes(cfg: Dict[str, Any], seq_len: int, batch: int, sliding: bool, backward: bool,
                 itemsize: int = 2) -> float:
    """The least one call moves: forward q in and o out over the query heads, k
    and v in over the key/value heads; backward q, o's cotangent in and dq out, k,
    v in and dk, dv out (per query head, as the kernel writes them:
    ``flops_smallthinker.kernel_bytes``)."""
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
    """The least one call of THIS convolution moves (ONE stream of all 8,192
    channels, q's, k's and v's side by side; no gates): forward the stream in and
    out; backward the stream and the cotangent in and the stream's cotangent out;
    the float32 taps in (and the zeros the kernel takes as its bias), and their
    gradients out backward."""
    channels = conv_channels(cfg)
    positions = batch * seq_len * channels * itemsize
    taps = (_dims(cfg)["taps"] + 1) * channels * 4
    return float(3 * positions + 2 * taps if backward else 2 * positions + taps)
