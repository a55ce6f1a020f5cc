"""FLOP and byte counts of an Ouro-shaped training step (a looped language
model: L dense layers of one shape, sandwich norms, a SwiGLU FFN, run R =
``total_ut_steps`` times over the same weights; an untied head and an exit gate
after EVERY pass), from a configuration file's keys. Read by ``step.mfu_model``
(``train_flops_per_token``), by ``attention.roofline``
(``kernel_least_seconds``) and by the yardstick tests. The peak table is
``flops.PEAKS``, the bandwidth ``flops_moe``'s.

Everything is per DATA token, the token ``tok_s_chip`` counts: a data token
runs R times through every layer AND R times through the head (the loss weighs
every pass's logits), over the causal pairs of its sequence in every layer-run.
A weight is held once and multiplied R times: the parameters are counted once,
the products R times. A pair a head costs ``4 D`` forward, ``8 D`` backward as
the algorithm requires it and ``10 D`` as the fused kernel runs it (it
recomputes the score): the model's FLOPs count the first two, the kernels'
roofline the first and the third. With the recomputed forward of every
rematerialised layer-run (the program's default) a step runs the layers'
products a fourth time: ``train_flops_per_token_as_run``. The exit gate's d
products a token a pass and the norms are not counted."""

from __future__ import annotations

from typing import Any, Dict


def _dims(cfg: Dict[str, Any]):
    return (int(cfg["hidden_size"]), int(cfg["head_dim"]), int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), int(cfg["intermediate_size"]), int(cfg["vocab_size"]),
            int(cfg["num_hidden_layers"]), int(cfg["total_ut_steps"]))


def causal_pairs(seq_len: int) -> int:
    """Pairs a causal mask keeps, a head a sequence."""
    return seq_len * (seq_len + 1) // 2


def layer_matrix_params(cfg: Dict[str, Any]) -> int:
    """The matrices of one layer: q, k, v, o and the FFN's three."""
    d, hd, heads, kv, f = _dims(cfg)[:5]
    return d * heads * hd + 2 * d * kv * hd + heads * hd * d + 3 * d * f


def head_params(cfg: Dict[str, Any]) -> int:
    d, v = _dims(cfg)[0], _dims(cfg)[5]
    return d * v


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter the program holds, each once however often it runs: a
    layer's matrices and four norm vectors; the embedding and the head over the
    vocabulary, the final norm, the gate's vector and its bias."""
    d, _, _, _, _, v, layers, _ = _dims(cfg)
    return layers * (layer_matrix_params(cfg) + 4 * d) + 2 * v * d + d + d + 1


def products_a_token_a_pass(cfg: Dict[str, Any]) -> int:
    """Parameters whose matrix products one token's forward through ONE pass runs: the layers and the head."""
    return _dims(cfg)[6] * layer_matrix_params(cfg) + head_params(cfg)


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """What one DATA token's training needs: R passes of 6 N (2 forward, 4
    backward) over the layers and the head, plus 12 D a kept pair a head a
    layer-run (4 forward, 8 backward) over the sequence's tokens; recomputation
    (remat, the kernel's recomputed score) is not counted."""
    _, hd, heads, _, _, _, layers, passes = _dims(cfg)
    pairs = layers * heads * causal_pairs(seq_len)
    return passes * (6.0 * products_a_token_a_pass(cfg) + 12.0 * hd * pairs / seq_len)


def train_flops_per_token_as_run(cfg: Dict[str, Any], seq_len: int) -> float:
    """With the rematerialised layers' recomputed forward (2 N of the layers'
    matrices a pass more; the kernel's results are kept, so no pair is run
    again forward) and the kernel's backward at 10 D a pair: what the chip
    multiplies, not what the model requires."""
    _, hd, heads, _, _, _, layers, passes = _dims(cfg)
    pairs = layers * heads * causal_pairs(seq_len)
    return train_flops_per_token(cfg, seq_len) + passes * (
        2.0 * layers * layer_matrix_params(cfg) + 2.0 * hd * pairs / seq_len)


def kernel_flops(cfg: Dict[str, Any], seq_len: int, batch: int, backward: bool) -> float:
    """One call of the causal attention kernel (a layer-run), as it runs: 4 D a
    kept pair a head forward, 10 D backward (five products)."""
    _, hd, heads = _dims(cfg)[:3]
    return (10.0 if backward else 4.0) * hd * batch * heads * causal_pairs(seq_len)


def kernel_bytes(cfg: Dict[str, Any], seq_len: int, batch: int, backward: bool, itemsize: int = 2) -> float:
    """The least one call moves: forward q in and o out over the query heads,
    k and v in over the key/value heads; backward q, o's cotangent in and dq
    out, k, v in and dk, dv out (per query head, as the kernel writes them): the
    convention of ``flops_smallthinker``."""
    _, hd, heads, kv = _dims(cfg)[:4]
    rows = batch * seq_len * hd * itemsize
    return float(rows * (5 * heads + 2 * kv) if backward else rows * (2 * heads + 2 * kv))


def kernel_least_seconds(cfg, seq_len, batch, windowed, backward, peak_flops, hbm_bytes_per_s) -> float:
    """The roofline of one call, as ``attention.roofline`` asks for it (this
    model has no windowed layer): the larger of FLOPs over the peak and bytes
    over the bandwidth."""
    if windowed:
        raise ValueError("an Ouro layer has no sliding window")
    return max(kernel_flops(cfg, seq_len, batch, backward) / peak_flops,
               kernel_bytes(cfg, seq_len, batch, backward) / hbm_bytes_per_s)
