"""Qwen3-Next-80B-A3B-Instruct as published
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``,
``model_type`` ``qwen3_next``), plain: float32 ``jax.numpy`` at the highest
matmul precision, no kernel, no sort, no grouped matmul, NO CHUNK ALGEBRA.

The layer equations, written out here and followed independently of the
program (``models/qwen3_next.py``). Pre-norm residual blocks, d = 2,048, an
untied head, a final norm: ``x <- x + mixer(N(x))``, ``x <- x + moe(N(x))``;
``N(x) = x / sqrt(mean(x^2) + 1e-6) * (1 + w)`` (the family's zero-centred norm:
the layer norms, the final norm, the q and k head norms). Layer i (from 0) is
gated attention where ``(i + 1) % full_attention_interval == 0`` and a Gated
DeltaNet otherwise.

**Gated DeltaNet** (16 key heads, 32 value heads, both of 128), for a token t:

- ``[q | k | v | z] = x W_qkvz`` (2,048 + 2,048 + 4,096 + 4,096 columns), ``[b |
  a] = x W_ba`` (32 + 32); q, k, v side by side pass one causal depthwise
  convolution of four taps a channel, no bias, here a sum of shifted copies,
  then SiLU; z, b, a do not.
- ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``: ONE log
  decay a value head a token.
- q and k are brought to length 1 a head (1e-6 under the root), q then times
  ``128^-1/2``; value head j reads key head ``j // 2``: q and k are REPEATED to
  the 32 value heads along the head axis (``jnp.repeat``: ``repeat_interleave``),
  as the published file does.
- state ``S`` in ``R^{128 x 128}`` (key x value) a value head, ONE TOKEN AT A
  TIME, exactly as these steps read: ``S <- exp(g_t) S``; ``u_t = beta_t (v_t -
  S^T k_t)``; ``S <- S + k_t u_t^T``; ``o_t = S^T q_t``. (A ``lax.scan`` over the
  tokens inside a ``lax.scan`` over stretches of ``SCAN_STRETCH`` of them whose
  body is checkpointed; no triangular system, no decay matrix, no product over a
  chunk.)
- ``y = (rmsnorm_128(o_t) * w_o * silu(z_t)) W_out``: the norm over each value
  head's 128 with one learned scale of 128 (plain ``* w``, not ``1 + w``).

**Gated attention** (16 query heads over 2 key/value heads of 256): ``x W_q`` as
[T, 16, 512] is a head's query (first 256) and its output gate (last 256); ``k =
x W_k``, ``v = x W_v`` as [T, 2, 256]; ``q = N_256(q)``, ``k = N_256(k)``; rotary
on the first 64 coordinates of a head (rotate-half pairs (i, i + 32), theta
1e7); causal softmax of ``q k^T / 16`` under an explicit mask, query head h
reading key/value head ``h // 8``; the result times ``sigmoid(gate)``; ``W_o``.

**Experts.** ``softmax(x W_r)`` over all 512, the top 10, weights the chosen
scores over their sum; SwiGLU experts of 512; one shared SwiGLU expert of 512 on
every token times ``sigmoid(x w_sg)``, a scalar a token. Every HELD expert runs
on every token and is masked by the top-k one-hot times the weight; the experts
this chip does not hold add nothing, here as in the program. Loss = mean token
cross-entropy over the vocabulary slice + ``aux_coef`` x ``E sum_e f_e P_e``
(``f_e``: the mean over layers and tokens of the assignments on expert e, ``P_e``
of its softmax score).

Computed in blocks so that 8,192 tokens fit beside the training state (none
changes a result): a delta mixer a slice of its key heads at a time (the heads
meet only in ``W_out``, whose rows' products are summed as they come), attention
one query head and one block of ``ATTN_BLOCK`` queries at a time, the experts
scanned one at a time, the head in chunks of positions, every layer
checkpointed; the layers of a stack one after the other, each from its slice.

``routes`` (``[L, S, k]`` expert indices), where given, replaces the reference's
own top-k. ``variant`` swaps one term for what a mistaken implementation would
compute (``VARIANTS``). It reads the program's parameter tree
(``models/qwen3_next.py:init``) because that is what the weights come in;
nothing else is shared with the code under test.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

ATTN_BLOCK = 1024    # queries a score block holds
HEAD_CHUNK = 1024    # positions a chunk of the head's log-probabilities holds
SCAN_STRETCH = 64    # tokens between two kept states of the recurrence's backward
HEAD_SLICES = 8      # parts the delta mixer's key heads are taken in

# one term of the layer equations computed as a mistaken implementation would
VARIANTS = ("no_decay", "decay_after_update", "no_delta_term", "beta_one", "no_state_between_chunks",
            "no_l2norm", "key_heads_tiled", "no_conv", "out_norm_gate_sigmoid", "no_attention_gate",
            "rotary_whole_head", "norm_weight_not_offset", "no_head_norms", "weights_not_renormalised",
            "shared_expert_ungated", "no_aux_loss")

# published key (scalar) -> attribute of the program's Qwen3NextConfig
_PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "full_attention_interval": "period",
    "head_dim": "head_dim",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "partial_rotary_factor": "partial_rotary",
    "rope_theta": "rope_theta",
    "linear_num_key_heads": "key_heads",
    "linear_num_value_heads": "value_heads",
    "linear_key_head_dim": "key_head_dim",
    "linear_value_head_dim": "value_head_dim",
    "linear_conv_kernel_dim": "conv_taps",
    "moe_intermediate_size": "d_expert",
    "shared_expert_intermediate_size": "d_shared",
    "num_experts_per_tok": "top_k",
    "num_experts": "experts_held",
    "vocab_size": "vocab",
    "rms_norm_eps": "rms_eps",
}
# what the program cannot vary, so the file must say what the program does
_FIXED = {"decoder_sparse_step": 1, "mlp_only_layers": [], "norm_topk_prob": True, "hidden_act": "silu",
          "tie_word_embeddings": False, "rope_scaling": None, "use_sliding_window": False,
          "model_type": "qwen3_next"}

# the decay leaves' initialisation the program has (``models/qwen3_next.init``), as the file must name it
GDN_INIT = "a_log=log(uniform(0,16)), dt_bias=softplus^-1(loguniform(1e-3,1e-1)), conv=uniform(1/sqrt(taps))"


def _routed(file_cfg: Dict[str, Any]) -> int:
    """The router's outputs: the published count where the file's
    ``num_experts`` is the share held here."""
    return int(file_cfg.get("published", {}).get("num_experts", file_cfg["num_experts"]))


def check_config(program_config: Any, file_cfg: Dict[str, Any]) -> None:
    """The registry's configuration must be the file's, key for key."""
    name = file_cfg["name"]

    def same(what, have, want):
        if have != want:
            raise ValueError(f"configuration {name}: the program runs {what}={have!r}, the file says {want!r}")

    c = program_config
    for pub, attr in _PUBLISHED_TO_PROGRAM.items():
        same(f"{attr} ({pub})", getattr(c, attr), file_cfg[pub])
    same("n_experts (the router's outputs)", c.n_experts, _routed(file_cfg))
    same("expert_offset", c.expert_offset, int(file_cfg["expert_offset"]))
    assumed = file_cfg["assumed"]
    same("max_len (assumed.seq_len)", c.max_len, int(assumed["seq_len"]["value"]))
    same("chunk (assumed.chunk)", c.chunk, int(assumed["chunk"]["value"]))
    same("aux_coef", c.aux_coef, assumed["aux_coefficients"]["load_balancing"])
    for pub, want in _FIXED.items():
        if file_cfg.get(pub, want) != want:
            raise ValueError(f"configuration {name}: {pub}={file_cfg[pub]!r} is not what is built")
    if assumed["gdn_init"]["value"] != GDN_INIT:
        raise ValueError(f"configuration {name}: the decay leaves' initialisation is models/qwen3_next.init's")
    if assumed["key_head_of_value_head"]["value"] != "j // 2":
        raise ValueError(f"configuration {name}: value head j reads key head j // (value heads / key heads)")


def sizes(file_cfg: Dict[str, Any]) -> Dict[str, int]:
    """What the FLOP arithmetic and the data generator need."""
    return {
        "n_layer": int(file_cfg["num_hidden_layers"]),
        "d_model": file_cfg["hidden_size"],
        "seq_len": int(file_cfg["assumed"]["seq_len"]["value"]),
        "vocab": file_cfg["vocab_size"],
    }


def hyper(file_cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "heads": int(file_cfg["num_attention_heads"]),
        "kv_heads": int(file_cfg["num_key_value_heads"]),
        "head_dim": int(file_cfg["head_dim"]),
        "rotary_dim": int(int(file_cfg["head_dim"]) * float(file_cfg["partial_rotary_factor"])),
        "theta": float(file_cfg["rope_theta"]),
        "key_heads": int(file_cfg["linear_num_key_heads"]),
        "value_heads": int(file_cfg["linear_num_value_heads"]),
        "key_dim": int(file_cfg["linear_key_head_dim"]),
        "value_dim": int(file_cfg["linear_value_head_dim"]),
        "taps": int(file_cfg["linear_conv_kernel_dim"]),
        "chunk": int(file_cfg["assumed"]["chunk"]["value"]),   # read by the variant ``no_state_between_chunks`` only
        "eps": float(file_cfg["rms_norm_eps"]),
        "top_k": int(file_cfg["num_experts_per_tok"]),
        "offset": int(file_cfg["expert_offset"]),
        "aux_coef": float(file_cfg["assumed"]["aux_coefficients"]["load_balancing"]),
    }


def _rms(x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm(w: jax.Array, x: jax.Array, eps: float, variant: Optional[str]) -> jax.Array:
    """The zero-centred norm: ``* (1 + w)``."""
    return _rms(x, eps) * (w if variant == "norm_weight_not_offset" else 1.0 + w)


# ---------------------------------------------------------------------------
# the Gated DeltaNet
# ---------------------------------------------------------------------------


def _delta_rule(q, k, v, g, beta, reset_every: int, variant: Optional[str]):
    """``o`` [Z, T, H, V] of the steps at the top of this module, one token at a
    time from a zero state: ``q``, ``k`` [Z, T, H, K] (already at the value
    heads' count), ``v`` [Z, T, H, V], ``g``, ``beta`` [Z, T, H]. ``reset_every``:
    a mistaken implementation's, the state set to zero every so many tokens."""
    z, t, h, dk = q.shape
    stretch = SCAN_STRETCH if t % SCAN_STRETCH == 0 else t

    def token(s, now):
        q_t, k_t, v_t, g_t, b_t, i = now                           # [Z, H, *]
        if reset_every:
            s = jnp.where(i % reset_every == 0, 0.0, s)
        alpha = jnp.exp(g_t)[..., None, None]                      # one number a head
        if variant == "no_decay":
            alpha = 1.0
        if variant != "decay_after_update":
            s = alpha * s
        held = jnp.sum(s * k_t[..., None], axis=-2)                # S^T k: what the state holds along the key
        if variant == "no_delta_term":
            held = 0.0
        u = b_t[..., None] * (v_t - held)
        s = s + k_t[..., None] * u[..., None, :]
        if variant == "decay_after_update":
            s = alpha * s
        return s, jnp.sum(s * q_t[..., None], axis=-2)             # S^T q

    @jax.checkpoint  # the backward pass recomputes a stretch's states from the one at its start
    def one_stretch(s, xs):
        return jax.lax.scan(token, s, xs)

    def by_stretch(a):
        return jnp.moveaxis(a, 1, 0).reshape(t // stretch, stretch, *a.shape[:1], *a.shape[2:])

    xs = tuple(by_stretch(a) for a in (q, k, v, g, beta)) + (jnp.arange(t).reshape(t // stretch, stretch),)
    _, o = jax.lax.scan(one_stretch, jnp.zeros((z, h, dk, v.shape[-1]), q.dtype), xs)
    return jnp.moveaxis(o.reshape(t, z, h, v.shape[-1]), 0, 1)


def _causal_conv(u: jax.Array, w: jax.Array) -> jax.Array:
    """``c_t = sum_j w[j] u_{t - (K - 1 - j)}``, zeros before the start; ``u`` [Z, T, C], ``w`` [K, C]."""
    k, t = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * w[j] for j in range(k))


def _l2norm(x: jax.Array) -> jax.Array:
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _delta(p: Dict[str, Any], n: jax.Array, hp: Dict[str, Any], variant: Optional[str]) -> jax.Array:
    """The mixer on the normed stream ``n`` [Z, T, d], a slice of its key heads
    (with their value heads) at a time: every product of a slice's own columns
    and rows inside the scan over the slices, each slice checkpointed, the
    slices' outputs summed as they come."""
    z, t, d = n.shape
    hk, hv, dk, dv, taps = hp["key_heads"], hp["value_heads"], hp["key_dim"], hp["value_dim"], hp["taps"]
    r = hv // hk
    parts = HEAD_SLICES if hk % HEAD_SLICES == 0 else 1
    sk, sv = hk // parts, hv // parts                         # key and value heads a slice
    kd, vd = hk * dk, hv * dv
    b, a = jnp.split(n @ p["w_ba"], 2, axis=-1)                                # [Z, T, Hv] each
    beta = jnp.ones_like(b) if variant == "beta_one" else jax.nn.sigmoid(b)
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])
    if variant == "key_heads_tiled":   # value head j reads key head j % Hk: the heads cross slices, so no slices
        parts, sk, sv = 1, hk, hv

    def cols(w, at: int, heads: int, width: int):
        """Columns ``at ..`` of ``w`` [*, C], ``heads`` heads of ``width``: [parts, *, heads / parts x width]."""
        part = w[..., at:at + heads * width]
        return jnp.moveaxis(part.reshape(*part.shape[:-1], parts, (heads // parts) * width), -2, 0)

    w, cw = p["w_qkvz"], p["conv_w"]
    by_slice = (
        cols(w, 0, hk, dk), cols(w, kd, hk, dk), cols(w, 2 * kd, hv, dv), cols(w, 2 * kd + vd, hv, dv),
        cols(cw, 0, hk, dk), cols(cw, kd, hk, dk), cols(cw, 2 * kd, hv, dv),
        p["wo"].reshape(parts, sv * dv, d),
        jnp.moveaxis(g.reshape(z, t, parts, sv), 2, 0), jnp.moveaxis(beta.reshape(z, t, parts, sv), 2, 0),
    )

    @jax.checkpoint
    def one_slice(wq, wk, wv, wz, cq, ck, cv, wo, g, beta):
        u = n @ jnp.concatenate([wq, wk, wv], axis=-1)            # the slice's q, k and v columns: one product
        if variant != "no_conv":
            u = _causal_conv(u, jnp.concatenate([cq, ck, cv], axis=-1))
        u = jax.nn.silu(u)
        q = u[..., :sk * dk].reshape(z, t, sk, dk)
        k = u[..., sk * dk:2 * sk * dk].reshape(z, t, sk, dk)
        v = u[..., 2 * sk * dk:].reshape(z, t, sv, dv)
        if variant != "no_l2norm":
            q, k = _l2norm(q), _l2norm(k)
        q = q / math.sqrt(dk)
        if variant == "key_heads_tiled":
            q, k = jnp.tile(q, (1, 1, r, 1)), jnp.tile(k, (1, 1, r, 1))
        else:   # repeat_interleave along the head axis: value head j reads key head j // r
            q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
        o = _delta_rule(q, k, v, g, beta, hp["chunk"] if variant == "no_state_between_chunks" else 0, variant)
        o = _rms(o, hp["eps"]) * p["o_norm"]["g"]
        gate = n @ wz
        gate = jax.nn.sigmoid(gate) if variant == "out_norm_gate_sigmoid" else jax.nn.silu(gate)
        return (o.reshape(z, t, sv * dv) * gate) @ wo

    total, _ = jax.lax.scan(lambda acc, ws: (acc + one_slice(*ws), None), jnp.zeros_like(n), by_slice)
    return total


# ---------------------------------------------------------------------------
# gated attention
# ---------------------------------------------------------------------------


def _rotate(x: jax.Array, rotary_dim: int, theta: float) -> jax.Array:
    """Rotate-half over the first ``rotary_dim`` coordinates of ``x`` [B, T, D], positions 0..T-1."""
    t = x.shape[1]
    half = rotary_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]          # [T, half]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention_head(q: jax.Array, k: jax.Array, v: jax.Array, scale: float) -> jax.Array:
    """ONE head, ``q``, ``k``, ``v`` [B, T, D] -> [B, T, D]: a block of queries
    at a time against every key, an explicit mask ``j <= i``."""
    b, t, d = q.shape
    block = ATTN_BLOCK if t % ATTN_BLOCK == 0 else t
    j = jnp.arange(t)[None, :]

    @jax.checkpoint  # the backward pass recomputes a block's [block, T] scores
    def one_block(qb, i0):
        i = i0 + jnp.arange(block)[:, None]
        scores = qb @ jnp.swapaxes(k, -1, -2) * scale
        return jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1) @ v

    blocks = jnp.moveaxis(q.reshape(b, t // block, block, d), 1, 0)
    starts = jnp.arange(t // block) * block
    _, out = jax.lax.scan(lambda c, qi: (c, one_block(*qi)), None, (blocks, starts))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, d)


def _attention(p: Dict[str, Any], n: jax.Array, hp: Dict[str, Any], variant: Optional[str]) -> jax.Array:
    """The mixer on the normed stream ``n`` [B, T, d]: the two key/value heads
    for the whole sequence, each query head's own products inside the scan over heads."""
    b, t, d = n.shape
    heads, kv, hd = hp["heads"], hp["kv_heads"], hp["head_dim"]
    rot = hd if variant == "rotary_whole_head" else hp["rotary_dim"]

    def head_norm(w, x):
        return x if variant == "no_head_norms" else _norm(w, x, hp["eps"], variant)

    k = (n @ p["wk"]).reshape(b, t, kv, hd)
    v = (n @ p["wv"]).reshape(b, t, kv, hd)
    k = jnp.stack([_rotate(head_norm(p["k_norm"]["w"], k[:, :, i]), rot, hp["theta"]) for i in range(kv)])
    v = jnp.moveaxis(v, 2, 0)                                                       # [kv, B, T, D]
    group = heads // kv
    by_head = (jnp.moveaxis(p["wq"].reshape(d, heads, 2 * hd), 1, 0),               # [H, d, query | gate]
               p["wo"].reshape(heads, hd, d), jnp.arange(heads) // group)

    @jax.checkpoint
    def one_head(wq, wo, kv_head):
        qg = n @ wq
        q = _rotate(head_norm(p["q_norm"]["w"], qg[..., :hd]), rot, hp["theta"])
        a = _attention_head(q, k[kv_head], v[kv_head], 1.0 / math.sqrt(hd))
        if variant != "no_attention_gate":
            a = a * jax.nn.sigmoid(qg[..., hd:])
        return a @ wo

    total, _ = jax.lax.scan(lambda acc, w: (acc + one_head(*w), None), jnp.zeros_like(n), by_head)
    return total


# ---------------------------------------------------------------------------
# the experts, the layer, the loss
# ---------------------------------------------------------------------------


def _swiglu(h: jax.Array, w: Dict[str, jax.Array]) -> jax.Array:
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def _experts(p: Dict[str, jax.Array], h: jax.Array, weight: jax.Array) -> jax.Array:
    """Every held expert on every token of ``h`` [S, d], each scaled by its
    column of ``weight`` [S, held]: a scan over single experts that carries
    their sum, the body checkpointed."""

    @jax.checkpoint
    def one(w, col):
        return col[:, None] * _swiglu(h, w)

    total, _ = jax.lax.scan(lambda acc, w_col: (acc + one(*w_col), None),
                            jnp.zeros_like(h), (dict(p), weight.T))
    return total


def _moe(p: Dict[str, Any], n2: jax.Array, routes: Optional[jax.Array], hp: Dict[str, Any],
         variant: Optional[str]):
    """(The layer's expert output [B, T, d], the routes it used [S, k], (f [E]:
    assignments a token on each expert, P [E]: its mean softmax score).)"""
    b, t, d = n2.shape
    flat = n2.reshape(b * t, d)
    scores = jax.nn.softmax(flat @ p["router"], axis=-1)                          # [S, E]
    if routes is None:
        _, routes = jax.lax.top_k(scores, hp["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(routes, scores.shape[-1], dtype=scores.dtype), axis=1)
    weight = chosen * scores
    if variant != "weights_not_renormalised":
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    held = p["experts"]["w_gate"].shape[0]
    y = _experts(p["experts"], flat, weight[:, hp["offset"]:hp["offset"] + held])
    shared = _swiglu(flat, p["shared"])
    if variant != "shared_expert_ungated":
        shared = shared * jax.nn.sigmoid(flat @ p["shared_gate"])
    return (shared + y).reshape(b, t, d), routes, (jnp.mean(chosen, axis=0), jnp.mean(scores, axis=0))


def _block(p: Dict[str, Any], x: jax.Array, routes: Optional[jax.Array], hp: Dict[str, Any],
           variant: Optional[str] = None):
    """One layer on ``x`` [B, T, d], its mixer's kind read off ``p``; returns
    the routes it used (``[S, k]``) and its balancing statistics."""
    n = _norm(p["ln_mixer"]["w"], x, hp["eps"], variant)
    mixer = _delta if "w_qkvz" in p["mixer"] else _attention
    x = x + mixer(p["mixer"], n, hp, variant)
    y, routes, stats = _moe(p, _norm(p["ln_ffn"]["w"], x, hp["eps"], variant), routes, hp, variant)
    return x + y, routes, stats


def _head_loss(x: jax.Array, w_norm: jax.Array, w: jax.Array, targets: jax.Array, eps: float,
               variant: Optional[str]) -> jax.Array:
    b, t, d = x.shape
    chunk = HEAD_CHUNK if t % HEAD_CHUNK == 0 else t

    @jax.checkpoint
    def one(xc, tc):
        logp = jax.nn.log_softmax(_norm(w_norm, xc, eps, variant) @ w, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tc[..., None], axis=-1))

    xs = jnp.moveaxis(x.reshape(b, t // chunk, chunk, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(b, t // chunk, chunk), 1, 0)
    total, _ = jax.lax.scan(lambda acc, xt: (acc + one(*xt), None), jnp.zeros((), x.dtype), (xs, ts))
    return total / (b * t)


def layers_in_order(blocks: Dict[str, Any]):
    """The layers' parameter trees in layer order, from the program's stacks
    ``{"linear": [P, 3, ...], "full": [P, ...]}``: a period's delta layers, then its attention layer."""
    periods, per = jax.tree_util.tree_leaves(blocks["linear"])[0].shape[:2]
    for period in range(periods):
        for i in range(per):
            yield jax.tree_util.tree_map(lambda a: a[period, i], blocks["linear"])
        yield jax.tree_util.tree_map(lambda a: a[period], blocks["full"])


def loss(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array, hp: Dict[str, Any],
         routes: Optional[jax.Array] = None, with_routes: bool = False,
         variant: Optional[str] = None):
    """Mean next-token cross-entropy plus the load-balancing term, float32
    throughout. ``with_routes`` also returns the ``[L, S, k]`` routes used."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)

        # checkpointed: the backward pass keeps one layer's activations
        @jax.checkpoint
        def layer(x, p, given):
            return _block(p, x, given, hp, variant)

        x = params["wte"][tokens]
        used, choices, probs = [], [], []
        # one layer after the other, NOT a scan over the stack: a scan keeps the stack's float32
        # parameters and gradients a second time inside the loop (benchmark/references/nemotron_h.py, PR 48)
        for i, p in enumerate(layers_in_order(params["blocks"])):
            x, out, (f, pm) = layer(x, p, None if routes is None else routes[i])
            used.append(out)
            choices.append(f)
            probs.append(pm)
        total = _head_loss(x, params["ln_f"]["w"], params["lm_head"], targets, hp["eps"], variant)
        if variant != "no_aux_loss":
            f, pm = jnp.mean(jnp.stack(choices), axis=0), jnp.mean(jnp.stack(probs), axis=0)
            total = total + hp["aux_coef"] * f.shape[0] * jnp.sum(f * pm)
        return (total, jnp.stack(used)) if with_routes else total


def make_loss_and_grad(file_cfg: Dict[str, Any]):
    """``(params, tokens, targets[, routes]) -> (loss, grads)`` for this configuration."""
    hp = hyper(file_cfg)

    def fn(params, tokens, targets, routes=None) -> Tuple[jax.Array, Any]:
        return jax.value_and_grad(loss)(params, tokens, targets, hp, routes)

    return fn
