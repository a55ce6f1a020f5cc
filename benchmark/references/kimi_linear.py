"""Kimi-Linear-48B-A3B-Instruct as published
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct ``config.json``,
``model_type`` ``kimi_linear``), plain: float32 ``jax.numpy`` at the highest
matmul precision, no kernel, no sort, no grouped matmul, NO CHUNK ALGEBRA.

The layer equations, written out here and followed independently of the
program (``models/kimi_linear.py``). Pre-norm residual blocks, RMSNorm eps
1e-5, d = 2,304, an untied head, a final norm: ``x <- x + mixer(norm(x))``,
``x <- x + ffn(norm(x))``. The config counts layers from 1: ``kda_layers`` 1-3,
5-7, ... and ``full_attn_layers`` 4, 8, ..., 24, 27; ``first_k_dense_replace``
1 makes layer 1's FFN dense.

**KDA mixer** (H = 32 heads, key and value head 128), for a token t and a head h:

- ``q = l2norm_head(silu(conv4(x W_q))) / sqrt(128)``, ``k = l2norm_head(silu(conv4(x W_k)))``,
  ``v = silu(conv4(x W_v))``; the convolutions causal, depthwise, four taps a
  channel, no bias, here a sum of shifted copies (the program holds ``W_q``,
  ``W_k``, ``W_v`` side by side as ``w_qkv`` [2304, 3 x 4096]); the l2 norm adds
  1e-6 under its root.
- log decay, a KEY CHANNEL: ``g_t = -exp(A_log_h) softplus((x_t W_fa) W_fb + dt_bias)``
  in ``R^128`` a head; ``alpha_t = exp(g_t)`` in (0, 1).
- ``beta_t = sigmoid(x_t W_b)``, one a head.
- state ``S`` in ``R^{128 x 128}`` (key x value), ONE TOKEN AT A TIME, exactly
  as these three steps read: decay the state by channel, ``S <- Diag(alpha_t) S``;
  ``u_t = beta_t (v_t - S^T k_t)``; ``S <- S + k_t u_t^T``; ``o_t = S^T q_t``.
  (A ``lax.scan`` over the tokens inside a ``lax.scan`` over stretches of
  ``SCAN_STRETCH`` of them whose body is checkpointed, so that the backward keeps
  a state a stretch and not a state a token: the stretches change no result;
  there is no triangular system, no decay matrix and no product over a chunk.)
- ``y = (rmsnorm_128(o_t) * sigmoid((x_t W_ga) W_gb + b_g)) W_o``: the norm over
  each head's 128 with one learned scale of 128.

**Latent attention mixer** (32 heads): ``q = x W_q`` as [T, 32, 192]; ``x W_kva``
as [T, 576] splits into a latent of 512 and a shared key part of 64;
``[k_nope | v] = rmsnorm(latent) W_kvb`` as [T, 32, 128 + 128]; ``k = [k_nope |
shared part]`` (192), the same shared part for every head; NOTHING is rotated
(``mla_use_nope``); causal softmax of ``q k^T / sqrt(192)`` under an explicit
mask; values of width 128; ``W_o``: 4,096 x 2,304.

**FFN.** Layer 1: SwiGLU of 9,216. Every other layer: sigmoid scores over all
256, the top 8 of ``scores + bias`` (the bias enters the choice and nothing
else; no group limits it), weights the chosen scores over their sum (+1e-20)
times 2.446; SwiGLU experts of 1,024; one shared expert of 1,024 on every token,
unweighted. Every HELD expert runs on every token and is masked by the top-k
one-hot times the weight; the experts this chip does not hold add nothing, here
as in the program. Loss = mean token cross-entropy over the vocabulary slice; no
auxiliary term. The selection bias gets no gradient.

Computed in blocks so that 8,192 tokens fit beside the training state (none
changes a result): a KDA mixer a slice of its heads at a time (the heads meet
only in ``W_o``, whose rows' products are summed as they come), attention one
head and one block of ``ATTN_BLOCK`` queries at a time, the experts scanned one
at a time, the dense FFN and the head in chunks of positions, every layer
checkpointed; the layers of a run (the program stacks them) one after the other,
each from its slice of the stack.

Departures, here as in the program (the configuration file's ``assumed`` says
why): the gates' inner width 128; a bias on ``W_gb`` and none on the
convolutions; nothing trains the selection bias here (the rule is the step's).

``routes`` (``[L_sparse, S, k]`` expert indices), where given, replaces the
reference's own top-k. ``variant`` swaps one term for what a mistaken
implementation would compute (``VARIANTS``). It reads the program's parameter
tree (``models/kimi_linear.py:init``) because that is what the weights come in;
nothing else is shared with the code under test.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

ATTN_BLOCK = 1024    # queries a score block holds
HEAD_CHUNK = 1024    # positions a chunk of the head's log-probabilities holds
FFN_CHUNK = 1024     # positions a chunk of the dense FFN's gate and up products holds
SCAN_STRETCH = 64    # tokens between two kept states of the recurrence's backward
HEAD_SLICES = 8      # parts the KDA mixer's heads are taken in

# one term of the layer equations computed as a mistaken implementation would
VARIANTS = ("no_delta_term", "scalar_decay", "decay_after_update", "beta_one", "no_l2norm",
            "no_output_gate", "no_head_norm", "no_conv", "no_state_between_chunks",
            "value_head_192", "shared_key_per_head", "scale_128_for_192",
            "weights_not_renormalised", "no_scaling_factor", "bias_in_weights", "shared_expert_weighted")

# published key (scalar) -> attribute of the program's KimiLinearConfig
_PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "first_k_dense_replace": "dense_layers",
    "intermediate_size": "d_ff",
    "moe_intermediate_size": "d_expert",
    "num_shared_experts": "n_shared",
    "num_experts_per_token": "top_k",
    "num_experts": "experts_held",
    "vocab_size": "vocab",
    "rms_norm_eps": "rms_eps",
    "routed_scaling_factor": "routed_scale",
}
# linear_attn_config's keys -> the program's
_LINEAR_TO_PROGRAM = {"num_heads": "kda_heads", "head_dim": "kda_head_dim", "short_conv_kernel_size": "conv_taps"}
# what the program cannot vary, so the file must say what the program does
_FIXED = {"mla_use_nope": True, "q_lora_rank": None, "moe_renormalize": True,
          "moe_router_activation_func": "sigmoid", "num_expert_group": 1, "topk_group": 1,
          "tie_word_embeddings": False, "num_nextn_predict_layers": 0, "hidden_act": "silu",
          "moe_layer_freq": 1, "rope_scaling": None, "model_type": "kimi_linear"}

# the decay leaves' initialisation the program has (``models/kimi_linear.init``), as the file must name it
KDA_INIT = "a_log=log(uniform(1,16)), dt_bias=softplus^-1(loguniform(1e-3,1e-1)), conv=uniform(1/sqrt(taps))"


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
    linear = file_cfg["linear_attn_config"]
    for pub, attr in _LINEAR_TO_PROGRAM.items():
        same(f"{attr} (linear_attn_config.{pub})", getattr(c, attr), linear[pub])
    same("kda_layers", tuple(c.kda_layers), tuple(linear["kda_layers"]))
    same("full_attn_layers", tuple(c.full_attn_layers), tuple(linear["full_attn_layers"]))
    same("n_experts (the router's outputs)", c.n_experts, _routed(file_cfg))
    same("expert_offset", c.expert_offset, int(file_cfg["expert_offset"]))
    same("num_key_value_heads (latent attention: a key and a value a query head)", c.n_heads,
         int(file_cfg["num_key_value_heads"]))
    assumed = file_cfg["assumed"]
    same("max_len (assumed.seq_len)", c.max_len, int(assumed["seq_len"]["value"]))
    same("gate_rank (assumed.gate_rank)", c.gate_rank, int(assumed["gate_rank"]["value"]))
    same("chunk (assumed.chunk)", c.chunk, int(assumed["chunk"]["value"]))
    same("bias_gamma (assumed.expert_bias)", c.bias_gamma, float(assumed["expert_bias"]["gamma"]))
    for pub, want in _FIXED.items():
        if file_cfg.get(pub, want) != want:
            raise ValueError(f"configuration {name}: {pub}={file_cfg[pub]!r} is not what is built")
    if assumed["aux_coefficients"]["load_balancing"] != 0:
        raise ValueError(f"configuration {name}: the program has no auxiliary loss")
    if assumed["kda_init"]["value"] != KDA_INIT:
        raise ValueError(f"configuration {name}: the decay leaves' initialisation is models/kimi_linear.init's")
    if assumed["biases"]["value"] != "output gate: yes; convolutions: none":
        raise ValueError(f"configuration {name}: the program has a bias on W_gb and none on the convolutions")


def sizes(file_cfg: Dict[str, Any]) -> Dict[str, int]:
    """What the FLOP arithmetic and the data generator need."""
    return {
        "n_layer": int(file_cfg["num_hidden_layers"]),
        "d_model": file_cfg["hidden_size"],
        "seq_len": int(file_cfg["assumed"]["seq_len"]["value"]),
        "vocab": file_cfg["vocab_size"],
    }


def hyper(file_cfg: Dict[str, Any]) -> Dict[str, Any]:
    linear = file_cfg["linear_attn_config"]
    return {
        "heads": int(file_cfg["num_attention_heads"]),
        "latent": int(file_cfg["kv_lora_rank"]),
        "nope": int(file_cfg["qk_nope_head_dim"]),
        "shared_key": int(file_cfg["qk_rope_head_dim"]),
        "v_dim": int(file_cfg["v_head_dim"]),
        "kda_heads": int(linear["num_heads"]),
        "kda_head": int(linear["head_dim"]),
        "taps": int(linear["short_conv_kernel_size"]),
        "chunk": int(file_cfg["assumed"]["chunk"]["value"]),   # read by the variant ``no_state_between_chunks`` only
        "eps": float(file_cfg["rms_norm_eps"]),
        "top_k": int(file_cfg["num_experts_per_token"]),
        "offset": int(file_cfg["expert_offset"]),
        "scale": float(file_cfg["routed_scaling_factor"]),
    }


def _rmsnorm(g: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _by_positions(fn, x: jax.Array, chunk: int) -> jax.Array:
    """``fn`` (position-wise, [B, c, d] -> [B, c, e]) over ``x`` a chunk of
    positions at a time, the body checkpointed."""
    b, t, d = x.shape
    if t % chunk:
        return fn(x)
    xs = jnp.moveaxis(x.reshape(b, t // chunk, chunk, d), 1, 0)
    _, out = jax.lax.scan(lambda c, xc: (c, jax.checkpoint(fn)(xc)), None, xs)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, -1)


# ---------------------------------------------------------------------------
# the KDA mixer
# ---------------------------------------------------------------------------


def _delta_rule(q, k, v, g, beta, reset_every: int, variant: Optional[str]):
    """``o`` [Z, T, H, V] of the three steps at the top of this module, one
    token at a time from a zero state: ``q``, ``k``, ``g`` [Z, T, H, K], ``v``
    [Z, T, H, V], ``beta`` [Z, T, H]. ``reset_every``: a mistaken
    implementation's, the state set to zero every so many tokens (0: never)."""
    z, t, h, dk = q.shape
    stretch = SCAN_STRETCH if t % SCAN_STRETCH == 0 else t

    def token(s, now):
        q_t, k_t, v_t, g_t, b_t, i = now                           # [Z, H, *]
        if reset_every:
            s = jnp.where(i % reset_every == 0, 0.0, s)
        alpha = jnp.exp(g_t)[..., None]                            # [Z, H, K, 1]
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


def _kda(p: Dict[str, Any], n: jax.Array, hp: Dict[str, Any], variant: Optional[str]) -> jax.Array:
    """The mixer on the normed stream ``n`` [Z, T, d], a slice of its heads at
    a time: every product of a head's own columns (of ``W_q``, ``W_k``, ``W_v``,
    the taps, ``W_fb``, ``W_gb``) and rows (of ``W_o``) inside the scan over the
    slices, each slice checkpointed, the slices' outputs summed as they come."""
    z, t, d = n.shape
    h, hd, taps = hp["kda_heads"], hp["kda_head"], hp["taps"]
    parts = HEAD_SLICES if h % HEAD_SLICES == 0 else 1
    hs = h // parts
    # the two gates' inner products and beta's as the parts of one product (two fewer for the compiler to emit:
    # each is megabytes of a float32 program's code, and the program is a third of the machine's compile cache)
    rank = p["w_fa"].shape[-1]
    fa, ga, beta = jnp.split(n @ jnp.concatenate([p["w_fa"], p["w_ga"], p["w_beta"]], axis=-1), [rank, 2 * rank], axis=-1)
    beta = jax.nn.sigmoid(beta)                                             # [Z, T, H]
    if variant == "beta_one":
        beta = jnp.ones_like(beta)

    def cols(w):      # [*, H hd] -> [parts, *, hs hd]: a slice's columns
        return jnp.moveaxis(w.reshape(*w.shape[:-1], parts, hs * hd), -2, 0)

    by_slice = (
        jnp.moveaxis(p["w_qkv"].reshape(d, 3, parts, hs * hd), 2, 0),       # [parts, d, 3, hs hd]
        jnp.moveaxis(p["conv_w"].reshape(taps, 3, parts, hs * hd), 2, 0),   # [parts, taps, 3, hs hd]
        cols(p["w_fb"]), cols(p["dt_bias"]), p["a_log"].reshape(parts, hs),
        cols(p["w_gb"]), cols(p["gate_b"]), p["wo"].reshape(parts, hs * hd, d),
        jnp.moveaxis(beta.reshape(z, t, parts, hs), 2, 0),
    )

    @jax.checkpoint
    def one_slice(w_qkv, conv_w, w_fb, dt_bias, a_log, w_gb, gate_b, wo, beta):
        u = n @ w_qkv.reshape(d, 3 * hs * hd)       # the slice's q, k and v columns side by side: one product
        if variant != "no_conv":
            u = _causal_conv(u, conv_w.reshape(taps, 3 * hs * hd))
        q, k, v = (a.reshape(z, t, hs, hd) for a in jnp.split(jax.nn.silu(u), 3, axis=-1))
        if variant != "no_l2norm":
            q, k = _l2norm(q), _l2norm(k)
        q = q / math.sqrt(hd)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(fa @ w_fb + dt_bias).reshape(z, t, hs, hd)
        if variant == "scalar_decay":   # a head's mean decay on every channel
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        o = _delta_rule(q, k, v, g, beta, hp["chunk"] if variant == "no_state_between_chunks" else 0, variant)
        if variant != "no_head_norm":
            o = _rmsnorm(p["o_norm"]["g"], o, hp["eps"])
        o = o.reshape(z, t, hs * hd)
        if variant != "no_output_gate":
            o = o * jax.nn.sigmoid(ga @ w_gb + gate_b)
        return o @ wo

    total, _ = jax.lax.scan(lambda acc, w: (acc + one_slice(*w), None), jnp.zeros_like(n), by_slice)
    return total


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------


def _attention_head(q: jax.Array, k: jax.Array, v: jax.Array, scale: float) -> jax.Array:
    """ONE head, ``q`` and ``k`` [B, T, D], ``v`` [B, T, Dv] -> [B, T, Dv]: a
    block of queries at a time against every key, an explicit mask ``j <= i``."""
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
    return jnp.moveaxis(out, 0, 1).reshape(b, t, v.shape[-1])


def _latent(p: Dict[str, Any], n: jax.Array, hp: Dict[str, Any], variant: Optional[str]) -> jax.Array:
    """The mixer on the normed stream ``n`` [B, T, d]: the latent and the one
    shared key part for the whole sequence, each head's own products inside the
    scan over heads."""
    b, t, d = n.shape
    heads, latent, nope, shared, v_dim = hp["heads"], hp["latent"], hp["nope"], hp["shared_key"], hp["v_dim"]
    joint = n @ p["wkv_a"]
    c, k_shared = _rmsnorm(p["kv_a_norm"]["g"], joint[..., :latent], hp["eps"]), joint[..., latent:]
    scale = 1.0 / math.sqrt(nope if variant == "scale_128_for_192" else nope + shared)
    by_head = (jnp.moveaxis(p["wq"].reshape(d, heads, nope + shared), 1, 0),       # [H, d, 192]
               jnp.moveaxis(p["wkv_b"].reshape(latent, heads, nope + v_dim), 1, 0),  # [H, latent, 128 + 128]
               p["wo"].reshape(heads, v_dim, d))                                    # [H, 128, d]

    @jax.checkpoint
    def one_head(wq, wkv, wo):
        q, kv = n @ wq, c @ wkv
        k_nope, v = kv[..., :nope], kv[..., nope:]
        if variant == "value_head_192":   # the value taken from where a key of ``shared`` coordinates would end
            v = kv[..., shared:shared + v_dim]
        # a shared part of the head's own, taken from W_kvb's output (its value's first coordinates), or the one
        k_own = v[..., :shared] if variant == "shared_key_per_head" else k_shared
        return _attention_head(q, jnp.concatenate([k_nope, k_own], axis=-1), v, scale) @ wo

    total, _ = jax.lax.scan(lambda acc, w: (acc + one_head(*w), None), jnp.zeros_like(n), by_head)
    return total


# ---------------------------------------------------------------------------
# the FFNs, the layer, the loss
# ---------------------------------------------------------------------------


def _swiglu(h: jax.Array, w: Dict[str, jax.Array]) -> jax.Array:
    """``(silu(h w_gate) * (h w_up)) w_down``, gate and up as the two halves of one product (the same sums)."""
    gate, up = jnp.split(h @ jnp.concatenate([w["w_gate"], w["w_up"]], axis=-1), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w["w_down"]


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


def _ffn(p: Dict[str, Any], n2: jax.Array, routes: Optional[jax.Array], hp: Dict[str, Any],
         variant: Optional[str]):
    b, t, d = n2.shape
    if "mlp" in p:
        return _by_positions(lambda h: _swiglu(h, p["mlp"]), n2, FFN_CHUNK), None
    flat = n2.reshape(b * t, d)
    scores = jax.nn.sigmoid(flat @ p["router"])                                  # [S, E]
    biased = scores + jax.lax.stop_gradient(p["bias"])
    if routes is None:
        _, routes = jax.lax.top_k(biased, hp["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(routes, scores.shape[-1], dtype=scores.dtype), axis=1)
    weight = chosen * (biased if variant == "bias_in_weights" else scores)
    if variant != "weights_not_renormalised":
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    if variant != "no_scaling_factor":
        weight = hp["scale"] * weight
    held = p["experts"]["w_gate"].shape[0]
    y = _experts(p["experts"], flat, weight[:, hp["offset"]:hp["offset"] + held])
    shared = _by_positions(lambda h: _swiglu(h, p["shared"]), n2, FFN_CHUNK).reshape(b * t, d)
    if variant == "shared_expert_weighted":  # as one more chosen expert, at the mean of the chosen weights
        shared = shared * (jnp.sum(weight, axis=-1, keepdims=True) / hp["top_k"])
    return (shared + y).reshape(b, t, d), routes


def _block(p: Dict[str, Any], x: jax.Array, routes: Optional[jax.Array], hp: Dict[str, Any],
           variant: Optional[str] = None):
    """One layer on ``x`` [B, T, d], its mixer's and its FFN's kind read off
    ``p``; returns the routes it used (``[S, k]``; None for a dense layer)."""
    n = _rmsnorm(p["ln_mixer"]["g"], x, hp["eps"])
    mixer = _kda if "w_qkv" in p["mixer"] else _latent
    x = x + mixer(p["mixer"], n, hp, variant)
    y, routes = _ffn(p, _rmsnorm(p["ln_ffn"]["g"], x, hp["eps"]), routes, hp, variant)
    return x + y, routes


def _head_loss(x: jax.Array, g: jax.Array, w: jax.Array, targets: jax.Array, eps: float) -> jax.Array:
    b, t, d = x.shape
    chunk = HEAD_CHUNK if t % HEAD_CHUNK == 0 else t

    @jax.checkpoint
    def one(xc, tc):
        logp = jax.nn.log_softmax(_rmsnorm(g, xc, eps) @ w, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tc[..., None], axis=-1))

    xs = jnp.moveaxis(x.reshape(b, t // chunk, chunk, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(b, t // chunk, chunk), 1, 0)
    total, _ = jax.lax.scan(lambda acc, xt: (acc + one(*xt), None), jnp.zeros((), x.dtype), (xs, ts))
    return total / (b * t)


def loss(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array, hp: Dict[str, Any],
         routes: Optional[jax.Array] = None, with_routes: bool = False,
         variant: Optional[str] = None):
    """Mean next-token cross-entropy, float32 throughout. ``with_routes`` also
    returns the ``[L_sparse, S, k]`` routes used."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)

        # checkpointed: the backward pass keeps one layer's activations
        @jax.checkpoint
        def layer(x, p, given):
            return _block(p, x, given, hp, variant)

        x = params["wte"][tokens]
        used, first = [], 0
        for run in params["blocks"]:
            n = jax.tree_util.tree_leaves(run)[0].shape[0]
            sparse = "router" in run
            # one layer after the other, NOT a scan over the run: a scan keeps the stack's float32
            # parameters and gradients a second time inside the loop (benchmark/references/nemotron_h.py, PR 48)
            for i in range(n):
                given = routes[first] if sparse and routes is not None else None
                x, out = layer(x, jax.tree_util.tree_map(lambda a: a[i], run), given)
                if sparse:
                    used.append(out)
                    first += 1
        total = _head_loss(x, params["ln_f"]["g"], params["lm_head"], targets, hp["eps"])
        if not with_routes:
            return total
        k = hp["top_k"]
        return total, jnp.stack(used) if used else jnp.zeros((0, tokens.size, k), jnp.int32)


def make_loss_and_grad(file_cfg: Dict[str, Any]):
    """``(params, tokens, targets[, routes]) -> (loss, grads)`` for this configuration."""
    hp = hyper(file_cfg)

    def fn(params, tokens, targets, routes=None) -> Tuple[jax.Array, Any]:
        return jax.value_and_grad(loss)(params, tokens, targets, hp, routes)

    return fn
