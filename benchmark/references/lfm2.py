"""LFM2-24B-A2B as published
(https://huggingface.co/LiquidAI/LFM2-24B-A2B ``config.json``, ``model_type``
``lfm2_moe``; the layer equations are in ``models/lfm2.py``'s docstring and
are followed here independently), plain.

Float32 ``jax.numpy`` at the highest matmul precision: no kernel, no sort, no
grouped matmul, no chunked-by-scan loss carried through a custom derivative.
A layer's kind is read off its parameters: one with ``conv`` is a gated short
convolution (``[B, C, u] = split3(W_in n)``, ``g = B u``, ``c_t = w[0] g_{t-2}
+ w[1] g_{t-1} + w[2] g_t`` as an explicit sum over three shifted copies with
zeros before the sequence's start, ``W_out (C c)``), one with ``wq`` is
grouped-query attention (each head's 64 coordinates of q and k RMS-normed with
learned scales, then turned by the rotary embedding over the whole head,
half-split convention; query head ``h`` reads key/value head ``h // (H /
Hkv)``; an explicit causal mask); one with ``mlp`` has the dense SwiGLU FFN,
one with ``router`` the experts: sigmoid scores over all E, the top ``k`` of
``scores + bias`` (the selection bias enters the choice and nothing else),
weights the chosen scores over their sum (+1e-6), times
``routed_scaling_factor``. Every HELD expert runs on every token and is masked
by the top-k one-hot times the weight; the experts this chip does not hold add
nothing, here as in the program. Final RMSNorm, logits from the tied embedding
over the vocabulary slice. Loss = mean token cross-entropy; no auxiliary term.
The selection bias gets no gradient (the choice has none): its gradient leaf
is zeros, as the program's is.

Computed in blocks so that 8,192 tokens fit beside the training state (none
changes a result): attention one query head and one block of ``ATTN_BLOCK``
queries at a time against all keys, each block's body checkpointed; the
experts scanned one at a time with a carried sum and the body checkpointed;
the head in chunks of ``HEAD_CHUNK`` positions; every layer checkpointed; a
run of equal layers (the program stacks them) is a ``lax.scan`` over the one
layer body.

Departures from the published description, each also in the configuration
file's ``assumed``: the tied head (the catalog's ``config`` drops the key; the
family's configuration class defaults to tied); nothing trains the bias here
(the rule is the step's, ``models/lfm2.balance``, and a loss has no part in it).

``routes`` (``[L_sparse, S, k]`` expert indices), where given, replaces the
reference's own top-k, as in ``references/olmoe.py``. ``variant`` swaps one term
for what a mistaken implementation would compute (``VARIANTS``), for the
readings that show the comparison notices it.

It reads the program's parameter tree (``models/lfm2.py:init``: ``wte``,
``blocks`` = a list of runs, each a tree stacked on a leading layer axis,
``ln_f``) because that is what the weights come in; nothing else is shared
with the code under test.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

ATTN_BLOCK = 1024   # queries a score block holds
HEAD_CHUNK = 2048   # positions a chunk of the head's log-probabilities holds

# one term of the layer equations computed as a mistaken implementation would
VARIANTS = ("bias_in_weights", "softmax_for_sigmoid", "weights_not_renormalised", "no_qk_norm",
            "conv_taps_reversed", "gates_swapped", "conv_not_causal", "untied_head")

# published key (scalar) -> attribute of the program's LFM2Config
_PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "moe_intermediate_size": "d_expert",
    "num_experts_per_tok": "top_k",
    "num_experts": "experts_held",
    "num_dense_layers": "dense_layers",
    "vocab_size": "vocab",
    "norm_eps": "rms_eps",
    "conv_L_cache": "conv_taps",
    "routed_scaling_factor": "routed_scale",
}
# what the program cannot vary, so the file must say what the program does
_FIXED = {"conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True}


def layer_types(file_cfg: Dict[str, Any]) -> List[str]:
    """The mixer kinds of the layers the program runs: the published list's
    entries at ``layers_run`` (published layer indices), all of it without."""
    kinds = list(file_cfg["layer_types"])
    return [kinds[i] for i in file_cfg.get("layers_run", range(len(kinds)))]


def _routed(file_cfg: Dict[str, Any]) -> int:
    """The router's outputs: the published count where the file's
    ``num_experts`` is the share held here."""
    return int(file_cfg.get("published", {}).get("num_experts", file_cfg["num_experts"]))


def _head_dim(file_cfg: Dict[str, Any]) -> int:
    return int(file_cfg["hidden_size"]) // int(file_cfg["num_attention_heads"])


def check_config(program_config: Any, file_cfg: Dict[str, Any]) -> None:
    """The registry's configuration must be the file's, key for key."""
    name = file_cfg["name"]

    def same(what, have, want):
        if have != want:
            raise ValueError(f"configuration {name}: the program runs {what}={have!r}, the file says {want!r}")

    c = program_config
    for pub, attr in _PUBLISHED_TO_PROGRAM.items():
        same(f"{attr} ({pub})", getattr(c, attr), file_cfg[pub])
    same("layer_types (at layers_run)", list(c.layer_types), layer_types(file_cfg))
    same("num_hidden_layers", c.n_layers, int(file_cfg["num_hidden_layers"]))
    same("head_dim (hidden_size / num_attention_heads)", c.head_dim, _head_dim(file_cfg))
    same("n_experts (the router's outputs)", c.n_experts, _routed(file_cfg))
    same("expert_offset", c.expert_offset, int(file_cfg["expert_offset"]))
    same("rope_theta", float(c.rope_theta), float(file_cfg["rope_parameters"]["rope_theta"]))
    same("max_len (assumed.seq_len)", c.max_len, int(file_cfg["assumed"]["seq_len"]["value"]))
    same("bias_gamma (assumed.expert_bias)", c.bias_gamma,
         float(file_cfg["assumed"]["expert_bias"]["gamma"]))
    for pub, want in _FIXED.items():
        if file_cfg.get(pub, want) != want:
            raise ValueError(f"configuration {name}: {pub}={file_cfg[pub]!r} is not what is built")
    if file_cfg["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError(f"configuration {name}: only the default rotary embedding is built")
    if not file_cfg["assumed"]["tie_word_embeddings"]["value"]:
        raise ValueError(f"configuration {name}: the program ties its head to the embedding")
    if file_cfg["assumed"]["aux_coefficients"]["load_balancing"] != 0:
        raise ValueError(f"configuration {name}: the program has no auxiliary loss")


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
        "n_kv": int(file_cfg["num_key_value_heads"]),
        "head_dim": _head_dim(file_cfg),
        "theta": float(file_cfg["rope_parameters"]["rope_theta"]),
        "eps": float(file_cfg["norm_eps"]),
        "top_k": int(file_cfg["num_experts_per_tok"]),
        "offset": int(file_cfg["expert_offset"]),
        "scale": float(file_cfg["routed_scaling_factor"]),
    }


def _rmsnorm(g: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """``x`` [B, H, T, D]: ``x cos + rotate_half(x) sin`` over the whole head."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return x * jnp.cos(angles) + _rotate_half(x) * jnp.sin(angles)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """[B, H, T, D] x [B, Hkv, T, D] -> [B, H, T, D]: one query head and one
    block of queries at a time against every key, an explicit mask ``j <= i``."""
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    block = ATTN_BLOCK if t % ATTN_BLOCK == 0 else t
    j = jnp.arange(t)[None, :]

    @jax.checkpoint  # the backward pass recomputes a block's [block, T] scores
    def one_block(qb, i0, kh, vh):
        i = i0 + jnp.arange(block)[:, None]
        scores = qb @ jnp.swapaxes(kh, -1, -2) / math.sqrt(d)  # [B, block, T]
        return jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1) @ vh

    def one_head(_, head):
        qh, kv = head                                            # [B, T, D], index
        kh, vh = k[:, kv], v[:, kv]
        blocks = jnp.moveaxis(qh.reshape(b, t // block, block, d), 1, 0)
        starts = jnp.arange(t // block) * block
        _, out = jax.lax.scan(lambda c, qi: (c, one_block(qi[0], qi[1], kh, vh)), None,
                              (blocks, starts))
        return None, jnp.moveaxis(out, 0, 1).reshape(b, t, d)

    kv_of = jnp.arange(h) // (h // h_kv)
    _, out = jax.lax.scan(one_head, None, (jnp.moveaxis(q, 1, 0), kv_of))
    return jnp.moveaxis(out, 0, 1)


def _swiglu(h: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """``(silu(h w_gate) * (h w_up)) w_down``, gate and up as the two halves of
    one product (the same sums, and half as many matrix products for the
    compiler to emit: each is megabytes of a float32 program's code)."""
    gate, up = jnp.split(h @ jnp.concatenate([w_gate, w_up], axis=-1), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down


def _experts(p: Dict[str, jax.Array], h: jax.Array, weight: jax.Array) -> jax.Array:
    """Every held expert on every token of ``h`` [S, d], each scaled by its
    column of ``weight`` [S, held] (the weight where chosen, 0 elsewhere): a
    scan over single experts that carries their sum, the body checkpointed."""

    @jax.checkpoint  # the backward pass recomputes an expert's [S, f] activations
    def one(w, col):
        return col[:, None] * _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])

    total, _ = jax.lax.scan(lambda acc, w_col: (acc + one(*w_col), None),
                            jnp.zeros_like(h), (dict(p), weight.T))
    return total


def _shift(g: jax.Array, by: int) -> jax.Array:
    """``out[:, t] = g[:, t - by]``, zeros where ``t - by`` is outside the
    sequence (``by`` may be negative)."""
    t = g.shape[1]
    padded = jnp.pad(g, ((0, 0), (max(by, 0), max(-by, 0)), (0, 0)))
    return padded[:, :t] if by >= 0 else padded[:, -by:]


def _conv_mixer(p: Dict[str, jax.Array], n: jax.Array, variant: Optional[str]) -> jax.Array:
    b_, c_, u_ = jnp.split(n @ p["w_in"], 3, axis=-1)
    if variant == "gates_swapped":
        b_, c_ = c_, b_
    g = b_ * u_
    w = p["taps"][::-1] if variant == "conv_taps_reversed" else p["taps"]
    k = w.shape[0]
    # tap j multiplies g at t - (k - 1 - j); not causal: one position later each
    late = 1 if variant == "conv_not_causal" else 0
    conv = sum(w[j] * _shift(g, k - 1 - j - late) for j in range(k))
    return (c_ * conv) @ p["w_out"]


def _attention_mixer(p: Dict[str, Any], n: jax.Array, hp: Dict[str, Any],
                     variant: Optional[str]) -> jax.Array:
    b, t, _ = n.shape
    heads, n_kv, hd = hp["heads"], hp["n_kv"], hp["head_dim"]
    # q, k and v as the three parts of one product, as gate and up in _swiglu
    qkv = n @ jnp.concatenate([p["wq"], p["wk"], p["wv"]], axis=-1)
    q, k, v = (a.reshape(b, t, -1, hd).transpose(0, 2, 1, 3)
               for a in jnp.split(qkv, [heads * hd, (heads + n_kv) * hd], axis=-1))
    if variant != "no_qk_norm":
        q = _rmsnorm(p["q_norm"]["g"], q, hp["eps"])
        k = _rmsnorm(p["k_norm"]["g"], k, hp["eps"])
    a = _attention(_rope(q, hp["theta"]), _rope(k, hp["theta"]), v)
    return a.transpose(0, 2, 1, 3).reshape(b, t, heads * hd) @ p["wo"]


def _block(p: Dict[str, Any], x: jax.Array, routes: Optional[jax.Array], hp: Dict[str, Any],
           variant: Optional[str] = None):
    """One layer on ``x`` [B, T, d], its kind read off ``p``; returns the
    routes it used (``[S, k]``; None for a dense layer)."""
    b, t, d = x.shape
    n = _rmsnorm(p["ln_mixer"]["g"], x, hp["eps"])
    if "conv" in p:
        x = x + _conv_mixer(p["conv"], n, variant)
    else:
        x = x + _attention_mixer(p, n, hp, variant)
    n2 = _rmsnorm(p["ln_ffn"]["g"], x, hp["eps"])
    if "mlp" in p:
        m = p["mlp"]
        return x + _swiglu(n2, m["w_gate"], m["w_up"], m["w_down"]), None
    n2 = n2.reshape(b * t, d)
    logits = n2 @ p["router"]                                                   # [S, E]
    scores = jax.nn.softmax(logits, -1) if variant == "softmax_for_sigmoid" else jax.nn.sigmoid(logits)
    biased = scores + jax.lax.stop_gradient(p["bias"])
    if routes is None:
        _, routes = jax.lax.top_k(biased, hp["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(routes, scores.shape[-1], dtype=scores.dtype), axis=1)  # [S, E]
    weight = chosen * (biased if variant == "bias_in_weights" else scores)
    if variant != "weights_not_renormalised":
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
    weight = hp["scale"] * weight
    held = p["experts"]["w_gate"].shape[0]
    y = _experts(p["experts"], n2, weight[:, hp["offset"]:hp["offset"] + held])
    return x + y.reshape(b, t, d), routes


def _head_loss(x: jax.Array, g: jax.Array, w: jax.Array, targets: jax.Array, eps: float) -> jax.Array:
    """Mean cross-entropy of the final norm and the head ``w`` [d, V], a chunk
    of positions at a time: the [chunk, V] log-probabilities are recomputed,
    not kept."""
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

        # checkpointed: the backward pass keeps one layer's activations (the
        # training state shares the chip); the recomputation changes no result
        @jax.checkpoint
        def layer(x, p, given):
            return _block(p, x, given, hp, variant)

        x = params["wte"][tokens]
        used, first = [], 0
        for run in params["blocks"]:
            n = jax.tree_util.tree_leaves(run)[0].shape[0]
            sparse = "router" in run
            given = routes[first:first + n] if sparse and routes is not None else None
            if n == 1:
                x, out = layer(x, jax.tree_util.tree_map(lambda a: a[0], run),
                               None if given is None else given[0])
                out = None if out is None else out[None]
            elif given is None:
                x, out = jax.lax.scan(lambda x, p: layer(x, p, None), x, run)
            else:
                x, out = jax.lax.scan(lambda x, pg: layer(x, *pg), x, (run, given))
            if sparse:
                used.append(out)
                first += n
        head = params["wte"].T
        if variant == "untied_head":  # a head of its own, as at its initialisation: not the embedding
            head = jnp.roll(head, 1, axis=1)
        total = _head_loss(x, params["ln_f"]["g"], head, targets, hp["eps"])
        if not with_routes:
            return total
        k = hp["top_k"]
        return total, jnp.concatenate(used) if used else jnp.zeros((0, tokens.size, k), jnp.int32)


def make_loss_and_grad(file_cfg: Dict[str, Any]):
    """``(params, tokens, targets[, routes]) -> (loss, grads)`` for this configuration."""
    hp = hyper(file_cfg)

    def fn(params, tokens, targets, routes=None) -> Tuple[jax.Array, Any]:
        return jax.value_and_grad(loss)(params, tokens, targets, hp, routes)

    return fn
