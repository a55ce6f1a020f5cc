"""SmallThinker-21BA3B-Instruct as published
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct ``config.json``;
the layer equations are in ``models/smallthinker.py``'s docstring and are
followed here independently), plain.

Float32 ``jax.numpy`` at the highest matmul precision: no kernel, no sort, no
grouped matmul, no chunked-by-scan loss carried through a custom derivative.
By layer ``l`` of the published lists: ``sliding_window_layout[l]`` 1 masks
keys to ``i - window < j <= i`` (0: every ``j <= i``), ``rope_layout[l]`` 1
turns q and k by the rotary embedding over the whole head (half-split
convention, theta ``rope_theta``), 0 encodes no position at all. All layers
have one parameter shape, so the two lists are the loop's DATA: one layer body
under a ``lax.scan`` over the layers, the mask's window term switched by the
first list's entry and the rotary angles multiplied by the second's (angle 0
is cos 1 and sin 0: exactly no rotation). Query head
``h`` reads key/value head ``h // (H / Hkv)``. The router reads the layer's
INPUT ``x`` (before the input norm and attention): logits over all E, the top
``k`` of them, weights the softmax over all E renormalised over the chosen
(``moe_primary_router_apply_softmax`` and ``norm_topk_prob``). Every HELD
expert, ``down(relu(gate u) * up u)`` (ReGLU), runs on every token and is
masked by the top-k one-hot times the weight; the experts this chip does not
hold add nothing, here as in the program. Final RMSNorm, untied head over the
vocabulary slice.

Loss = mean token cross-entropy + ``aux_coef`` x load balancing over the
layers: ``E sum_e f_e P_e`` with ``f_e`` the mean (over layers and tokens)
number of a token's choices on expert e and ``P_e`` the mean softmax
probability, both over all E experts.

Computed in blocks so that 16,384 tokens fit beside the training state (none
changes a result): attention one query head and one block of ``ATTN_BLOCK``
queries at a time against all keys (a ``[1024, 16384]`` float32 score block is
67 MB where a head's ``[16384, 16384]`` is 1.07 GB), each block's body
checkpointed; the experts scanned one at a time with a carried sum and the
body checkpointed; the head in chunks of ``HEAD_CHUNK`` positions; every layer
checkpointed. (A Python loop over the four layers traced the layer four times:
a 44.9 MB entry of the machine's 192 MiB compile cache, my chip run, PR 35.)

Departures from the published description, each also in the configuration
file's ``assumed``: the router's input (the catalog's summary says "router
placed before attention"; no key of ``config.json`` does); no secondary
experts (the summary names them, ``config.json`` has no key); the balancing
term and its coefficient (training-side, not in a config).

``routes`` (``[L, S, k]`` expert indices), where given, replaces the
reference's own top-k, as in ``references/olmoe.py``. ``variant`` swaps one term
for what a mistaken implementation would compute (``VARIANTS``), for the
readings that show the comparison notices it.

It reads the program's parameter tree (``models/smallthinker.py:init``:
``wte``, ``blocks`` = ``{"global": [P, ...], "sliding": [P, 3, ...]}``,
``ln_f``, ``lm_head``) because that is what the weights come in; nothing else
is shared with the code under test.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

ATTN_BLOCK = 1024   # queries a score block holds
HEAD_CHUNK = 2048   # positions a chunk of the head's log-probabilities holds

# one term of the layer equations computed as a mistaken implementation would
VARIANTS = ("router_after_attention", "silu_for_relu", "rotary_on_global", "no_window",
            "weights_not_renormalised")

# published key (scalar) -> attribute of the program's SmallThinkerConfig
_PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model",
    "head_dim": "head_dim",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "moe_ffn_hidden_size": "d_expert",
    "moe_num_active_primary_experts": "top_k",
    "moe_num_primary_experts": "experts_held",
    "vocab_size": "vocab",
    "rms_norm_eps": "rms_eps",
    "sliding_window_size": "window",
    "rope_theta": "rope_theta",
}
# what the program cannot vary, so the file must say what the program does
_FIXED = {"tie_word_embeddings": False, "moe_primary_router_apply_softmax": True,
          "norm_topk_prob": True, "rope_scaling": None}


def _depth(file_cfg: Dict[str, Any]) -> int:
    return int(file_cfg["num_hidden_layers"])


def _routed(file_cfg: Dict[str, Any]) -> int:
    """The router's outputs: the published count where the file's
    ``moe_num_primary_experts`` is the share held here."""
    return int(file_cfg.get("published", {}).get(
        "moe_num_primary_experts", file_cfg["moe_num_primary_experts"]))


def check_config(program_config: Any, file_cfg: Dict[str, Any]) -> None:
    """The registry's configuration must be the file's, key for key."""
    name = file_cfg["name"]

    def same(what, have, want):
        if have != want:
            raise ValueError(f"configuration {name}: the program runs {what}={have!r}, the file says {want!r}")

    c = program_config
    for pub, attr in _PUBLISHED_TO_PROGRAM.items():
        same(f"{attr} ({pub})", getattr(c, attr), file_cfg[pub])
    n = _depth(file_cfg)
    same("n_layers", c.n_layers, n)
    same("n_experts (the router's outputs)", c.n_experts, _routed(file_cfg))
    same("num_experts_per_tok", c.top_k, int(file_cfg["num_experts_per_tok"]))
    same("expert_offset", c.expert_offset, int(file_cfg["expert_offset"]))
    same("max_len (assumed.seq_len)", c.max_len, int(file_cfg["assumed"]["seq_len"]["value"]))
    sliding = [int(c.attention_kind(l) == "sliding") for l in range(n)]
    same("sliding_window_layout", sliding, file_cfg["sliding_window_layout"][:n])
    same("rope_layout", sliding, file_cfg["rope_layout"][:n])
    same("router_site (assumed.router)", c.router_site, file_cfg["assumed"]["router"]["site"])
    for pub, want in _FIXED.items():
        if file_cfg.get(pub, want) != want:
            raise ValueError(f"configuration {name}: {pub}={file_cfg[pub]!r} is not what is built")
    same("aux_coef", c.aux_coef, file_cfg["assumed"]["aux_coefficients"]["load_balancing"])


def sizes(file_cfg: Dict[str, Any]) -> Dict[str, int]:
    """What the FLOP arithmetic and the data generator need."""
    return {
        "n_layer": _depth(file_cfg),
        "d_model": file_cfg["hidden_size"],
        "seq_len": int(file_cfg["assumed"]["seq_len"]["value"]),
        "vocab": file_cfg["vocab_size"],
    }


def hyper(file_cfg: Dict[str, Any]) -> Dict[str, Any]:
    n = _depth(file_cfg)
    return {
        "windowed": tuple(bool(w) for w in file_cfg["sliding_window_layout"][:n]),
        "rotary": tuple(bool(r) for r in file_cfg["rope_layout"][:n]),
        "heads": int(file_cfg["num_attention_heads"]),
        "n_kv": int(file_cfg["num_key_value_heads"]),
        "head_dim": int(file_cfg["head_dim"]),
        "window": int(file_cfg["sliding_window_size"]),
        "theta": float(file_cfg["rope_theta"]),
        "eps": float(file_cfg["rms_norm_eps"]),
        "top_k": int(file_cfg["moe_num_active_primary_experts"]),
        "offset": int(file_cfg["expert_offset"]),
        "aux_coef": float(file_cfg["assumed"]["aux_coefficients"]["load_balancing"]),
    }


def stacked_layers(params: Dict[str, Any]) -> Dict[str, Any]:
    """The program's scanned tree as one ``[L, ...]`` stack in layer order:
    period ``p`` is ``blocks["global"][p]`` and then ``blocks["sliding"][p, 0..2]``."""
    return jax.tree_util.tree_map(
        lambda g, sl: jnp.concatenate([g[:, None], sl], axis=1).reshape((-1,) + g.shape[1:]),
        params["blocks"]["global"], params["blocks"]["sliding"])


def layer_tree(params: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s own tree."""
    return jax.tree_util.tree_map(lambda a: a[layer], stacked_layers(params))


def _rmsnorm(g: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x: jax.Array, theta: float, on: jax.Array) -> jax.Array:
    """``x`` [B, H, T, D]: ``x cos + rotate_half(x) sin`` over the whole head;
    ``on`` is the layer's ``rope_layout`` entry (0: every angle 0, ``x`` itself)."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = on * jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return x * jnp.cos(angles) + _rotate_half(x) * jnp.sin(angles)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array, window: int, windowed) -> jax.Array:
    """[B, H, T, D] x [B, Hkv, T, D] -> [B, H, T, D]: one query head and one
    block of queries at a time against every key, an explicit mask: ``j <= i``,
    and ``i - window < j`` where ``windowed`` (the layer's
    ``sliding_window_layout`` entry) is set."""
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    block = ATTN_BLOCK if t % ATTN_BLOCK == 0 else t
    j = jnp.arange(t)[None, :]

    @jax.checkpoint  # the backward pass recomputes a block's [block, T] scores
    def one_block(qb, i0, kh, vh):
        i = i0 + jnp.arange(block)[:, None]
        mask = (j <= i) & ((j > i - window) | jnp.logical_not(windowed))
        scores = qb @ jnp.swapaxes(kh, -1, -2) / math.sqrt(d)  # [B, block, T]
        return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1) @ vh

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


def _experts(p: Dict[str, jax.Array], h: jax.Array, weight: jax.Array, act) -> jax.Array:
    """Every held expert on every token of ``h`` [S, d], each scaled by its
    column of ``weight`` [S, held] (the weight where chosen, 0 elsewhere): a
    scan over single experts that carries their sum, the body checkpointed."""

    @jax.checkpoint  # the backward pass recomputes an expert's [S, f] activations
    def one(w, col):
        return col[:, None] * ((act(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"])

    total, _ = jax.lax.scan(lambda acc, w_col: (acc + one(*w_col), None),
                            jnp.zeros_like(h), (dict(p), weight.T))
    return total


def _block(p: Dict[str, Any], x: jax.Array, windowed, rotary, routes: Optional[jax.Array],
           hp: Dict[str, Any], variant: Optional[str] = None):
    """One layer on ``x`` [B, T, d]; ``windowed`` and ``rotary`` are its entries
    of the two published lists."""
    b, t, d = x.shape
    heads, n_kv, hd, eps = hp["heads"], hp["n_kv"], hp["head_dim"], hp["eps"]
    router_in = x                                   # the layer's input, not normed
    n = _rmsnorm(p["ln_attn"]["g"], x, eps)
    q = (n @ p["wq"]).reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
    k = (n @ p["wk"]).reshape(b, t, n_kv, hd).transpose(0, 2, 1, 3)
    v = (n @ p["wv"]).reshape(b, t, n_kv, hd).transpose(0, 2, 1, 3)
    on = jnp.asarray(rotary, jnp.float32)
    q, k = _rope(q, hp["theta"], on), _rope(k, hp["theta"], on)
    a = _attention(q, k, v, hp["window"], jnp.asarray(windowed, bool))
    x = x + a.transpose(0, 2, 1, 3).reshape(b, t, heads * hd) @ p["wo"]

    n2 = _rmsnorm(p["ln_mlp"]["g"], x, eps).reshape(b * t, d)
    if variant == "router_after_attention":
        router_in = n2
    probs = jax.nn.softmax(router_in.reshape(b * t, d) @ p["router"], axis=-1)  # [S, E]
    if routes is None:
        _, routes = jax.lax.top_k(probs, hp["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(routes, probs.shape[-1], dtype=probs.dtype), axis=1)  # [S, E]
    weight = chosen * probs
    if variant != "weights_not_renormalised":
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    held = p["experts"]["w_gate"].shape[0]
    act = jax.nn.silu if variant == "silu_for_relu" else jax.nn.relu
    y = _experts(p["experts"], n2, weight[:, hp["offset"]:hp["offset"] + held], act)
    stats = {"choices": jnp.mean(chosen, axis=0), "probs": jnp.mean(probs, axis=0)}
    return x + y.reshape(b, t, d), stats, routes


def _head_loss(x: jax.Array, g: jax.Array, w: jax.Array, targets: jax.Array, eps: float) -> jax.Array:
    """Mean cross-entropy of the final norm and head, a chunk of positions at
    a time: the [chunk, V] log-probabilities are recomputed, not kept."""
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
    """Mean next-token cross-entropy plus the load-balancing term, float32
    throughout. ``with_routes`` also returns the ``[L, S, k]`` routes used."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    windowed = [w and variant != "no_window" for w in hp["windowed"]]
    rotary = [r or variant == "rotary_on_global" for r in hp["rotary"]]
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)

        # checkpointed: the backward pass keeps one layer's activations (the
        # training state shares the chip); the recomputation changes no result
        @jax.checkpoint
        def layer(x, p, is_windowed, is_rotary, given):
            return _block(p, x, is_windowed, is_rotary, given, hp, variant)

        def step(x, per_layer):
            x, stats, used = layer(x, *per_layer)
            return x, (stats, used)

        lists = (jnp.asarray(windowed), jnp.asarray(rotary))
        x = params["wte"][tokens]
        if routes is None:
            x, (stats, used) = jax.lax.scan(
                lambda x, pl: step(x, (*pl, None)), x, (stacked_layers(params), *lists))
        else:
            x, (stats, used) = jax.lax.scan(step, x, (stacked_layers(params), *lists, routes))
        total = _head_loss(x, params["ln_f"]["g"], params["lm_head"], targets, hp["eps"])
        f, p_mean = jnp.mean(stats["choices"], axis=0), jnp.mean(stats["probs"], axis=0)
        total = total + hp["aux_coef"] * f.shape[0] * jnp.sum(f * p_mean)
        return (total, used) if with_routes else total


def make_loss_and_grad(file_cfg: Dict[str, Any]):
    """``(params, tokens, targets[, routes]) -> (loss, grads)`` for this configuration."""
    hp = hyper(file_cfg)

    def fn(params, tokens, targets, routes=None) -> Tuple[jax.Array, Any]:
        return jax.value_and_grad(loss)(params, tokens, targets, hp, routes)

    return fn
