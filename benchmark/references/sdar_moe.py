"""SDAR-30B-A3B-Chat's training step as published
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat ``config.json``, ``model_type``
``sdar_moe``: the Qwen3-MoE layer; the objective of arXiv:2510.06303 after
BD3-LMs, arXiv:2503.09573; the equations are in ``models/sdar_moe.py``'s
docstring and are followed here independently), plain.

Float32 ``jax.numpy`` at the highest matmul precision: no kernel, no sort, no
grouped matmul, no chunked loss, no remat of a layer's own (``jax.checkpoint``
only bounds what the backward keeps: it changes no result).

- noise: this module's own copy of the rule, keyed ``jax.random.PRNGKey(0)``
  as ``benchmark/probe.py:reference_check`` keys the program: ``k_rate, k_mask
  = split(key)``; a rate ``t = eps_t + (1 - eps_t) U(k_rate, [B, L / bd])`` a
  block; a token is masked where ``U(k_mask, [B, L]) < t`` of its block.
- rows: ``[x_0 ; x_t]`` (clean copy, then the noised copy: ``mask_id`` where
  masked), 2L rows, positions ``0..L-1`` twice.
- attention: one query head at a time over an explicit boolean ``[2L, 2L]``
  mask built from the three rules, a quadrant at a time (a ``lax.scan`` over
  the heads, body checkpointed: 8,192 x 8,192 float32 scores are 268 MB, not
  8.6 GB); query head ``h`` reads key/value head ``h // (H / Hkv)``; q and k
  normed per head (RMSNorm over a head's 128 coordinates, one weight vector
  for all heads) before the half-split rotary turn at theta, by position.
- experts: every HELD expert runs on every row and is masked by the top-k
  one-hot times the renormalised softmax weight; the experts this chip does not
  hold add nothing, here as in the program.
- loss: ``(1 / (B L)) sum_i m_i / t_blk(i) * nll_i`` over the noised half
  against ``x_0`` at the same position, plus ``aux_coef`` x ``E sum_e f_e P_e``
  (means over layers and all 2L rows first, product after).

``routes`` (``[L, S, k]`` expert indices), where given, replaces the
reference's own top-k, as in ``references/olmoe.py``. ``targets`` is taken and
not read: the objective has no shifted target. ``variant`` swaps one term for
what a mistaken implementation would compute (``VARIANTS``), for the readings
that show the comparison notices it.

It reads the program's parameter tree (``models/sdar_moe.py:init``: ``wte``,
``blocks`` a list of one stacked run, ``ln_f``, ``lm_head``) because that is
what the weights come in; nothing else is shared with the code under test.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# one term of the objective or of the layer computed as a mistaken implementation would
VARIANTS = ("weights_not_renormalised", "no_qk_norm", "shifted_target", "causal_inside_a_block",
            "noised_sees_its_own_clean_block", "positions_by_row", "unweighted_loss")

# published key (scalar) -> attribute of the program's SdarMoeConfig
_PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model",
    "head_dim": "head_dim",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "moe_intermediate_size": "d_expert",
    "num_experts_per_tok": "top_k",
    "num_experts": "experts_held",
    "vocab_size": "vocab",
    "rms_norm_eps": "rms_eps",
    "rope_theta": "rope_theta",
    "num_hidden_layers": "n_layers",
}
# what the program cannot vary, so the file must say what the program does
_FIXED = {"attention_bias": False, "tie_word_embeddings": False, "norm_topk_prob": True,
          "decoder_sparse_step": 1, "mlp_only_layers": [], "hidden_act": "silu",
          "rope_scaling": None, "use_sliding_window": False}


def _routed(file_cfg: Dict[str, Any]) -> int:
    """The router's outputs: the published count where the file's
    ``num_experts`` is the share held here."""
    return int(file_cfg.get("published", {}).get("num_experts", file_cfg["num_experts"]))


def _assumed(file_cfg: Dict[str, Any], key: str):
    return file_cfg["assumed"][key]["value"]


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
    same("max_len (assumed.seq_len)", c.max_len, int(_assumed(file_cfg, "seq_len")))
    same("block_length", c.block_length, int(_assumed(file_cfg, "block_length")))
    same("eps_t", c.eps_t, float(_assumed(file_cfg, "schedule")["eps_t"]))
    same("mask_id", c.mask_id, int(_assumed(file_cfg, "mask_id")))
    same("aux_coef", c.aux_coef, file_cfg["assumed"]["aux_coefficients"]["load_balancing"])
    for pub, want in _FIXED.items():
        if file_cfg.get(pub, want) != want:
            raise ValueError(f"configuration {name}: {pub}={file_cfg[pub]!r} is not what is built")


def sizes(file_cfg: Dict[str, Any]) -> Dict[str, int]:
    """What the FLOP arithmetic and the data generator need: ``seq_len`` is
    the DATA's (the layers run twice as many rows)."""
    return {
        "n_layer": int(file_cfg["num_hidden_layers"]),
        "d_model": file_cfg["hidden_size"],
        "seq_len": int(_assumed(file_cfg, "seq_len")),
        "vocab": file_cfg["vocab_size"],
    }


def hyper(file_cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "heads": int(file_cfg["num_attention_heads"]),
        "n_kv": int(file_cfg["num_key_value_heads"]),
        "head_dim": int(file_cfg["head_dim"]),
        "theta": float(file_cfg["rope_theta"]),
        "eps": float(file_cfg["rms_norm_eps"]),
        "top_k": int(file_cfg["num_experts_per_tok"]),
        "norm_topk": bool(file_cfg["norm_topk_prob"]),
        "offset": int(file_cfg["expert_offset"]),
        "bd": int(_assumed(file_cfg, "block_length")),
        "eps_t": float(_assumed(file_cfg, "schedule")["eps_t"]),
        "mask_id": int(_assumed(file_cfg, "mask_id")),
        "aux_coef": float(file_cfg["assumed"]["aux_coefficients"]["load_balancing"]),
    }


def noise(key: jax.Array, b: int, l: int, bd: int, eps_t: float) -> Tuple[jax.Array, jax.Array]:
    """(masked [B, L] bool, rate [B, L]): the rule the program states, repeated."""
    k_rate, k_mask = jax.random.split(key)
    t = eps_t + (1.0 - eps_t) * jax.random.uniform(k_rate, (b, l // bd), jnp.float32)
    u = jax.random.uniform(k_mask, (b, l), jnp.float32)
    rate = jnp.repeat(t, bd, axis=1)
    return u < rate, rate


def three_part_mask(l: int, bd: int) -> jax.Array:
    """``[2L, 2L]`` bool over rows ``[x_0 ; x_t]``, a quadrant at a time from
    the rules: clean -> clean ``blk(j) <= blk(i)``; clean -> noised never;
    noised -> clean ``blk(j) < blk(i)``; noised -> noised ``blk(j) == blk(i)``."""
    blk = jnp.arange(l) // bd
    bi, bj = blk[:, None], blk[None, :]
    top = jnp.concatenate([bj <= bi, jnp.zeros((l, l), bool)], axis=1)
    bottom = jnp.concatenate([bj < bi, bj == bi], axis=1)
    return jnp.concatenate([top, bottom], axis=0)


def kept_pairs(l: int, bd: int) -> int:
    """Pairs the mask keeps, a head a sequence: ``L^2 + L bd``."""
    return l * l + l * bd


def _rmsnorm(g: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """``x`` [B, H, T, D]: ``x cos + rotate_half(x) sin`` by ``positions`` [T]."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return x * jnp.cos(angles) + _rotate_half(x) * jnp.sin(angles)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array) -> jax.Array:
    """[B, H, T, D] x [B, Hkv, T, D] -> [B, H, T, D], one query head at a time."""
    h, h_kv, d = q.shape[1], k.shape[1], q.shape[3]

    def one(_, head):
        qh, kv = head
        scores = qh @ jnp.swapaxes(k[:, kv], -1, -2) / math.sqrt(d)  # [B, T, T]
        scores = jnp.where(mask, scores, -jnp.inf)
        return None, jax.nn.softmax(scores, axis=-1) @ v[:, kv]

    kv_of = jnp.arange(h) // (h // h_kv)
    _, out = jax.lax.scan(jax.checkpoint(one), None, (jnp.moveaxis(q, 1, 0), kv_of))
    return jnp.moveaxis(out, 0, 1)


def _experts(p: Dict[str, jax.Array], h: jax.Array, weight: jax.Array) -> jax.Array:
    """Every held expert on every row of ``h`` [S, d], each scaled by its
    column of ``weight`` [S, held] (the weight where chosen, 0 elsewhere); a
    scan over the experts that carries their sum (``references/laguna.py``)."""

    @jax.checkpoint  # the backward pass recomputes an expert's [S, f] activations
    def one(w, col):
        return col[:, None] * ((jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"])

    total, _ = jax.lax.scan(lambda acc, w_col: (acc + one(*w_col), None),
                            jnp.zeros_like(h), (dict(p), weight.T))
    return total


def _block(p: Dict[str, Any], x: jax.Array, mask: jax.Array, positions: jax.Array,
           routes: Optional[jax.Array], hp: Dict[str, Any], variant: Optional[str] = None):
    b, t, d = x.shape
    heads, n_kv, hd, eps = hp["heads"], hp["n_kv"], hp["head_dim"], hp["eps"]
    n = _rmsnorm(p["ln_attn"]["g"], x, eps)
    q = (n @ p["wq"]).reshape(b, t, heads, hd)
    k = (n @ p["wk"]).reshape(b, t, n_kv, hd)
    v = (n @ p["wv"]).reshape(b, t, n_kv, hd).transpose(0, 2, 1, 3)
    if variant != "no_qk_norm":
        q = _rmsnorm(p["q_norm"]["g"], q, eps)  # per head: over its hd coordinates
        k = _rmsnorm(p["k_norm"]["g"], k, eps)
    q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
    q, k = _rope(q, positions, hp["theta"]), _rope(k, positions, hp["theta"])
    a = _attention(q, k, v, mask)
    x = x + a.transpose(0, 2, 1, 3).reshape(b, t, heads * hd) @ p["wo"]

    n2 = _rmsnorm(p["ln_mlp"]["g"], x, eps).reshape(b * t, d)
    probs = jax.nn.softmax(n2 @ p["router"], axis=-1)  # [S, E]
    if routes is None:
        _, routes = jax.lax.top_k(probs, hp["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(routes, probs.shape[-1], dtype=probs.dtype), axis=1)  # [S, E]
    weight = chosen * probs
    if hp["norm_topk"] and variant != "weights_not_renormalised":  # ``norm_topk_prob``: the chosen eight's sum to 1
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    held = p["experts"]["w_gate"].shape[0]
    y = _experts(p["experts"], n2, weight[:, hp["offset"]:hp["offset"] + held])
    stats = {"choices": jnp.mean(chosen, axis=0), "probs": jnp.mean(probs, axis=0)}
    return x + y.reshape(b, t, d), stats, routes


def loss(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array, hp: Dict[str, Any],
         routes: Optional[jax.Array] = None, with_routes: bool = False, variant: Optional[str] = None,
         key: Optional[jax.Array] = None):
    """The weighted denoising loss plus the load-balancing term, float32
    throughout; ``key`` defaults to the harness's ``PRNGKey(0)``. ``with_routes``
    also returns the ``[L, S, k]`` routes used."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    if variant != "shifted_target":
        targets = tokens  # x_0 at the same position: no shifted target in this objective
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        b, l = tokens.shape
        masked, rate = noise(jax.random.PRNGKey(0) if key is None else key, b, l, hp["bd"], hp["eps_t"])
        rows = jnp.concatenate([tokens, jnp.where(masked, hp["mask_id"], tokens)], axis=1)  # [x_0 ; x_t]
        positions = jnp.arange(2 * l) if variant == "positions_by_row" else jnp.concatenate(
            [jnp.arange(l), jnp.arange(l)])
        mask = three_part_mask(l, hp["bd"])
        if variant == "causal_inside_a_block":  # a noised row blind to the later rows of its own block
            mask = mask & jnp.tril(jnp.ones_like(mask))
        if variant == "noised_sees_its_own_clean_block":  # noised -> clean with <= for <: the answer leaks
            blk = jnp.arange(l) // hp["bd"]
            mask = mask.at[l:, :l].set(blk[None, :] <= blk[:, None])
        x = params["wte"][rows]
        (stack,) = params["blocks"]  # the equal layers, stacked

        def layer(x, p_given):
            # checkpointed: the backward pass keeps one layer's activations (the
            # training state shares the chip); the recomputation changes no result
            x, s, r = jax.checkpoint(
                lambda p, x, given: _block(p, x, mask, positions, given, hp, variant))(p_given[0], x, p_given[1])
            return x, (s, r)

        x, (stats, used) = jax.lax.scan(layer, x, (stack, routes))

        @jax.checkpoint  # the [L, V] log-probabilities are recomputed, not kept
        def head(x, g, w):
            logp = jax.nn.log_softmax(_rmsnorm(g, x[:, l:], hp["eps"]) @ w, axis=-1)  # the noised half
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]  # against x_0, no shift
            weight = masked if variant == "unweighted_loss" else masked / rate
            return jnp.sum(weight * nll) / (b * l)

        total = head(x, params["ln_f"]["g"], params["lm_head"])
        f, p_mean = jnp.mean(stats["choices"], axis=0), jnp.mean(stats["probs"], axis=0)
        total = total + hp["aux_coef"] * f.shape[0] * jnp.sum(f * p_mean)
        return (total, used) if with_routes else total


def make_loss_and_grad(file_cfg: Dict[str, Any]):
    """``(params, tokens, targets[, routes]) -> (loss, grads)`` for this configuration."""
    hp = hyper(file_cfg)

    def fn(params, tokens, targets, routes=None) -> Tuple[jax.Array, Any]:
        return jax.value_and_grad(loss)(params, tokens, targets, hp, routes)

    return fn
