"""OLMoE-1B-7B as published (Muennighoff et al. 2024, arXiv:2409.02060;
``modeling_olmoe.py``; config.json of allenai/OLMoE-1B-7B-0125-Instruct), plain.

Float32 ``jax.numpy`` at the highest matmul precision: no kernel, no chunked
loss, no sort, no grouped matmul. Every expert runs on every token and the
result is masked by the top-8 one-hot times the gate: one expert at a time, a
``lax.scan`` over the stacked experts whose body is checkpointed, so that the
chip, which still holds the training state when the check runs, keeps one
expert's ``[S, 1024]`` activations and not 64 (2.67 GB of temporaries without,
0.94 with, at 1,024 tokens: sandbox compile for a described v5e, PR 28; the
recomputation changes no result). Pre-norm decoder: RMSNorm (eps from the
file), q/k/v/o without bias, RMSNorm over the whole q and k projections before
the heads are split, rotary embedding in the half-split (``rotate_half``)
convention, causal softmax attention, router softmax over all experts with the
chosen gates NOT renormalised, SwiGLU experts, final RMSNorm, untied head.

Loss = mean token cross-entropy + ``aux_coef`` x load balancing +
``z_coef`` x router z-loss (coefficients under ``assumed`` in the file):
load balancing as ``load_balancing_loss_func`` computes it over the
concatenated layers, ``E * sum_e f_e P_e`` with ``f_e`` the mean (over layers and
tokens) number of a token's choices on expert e and ``P_e`` the mean router
probability; z-loss the mean of ``logsumexp(router logits)^2``.

``routes`` (``[L, S, k]`` expert indices), where given, replaces the reference's
own top-k: a token whose k-th and (k+1)-th probabilities are closer than the
program's bf16 rounding picks another expert there, and a comparison of
arithmetic should not be a comparison of coin flips. The gates are still the
reference's own probabilities at those indices.

It reads the program's parameter tree (``models/olmoe.py:init``: ``wte``,
``blocks`` stacked on a leading layer axis, ``ln_f``, ``lm_head``) because that
is what the weights come in; nothing else is shared with the code under test.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# published key -> attribute of the program's OlmoeConfig
_PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "intermediate_size": "d_expert",
    "vocab_size": "vocab",
    "max_position_embeddings": "max_len",
    "rms_norm_eps": "rms_eps",
    "rope_theta": "rope_theta",
}
# what the program cannot vary, so the file must say what the program does
_FIXED = {"norm_topk_prob": False, "tie_word_embeddings": False, "hidden_act": "silu",
          "attention_bias": False, "clip_qkv": None, "rope_scaling": None}


def _depth(file_cfg: Dict[str, Any]) -> int:
    """The depth the cell runs: the file's ``model_overrides`` cut it."""
    return int(file_cfg.get("model_overrides", {}).get("n_layers", file_cfg["num_hidden_layers"]))


def check_config(program_config: Any, file_cfg: Dict[str, Any]) -> None:
    """The registry's configuration must be the file's, key for key."""
    name = file_cfg["name"]
    for pub, attr in _PUBLISHED_TO_PROGRAM.items():
        have, want = getattr(program_config, attr), file_cfg[pub]
        if have != want:
            raise ValueError(
                f"configuration {name}: the program runs {attr}={have}, the file says {pub}={want}")
    if program_config.n_layers != _depth(file_cfg):
        raise ValueError(f"configuration {name}: the program runs {program_config.n_layers} layers")
    for pub, want in _FIXED.items():
        if file_cfg.get(pub, want) != want:
            raise ValueError(f"configuration {name}: {pub}={file_cfg[pub]!r} is not what is built")
    if file_cfg["num_key_value_heads"] != file_cfg["num_attention_heads"]:
        raise ValueError(f"configuration {name}: grouped key/value heads are not built")
    coefs = file_cfg["assumed"]["aux_coefficients"]
    if (program_config.aux_coef, program_config.z_coef) != (coefs["load_balancing"], coefs["router_z"]):
        raise ValueError(f"configuration {name}: auxiliary coefficients differ from the file's")


def sizes(file_cfg: Dict[str, Any]) -> Dict[str, int]:
    """What the FLOP arithmetic and the data generator need."""
    return {
        "n_layer": _depth(file_cfg),
        "d_model": file_cfg["hidden_size"],
        "seq_len": file_cfg["max_position_embeddings"],
        "vocab": file_cfg["vocab_size"],
    }


def _rmsnorm(g: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """``x`` [B, H, T, D]: ``x cos + rotate_half(x) sin``, angles ``t * theta^(-2i/D)``
    repeated over the two halves."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return x * jnp.cos(angles) + _rotate_half(x) * jnp.sin(angles)


def _experts(p: Dict[str, jax.Array], h: jax.Array, weight: jax.Array) -> jax.Array:
    """Every expert on every token of ``h`` [S, d], each scaled by its column
    of ``weight`` [S, E] (the gate where the expert was chosen, 0 elsewhere)."""

    def one(_, w):
        gate, up, down, col = w
        return None, col[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)

    # checkpointed: the backward pass recomputes one expert's [S, f] activations
    # instead of keeping all E experts' (the training state shares the chip)
    _, outs = jax.lax.scan(
        jax.checkpoint(one), None, (p["w_gate"], p["w_up"], p["w_down"], weight.T))
    return jnp.sum(outs, axis=0)


def _block(p: Dict[str, Any], x: jax.Array, routes: Optional[jax.Array], hp: Dict[str, Any]):
    b, t, d = x.shape
    n_head, eps, k_top = hp["n_head"], hp["eps"], hp["top_k"]
    dh = d // n_head
    h = _rmsnorm(p["ln_attn"]["g"], x, eps)
    q = _rmsnorm(p["q_norm"]["g"], h @ p["wq"], eps)
    k = _rmsnorm(p["k_norm"]["g"], h @ p["wk"], eps)
    v = h @ p["wv"]
    q, k, v = (a.reshape(b, t, n_head, dh).transpose(0, 2, 1, 3) for a in (q, k, v))
    q, k = _rope(q, hp["theta"]), _rope(k, hp["theta"])
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(dh)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    attn = (jax.nn.softmax(scores, axis=-1) @ v).transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + attn @ p["wo"]

    h = _rmsnorm(p["ln_mlp"]["g"], x, eps).reshape(b * t, d)
    logits = h @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    if routes is None:
        _, routes = jax.lax.top_k(probs, k_top)
    chosen = jnp.sum(jax.nn.one_hot(routes, probs.shape[-1], dtype=probs.dtype), axis=1)  # [S, E]
    y = _experts(p["experts"], h, chosen * probs)  # gates as they are: not renormalised
    stats = {
        "choices": jnp.mean(chosen, axis=0),
        "probs": jnp.mean(probs, axis=0),
        "z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return x + y.reshape(b, t, d), stats, routes


def loss(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array, hp: Dict[str, Any],
         routes: Optional[jax.Array] = None, with_routes: bool = False):
    """Mean next-token cross-entropy plus the auxiliary terms, float32
    throughout. ``with_routes`` also returns the ``[L, S, k]`` routes used."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        x = params["wte"][tokens]

        def layer(h, p_r):
            p, r = p_r if routes is not None else (p_r, None)
            h, stats, used = _block(p, h, r, hp)
            return h, (stats, used)

        xs = (params["blocks"], routes) if routes is not None else params["blocks"]
        x, (stats, used) = jax.lax.scan(layer, x, xs)
        x = _rmsnorm(params["ln_f"]["g"], x, hp["eps"])
        logp = jax.nn.log_softmax(x @ params["lm_head"], axis=-1)
        lm = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0])
        n_experts = stats["probs"].shape[-1]
        aux = n_experts * jnp.sum(jnp.mean(stats["choices"], axis=0) * jnp.mean(stats["probs"], axis=0))
        total = lm + hp["aux_coef"] * aux + hp["z_coef"] * jnp.mean(stats["z"])
        return (total, used) if with_routes else total


def hyper(file_cfg: Dict[str, Any]) -> Dict[str, Any]:
    coefs = file_cfg["assumed"]["aux_coefficients"]
    return {
        "n_head": int(file_cfg["num_attention_heads"]),
        "top_k": int(file_cfg["num_experts_per_tok"]),
        "eps": float(file_cfg["rms_norm_eps"]),
        "theta": float(file_cfg["rope_theta"]),
        "aux_coef": float(coefs["load_balancing"]),
        "z_coef": float(coefs["router_z"]),
    }


def make_loss_and_grad(file_cfg: Dict[str, Any]):
    """``(params, tokens, targets[, routes]) -> (loss, grads)`` for this configuration."""
    hp = hyper(file_cfg)

    def fn(params, tokens, targets, routes=None) -> Tuple[jax.Array, Any]:
        return jax.value_and_grad(loss)(params, tokens, targets, hp, routes)

    return fn
