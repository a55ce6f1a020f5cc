"""GPT-2 as published (Radford et al. 2019; ``modeling_gpt2.py``), plain.

Float32 ``jax.numpy`` at the highest matmul precision: no kernel, no remat, no
chunked loss, full ``[B, T, V]`` logits. Pre-LN decoder, learned positions,
fused qkv, ``gelu_new`` (the tanh form), tied output embedding, mean
cross-entropy over all positions.

It reads the program's parameter tree (``models/gpt2.py:init``: ``wte``,
``wpe``, ``blocks`` stacked on a leading layer axis, ``ln_f``) because that is
what the weights come in; nothing else is shared with the code under test.
The layer loop is a ``lax.scan`` over the stacked blocks with nothing
recomputed, which compiles once a layer instead of 36 times.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# published key -> attribute of the program's GPT2Config
_PUBLISHED_TO_PROGRAM = {
    "n_layer": "n_layers",
    "n_embd": "d_model",
    "n_head": "n_heads",
    "n_positions": "max_len",
    "vocab_size": "vocab",
    "n_inner": "d_ff",
}


def check_config(program_config: Any, file_cfg: Dict[str, Any]) -> None:
    """The registry's configuration must be the file's, key for key."""
    for pub, attr in _PUBLISHED_TO_PROGRAM.items():
        have, want = getattr(program_config, attr), file_cfg[pub]
        if have != want:
            raise ValueError(
                f"configuration {file_cfg['name']}: the program runs {attr}={have}, "
                f"the file says {pub}={want}"
            )


def sizes(file_cfg: Dict[str, Any]) -> Dict[str, int]:
    """What the FLOP arithmetic and the data generator need."""
    return {
        "n_layer": file_cfg["n_layer"],
        "d_model": file_cfg["n_embd"],
        "seq_len": file_cfg["n_positions"],
        "vocab": file_cfg["vocab_size"],
    }


def _layernorm(p: Dict[str, jax.Array], x: jax.Array, eps: float) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _gelu_new(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(p: Dict[str, Any], x: jax.Array, n_head: int, eps: float) -> jax.Array:
    b, t, d = x.shape
    dh = d // n_head
    h = _layernorm(p["ln1"], x, eps)
    qkv = h @ p["qkv"]["w"] + p["qkv"]["b"]
    q, k, v = (
        a.reshape(b, t, n_head, dh).transpose(0, 2, 1, 3)
        for a in jnp.split(qkv, 3, axis=-1)
    )
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1) @ v
    attn = attn.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + attn @ p["attn_out"]["w"] + p["attn_out"]["b"]
    h = _layernorm(p["ln2"], x, eps)
    h = _gelu_new(h @ p["mlp_in"]["w"] + p["mlp_in"]["b"])
    return x + h @ p["mlp_out"]["w"] + p["mlp_out"]["b"]


def loss(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array,
         n_head: int, eps: float = 1e-5) -> jax.Array:
    """Mean next-token cross-entropy, float32 throughout."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        t = tokens.shape[1]
        x = params["wte"][tokens] + params["wpe"][:t][None]

        def layer(h, p):
            return _block(p, h, n_head, eps), None

        x, _ = jax.lax.scan(layer, x, params["blocks"])
        x = _layernorm(params["ln_f"], x, eps)
        logits = x @ params["wte"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        gold = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -jnp.mean(gold)


def make_loss_and_grad(file_cfg: Dict[str, Any]):
    """``(params, tokens, targets) -> (loss, grads)`` for this configuration."""
    n_head = int(file_cfg["n_head"])
    eps = float(file_cfg.get("layer_norm_epsilon", 1e-5))

    def fn(params, tokens, targets) -> Tuple[jax.Array, Any]:
        return jax.value_and_grad(loss)(params, tokens, targets, n_head, eps)

    return fn
