"""Ouro-2.6B's training step as published
(https://huggingface.co/ByteDance/Ouro-2.6B ``config.json``, ``model_type``
``ouro``; the looped language model of arXiv:2510.25741; the equations are in
``models/ouro.py``'s docstring and are followed here independently), plain.

Float32 ``jax.numpy`` at the highest matmul precision: no kernel, no chunked
loss head of the program's, no remat policy of the program's
(``jax.checkpoint`` only bounds what the backward keeps: it changes no result).

- a layer: ``a = h + N2(Wo attn(N1 h))``, ``h' = a + N4(MLP(N3 a))``, four
  RMSNorms with their own weights; attention one head at a time over an
  explicit causal mask (a ``lax.scan`` over the heads, body checkpointed: 4,096
  x 4,096 float32 scores are 67 MB a head); half-split rotary at theta by
  positions 0..T-1, the same in every pass.
- a pass: the L layers (a ``lax.scan`` over the stacked weights, body
  checkpointed), then ``z_r = Nf(h)``; the next pass starts from ``z_r``. The
  passes are a ``lax.scan`` whose body closes over the weights, the same arrays
  in every pass, its body checkpointed too (a Python loop computes the same and
  keeps every pass's own gradient tree until the end: 6.5 GB at the published
  widths, which the chip cannot hold beside the check's state).
- heads ONE pass at a time, and a pass's rows in slices of 512 (checkpointed: a
  slice's ``[512, V]`` log-probabilities are recomputed, not kept), each giving
  the tokens' own cross-entropy ``l_r,i``.
- exit distribution: ``lam_r = sigmoid(z_r . wg + bg)``; ``p_1 = lam_1``,
  ``p_r = lam_r prod_{j<r}(1 - lam_j)``, ``p_R = prod_{j<R}(1 - lam_j)``,
  computed as products of probabilities (the program goes through logarithms).
- loss: ``mean_i [ sum_r p_r,i l_r,i - beta H(p_.,i) ]``, ``H = -sum_r p_r log
  p_r``; nothing is held constant.

``variant`` swaps one term for what a mistaken implementation would compute
(``VARIANTS``), for the readings that show the comparison notices it.

It reads the program's parameter tree (``models/ouro.py:init``: ``wte``,
``blocks`` stacked on a leading layer axis, ``ln_f``, ``lm_head``,
``exit_gate``) because that is what the weights come in; nothing else is shared
with the code under test.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# one term of the objective, of the loop or of the layer computed as a mistaken implementation would
VARIANTS = ("no_post_norms", "unnormed_state_carried", "three_passes", "last_pass_not_the_remainder",
            "weights_held_constant", "no_entropy_term", "last_pass_loss_only", "gate_reads_unnormed_state",
            "positions_run_on_over_passes")

# published key (scalar) -> attribute of the program's OuroConfig
_PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model",
    "head_dim": "head_dim",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rms_norm_eps": "rms_eps",
    "rope_theta": "rope_theta",
    "num_hidden_layers": "n_layers",
    "total_ut_steps": "passes",
}
# what the program cannot vary, so the file must say what the program does
_FIXED = {"tie_word_embeddings": False, "hidden_act": "silu", "rope_scaling": None, "use_sliding_window": False,
          "sliding_window": None}
HEAD_ROWS = 512  # rows of a pass the head takes at a time


def _assumed(file_cfg: Dict[str, Any], key: str):
    return file_cfg["assumed"][key]["value"]


def check_config(program_config: Any, file_cfg: Dict[str, Any]) -> None:
    """The registry's configuration must be the file's, key for key."""
    name = file_cfg["name"]

    def same(what, have, want):
        if have != want:
            raise ValueError(f"configuration {name}: the program runs {what}={have!r}, the file says {want!r}")

    for pub, attr in _PUBLISHED_TO_PROGRAM.items():
        same(f"{attr} ({pub})", getattr(program_config, attr), file_cfg[pub])
    same("max_len (assumed.seq_len)", program_config.max_len, int(_assumed(file_cfg, "seq_len")))
    same("entropy_coef (assumed.objective beta)", program_config.entropy_coef,
         float(_assumed(file_cfg, "objective")["beta"]))
    for pub, want in _FIXED.items():
        if file_cfg.get(pub, want) != want:
            raise ValueError(f"configuration {name}: {pub}={file_cfg[pub]!r} is not what is built")
    if set(file_cfg.get("layer_types", ["full_attention"])) != {"full_attention"}:
        raise ValueError(f"configuration {name}: layer_types other than full_attention are not what is built")


def sizes(file_cfg: Dict[str, Any]) -> Dict[str, int]:
    """What the FLOP arithmetic and the data generator need."""
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
        "passes": int(file_cfg["total_ut_steps"]),
        "beta": float(_assumed(file_cfg, "objective")["beta"]),
    }


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


def _attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal, [B, H, T, D] x [B, Hkv, T, D] -> [B, H, T, D], one query head at a time."""
    h, h_kv, t, d = q.shape[1], k.shape[1], q.shape[2], q.shape[3]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one(_, head):
        qh, kv = head
        scores = qh @ jnp.swapaxes(k[:, kv], -1, -2) / math.sqrt(d)  # [B, T, T]
        scores = jnp.where(causal, scores, -jnp.inf)
        return None, jax.nn.softmax(scores, axis=-1) @ v[:, kv]

    kv_of = jnp.arange(h) // (h // h_kv)
    _, out = jax.lax.scan(jax.checkpoint(one), None, (jnp.moveaxis(q, 1, 0), kv_of))
    return jnp.moveaxis(out, 0, 1)


def _block(p: Dict[str, Any], x: jax.Array, positions: jax.Array, hp: Dict[str, Any],
           variant: Optional[str] = None) -> jax.Array:
    b, t, _ = x.shape
    heads, n_kv, hd, eps = hp["heads"], hp["n_kv"], hp["head_dim"], hp["eps"]
    post = variant != "no_post_norms"
    n = _rmsnorm(p["ln_attn"]["g"], x, eps)
    q = (n @ p["wq"]).reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
    k = (n @ p["wk"]).reshape(b, t, n_kv, hd).transpose(0, 2, 1, 3)
    v = (n @ p["wv"]).reshape(b, t, n_kv, hd).transpose(0, 2, 1, 3)
    a = _attention(_rope(q, positions, hp["theta"]), _rope(k, positions, hp["theta"]), v)
    a = a.transpose(0, 2, 1, 3).reshape(b, t, heads * hd) @ p["wo"]
    x = x + (_rmsnorm(p["ln_attn_post"]["g"], a, eps) if post else a)
    n = _rmsnorm(p["ln_mlp"]["g"], x, eps)
    m = (jax.nn.silu(n @ p["mlp"]["w_gate"]) * (n @ p["mlp"]["w_up"])) @ p["mlp"]["w_down"]
    return x + (_rmsnorm(p["ln_mlp_post"]["g"], m, eps) if post else m)


def _token_losses(zs: jax.Array, w_head: jax.Array, targets: jax.Array) -> jax.Array:
    """Every pass's next-token cross-entropy a token, ``[R, B, T]`` of ``zs``
    ``[R, B, T, d]``: one pass at a time and a pass's rows in slices of
    ``HEAD_ROWS``, each slice's log-probabilities recomputed in the backward
    pass (one loop over all of them, so the head's gradient is one running
    sum)."""
    r, b, t, d = zs.shape
    rows = HEAD_ROWS if t % HEAD_ROWS == 0 else t

    @jax.checkpoint
    def some(zc, yc):
        logp = jax.nn.log_softmax(zc @ w_head, axis=-1)
        return -jnp.take_along_axis(logp, yc[..., None], axis=-1)[..., 0]

    def by_slice(a):  # [R, B, T, ...] -> [R * T / rows, B, rows, ...]
        a = jnp.moveaxis(a.reshape(r, b, t // rows, rows, *a.shape[3:]), 2, 1)
        return a.reshape(r * (t // rows), b, rows, *a.shape[4:])

    _, nll = jax.lax.scan(lambda _, zy: (None, some(*zy)), None,
                          (by_slice(zs), by_slice(jnp.broadcast_to(targets, (r, b, t)))))
    return jnp.moveaxis(nll.reshape(r, t // rows, b, rows), 1, 2).reshape(r, b, t)


def exit_distribution(lam: jax.Array, remainder: bool = True) -> jax.Array:
    """``lam`` [R, ...], every pass's gate -> ``p`` [R, ...]: a pass's gate
    times the chance of having stayed so far; the LAST pass takes what is left,
    whatever its gate says (``remainder``: the published rule)."""
    stayed = jnp.concatenate([jnp.ones_like(lam[:1]), jnp.cumprod(1.0 - lam[:-1], axis=0)])  # prod_{j<r}
    p = lam * stayed
    return p.at[-1].set(stayed[-1]) if remainder else p


def loss(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array, hp: Dict[str, Any],
         variant: Optional[str] = None) -> jax.Array:
    """The exit-weighted loss less ``beta`` times the exit distribution's entropy, float32 throughout."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        t = tokens.shape[1]
        passes = hp["passes"] - 1 if variant == "three_passes" else hp["passes"]
        gate = params["exit_gate"]

        @jax.checkpoint  # the backward pass keeps a pass's input and runs the pass again
        def one_pass(h, r):
            # positions 0..T-1 in every pass; the mistaken form counts on from the pass before
            positions = jnp.arange(t) + (r * t if variant == "positions_run_on_over_passes" else 0)

            def layer(x, p):
                # checkpointed: the backward pass keeps one layer's activations
                return jax.checkpoint(lambda p, x: _block(p, x, positions, hp, variant))(p, x), None

            h, _ = jax.lax.scan(layer, h, params["blocks"])
            z = _rmsnorm(params["ln_f"]["g"], h, hp["eps"])
            lam = jax.nn.sigmoid((h if variant == "gate_reads_unnormed_state" else z) @ gate["w"] + gate["b"])
            # the normed state is what the next pass starts from
            return (h if variant == "unnormed_state_carried" else z), (z, lam)

        # the passes close over the weights: the same arrays in every pass, their gradients summed by the loop
        _, (zs, lam) = jax.lax.scan(one_pass, params["wte"][tokens], jnp.arange(passes))  # [R, B, T, d], [R, B, T]
        nll = _token_losses(zs, params["lm_head"], targets)  # [R, B, T]
        if variant == "last_pass_loss_only":
            return jnp.mean(nll[-1])
        p = exit_distribution(lam, remainder=variant != "last_pass_not_the_remainder")
        entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
        if variant == "weights_held_constant":
            p = jax.lax.stop_gradient(p)  # the entropy above still sees the gate
        total = jnp.mean(jnp.sum(p * nll, axis=0))
        if variant != "no_entropy_term":
            total = total - hp["beta"] * jnp.mean(entropy)
        return total


def make_loss_and_grad(file_cfg: Dict[str, Any]):
    """``(params, tokens, targets) -> (loss, grads)`` for this configuration."""
    hp = hyper(file_cfg)

    def fn(params, tokens, targets) -> Tuple[jax.Array, Any]:
        return jax.value_and_grad(loss)(params, tokens, targets, hp)

    return fn
