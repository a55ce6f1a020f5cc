"""Ouro-2.6B (ByteDance; https://huggingface.co/ByteDance/Ouro-2.6B
``config.json``, ``model_type`` ``ouro``; the looped language model of
arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language Models"): a
dense decoder of 48 layers, d 2,048, whose whole stack runs ``total_ut_steps``
= 4 times over the SAME weights, with the output head and an exit gate after
every pass. 2.67 B parameters, each at work four times on a token.

One layer (sandwich norms: four RMSNorms, each with its own weights, computed
in float32, eps 1e-6; no biases, no QK-norm)::

    a  = h + N2(Wo attn(rope(Wq N1(h)), rope(Wk N1(h)), Wv N1(h)))
    h' = a + N4(Wdown(silu(Wgate N3(a)) * (Wup N3(a))))

16 heads of 128 over 16 key/value heads, causal, scale 1/sqrt(128); rotary on
the whole head in the half-split convention (``rotate_half``), theta 1e6, by
positions 0..T-1, the same in every pass. FFN width 5,632.

A pass r = 1..R runs layers 1..L and ends in ``z_r = Nf(h)``, ONE final norm
for all passes; the next pass starts from ``z_r`` (the normed state is what is
carried). After each pass: logits ``z_r Whead`` (untied) and the exit gate
``lam_r = sigmoid(z_r . wg + bg)``, a token each. The exit distribution of a
token: ``p_1 = lam_1``, ``p_r = lam_r prod_{j<r} (1 - lam_j)`` for r < R, and
``p_R = prod_{j<R} (1 - lam_j)``: the last pass takes what is left (``lam_R``
is never read in training).

Objective (the paper's Stage I, uniform prior), ``l_r,i`` the next-token
cross-entropy of pass r at token i and N = B T::

    loss = (1 / N) sum_i [ sum_r p_r,i l_r,i  -  beta H(p_.,i) ],   H = -sum_r p_r log p_r

Gradients reach the trunk through ``l`` weighted by ``p``, and the gate through
BOTH terms: ``p`` is not held constant. The four passes' rows go through ONE
call of ``common.lm_xent_chunked`` (``x`` of ``[R * B, T, d]``, the labels R
times, weights ``p``, divisor B T), whose ``d loss / d weights`` is each
token's own loss: one cast of the float32 head and one float32 ``[d, V]``
accumulator a step, not four of each.

``early_exit_threshold`` (1 as published: never exit early) concerns decoding;
training runs every pass and this module does not read it.

Assumed where ``config.json`` is silent (the benchmark's configuration file
gives each reason): the four norms a layer, no biases, the carried normed
state, the gate on ``z_r``, the objective and ``beta`` 0.1.

The trunk is an outer ``lax.scan`` over the passes around
``common.scan_blocks`` over the layers; the stacked weights are closed over by
the outer loop, so a weight is ONE leaf and its gradient the sum over its R
uses (the backward loop's carry). The cut a chip makes without touching a
width: ``n_layers`` (the layers left out lie on further chips as pipeline
stages, which for a looped model close into a ring) and ``max_len``;
``passes`` is not depth and is not cut. Departures as in ``models/olmoe.py``:
float32 parameters and bfloat16 compute on a TPU, rotary angles and the gate's
product in float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common
from distributedvolunteercomputing_tpu.models.common import StepSpan, matrix, swiglu_init
from distributedvolunteercomputing_tpu.ops.attention import Rotary, attention_merged


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """Defaults are the published sizes of Ouro-2.6B."""

    vocab: int = 49152
    max_len: int = 65536      # ``max_position_embeddings``; a cell trains at its own length
    d_model: int = 2048
    head_dim: int = 128
    n_heads: int = 16
    n_kv_heads: int = 16
    n_layers: int = 48
    d_ff: int = 5632          # ``intermediate_size``
    passes: int = 4           # ``total_ut_steps``: how often the stack runs; not depth
    rms_eps: float = 1e-6
    rope_theta: float = 1000000.0
    entropy_coef: float = 0.1  # ``beta`` of the objective
    remat: bool = True
    xent_chunk: int = 128     # the head sees R x B rows a chunk: [8, 128, V] float32 logits at 2 sequences

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_kv_heads} key/value heads do not divide {self.n_heads} query heads")
        if self.passes < 2:
            raise ValueError(f"passes={self.passes}: a looped model runs its stack at least twice")


def _layer_init(rng: jax.Array, cfg: OuroConfig) -> common.Params:
    k = jax.random.split(rng, 7)
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "ln_attn": common.rmsnorm_init(d),
        "wq": matrix(k[0], (d, cfg.n_heads * hd)),
        "wk": matrix(k[1], (d, cfg.n_kv_heads * hd)),
        "wv": matrix(k[2], (d, cfg.n_kv_heads * hd)),
        "wo": matrix(k[3], (cfg.n_heads * hd, d)),
        "ln_attn_post": common.rmsnorm_init(d),
        "ln_mlp": common.rmsnorm_init(d),
        "mlp": swiglu_init(k, d, cfg.d_ff, first=4),
        "ln_mlp_post": common.rmsnorm_init(d),
    }


@functools.partial(jax.jit, static_argnums=1)
def init(rng: jax.Array, cfg: OuroConfig) -> common.Params:
    """One program for the whole tree; ``blocks`` is the L layers stacked, each
    leaf once however often the passes use it."""
    keys = jax.random.split(rng, 4)
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "blocks": common.stacked_init(lambda k: _layer_init(k, cfg), keys[1], cfg.n_layers),
        "ln_f": common.rmsnorm_init(cfg.d_model),
        "lm_head": matrix(keys[2], (cfg.d_model, cfg.vocab)),
        "exit_gate": {"w": matrix(keys[3], (cfg.d_model,)), "b": jnp.zeros((), jnp.float32)},
    }


def _layer(p: common.Params, h: jax.Array, cfg: OuroConfig) -> jax.Array:
    dtype = h.dtype
    with jax.named_scope("attention"):
        u = common.rmsnorm(p["ln_attn"], h, cfg.rms_eps)
        a = attention_merged(  # q, k and v as the projections leave them; [B, T, H * 128] back
            u @ p["wq"].astype(dtype), u @ p["wk"].astype(dtype), u @ p["wv"].astype(dtype),
            cfg.n_heads, cfg.n_kv_heads, causal=True, rotary=Rotary(base=cfg.rope_theta, layout="half"),
        )
        h = h + common.rmsnorm(p["ln_attn_post"], a @ p["wo"].astype(dtype), cfg.rms_eps)
    with jax.named_scope("mlp"):
        u = common.rmsnorm(p["ln_mlp"], h, cfg.rms_eps)
        h = h + common.rmsnorm(p["ln_mlp_post"], common.swiglu(p["mlp"], u), cfg.rms_eps)
    return h


def trunk(params: common.Params, tokens: jax.Array, cfg: OuroConfig) -> jax.Array:
    """The passes' normed states ``z_r``, ``[R, B, T, d]``, of ``tokens`` [B, T]."""
    h = params["wte"][tokens].astype(common.compute_dtype())
    layer = functools.partial(_layer, cfg=cfg)

    # checkpointed: the backward pass keeps a pass's last state as it is (bf16) and not the norm's float32 copies of it
    final_norm = jax.checkpoint(lambda g, h: common.rmsnorm(g, h, cfg.rms_eps))

    def one_pass(h, _):
        # ``params`` is closed over: the loop's constant, one leaf for every pass
        h = common.scan_blocks(layer, params["blocks"], h, remat=cfg.remat, passes=cfg.passes)
        z = final_norm(params["ln_f"], h)
        return z, z

    with jax.named_scope("recur"):
        _, zs = jax.lax.scan(one_pass, h, None, length=cfg.passes)
    return zs


def exit_distribution(gate_logits: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``gate_logits`` [R - 1, ...] float32, the first R - 1 passes' (the last
    pass takes the remainder) -> (``p`` [R, ...], its entropy ``H`` [...] in
    nats), through the logarithms: ``log p_r = log lam_r + sum_{j<r} log (1 -
    lam_j)``, finite for any gate."""
    stay = jax.nn.log_sigmoid(-gate_logits)                       # log (1 - lam_j)
    before = jnp.cumsum(stay, axis=0) - stay                      # sum_{j<r}
    log_p = jnp.concatenate([jax.nn.log_sigmoid(gate_logits) + before, jnp.sum(stay, axis=0)[None]])
    p = jnp.exp(log_p)
    return p, -jnp.sum(p * log_p, axis=0)


def loss_fn(params: common.Params, batch: Dict[str, jax.Array], rng: jax.Array, cfg: OuroConfig):
    """(loss, metrics); the objective draws nothing from ``rng``."""
    del rng
    tokens, targets = batch["tokens"], batch["targets"]
    b, t = tokens.shape
    r = cfg.passes
    zs = trunk(params, tokens, cfg)
    gate = params["exit_gate"]
    # float32, as a sum of products (one pass over z, no float32 copy of it)
    p, entropy = exit_distribution(jnp.sum(zs[:-1].astype(jnp.float32) * gate["w"], axis=-1) + gate["b"])
    lm = common.lm_xent_chunked(  # every pass's rows in one loop; its weights are learnt through
        zs.reshape(r * b, t, zs.shape[-1]), params["lm_head"], jnp.tile(targets, (r, 1)),
        mask=p.reshape(r * b, t), chunk=cfg.xent_chunk, head_layout="dv", denominator=float(b * t),
    )
    entropy = jnp.mean(entropy)
    loss = lm - cfg.entropy_coef * entropy
    by_pass = jnp.mean(p, axis=(1, 2))  # [R]
    metrics = {
        "loss": loss, "lm_loss": lm, "exit_entropy": entropy,
        "expected_passes": jnp.sum(by_pass * jnp.arange(1, r + 1)),
        # the mean of p_r a pass, R numbers, a scalar each (the loop's metrics are scalars)
        **{exit_p_key(i, r): by_pass[i] for i in range(r)},
    }
    return loss, metrics


def exit_p_key(i: int, passes: int) -> str:
    """The metrics' key of pass ``i + 1``'s mean exit probability."""
    return "exit_p_first" if i == 0 else "exit_p_last" if i == passes - 1 else f"exit_p_{i + 1}"


def spans(cfg: OuroConfig):
    """The span the train loop records of this step's exits: every pass's mean
    exit probability (``exit_p_first`` ... ``exit_p_last``) with the entropy, the
    expected pass and the exit-weighted cross-entropy."""
    return {"recur.exit": StepSpan(
        keys=("exit_entropy", "expected_passes", *(exit_p_key(i, cfg.passes) for i in range(cfg.passes)), "lm_loss"),
        attrs={"passes": cfg.passes, "layers": cfg.n_layers})}
