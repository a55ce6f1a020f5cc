"""Mixture-of-Experts FFN (Switch top-1 / GShard top-2 routing) + GPT-2-MoE.

Build-side extension beyond reference parity (SURVEY.md §2 lists the
reference as dense volunteer-DP only), completing the parallelism set with
EXPERT parallelism: expert weights are stacked on a leading E axis and
sharded over the mesh's ``ep`` axis (parallel/sharding.py rules), so the
dispatch/combine einsums below compile to GSPMD all-to-alls over ICI — the
canonical GShard/Switch TPU formulation, where routing is expressed as
dense one-hot einsums the MXU eats, never as data-dependent gathers.

Routing (``router_top_k``; 1 = Switch Transformer, 2 = GShard top-2):
- router logits [S, E] -> softmax gates; each token goes to its top-k
  experts, output scaled by the gate(s) (renormalized over the chosen
  experts for k > 1; the raw argmax gate for k = 1, as in Switch);
- static capacity C = ceil(capacity_factor * router_top_k * S / E) per
  expert (capacity scales with k — 2S assignments need 2x the slots);
  tokens beyond an expert's capacity are DROPPED for the FFN (their
  residual stream passes through unchanged) — the standard fixed-shape
  trade that keeps the whole layer jit-compatible;
- load-balancing aux loss (Switch eq. 4): E * sum_e(frac_tokens_e *
  mean_gate_e), minimized at uniform routing; returned in metrics and
  added to the objective with ``aux_coef``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common
from distributedvolunteercomputing_tpu.models.gpt2 import GPT2Config


@dataclasses.dataclass(frozen=True)
class GPT2MoEConfig(GPT2Config):
    n_experts: int = 8
    capacity_factor: float = 1.25
    aux_coef: float = 0.01
    # Experts each token is routed to: 1 = Switch, 2 = GShard-style top-2
    # (gates renormalized over the chosen experts; the second choice queues
    # for capacity AFTER all first choices).
    router_top_k: int = 1
    # MoE replaces the dense FFN in EVERY block (Switch layout); d_ff is the
    # per-expert hidden width.

    def __post_init__(self):
        if not 1 <= self.router_top_k <= self.n_experts:
            raise ValueError(
                f"router_top_k={self.router_top_k} must be in [1, n_experts={self.n_experts}]"
            )


def moe_init(rng: jax.Array, cfg: GPT2MoEConfig) -> common.Params:
    kr, ki, ko = jax.random.split(rng, 3)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    res_scale = 1.0 / ((2 * cfg.n_layers) ** 0.5 * d**0.5)
    return {
        "router": jax.random.normal(kr, (d, e), jnp.float32) * 0.02,
        # experts stacked on the leading E axis -> sharded over ep
        "moe_in": jax.random.normal(ki, (e, d, f), jnp.float32) * 0.02,
        "moe_out": jax.random.normal(ko, (e, f, d), jnp.float32) * res_scale,
    }


def moe_ffn(p: common.Params, x: jax.Array, cfg: GPT2MoEConfig) -> Tuple[jax.Array, jax.Array]:
    """x: [B, T, d] -> (y [B, T, d], aux_loss scalar)."""
    b, t, d = x.shape
    s = b * t
    e = cfg.n_experts
    # ceil, not truncation: capacity_factor=1.25 must mean >= 25% headroom
    # over the uniform share, never less. Capacity scales with router_top_k
    # (GShard): top-2 makes 2S total assignments, so per-expert slots must
    # double for the same factor or ~a third of assignments drop even under
    # perfectly uniform routing.
    cap = max(math.ceil(cfg.capacity_factor * cfg.router_top_k * s / e), 1)
    xs = x.reshape(s, d)

    # Router in f32 (softmax statistics), gates carry the gradient.
    logits = jnp.einsum("sd,de->se", xs.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)  # [S, E]
    k_router = cfg.router_top_k
    top_gates, top_idx = jax.lax.top_k(gates, k_router)  # [S, K]
    if k_router > 1:
        # GShard: renormalize over the chosen experts so the combined output
        # is a convex mixture. (Deliberately NOT applied at K=1, matching
        # Switch — the raw gate carries the router gradient.)
        top_gates = top_gates / jnp.sum(top_gates, axis=-1, keepdims=True)

    # Per-choice dispatch: choice i's tokens queue for expert capacity AFTER
    # every earlier choice's assignments (count_prev), the standard GShard
    # ordering — a token's second choice never displaces a first choice.
    dispatch = jnp.zeros((s, e, cap), x.dtype)
    combine = jnp.zeros((s, e, cap), x.dtype)
    count_prev = jnp.zeros((e,), jnp.float32)
    onehot1 = None
    for i in range(k_router):
        oh = jax.nn.one_hot(top_idx[:, i], e, dtype=jnp.float32)  # [S, E]
        if i == 0:
            onehot1 = oh
        # Position within the expert queue; -1 where unrouted, >= cap drops.
        pos = (jnp.cumsum(oh, axis=0) + count_prev[None, :]) * oh - 1.0
        kept = (pos >= 0) & (pos < cap)
        pos_oh = jax.nn.one_hot(
            jnp.clip(pos, 0, cap - 1).astype(jnp.int32), cap, dtype=x.dtype
        )  # [S, E, C]
        disp = pos_oh * kept.astype(x.dtype)[..., None]
        dispatch = dispatch + disp
        combine = combine + disp * top_gates[:, i].astype(x.dtype)[:, None, None]
        count_prev = count_prev + jnp.sum(oh, axis=0)

    # dispatch/combine einsums: with moe_in/out sharded over ep, GSPMD emits
    # the all-to-alls here.
    ein = jnp.einsum("sec,sd->ecd", dispatch, xs)  # [E, C, d]
    dtype = x.dtype
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", ein, p["moe_in"].astype(dtype)))
    eout = jnp.einsum("ecf,efd->ecd", h, p["moe_out"].astype(dtype))  # [E, C, d]
    y = jnp.einsum("sec,ecd->sd", combine, eout)

    # Load-balance loss (Switch eq. 4 / GShard): E * sum_e(frac of tokens
    # whose FIRST choice is e * mean_gate_e).
    frac = jnp.mean(onehot1, axis=0)  # [E]
    mean_gate = jnp.mean(gates, axis=0)  # [E]
    aux = e * jnp.sum(frac * mean_gate)
    return y.reshape(b, t, d), aux.astype(jnp.float32)


def _layer_init(rng: jax.Array, cfg: GPT2MoEConfig) -> common.Params:
    k = jax.random.split(rng, 3)
    res_scale = 1.0 / ((2 * cfg.n_layers) ** 0.5 * cfg.d_model**0.5)
    return {
        "ln1": common.layernorm_init(cfg.d_model),
        "qkv": common.dense_init(k[0], cfg.d_model, 3 * cfg.d_model, scale=0.02),
        "attn_out": common.dense_init(k[1], cfg.d_model, cfg.d_model, scale=res_scale),
        "ln2": common.layernorm_init(cfg.d_model),
        "moe": moe_init(k[2], cfg),
    }


def init(rng: jax.Array, cfg: GPT2MoEConfig) -> common.Params:
    keys = jax.random.split(rng, 3)
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "wpe": common.embed_init(keys[1], cfg.max_len, cfg.d_model, scale=0.01),
        "blocks": common.stacked_init(
            lambda k: _layer_init(k, cfg), keys[2], cfg.n_layers
        ),
        "ln_f": common.layernorm_init(cfg.d_model),
    }


def _block(p: common.Params, x_aux, cfg: GPT2MoEConfig):
    x, aux = x_aux
    h = common.layernorm(p["ln1"], x)
    attn = common.fused_qkv_attention(p["qkv"], h, cfg.n_heads, causal=True)
    x = x + common.dense(p["attn_out"], attn)
    h = common.layernorm(p["ln2"], x)
    y, layer_aux = moe_ffn(p["moe"], h, cfg)
    return x + y, aux + layer_aux


def loss_fn(
    params: common.Params, batch: Dict[str, jax.Array], rng: jax.Array, cfg: GPT2MoEConfig
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    from distributedvolunteercomputing_tpu.models import gpt2

    x = gpt2.embed(params, batch["tokens"], cfg)
    aux0 = jnp.zeros((), jnp.float32)
    (x, aux) = common.scan_blocks(
        lambda p, xa: _block(p, xa, cfg), params["blocks"], (x, aux0), remat=cfg.remat
    )
    x = common.layernorm(params["ln_f"], x)
    lm = common.lm_xent_chunked(
        x, params["wte"], batch["targets"], chunk=cfg.xent_chunk, head_layout="vd"
    )
    aux = aux / cfg.n_layers
    loss = lm + cfg.aux_coef * aux
    return loss, {"loss": loss, "lm_loss": lm, "aux_loss": aux}
