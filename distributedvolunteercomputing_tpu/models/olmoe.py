"""OLMoE-1B-7B (Muennighoff et al. 2024, arXiv:2409.02060; the public
``modeling_olmoe.py`` and https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct
``config.json``): a decoder whose every layer's FFN is 64 SwiGLU experts of
width 1,024 with 8 chosen per token and none dropped. 6.9 B parameters, 1.3 B
of them at work on a token.

One layer, as the public implementation computes it::

    n  = rmsnorm(x)                                   eps 1e-5
    q  = q_norm(Wq n), k = k_norm(Wk n), v = Wv n     no biases; QK-norm is an
                                                      RMSNorm over the WHOLE
                                                      2,048-wide projection,
                                                      before the heads split
    h  = x + Wo attn(rope(q), rope(k), v)             causal, 16 heads of 128,
                                                      rotary in the half-split
                                                      convention, theta 10,000
    p  = softmax_64(Wr rmsnorm(h))                    float32
    y  = h + sum_{e in top8(p)} p_e * Wdown_e(silu(Wgate_e n2) * (Wup_e n2))

The chosen gates are NOT renormalised (``norm_topk_prob`` false): a token's
gates sum to less than 1. After the last layer a final RMSNorm and an untied
``lm_head``. ``clip_qkv`` is null in the published config and is not
implemented; ``num_key_value_heads`` equals the heads (no grouping).

Loss = token cross-entropy + ``aux_coef`` x load balancing + ``z_coef`` x
router z-loss. Load balancing is the public implementation's
``load_balancing_loss_func``: router logits of all layers are concatenated,
so it is ``E * sum_e f_e P_e`` with ``f_e`` the mean over layers and tokens of
the number of a token's choices that fell on e (it sums to 8) and ``P_e`` the
mean router probability: means first, product after, not a mean of per-layer
products (the two agree at one layer). The z-loss is the OLMoE paper's
(section 2, after ST-MoE eq. 5): the mean over tokens and layers of
``logsumexp(router logits)^2``. Coefficients 0.01 and 0.001 are the paper's;
``config.json`` carries ``router_aux_loss_coef`` 0.01 and no z-loss.

Departures, each deliberate: parameters are float32 and compute bfloat16 on a
TPU (the publication trains in bf16 mixed precision too); the router's
product runs in float32 at the highest matmul precision (the publication's
gate is a bf16 linear layer followed by a float32 softmax: ours rounds less,
which the reference check's routing-flip tolerance is glad of); the rotary
angles are computed in float32.

The expert layer is ``ops/moe_dispatch.py``'s sorted dropless dispatch;
``gpt2_moe`` (``models/gpt2_moe.py``) keeps its dense one-hot dispatch with a
capacity, top-1/2 and renormalised gates.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common, moe
from distributedvolunteercomputing_tpu.models.common import matrix, swiglu_init
from distributedvolunteercomputing_tpu.ops.attention import Rotary, attention_merged
from distributedvolunteercomputing_tpu.ops.moe_dispatch import dropless_glu_experts


@dataclasses.dataclass(frozen=True)
class OlmoeConfig:
    """Defaults are the published sizes of OLMoE-1B-7B-0125-Instruct."""

    vocab: int = 50304
    max_len: int = 4096
    d_model: int = 2048
    n_heads: int = 16
    n_layers: int = 16
    d_expert: int = 1024  # ``intermediate_size``: one expert's width
    n_experts: int = 64
    top_k: int = 8
    rms_eps: float = 1e-5
    rope_theta: float = 10000.0
    aux_coef: float = 0.01
    z_coef: float = 0.001
    remat: bool = True  # see GPT2Config.remat
    xent_chunk: int = 512  # T / 8, as gpt2 at T=1,024: the untied head's f32 gradient is updated once a chunk

    def __post_init__(self):
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k={self.top_k} must be in [1, n_experts={self.n_experts}]")
        if self.d_model % self.n_heads:
            raise ValueError(f"d_model={self.d_model} is not a multiple of n_heads={self.n_heads}")


def _layer_init(rng: jax.Array, cfg: OlmoeConfig) -> common.Params:
    k = jax.random.split(rng, 8)
    d, f, e = cfg.d_model, cfg.d_expert, cfg.n_experts
    return {
        "ln_attn": common.rmsnorm_init(d),
        "wq": matrix(k[0], (d, d)),
        "wk": matrix(k[1], (d, d)),
        "wv": matrix(k[2], (d, d)),
        "wo": matrix(k[3], (d, d)),
        "q_norm": common.rmsnorm_init(d),
        "k_norm": common.rmsnorm_init(d),
        "ln_mlp": common.rmsnorm_init(d),
        "router": matrix(k[4], (d, e)),
        # experts stacked on a leading E axis -> sharded over ep (parallel/sharding.py)
        "experts": swiglu_init(k, d, f, (e,), first=5),
    }


def init(rng: jax.Array, cfg: OlmoeConfig) -> common.Params:
    keys = jax.random.split(rng, 3)
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "blocks": common.stacked_init(lambda k: _layer_init(k, cfg), keys[1], cfg.n_layers),
        "ln_f": common.rmsnorm_init(cfg.d_model),
        "lm_head": matrix(keys[2], (cfg.d_model, cfg.vocab)),
    }


def route(p_router: jax.Array, h: jax.Array, top_k: int):
    """Router of one layer: ``h`` [S, d] -> (top_idx [S, k], top_gates [S, k]
    float32, probs [S, E] float32, logits [S, E] float32). Float32 product at
    the highest precision; gates are the chosen probabilities as they are."""
    logits = jnp.dot(
        h.astype(jnp.float32), p_router, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_gates, top_idx = jax.lax.top_k(probs, top_k)
    return top_idx, top_gates, probs, logits


def _zero_stats(cfg: OlmoeConfig) -> Dict[str, jax.Array]:
    e = cfg.n_experts
    return {
        "choices": jnp.zeros((e,), jnp.float32),  # sum over layers of f_e
        "probs": jnp.zeros((e,), jnp.float32),    # sum over layers of P_e
        "z": jnp.zeros((), jnp.float32),          # sum over layers of mean lse^2
        "load_max": jnp.zeros((), jnp.float32),   # fullest expert of any layer, rows
        "dropped": jnp.zeros((), jnp.float32),    # assignments no grouped matmul computed
    }


def _layer(p: common.Params, x: jax.Array, stats: Dict[str, jax.Array], cfg: OlmoeConfig):
    """One layer: (x, running routing statistics) -> the same, and the
    layer's routes ``top_idx`` [S, k]."""
    dtype = x.dtype
    b, t, d = x.shape
    with jax.named_scope("attention"):
        h = common.rmsnorm(p["ln_attn"], x, cfg.rms_eps)
        q = common.rmsnorm(p["q_norm"], h @ p["wq"].astype(dtype), cfg.rms_eps)
        k = common.rmsnorm(p["k_norm"], h @ p["wk"].astype(dtype), cfg.rms_eps)
        v = h @ p["wv"].astype(dtype)
        attn = attention_merged(  # [B, T, H * D] in and out: the projections' own layout
            q, k, v, cfg.n_heads, cfg.n_heads, causal=True,
            rotary=Rotary(base=cfg.rope_theta, layout="half"),
        )
        x = x + attn @ p["wo"].astype(dtype)
    with jax.named_scope("moe"):
        h = common.rmsnorm(p["ln_mlp"], x, cfg.rms_eps).reshape(b * t, d)
        top_idx, top_gates, probs, logits = route(p["router"], h, cfg.top_k)
        ex = p["experts"]
        y, group_sizes, dropped, _ = dropless_glu_experts(
            h, top_idx, top_gates, ex["w_gate"], ex["w_up"], ex["w_down"]
        )
        x = x + y.reshape(b, t, d)
        load = group_sizes.astype(jnp.float32)
        stats = {
            "choices": stats["choices"] + load / (b * t),
            "probs": stats["probs"] + jnp.mean(probs, axis=0),
            "z": stats["z"] + jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            "load_max": jnp.maximum(stats["load_max"], jnp.max(load)),
            "dropped": stats["dropped"] + dropped.astype(jnp.float32),
        }
    return x, stats, top_idx


def _trunk(params: common.Params, tokens: jax.Array, cfg: OlmoeConfig):
    """Final hidden states [B, T, d], the routing statistics summed over the
    layers, and every layer's routes ``[L, S, k]``."""
    x = params["wte"][tokens].astype(common.compute_dtype())

    def block(p, carry):
        x, stats, top_idx = _layer(p, *carry, cfg)
        return (x, stats), top_idx

    (x, stats), routes = common.scan_blocks(
        block, params["blocks"], (x, _zero_stats(cfg)), remat=cfg.remat, with_outputs=True
    )
    return common.rmsnorm(params["ln_f"], x, cfg.rms_eps), stats, routes


def loss_and_routes(
    params: common.Params, batch: Dict[str, jax.Array], cfg: OlmoeConfig
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """(loss, metrics, the experts every layer chose ``[L, S, k]``). The routes
    are what a comparison with a float32 reference hands over, so that it
    compares arithmetic and not which way a near-tie between the k-th and
    (k+1)-th expert fell; they must come out of the very computation whose
    gradients are compared (a second compilation rounds a little differently
    and flips a few near-ties of its own)."""
    tokens = batch["tokens"]
    x, stats, routes = _trunk(params, tokens, cfg)
    lm = common.lm_xent_chunked(
        x, params["lm_head"], batch["targets"], chunk=cfg.xent_chunk, head_layout="dv"
    )
    aux = moe.balance_loss(stats, cfg.n_layers, cfg.n_experts)
    z = stats["z"] / cfg.n_layers
    loss = lm + cfg.aux_coef * aux + cfg.z_coef * z
    rows = tokens.size * cfg.top_k  # assignments a layer routes
    metrics = {
        "loss": loss, "lm_loss": lm, "aux_loss": aux, "z_loss": z,
        # tokens per expert over the step: the fullest expert of any layer,
        # and the even share; assignments no grouped matmul computed, counted
        # from what the kernel is handed (moe_dispatch.rows_not_computed)
        "moe_load_max": stats["load_max"],
        "moe_load_mean": jnp.asarray(rows / cfg.n_experts, jnp.float32),
        "moe_dropped": stats["dropped"],
    }
    return loss, metrics, routes


def spans(cfg: OlmoeConfig):
    """The span the train loop records of this step's routing."""
    return {"moe.route": moe.route_span(cfg, share=False)}
