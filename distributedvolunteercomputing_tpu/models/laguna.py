"""Laguna-XS.2 (poolside; https://huggingface.co/poolside/Laguna-XS.2
``config.json``): a 40-layer decoder, d 2,048, whose layers differ from each
other. 33.4 B parameters, 3 B of them at work on a token.

By layer, from the published lists ``layer_types``,
``num_attention_heads_per_layer`` and ``mlp_layer_types``: layer 0 has full
attention with 48 query heads and a dense SwiGLU FFN of width 8,192; then
periods of four: three layers of sliding-window attention (512) with 64 query
heads, one of full attention with 48; all with 256 routed SwiGLU experts of
width 512, 8 chosen per token, and one shared expert of width 512. Every layer
has 8 key/value heads of 128. RMSNorm eps 1e-6, no biases::

    n  = rmsnorm(x)
    q  = Wq n  [H_l x 128],  k = Wk n  [8 x 128],  v = Wv n  [8 x 128]
    full layer:    rotary on the first 64 coordinates of each head, YaRN inverse
                   frequencies (theta 500,000, factor 64, original 4,096,
                   beta_fast 64, beta_slow 1), cos and sin x 1.4158883
    sliding layer: rotary on all 128 coordinates, theta 10,000
                   (both in the half-split, ``rotate_half``, convention)
    a  = causal softmax attention, query head h reads KV head h // (H_l / 8),
         scale 1/sqrt(128); sliding layer: query i sees keys j, i - 512 < j <= i
    g  = sigmoid(Wg n)  [H_l]                       one gate a head
    h  = x + Wo (g * a)
    n2 = rmsnorm(h)
    dense layer (layer 0):  y = h + Wdown(silu(Wgate n2) * Wup n2)
    expert layer:  s = sigmoid(Wr n2) over 256, float32;  T = top8(s)
                   w_e = 2.5 * s_e / sum_{T} s
                   y = h + shared(n2) + sum_{e in T and held here} w_e expert_e(n2)

Final RMSNorm, untied head. Loss = mean cross-entropy over the vocabulary
(slice) + ``aux_coef`` x load balancing: ``E sum_e f_e P_e`` as
``models/olmoe.py`` computes it (means over layers and tokens first, product
after), ``P_e`` from ``s / sum(s)`` over all 256; no z-loss.

Assumed where ``config.json`` is silent (the benchmark's configuration file
gives each reason): the gate is per head (``gating: true`` has no width; with
a ``2048 x H_l`` gate the model has the 33.4 B parameters its card states);
sigmoid scores, normalised over the chosen eight, scaled by
``moe_routed_scaling_factor`` 2.5, no correction bias and no expert groups; no
QK-norm; the window is the public sliding-window mask (512 keys, the query's
own among them).

The cut a chip makes without touching a width: ``n_layers`` (the first n of
the published lists), ``experts_held`` with ``expert_offset`` (which contiguous
slice of the 256 this chip holds: the router keeps its 256 outputs and its
top-8, and ``ops/moe_dispatch.share_glu_experts`` computes the held
experts' part of the sum; what the others would add is left out, as on one
chip of an expert-parallel deployment before the combine), ``vocab`` (a slice
of the vocabulary: embedding, head and loss over the slice).

The layers are not one stacked scan: ``params["blocks"]`` is a list of
per-layer trees of different shapes, run by an unrolled loop with each layer
rematerialised. Departures as in ``models/olmoe.py``: float32 parameters and
bfloat16 compute on a TPU, the router's product in float32 at the highest
precision, rotary angles in float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common, moe
from distributedvolunteercomputing_tpu.models.common import matrix, swiglu, swiglu_init
from distributedvolunteercomputing_tpu.ops.attention import (
    Rotary, attention_merged, rope, yarn_inv_freq,
)
from distributedvolunteercomputing_tpu.ops.moe_dispatch import share_glu_experts

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Defaults are the published sizes of Laguna-XS.2."""

    vocab: int = 100352
    max_len: int = 8192  # the sequences a step trains on (published limit: 262,144 positions)
    d_model: int = 2048
    head_dim: int = 128
    n_kv_heads: int = 8
    n_layers: int = 40
    heads_full: int = 48      # query heads of a full-attention layer
    heads_sliding: int = 64   # of a sliding-window layer
    period: int = 4           # layer l has full attention where l % period == 0
    dense_layers: int = 1     # leading layers with a dense FFN
    d_ff: int = 8192          # the dense FFN's width
    d_expert: int = 512       # one routed expert's width
    d_shared: int = 512       # the shared expert's width
    n_experts: int = 256      # the router's outputs
    top_k: int = 8
    experts_held: int = 256   # how many of them this chip holds ...
    expert_offset: int = 0    # ... from which on
    routed_scale: float = 2.5
    window: int = 512
    rms_eps: float = 1e-6
    rope_theta_full: float = 500000.0
    rope_theta_sliding: float = 10000.0
    rotary_dim_full: int = 64  # partial_rotary_factor 0.5 of the head
    yarn_factor: float = 64.0
    yarn_original_len: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672
    aux_coef: float = 0.01
    remat: bool = True
    xent_chunk: int = 512

    def __post_init__(self):
        moe.check_share(self)
        for h in (self.heads_full, self.heads_sliding):
            if h % self.n_kv_heads:
                raise ValueError(f"{self.n_kv_heads} key/value heads do not divide {h} query heads")

    def attention_kind(self, layer: int) -> str:
        return FULL if layer % self.period == 0 else SLIDING

    def ffn_kind(self, layer: int) -> str:
        return DENSE if layer < self.dense_layers else SPARSE

    def heads(self, layer: int) -> int:
        return self.heads_full if self.attention_kind(layer) == FULL else self.heads_sliding


def _layer_init(rng: jax.Array, cfg: LagunaConfig, layer: int) -> common.Params:
    k = jax.random.split(rng, 15)
    d, hd, h = cfg.d_model, cfg.head_dim, cfg.heads(layer)
    p = {
        "ln_attn": common.rmsnorm_init(d),
        "wq": matrix(k[0], (d, h * hd)),
        "wk": matrix(k[1], (d, cfg.n_kv_heads * hd)),
        "wv": matrix(k[2], (d, cfg.n_kv_heads * hd)),
        "wg": matrix(k[3], (d, h)),
        "wo": matrix(k[4], (h * hd, d)),
        "ln_mlp": common.rmsnorm_init(d),
    }
    if cfg.ffn_kind(layer) == DENSE:
        p["mlp"] = swiglu_init(k[5:8], d, cfg.d_ff)
    else:
        p["router"] = matrix(k[8], (d, cfg.n_experts))
        p["shared"] = swiglu_init(k[9:12], d, cfg.d_shared)
        # the held experts stacked on a leading axis -> sharded over ep (parallel/sharding.py)
        p["experts"] = swiglu_init(k[12:15], d, cfg.d_expert, (cfg.experts_held,))
    return p


@functools.partial(jax.jit, static_argnums=1)
def init(rng: jax.Array, cfg: LagunaConfig) -> common.Params:
    """One program for the whole tree: leaf by leaf, the unstacked layers' 52
    leaves are as many start-up compilations."""
    keys = jax.random.split(rng, 3)
    layer_keys = jax.random.split(keys[1], cfg.n_layers)
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "blocks": [_layer_init(layer_keys[l], cfg, l) for l in range(cfg.n_layers)],
        "ln_f": common.rmsnorm_init(cfg.d_model),
        "lm_head": matrix(keys[2], (cfg.d_model, cfg.vocab)),
    }


def rotary_of(cfg: LagunaConfig, kind: str) -> Rotary:
    """The layer kind's rotary embedding as ``rope`` is told of it."""
    if kind == SLIDING:
        return Rotary(base=cfg.rope_theta_sliding, layout="half")
    inv_freq = yarn_inv_freq(
        cfg.rotary_dim_full, cfg.rope_theta_full, cfg.yarn_factor, cfg.yarn_original_len,
        cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    return Rotary(layout="half", rotary_dim=cfg.rotary_dim_full, inv_freq=inv_freq,
                  scale=cfg.yarn_attention_factor)


def rotary(x: jax.Array, cfg: LagunaConfig, kind: str) -> jax.Array:
    """The layer kind's rotary embedding of ``x`` [B, H, T, 128]."""
    return rope(x, **rotary_of(cfg, kind)._asdict())


def _attention(p: common.Params, x: jax.Array, cfg: LagunaConfig, layer: int) -> jax.Array:
    dtype = x.dtype
    kind, heads = cfg.attention_kind(layer), cfg.heads(layer)
    n = common.rmsnorm(p["ln_attn"], x, cfg.rms_eps)
    a = attention_merged(  # q, k and v as the projections leave them; [B, T, H * 128] back
        n @ p["wq"].astype(dtype), n @ p["wk"].astype(dtype), n @ p["wv"].astype(dtype),
        heads, cfg.n_kv_heads, causal=True, window=cfg.window if kind == SLIDING else None,
        rotary=rotary_of(cfg, kind),
    )
    gate = jax.nn.sigmoid((n @ p["wg"].astype(dtype)).astype(jnp.float32)).astype(dtype)  # [B, T, H]
    # The gate reaches its head's 128 lanes of the merged array through a 0/1 product
    # [H, H * 128] (exact: one term a sum), and its gradient comes back through the same
    # product: on the chip a reshape to [B, T, H, 128] re-tiles the whole array, forward
    # (a broadcast written out) and backward (a copy before the heads' sums).
    spread = jnp.repeat(jnp.eye(heads, dtype=dtype), cfg.head_dim, axis=1)
    a = a * jnp.dot(gate, spread, precision=jax.lax.Precision.HIGHEST)
    return x + a @ p["wo"].astype(dtype)


def _layer(p: common.Params, x: jax.Array, stats: Dict[str, jax.Array], cfg: LagunaConfig,
           layer: int):
    """One layer: (x, running routing statistics) -> the same, and the layer's
    routes ``top_idx`` [S, k] (None for a dense layer)."""
    b, t, d = x.shape
    with jax.named_scope("attention"):
        x = _attention(p, x, cfg, layer)
    h = common.rmsnorm(p["ln_mlp"], x, cfg.rms_eps)
    if cfg.ffn_kind(layer) == DENSE:
        with jax.named_scope("mlp"):
            return x + swiglu(p["mlp"], h), stats, None
    with jax.named_scope("moe"):
        h = h.reshape(b * t, d)
        top_idx, weights, scores = moe.route(p["router"], h, cfg.top_k, cfg.routed_scale)
        ex = p["experts"]
        y, *dispatch = share_glu_experts(
            h, top_idx, weights, ex["w_gate"], ex["w_up"], ex["w_down"],
            cfg.expert_offset, cfg.n_experts,
        )
        x = x + (swiglu(p["shared"], h) + y).reshape(b, t, d)
        stats, _ = moe.note_share(stats, top_idx, dispatch, cfg, scores=scores)
    return x, stats, top_idx


def _trunk(params: common.Params, tokens: jax.Array, cfg: LagunaConfig):
    """Final hidden states [B, T, d], the routing statistics summed over the
    expert layers, and those layers' routes ``[L_sparse, S, k]``."""
    x = params["wte"][tokens].astype(common.compute_dtype())
    stats, routes = moe.zero_share_stats(balanced=cfg.n_experts), []
    for layer, p in enumerate(params["blocks"]):
        def block(p, x, stats, layer=layer):
            return _layer(p, x, stats, cfg, layer)

        x, stats, top_idx = (common.remat_layer(block) if cfg.remat else block)(p, x, stats)
        if top_idx is not None:
            routes.append(top_idx)
    routes = jnp.stack(routes) if routes else jnp.zeros((0, tokens.size, cfg.top_k), jnp.int32)
    return common.rmsnorm(params["ln_f"], x, cfg.rms_eps), stats, routes


def loss_and_routes(
    params: common.Params, batch: Dict[str, jax.Array], cfg: LagunaConfig
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """(loss, metrics, the experts every expert layer chose ``[L_sparse, S, k]``);
    see ``models/olmoe.loss_and_routes`` for what the routes are for."""
    tokens = batch["tokens"]
    x, stats, routes = _trunk(params, tokens, cfg)
    lm = common.lm_xent_chunked(
        x, params["lm_head"], batch["targets"], chunk=cfg.xent_chunk, head_layout="dv"
    )
    aux = moe.balance_loss(stats, max(cfg.n_layers - cfg.dense_layers, 1), cfg.n_experts)
    loss = lm + cfg.aux_coef * aux
    return loss, moe.share_metrics(loss, lm, aux, stats, tokens.size, cfg), routes


def spans(cfg: LagunaConfig):
    """The span the train loop records of this step's routing."""
    return {"moe.route": moe.route_span(cfg)}
