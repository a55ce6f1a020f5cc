"""SmallThinker-21BA3B-Instruct (PowerInfer;
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct ``config.json``):
a 52-layer decoder, d 2,560, 64 ReGLU experts of width 768 a layer, six chosen
per token, no shared expert and no dense layer. 21 B parameters, 3 B of them
at work on a token, context 16,384.

By layer, from the published lists ``sliding_window_layout`` and
``rope_layout``: layer ``l`` with ``l % 4 == 0`` is a GLOBAL layer (both 0:
every earlier key, no position encoding at all); the three that follow are
SLIDING layers (both 1: a 4,096-key window, rotary embedding). Every layer has
28 query heads over 4 key/value heads of 128. RMSNorm eps 1e-6, no biases, no
QK norm::

    r  = Wr x                  [64] float32: the router reads the layer's INPUT,
                               before the input norm and before attention
    n  = rmsnorm(x)
    q  = Wq n [28 x 128],  k = Wk n [4 x 128],  v = Wv n [4 x 128]
    sliding layer: rotary on all 128 coordinates of q and k, theta 1,500,000,
                   half-split (``rotate_half``) convention
    global layer:  nothing (NoPE)
    a  = causal softmax attention, scale 1/sqrt(128), query head h reads KV
         head h // 7; sliding layer: query i sees keys j, i - 4096 < j <= i
    h  = x + Wo a
    n2 = rmsnorm(h)
    T  = top6(r);  w_e = exp(r_e) / sum_{e' in T} exp(r_e')
    expert_e(u) = Wdown_e (relu(Wgate_e u) * Wup_e u)
    y  = h + sum_{e in T and held here} w_e expert_e(n2)

Final RMSNorm, untied head. Loss = mean cross-entropy over the vocabulary
(slice) + ``aux_coef`` x load balancing: ``E sum_e f_e P_e`` as
``models/olmoe.py`` computes it (means over layers and tokens first, product
after), ``P_e`` from the softmax of ``r`` over all 64.

Assumed where ``config.json`` is silent (the benchmark's configuration file
gives each reason): the router's input is the layer's input (the catalog's
"router placed before attention"; no key says it); its weights are the softmax
over all 64 renormalised over the chosen six
(``moe_primary_router_apply_softmax`` and ``norm_topk_prob`` both true: the
same number as the softmax over the six); no secondary experts (no key).

The cut a chip makes without touching a width, as ``models/laguna.py``:
``n_layers`` (whole periods of four), ``experts_held`` with ``expert_offset``
(``ops/moe_dispatch.share_glu_experts`` computes the held experts' part of
the sum; what the others would add is left out), ``vocab`` (a slice).

Because the router reads the layer's input, the layer routes FIRST: the float32
router product, the top-6 and the share's sort (``moe_dispatch.plan_share``)
need nothing of attention, and the plan's S x k vectors are spent after it.

All layers have one parameter shape and two kinds, so the model is scanned by
period: ``params["blocks"] = {"global": [P, ...], "sliding": [P, 3, ...]}``
(P periods), a ``lax.scan`` over the periods whose body runs the global layer
and an inner scan over the three sliding layers, each layer rematerialised
(``models/common.remat_layer``): one compiled layer of each kind, whatever the
depth. Departures as in ``models/olmoe.py``: float32 parameters and bfloat16
compute on a TPU, the router's product in float32 at the highest precision,
rotary angles in float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common, moe
from distributedvolunteercomputing_tpu.models.common import matrix, swiglu_init
from distributedvolunteercomputing_tpu.ops.attention import Rotary, attention_merged
from distributedvolunteercomputing_tpu.ops.moe_dispatch import plan_share, share_glu_experts

GLOBAL, SLIDING = "global", "sliding"

# Rows a chunk of the share's dispatch holds over the share's even part (the
# dispatch's own default is 3, measured on Laguna's sigmoid router). MEASURED
# (PR 35, TPU v5e, smallthinker-solo-16k, Adam at 1e-3 from scratch;
# experiments/laguna_routing_trace.py --config smallthinker-21b-a3b; PERF.md,
# Findings of PR 35): this router is a softmax over the raw residual stream and
# collapses WHOLLY within four steps (the fullest held expert takes all 32,768
# tokens of a step from step 4 on, for the rest of a 60-step run): every token
# picks the same six experts, of which m fall on this chip's eight of 64
# (hypergeometric: m >= 3 in 2.2% of layers, m >= 4 in 0.14%). At 3 (2.25 S
# rows) a layer with m = 3 needs 3 S rows and runs two chunks for as long as
# the collapse lasts: one run of seven read 33,487 tokens/s where the others
# read 36,090-36,179. At 4.25 (3.19 S: three collapsed experts and the other
# five at half their even share) such a layer is one chunk.
SHARE_ROWS_SLACK = 4.25


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """Defaults are the published sizes of SmallThinker-21BA3B-Instruct."""

    vocab: int = 151936
    max_len: int = 16384      # max_position_embeddings: the sequences a step trains on
    d_model: int = 2560
    head_dim: int = 128
    n_heads: int = 28
    n_kv_heads: int = 4
    n_layers: int = 52
    period: int = 4           # layer l is global where l % period == 0, sliding elsewhere
    d_expert: int = 768       # one expert's width
    n_experts: int = 64       # the router's outputs
    top_k: int = 6
    experts_held: int = 64    # how many of them this chip holds ...
    expert_offset: int = 0    # ... from which on
    window: int = 4096
    rms_eps: float = 1e-6
    rope_theta: float = 1500000.0
    aux_coef: float = 0.01
    remat: bool = True
    xent_chunk: int = 512

    # which stream the router reads (the ``moe.route`` span says it)
    router_site = "layer_input"

    def __post_init__(self):
        moe.check_share(self)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.n_kv_heads} key/value heads do not divide {self.n_heads} query heads")
        if self.period < 2 or self.n_layers % self.period:
            raise ValueError(
                f"n_layers={self.n_layers} is not whole periods of {self.period} layers")

    @property
    def periods(self) -> int:
        return self.n_layers // self.period

    def attention_kind(self, layer: int) -> str:
        return GLOBAL if layer % self.period == 0 else SLIDING


def _layer_init(rng: jax.Array, cfg: SmallThinkerConfig) -> common.Params:
    k = jax.random.split(rng, 8)
    d, hd, f = cfg.d_model, cfg.head_dim, cfg.d_expert
    return {
        "ln_attn": common.rmsnorm_init(d),
        "wq": matrix(k[0], (d, cfg.n_heads * hd)),
        "wk": matrix(k[1], (d, cfg.n_kv_heads * hd)),
        "wv": matrix(k[2], (d, cfg.n_kv_heads * hd)),
        "wo": matrix(k[3], (cfg.n_heads * hd, d)),
        "ln_mlp": common.rmsnorm_init(d),
        "router": matrix(k[4], (d, cfg.n_experts)),
        # the held experts stacked on a leading axis -> sharded over ep (parallel/sharding.py)
        "experts": swiglu_init(k, d, f, (cfg.experts_held,), first=5),
    }


@functools.partial(jax.jit, static_argnums=1)
def init(rng: jax.Array, cfg: SmallThinkerConfig) -> common.Params:
    """One program for the whole tree. A layer's key is its index's, so layer
    ``l`` is ``blocks["global"][l // 4]`` or ``blocks["sliding"][l // 4, l % 4 - 1]``."""
    keys = jax.random.split(rng, 3)
    layer_keys = jax.random.split(keys[1], cfg.n_layers).reshape(cfg.periods, cfg.period, -1)
    one = functools.partial(_layer_init, cfg=cfg)
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "blocks": {GLOBAL: jax.vmap(one)(layer_keys[:, 0]),
                   SLIDING: jax.vmap(jax.vmap(one))(layer_keys[:, 1:])},
        "ln_f": common.rmsnorm_init(cfg.d_model),
        "lm_head": matrix(keys[2], (cfg.d_model, cfg.vocab)),
    }


def route(p_router: jax.Array, x: jax.Array, top_k: int):
    """Router of one layer on its INPUT ``x`` [S, d] -> (top_idx [S, k],
    weights [S, k] float32, probs [S, E] float32). Logits from a float32
    product at the highest precision; the weights are the softmax over the
    chosen ``k`` logits, which is the softmax over all E renormalised over the
    chosen; ``probs`` is that softmax over all E (the balancing term's)."""
    logits = jnp.dot(
        x.astype(jnp.float32), p_router, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    top_logits, top_idx = jax.lax.top_k(logits, top_k)
    return top_idx, jax.nn.softmax(top_logits, axis=-1), jax.nn.softmax(logits, axis=-1)


def _attention(p: common.Params, x: jax.Array, cfg: SmallThinkerConfig, kind: str) -> jax.Array:
    dtype = x.dtype
    n = common.rmsnorm(p["ln_attn"], x, cfg.rms_eps)
    a = attention_merged(  # q, k and v as the projections leave them; [B, T, H * D] back
        n @ p["wq"].astype(dtype), n @ p["wk"].astype(dtype), n @ p["wv"].astype(dtype),
        cfg.n_heads, cfg.n_kv_heads, causal=True, window=cfg.window if kind == SLIDING else None,
        # a global layer has no position encoding at all
        rotary=Rotary(base=cfg.rope_theta, layout="half") if kind == SLIDING else None,
    )
    return x + a @ p["wo"].astype(dtype)


def _layer(p: common.Params, x: jax.Array, stats: Dict[str, jax.Array], cfg: SmallThinkerConfig,
           kind: str):
    """One layer: (x, running routing statistics) -> the same, and the layer's
    routes ``top_idx`` [S, k]."""
    b, t, d = x.shape
    held = cfg.experts_held
    with jax.named_scope("moe_route"):  # all of the routing, before attention
        top_idx, weights, probs = route(p["router"], x.reshape(b * t, d), cfg.top_k)
        plan = (plan_share(top_idx, cfg.expert_offset, held, cfg.n_experts, SHARE_ROWS_SLACK)
                if held < cfg.n_experts else None)
    with jax.named_scope("attention"):
        x = _attention(p, x, cfg, kind)
    with jax.named_scope("moe"):
        h = common.rmsnorm(p["ln_mlp"], x, cfg.rms_eps).reshape(b * t, d)
        ex = p["experts"]
        y, *dispatch = share_glu_experts(
            h, top_idx, weights, ex["w_gate"], ex["w_up"], ex["w_down"],
            cfg.expert_offset, cfg.n_experts, act="relu", plan=plan, slack=SHARE_ROWS_SLACK,
        )
        x = x + y.reshape(b, t, d)
        stats, _ = moe.note_share(stats, top_idx, dispatch, cfg, probs=probs)
    return x, stats, top_idx


def _trunk(params: common.Params, tokens: jax.Array, cfg: SmallThinkerConfig):
    """Final hidden states [B, T, d], the routing statistics summed over the
    layers, and the layers' routes ``[L, S, k]`` in layer order."""
    x = params["wte"][tokens].astype(common.compute_dtype())

    def layer_of(kind: str, layers: int):
        def body(p, x, stats):
            return _layer(p, x, stats, cfg, kind)

        return common.remat_layer(body, layers) if cfg.remat else body

    global_layer = layer_of(GLOBAL, cfg.periods)
    sliding_layer = layer_of(SLIDING, cfg.periods * (cfg.period - 1))

    def sliding_step(carry, p):
        x, stats, top_idx = sliding_layer(p, *carry)
        return (x, stats), top_idx

    def period_step(carry, p):
        x, stats, first = global_layer(p[GLOBAL], *carry)
        carry, rest = jax.lax.scan(sliding_step, (x, stats), p[SLIDING])
        return carry, jnp.concatenate([first[None], rest])

    stats = moe.zero_share_stats(balanced=cfg.n_experts, act_zeros=True)
    (x, stats), routes = jax.lax.scan(period_step, (x, stats), params["blocks"])
    routes = routes.reshape(cfg.n_layers, tokens.size, cfg.top_k)
    return common.rmsnorm(params["ln_f"], x, cfg.rms_eps), stats, routes


def loss_and_routes(
    params: common.Params, batch: Dict[str, jax.Array], cfg: SmallThinkerConfig
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """(loss, metrics, the experts every layer chose ``[L, S, k]``); see
    ``models/olmoe.loss_and_routes`` for what the routes are for."""
    tokens = batch["tokens"]
    x, stats, routes = _trunk(params, tokens, cfg)
    lm = common.lm_xent_chunked(
        x, params["lm_head"], batch["targets"], chunk=cfg.xent_chunk, head_layout="dv"
    )
    aux = moe.balance_loss(stats, cfg.n_layers, cfg.n_experts)
    loss = lm + cfg.aux_coef * aux
    return loss, moe.share_metrics(loss, lm, aux, stats, tokens.size, cfg), routes


def spans(cfg: SmallThinkerConfig):
    """The span the train loop records of this step's routing."""
    return {"moe.route": moe.route_span(cfg, act_zeros=True)}
