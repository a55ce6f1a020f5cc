"""LFM2-24B-A2B (LiquidAI;
https://huggingface.co/LiquidAI/LFM2-24B-A2B ``config.json``, ``model_type``
``lfm2_moe``): a 40-layer decoder, d 2,048, in which 30 of the token mixers
are gated short convolutions and 10 are grouped-query attention; two leading
layers with a dense SwiGLU FFN of width 11,776, then 64 SwiGLU experts of
width 1,536 a layer, four chosen per token, no shared expert. 24 B parameters,
2 B of them at work on a token.

By layer, from the published list ``layer_types`` (``conv, conv,
(full_attention, conv, conv, conv) x 9, full_attention, conv``: read, not
derived from a period) and ``num_dense_layers``. RMSNorm eps 1e-5 everywhere,
no biases::

    n  = rmsnorm(x)                                     (operator_norm)
    conv layer:
      [B, C, u] = split3(W_in n)       W_in: 2048 -> 6144, three streams of 2048
      g   = B * u
      c_t = w[0] g_{t-2} + w[1] g_{t-1} + w[2] g_t     depthwise, causal, 3 taps
                                       (conv_L_cache 3), zeros before the start
      h   = x + W_out (C * c)
    full_attention layer:
      q = Wq n [32 x 64],  k = Wk n [8 x 64],  v = Wv n [8 x 64]
      q, k = rmsnorm over each head's 64 (learned scales of 64), BEFORE rotary
      rotary on all 64 coordinates, theta 1,000,000, half-split (rotate_half)
      a = causal softmax attention, query head i reads KV head i // 4, 1/sqrt(64)
      h = x + Wo a
    n2 = rmsnorm(h)                                     (ffn_norm)
    layer < num_dense_layers:  y = h + W2 (silu(W1 n2) * W3 n2)     width 11,776
    else:  s = sigmoid(Wr n2) over 64, float32 at the highest precision
           T = top4(s + b)        b: the layer's selection bias; used HERE ONLY
           w_e = s_e / (sum_T s + 1e-6)      (norm_topk_prob; routed_scaling_factor 1)
           y = h + sum_{e in T and held here} w_e W2_e (silu(W1_e n2) * W3_e n2)

Final RMSNorm, logits from the TIED embedding. Loss = mean cross-entropy over
the vocabulary (slice); there is no auxiliary loss (``aux_loss`` reads 0).

**The selection bias** (``use_expert_bias``) is a leaf of the parameters,
``blocks[r]["bias"]`` ``[layers of the run, 64]`` float32, zeros at
initialisation, so that a checkpoint, a state sync and a round carry it (a
round averages it like any other leaf: members' biases differ by a few
``gamma`` and their mean is what the group's routers then choose with). No
gradient reaches it (it enters the choice, which has none, and is wrapped in
``stop_gradient``), so an optimizer would leave it at zero for ever. The STEP
moves it, by the rule of auxiliary-loss-free balancing (arXiv:2408.15664; as
DeepSeek-V3's ``noaux_tc`` router is trained, arXiv:2412.19437 section 2.1.2):
with ``c_e`` the number of the step's ``S x 4`` assignments that chose expert
``e`` in the layer, ``b_e <- b_e + gamma sign(mean(c) - c_e)``, gamma 0.001.
``config.json`` carries the switch only; the rule and gamma are the family's
convention (the benchmark's configuration file says so under ``assumed``).
``stepped(cfg)`` hands the rule to the train step (``models/common.SteppedLeaves``),
which keeps the optimizer off these leaves.

The cut a chip makes without touching a width, as ``models/laguna.py``:
``layer_types`` with ``dense_layers`` (which of the published layers run),
``experts_held`` with ``expert_offset`` (``ops/moe_dispatch.share_glu_experts``
computes the held experts' part of the sum; what the others would add is left
out), ``vocab`` (a slice: embedding, tied head and loss over the slice). The
share's dispatch takes a chunk of the even share and a quarter
(``SHARE_ROWS_SLACK``: ``ops/moe_dispatch.SHARE_ROWS_SLACK_LEVELLED``, not the
dispatch's default of three even shares), because every expert layer built
here carries the stepped bias, which keeps the held experts near their even
share; a layer that is sent more runs another chunk, and the step's metrics
count those (``moe_chunks_extra``: 0 on a levelled step).

Layers of one kind that follow each other are one RUN: ``params["blocks"]`` is
a list of runs, each a tree stacked on a leading layer axis, and a run of
several layers is a ``lax.scan`` over one traced layer (the published model is
21 runs of 4 shapes; the benchmark's cut is a dense conv layer, an attention
expert layer and a scan over three conv expert layers). Every layer is
rematerialised by ``models/common.remat_layer`` as it stands: an attention
layer keeps its kernel's output and row statistics, a conv layer names nothing
and keeps nothing of its mixer (recomputing it is one projection and the
convolution's one pass). Departures as in ``models/olmoe.py``: float32
parameters and bfloat16 compute on a TPU, the router's product in float32 at
the highest precision, rotary angles in float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common, moe
from distributedvolunteercomputing_tpu.models.common import matrix, swiglu, swiglu_init
from distributedvolunteercomputing_tpu.ops.attention import (
    attention_core, merge_heads, rope, split_heads,
)
from distributedvolunteercomputing_tpu.ops.moe_dispatch import SHARE_ROWS_SLACK_LEVELLED, share_glu_experts
from distributedvolunteercomputing_tpu.ops.short_conv import short_conv

CONV, FULL = "conv", "full_attention"
DENSE, SPARSE = "dense", "sparse"
# what the weights' divisor adds to the chosen scores' sum (the public lfm2_moe code's)
ROUTE_EPS = 1e-6

# Rows a chunk of the share's dispatch holds over the share's even part: this
# model's routers are levelled by the bias the step moves, every one of them.
SHARE_ROWS_SLACK = SHARE_ROWS_SLACK_LEVELLED

PUBLISHED_LAYER_TYPES = (CONV, CONV) + (FULL, CONV, CONV, CONV) * 9 + (FULL, CONV)


@dataclasses.dataclass(frozen=True)
class LFM2Config:
    """Defaults are the published sizes of LFM2-24B-A2B."""

    vocab: int = 65536
    max_len: int = 8192  # the sequences a step trains on (published limit: 128,000 positions)
    d_model: int = 2048
    head_dim: int = 64
    n_heads: int = 32
    n_kv_heads: int = 8
    # one mixer kind a layer; a list, or "conv,full_attention,..." from a command line
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    dense_layers: int = 2     # leading layers with a dense FFN
    d_ff: int = 11776         # the dense FFN's width
    d_expert: int = 1536      # one routed expert's width
    n_experts: int = 64       # the router's outputs
    top_k: int = 4
    experts_held: int = 64    # how many of them this chip holds ...
    expert_offset: int = 0    # ... from which on
    conv_taps: int = 3        # conv_L_cache
    routed_scale: float = 1.0
    bias_gamma: float = 0.001  # what a step moves a selection bias by
    rms_eps: float = 1e-5
    rope_theta: float = 1000000.0
    remat: bool = True
    xent_chunk: int = 512

    def __post_init__(self):
        kinds = self.layer_types
        if isinstance(kinds, str):
            kinds = kinds.split(",")
        object.__setattr__(self, "layer_types", tuple(str(k).strip() for k in kinds))
        unknown = sorted(set(self.layer_types) - {CONV, FULL})
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types holds {unknown or 'nothing'}; known: {CONV}, {FULL}")
        moe.check_share(self)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.n_kv_heads} key/value heads do not divide {self.n_heads} query heads")
        if not 0 <= self.dense_layers <= self.n_layers:
            raise ValueError(f"dense_layers={self.dense_layers} of {self.n_layers} layers")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def ffn_kind(self, layer: int) -> str:
        return DENSE if layer < self.dense_layers else SPARSE

    @property
    def runs(self) -> Tuple[Tuple[str, str, int], ...]:
        """(mixer kind, FFN kind, layers) of each run of equal layers, in order."""
        out: List[List[Any]] = []
        for layer, mixer in enumerate(self.layer_types):
            kind = (mixer, self.ffn_kind(layer))
            if out and tuple(out[-1][:2]) == kind:
                out[-1][2] += 1
            else:
                out.append([*kind, 1])
        return tuple((m, f, n) for m, f, n in out)


def _layer_init(rng: jax.Array, cfg: LFM2Config, mixer: str, ffn: str) -> common.Params:
    k = jax.random.split(rng, 11)
    d, hd = cfg.d_model, cfg.head_dim
    p: common.Params = {"ln_mixer": common.rmsnorm_init(d), "ln_ffn": common.rmsnorm_init(d)}
    if mixer == CONV:
        p["conv"] = {"w_in": matrix(k[0], (d, 3 * d)), "taps": matrix(k[1], (cfg.conv_taps, d)),
                     "w_out": matrix(k[2], (d, d))}
    else:
        p.update({
            "wq": matrix(k[0], (d, cfg.n_heads * hd)),
            "wk": matrix(k[1], (d, cfg.n_kv_heads * hd)),
            "wv": matrix(k[2], (d, cfg.n_kv_heads * hd)),
            "wo": matrix(k[3], (cfg.n_heads * hd, d)),
            "q_norm": common.rmsnorm_init(hd), "k_norm": common.rmsnorm_init(hd),
        })
    if ffn == DENSE:
        p["mlp"] = swiglu_init(k[4:7], d, cfg.d_ff)
    else:
        p["router"] = matrix(k[7], (d, cfg.n_experts))
        p["bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)  # the step's, not the optimizer's
        # the held experts stacked on a leading axis -> sharded over ep (parallel/sharding.py)
        p["experts"] = swiglu_init(k[8:11], d, cfg.d_expert, (cfg.experts_held,))
    return p


@functools.partial(jax.jit, static_argnums=1)
def init(rng: jax.Array, cfg: LFM2Config) -> common.Params:
    """One program for the whole tree. A layer's key is its index's; run ``r``
    holds its layers stacked, in order."""
    keys = jax.random.split(rng, 2)
    layer_keys = jax.random.split(keys[1], cfg.n_layers)
    blocks, first = [], 0
    for mixer, ffn, n in cfg.runs:
        one = functools.partial(_layer_init, cfg=cfg, mixer=mixer, ffn=ffn)
        blocks.append(jax.vmap(one)(layer_keys[first:first + n]))
        first += n
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "blocks": blocks,
        "ln_f": common.rmsnorm_init(cfg.d_model),
    }


def _conv_mixer(p: common.Params, x: jax.Array, cfg: LFM2Config) -> jax.Array:
    dtype = x.dtype
    n = common.rmsnorm(p["ln_mixer"], x, cfg.rms_eps)
    bcu = n @ p["conv"]["w_in"].astype(dtype)                       # [B, T, 3 d]
    return x + short_conv(bcu, p["conv"]["taps"]) @ p["conv"]["w_out"].astype(dtype)


def _attention(p: common.Params, x: jax.Array, cfg: LFM2Config) -> jax.Array:
    dtype = x.dtype
    n = common.rmsnorm(p["ln_mixer"], x, cfg.rms_eps)
    q = split_heads(n @ p["wq"].astype(dtype), cfg.n_heads)
    k = split_heads(n @ p["wk"].astype(dtype), cfg.n_kv_heads)
    v = split_heads(n @ p["wv"].astype(dtype), cfg.n_kv_heads)
    # each head's own 64 coordinates normed, then turned
    q = rope(common.rmsnorm(p["q_norm"], q, cfg.rms_eps), base=cfg.rope_theta, layout="half")
    k = rope(common.rmsnorm(p["k_norm"], k, cfg.rms_eps), base=cfg.rope_theta, layout="half")
    a = attention_core(q, k, v, causal=True)
    return x + merge_heads(a) @ p["wo"].astype(dtype)


def _layer(p: common.Params, x: jax.Array, stats: Dict[str, jax.Array], cfg: LFM2Config,
           mixer: str, ffn: str):
    """One layer: (x, running routing statistics) -> the same, and for an
    expert layer its routes ``top_idx`` [S, k] and how many assignments chose
    each expert ``[E]`` (None for a dense layer)."""
    b, t, d = x.shape
    if mixer == CONV:
        with jax.named_scope("conv_mixer"):
            x = _conv_mixer(p, x, cfg)
    else:
        with jax.named_scope("attention"):
            x = _attention(p, x, cfg)
    h = common.rmsnorm(p["ln_ffn"], x, cfg.rms_eps)
    if ffn == DENSE:
        with jax.named_scope("mlp"):
            return x + swiglu(p["mlp"], h), stats, None
    with jax.named_scope("moe"):
        h = h.reshape(b * t, d)
        top_idx, weights, _ = moe.route(p["router"], h, cfg.top_k, cfg.routed_scale, p["bias"], ROUTE_EPS)
        ex = p["experts"]
        y, *dispatch = share_glu_experts(
            h, top_idx, weights, ex["w_gate"], ex["w_up"], ex["w_down"],
            cfg.expert_offset, cfg.n_experts, slack=SHARE_ROWS_SLACK,
        )
        x = x + y.reshape(b, t, d)
        stats, chosen = moe.note_share(stats, top_idx, dispatch, cfg, SHARE_ROWS_SLACK)
    return x, stats, (top_idx, chosen)


def _trunk(params: common.Params, tokens: jax.Array, cfg: LFM2Config):
    """Final hidden states [B, T, d], the routing statistics summed over the
    expert layers, those layers' routes ``[L_sparse, S, k]`` and their
    experts' assignment counts ``[L_sparse, E]``."""
    x = params["wte"][tokens].astype(common.compute_dtype())
    runs = [(functools.partial(_layer, cfg=cfg, mixer=mixer, ffn=ffn), n, ffn == SPARSE)
            for mixer, ffn, n in cfg.runs]
    x, stats, routes, counts = moe.run_layers(
        runs, params["blocks"], x, moe.zero_share_stats(chunks_extra=True), cfg.remat, tokens.size, cfg)
    return common.rmsnorm(params["ln_f"], x, cfg.rms_eps), stats, routes, counts


def loss_and_routes(
    params: common.Params, batch: Dict[str, jax.Array], cfg: LFM2Config
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """(loss, metrics, the experts every expert layer chose ``[L_sparse, S, k]``);
    see ``models/olmoe.loss_and_routes`` for what the routes are for."""
    tokens = batch["tokens"]
    x, stats, routes, counts = _trunk(params, tokens, cfg)
    loss = common.lm_xent_chunked(
        x, params["wte"], batch["targets"], chunk=cfg.xent_chunk, head_layout="vd"
    )
    metrics = moe.share_metrics(
        loss, loss, jnp.zeros((), jnp.float32), stats, tokens.size, cfg, params, counts)
    return loss, metrics, routes


def stepped(cfg: LFM2Config):
    """What the train step needs to move the selection biases itself."""
    return moe.stepped(cfg.bias_gamma)


def spans(cfg: LFM2Config):
    """The spans the train loop records of this step."""
    return {"moe.route": moe.route_span(cfg, chunks_extra=True, stepped_bias=True)}
