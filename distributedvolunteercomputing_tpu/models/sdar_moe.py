"""SDAR-30B-A3B-Chat (JetLM; https://huggingface.co/JetLM/SDAR-30B-A3B-Chat
``config.json``, ``model_type`` ``sdar_moe``; the training recipe is
arXiv:2510.06303, after the block-diffusion objective of BD3-LMs,
arXiv:2503.09573): a Qwen3-MoE decoder, 48 layers, d 2,048, converted from an
autoregressive checkpoint into a model that generates by diffusion over blocks
of tokens. 30.5 B parameters, 3.3 B of them at work on a token.

One layer, the Qwen3-MoE layer (RMSNorm eps 1e-6, no biases)::

    n  = rmsnorm(x)
    q  = Wq n  [32 x 128],  k = Wk n  [4 x 128],  v = Wv n  [4 x 128]
    q, k each normed PER HEAD: an RMSNorm over the 128 lanes of a head, one
       weight vector of 128 for every head, before the rotary turn
    q, k turned: rotary on all 128 coordinates, half-split (``rotate_half``),
       theta 1e6, angles in float32, by the row's POSITION (below)
    h  = x + Wo attn(q, k, v)              query head h reads KV head h // 8,
                                           scale 1/sqrt(128), the mask below
    p  = softmax_128(Wr rmsnorm(h))        float32
    y  = h + sum_{e in top8(p) and held here} (p_e / sum_top8 p) *
             Wdown_e(silu(Wgate_e n2) * (Wup_e n2))         (``norm_topk_prob``)

No shared expert. Final RMSNorm, untied head.

The objective, for one sequence ``x_0`` of L tokens in ``nb = L / bd`` blocks
of ``bd`` (``block_length``):

- noise (``noise``, drawn inside the compiled step from the rng the step hands
  the loss): a rate ``t_b ~ U(eps_t, 1)`` a block, each token of the block
  masked with probability ``t_b`` independently (``m_i``); ``x_t`` holds
  ``mask_id`` where ``m_i = 1`` and ``x_0`` elsewhere.
- rows: the model runs ``[x_0 ; x_t]``, the CLEAN copy first and the noised copy
  after it, as 2L rows with positions ``0..L-1`` twice. Query row i sees key
  row j (``blk`` the block of a row's position): clean -> clean iff
  ``blk(j) <= blk(i)``; noised -> clean iff ``blk(j) < blk(i)``; noised ->
  noised iff ``blk(j) == blk(i)``; clean -> noised never
  (``ops/pallas_attention.bd_keep``). Kept pairs a head a sequence:
  ``L^2 + L bd`` (a causal mask over the 2L rows keeps ``2 L^2 + L``).
- loss: ``(1 / (B L)) sum_i m_i (1 / t_blk(i)) nll_i`` with ``nll_i`` the
  cross-entropy of the NOISED half's row i against ``x_0[i]``, the same
  position, no shift (the linear schedule ``alpha_t = 1 - t``, whose weight is
  ``1 / t``); the head's products run over the noised half's L rows only. Plus
  ``aux_coef`` x load balancing, ``E sum_e f_e P_e`` as ``models/olmoe.py``
  computes it, over all 2L rows of all layers. The batch's ``targets`` (the
  data path's shifted tokens) go unused.

Assumed where ``config.json`` is silent (the benchmark's configuration file
gives each reason): ``block_length`` 4, the schedule and ``eps_t`` 1e-3, the
mask id, per-head QK-norm, the load-balancing coefficient 0.001.

The cut a chip makes without touching a width, as ``models/laguna.py``:
``n_layers``, ``experts_held`` with ``expert_offset`` (the router keeps its
128 outputs and its top-8; ``ops/moe_dispatch.share_glu_experts`` computes the
held experts' part of the sum), ``vocab`` with ``mask_id`` inside it.

The layers are equal: one stacked tree, one scanned and rematerialised body
(``moe.run_layers``). Departures as in ``models/olmoe.py``: float32 parameters
and bfloat16 compute on a TPU, the router's product in float32 at the highest
precision, rotary angles in float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common, moe
from distributedvolunteercomputing_tpu.models.common import matrix, swiglu_init
from distributedvolunteercomputing_tpu.ops import attention as attention_ops
from distributedvolunteercomputing_tpu.ops.attention import Rotary, attention_merged
from distributedvolunteercomputing_tpu.ops.moe_dispatch import share_glu_experts


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    """Defaults are the published sizes of SDAR-30B-A3B-Chat."""

    vocab: int = 151936
    max_len: int = 4096       # the DATA tokens a sequence trains on; the layers run twice as many rows
    d_model: int = 2048
    head_dim: int = 128
    n_heads: int = 32
    n_kv_heads: int = 4
    n_layers: int = 48
    d_expert: int = 768       # ``moe_intermediate_size``: one expert's width
    n_experts: int = 128      # the router's outputs
    top_k: int = 8
    experts_held: int = 128   # how many of them this chip holds ...
    expert_offset: int = 0    # ... from which on
    rms_eps: float = 1e-6
    rope_theta: float = 1000000.0
    block_length: int = 4     # ``bd``: tokens a block
    eps_t: float = 1e-3       # the least masking rate a block draws
    mask_id: int = 151669     # the published ``mask_token_id``; a vocabulary slice names one inside it
    aux_coef: float = 0.001
    remat: bool = True
    xent_chunk: int = 512

    def __post_init__(self):
        moe.check_share(self)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_kv_heads} key/value heads do not divide {self.n_heads} query heads")
        if self.block_length < 1 or self.max_len % self.block_length:
            raise ValueError(f"blocks of {self.block_length} do not divide {self.max_len} tokens")
        if not 0 <= self.mask_id < self.vocab:
            raise ValueError(f"mask_id={self.mask_id} is not one of the {self.vocab} ids")
        if not 0.0 < self.eps_t < 1.0:
            raise ValueError(f"eps_t={self.eps_t} is not a rate")


def _layer_init(rng: jax.Array, cfg: SdarMoeConfig) -> common.Params:
    k = jax.random.split(rng, 8)
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "ln_attn": common.rmsnorm_init(d),
        "wq": matrix(k[0], (d, cfg.n_heads * hd)),
        "wk": matrix(k[1], (d, cfg.n_kv_heads * hd)),
        "wv": matrix(k[2], (d, cfg.n_kv_heads * hd)),
        "wo": matrix(k[3], (cfg.n_heads * hd, d)),
        "q_norm": common.rmsnorm_init(hd),
        "k_norm": common.rmsnorm_init(hd),
        "ln_mlp": common.rmsnorm_init(d),
        "router": matrix(k[4], (d, cfg.n_experts)),
        # the held experts stacked on a leading axis -> sharded over ep (parallel/sharding.py)
        "experts": swiglu_init(k, d, cfg.d_expert, (cfg.experts_held,), first=5),
    }


@functools.partial(jax.jit, static_argnums=1)
def init(rng: jax.Array, cfg: SdarMoeConfig) -> common.Params:
    """One program for the whole tree; ``blocks`` is a list of one run, the
    equal layers stacked (``moe.run_layers``)."""
    keys = jax.random.split(rng, 3)
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "blocks": [common.stacked_init(lambda k: _layer_init(k, cfg), keys[1], cfg.n_layers)],
        "ln_f": common.rmsnorm_init(cfg.d_model),
        "lm_head": matrix(keys[2], (cfg.d_model, cfg.vocab)),
    }


def noise(key: jax.Array, b: int, l: int, bd: int, eps_t: float) -> Tuple[jax.Array, jax.Array]:
    """The step's noise, a pure function of ``(key, B, L, bd)``: ``(masked
    [B, L] bool, rate [B, L] float32: the t of a token's own block)``. Exactly,
    and in this order (the plain reference repeats it from the same key)::

        k_rate, k_mask = jax.random.split(key)
        t = eps_t + (1 - eps_t) * jax.random.uniform(k_rate, (B, L // bd), float32)
        u = jax.random.uniform(k_mask, (B, L), float32)
        rate = jnp.repeat(t, bd, axis=1);  masked = u < rate
    """
    k_rate, k_mask = jax.random.split(key)
    t = eps_t + (1.0 - eps_t) * jax.random.uniform(k_rate, (b, l // bd), jnp.float32)
    u = jax.random.uniform(k_mask, (b, l), jnp.float32)
    rate = jnp.repeat(t, bd, axis=1)
    return u < rate, rate


def head_rmsnorm(g: jax.Array, x: jax.Array, heads: int, eps: float) -> jax.Array:
    """RMSNorm over each head's own lanes of ``x`` [B, T, H * D], weights ``g``
    [D] shared by the heads, without leaving the projection's layout (on the
    chip a reshape to [B, T, H, D] re-tiles the whole array, ``models/laguna``'s
    gate): a head's mean square is a 0/1 product [H * D, H] of the squares, its
    ``rsqrt`` comes back to the head's lanes through the same 0/1 matrix, float32
    as a high and a low bfloat16 part (two exact one-term products)."""
    dtype = x.dtype
    d = x.shape[-1] // heads
    spread = jnp.repeat(jnp.eye(heads, dtype=dtype), d, axis=1)  # [H, H * D]
    high = jax.lax.Precision.HIGHEST
    mean_sq = jnp.einsum("btl,nl->btn", x * x, spread, precision=high,
                         preferred_element_type=jnp.float32) / d
    r = jax.lax.rsqrt(mean_sq + eps)  # [B, T, H] float32
    if dtype == jnp.float32:
        scale = jnp.dot(r, spread, precision=high)
    else:
        hi = r.astype(dtype)
        lo = (r - hi.astype(jnp.float32)).astype(dtype)
        scale = (jnp.dot(hi, spread, preferred_element_type=jnp.float32)
                 + jnp.dot(lo, spread, preferred_element_type=jnp.float32))
    return (x.astype(jnp.float32) * scale * jnp.tile(g, heads)).astype(dtype)


def _attention(p: common.Params, x: jax.Array, cfg: SdarMoeConfig) -> jax.Array:
    dtype = x.dtype
    n = common.rmsnorm(p["ln_attn"], x, cfg.rms_eps)
    q = head_rmsnorm(p["q_norm"]["g"], n @ p["wq"].astype(dtype), cfg.n_heads, cfg.rms_eps)
    k = head_rmsnorm(p["k_norm"]["g"], n @ p["wk"].astype(dtype), cfg.n_kv_heads, cfg.rms_eps)
    half = x.shape[1] // 2
    positions = jnp.tile(jnp.arange(half), 2)  # [x_0 ; x_t]: 0..L-1 twice
    a = attention_merged(  # q, k and v as the projections leave them; [B, 2L, H * 128] back
        q, k, n @ p["wv"].astype(dtype), cfg.n_heads, cfg.n_kv_heads,
        rotary=Rotary(base=cfg.rope_theta, layout="half", positions=positions),
        block_diffusion=cfg.block_length,
    )
    return x + a @ p["wo"].astype(dtype)


def _layer(p: common.Params, x: jax.Array, stats: Dict[str, jax.Array], cfg: SdarMoeConfig):
    """One layer: (x [B, 2L, d], running routing statistics) -> the same, the
    layer's routes ``top_idx`` [S, k] and its experts' assignment counts [E]."""
    b, t, d = x.shape
    with jax.named_scope("attention"):
        x = _attention(p, x, cfg)
    with jax.named_scope("moe"):
        h = common.rmsnorm(p["ln_mlp"], x, cfg.rms_eps).reshape(b * t, d)
        top_idx, weights, probs = moe.route(p["router"], h, cfg.top_k, 1.0, score="softmax")
        ex = p["experts"]
        y, *dispatch = share_glu_experts(
            h, top_idx, weights, ex["w_gate"], ex["w_up"], ex["w_down"],
            cfg.expert_offset, cfg.n_experts,
        )
        x = x + y.reshape(b, t, d)
        stats, chosen = moe.note_share(stats, top_idx, dispatch, cfg, probs=probs)
    return x, stats, (top_idx, chosen)


def visited_share(cfg: SdarMoeConfig, x: jax.Array) -> float:
    """Of the (query, key) tiles a causal mask over the layers' 2L rows would
    make the attention kernels' loops visit, the share they visit under the
    block-diffusion mask (``pallas_attention.bd_tiles``, forward and backward
    together); where the call does not take the kernels, the XLA core's every
    pair over the causal pairs. From the shapes, at trace time."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    b, t, _ = x.shape
    hd = cfg.head_dim
    shapes = [jax.ShapeDtypeStruct((b, t, n * hd), x.dtype) for n in (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads)]
    rotary = Rotary(base=cfg.rope_theta, layout="half")
    if not attention_ops.merged_in_place(
            *shapes, cfg.n_heads, cfg.n_kv_heads, False, None, rotary, cfg.block_length):
        return t * t / (t * (t + 1) / 2)
    tiles = pa.bd_tiles(t, cfg.block_length, *pa.choose_blocks(t, t, hd, x.dtype, None, True, cfg.block_length))
    return (tiles["fwd"] + tiles["bwd"]) / (tiles["causal_fwd"] + tiles["causal_bwd"])


def rows(clean: jax.Array, rng: jax.Array, cfg: SdarMoeConfig):
    """The rows a step's sequences ``clean`` (x_0 [B, L]) run as: (tokens
    ``[x_0 ; x_t]`` [B, 2L], weights ``m_i / t_blk(i)`` [B, L] float32, masked
    ``m`` [B, L] bool), the noise drawn by ``noise`` from ``rng``."""
    b, l = clean.shape
    masked, rate = noise(rng, b, l, cfg.block_length, cfg.eps_t)
    noised = jnp.where(masked, cfg.mask_id, clean)  # x_t
    return jnp.concatenate([clean, noised], axis=1), masked.astype(jnp.float32) / rate, masked


def trunk(params: common.Params, tokens: jax.Array, cfg: SdarMoeConfig):
    """Hidden states [B, 2L, d] after the last layer (before the final norm)
    of the rows ``tokens`` [B, 2L], the routing statistics summed over the
    layers and the layers' routes ``[L, S, k]``."""
    x = params["wte"][tokens].astype(common.compute_dtype())
    runs = [(functools.partial(_layer, cfg=cfg), cfg.n_layers, True)]
    x, stats, routes, _ = moe.run_layers(
        runs, params["blocks"], x, moe.zero_share_stats(balanced=cfg.n_experts, chunks_extra=True), cfg.remat,
        tokens.size, cfg)
    return x, stats, routes


def loss_and_routes(
    params: common.Params, batch: Dict[str, jax.Array], rng: jax.Array, cfg: SdarMoeConfig
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """(loss, metrics, the experts every layer chose ``[L, S, k]``, S the 2 B L
    rows); see ``models/olmoe.loss_and_routes`` for what the routes are for."""
    clean = batch["tokens"]  # x_0 [B, L]
    b, l = clean.shape
    with jax.named_scope("noising"):
        tokens, weights, masked = rows(clean, rng, cfg)
    x, stats, routes = trunk(params, tokens, cfg)
    share = visited_share(cfg, x)
    x = common.rmsnorm(params["ln_f"], x[:, l:], cfg.rms_eps)  # the noised half: the head runs L rows of the 2L
    lm = common.lm_xent_chunked(
        x, params["lm_head"], clean, mask=weights, chunk=cfg.xent_chunk, head_layout="dv",
        denominator=float(b * l),
    )
    aux = moe.balance_loss(stats, cfg.n_layers, cfg.n_experts)
    loss = lm + cfg.aux_coef * aux
    metrics = moe.share_metrics(loss, lm, aux, stats, tokens.size, cfg)
    metrics.update({
        # of the batch's tokens, the share the step's noise masked (the mean rate is about a half)
        "diffusion_masked_share": jnp.mean(masked.astype(jnp.float32)),
        # rows the head's products ran over the rows the layers ran
        "diffusion_head_rows_share": jnp.asarray(x.shape[1] / tokens.shape[1], jnp.float32),
        # tiles the attention kernels' loops visit over what a causal mask over the same rows would
        "attention_bd_tiles_share": jnp.asarray(share, jnp.float32),
    })
    return loss, metrics, routes


def loss_fn(params: common.Params, batch: Dict[str, jax.Array], rng: jax.Array, cfg: SdarMoeConfig):
    """The bundle's loss: this family draws its noise from the step's rng."""
    return loss_and_routes(params, batch, rng, cfg)[:2]


def spans(cfg: SdarMoeConfig):
    """The span the train loop records of this step's routing, with what the block-diffusion objective adds."""
    return {"moe.route": moe.route_span(cfg, chunks_extra=True, more=(
        "diffusion_masked_share", "diffusion_head_rows_share", "attention_bd_tiles_share"))}
