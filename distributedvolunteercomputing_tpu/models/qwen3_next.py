"""Qwen3-Next-80B-A3B-Instruct (Qwen;
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``,
``model_type`` ``qwen3_next``): a 48-layer decoder, d 2,048, whose token mixers
are a Gated DeltaNet (linear attention with a state decayed by ONE number a head
and corrected by a delta rule; 36 layers) and, every fourth layer, output-gated
softmax attention at a head of 256 over 2 key/value heads (12 layers:
``full_attention_interval`` 4, layer i from 0 is full attention where ``(i + 1) %
4 == 0``). Every layer has 512 SwiGLU experts of width 512, ten chosen per token
by a plain softmax router, beside one shared expert scaled by a learnt scalar a
token. 80 B parameters, 3 B of them at work on a token.

By layer. Pre-norm residual blocks, no bias anywhere; ``N(x) = x / sqrt(mean(x^2)
+ 1e-6) * (1 + w)`` with ``w`` zeros at initialisation (the family's zero-centred
norm, float32 inside): the two layer norms, the final norm, the q and k head
norms::

    n  = N1(x)
    Gated DeltaNet (16 key heads and 32 value heads of 128):
        [q | k | v | z] = n W_qkvz              W_qkvz: 2048 -> 2048 + 2048 + 4096 + 4096
        [b | a] = n W_ba                        W_ba: 2048 -> 32 + 32, one of each a value head
        [q | k | v] = silu(conv4([q | k | v]))  causal, depthwise, 4 taps a channel over the 8,192 channels, no bias
        beta = sigmoid(b);   g = -exp(A_log) softplus(a + dt_bias)       ONE log decay a value head a token, float32
        q = l2norm_head(q) / sqrt(128),  k = l2norm_head(k)              value head j reads key head j // 2
        S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t      S: [128, 128] a value head
        y = (rmsnorm_128(o) * w_o * silu(z)) W_out     w_o: ones at initialisation, one weight of 128 for every head
    gated attention (16 query heads over 2 key/value heads of 256):
        [q | gate] a head = n W_q               W_q: 2048 -> 16 x (256 + 256)
        k = n W_k,  v = n W_v                   2048 -> 2 x 256 each
        q = N_256(q),  k = N_256(k)             over each head's 256, one weight each
        rotary on the first 64 of a head's 256 (``partial_rotary_factor`` 0.25), half-split pairs, theta 1e7
        a = causal softmax(q k^T / 16) v;   y = (a * sigmoid(gate)) W_o
    h  = x + y;  n2 = N2(h)
    s  = softmax(n2 W_r) over all 512, float32 at the highest precision;  T = top10(s)
    w_e = s_e / sum_T s                         (``norm_topk_prob``), no scaling factor
    out = h + sigmoid(n2 w_sg) shared(n2) + sum_{e in T and held here} w_e expert_e(n2)

Final norm, an untied head. Loss = mean next-token cross-entropy over the
vocabulary (slice) + ``aux_coef`` x load balancing, ``E sum_e f_e P_e`` as
``models/olmoe.py`` computes it. The published multi-token-prediction module is
left out (the config carries no key for it; ROADMAP R5 (c)).

The delta rule runs as ``ops/gdn.py``'s chunked scan with its own backward
(chunks of 64: the scalar-decay form; q and k stay at 16 heads and a chunk's ``q
k^T`` and ``k k^T`` are made once a key head), fed the convolution's ONE [B, T,
8192] array (``ops/short_conv.causal_conv`` over all 8,192 channels as one
stream: 48.8 of the kernel's 64 MB of VMEM at its block of 256 positions, where
Kimi-Linear's 12,288 did not fit; the kernel wants a bias and is handed constant
zeros that are no leaf), and its scan reads q's, k's and v's rows of a chunk out
of that array in place. q and z come off their own columns of ``W_qkvz`` by two
products (a slice of the WEIGHT, never of a stream). The attention layer's query
and gate are two products off ``W_q``'s columns by head, so both are [B, T, 4096]
in the kernels' own layout; q's and k's head norms stay in that layout
(``models/sdar_moe.head_rmsnorm``) and their rotary pairs are turned beside the
kernels by ``pallas_attention.rotary_merged`` as ``models/glm4_moe_lite.py`` does
(the tables of a turn on the kernel's tile do not fit VMEM at a head of 256 and
8,192 tokens), so ``attention_merged`` is given no rotary. The held experts run
through ``ops/moe_dispatch.share_glu_experts`` at the dispatch's default chunk.

**The decay's leaves have their own initialisation**, the family's public
kernels': ``A_log`` the log of a uniform draw in (0, 16) a value head,
``dt_bias`` the inverse softplus of a ``dt`` drawn log-uniform in [1e-3, 1e-1] a
value head, the convolution's taps uniform in +-1/sqrt(taps). With normal(0,
0.02) (or a ``dt_bias`` of ones) every head forgets within a chunk and a check on
the initial parameters cannot see the recurrence (``models/kimi_linear.py``).

The cut a chip makes without touching a width: ``n_layers`` (whole periods of
four), ``experts_held`` with ``expert_offset``, ``vocab``. All layers of a kind
have one parameter shape, so the model is scanned by period as
``models/smallthinker.py``: ``params["blocks"] = {"linear": [P, 3, ...], "full":
[P, ...]}``, a ``lax.scan`` over the periods whose body runs an inner scan over
the three delta layers and then the attention layer, each layer rematerialised
(``models/common.remat_layer``). Departures as in ``models/olmoe.py``: float32
parameters and bfloat16 compute on a TPU, the router's product, the decay and
``beta`` in float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common, moe
from distributedvolunteercomputing_tpu.models.common import matrix, swiglu, swiglu_init
from distributedvolunteercomputing_tpu.models.sdar_moe import head_rmsnorm
from distributedvolunteercomputing_tpu.ops import gdn
from distributedvolunteercomputing_tpu.ops.attention import (
    attention_merged, chips_in_step, merge_heads, merged_in_place, rope, split_heads)
from distributedvolunteercomputing_tpu.ops.moe_dispatch import share_glu_experts
from distributedvolunteercomputing_tpu.ops.short_conv import causal_conv

LINEAR, FULL = "linear", "full"
# the decay's initialisation (not in the published config; the configuration file's ``assumed.gdn_init``):
# ``exp(A_log)`` uniform in (0, A_MAX), ``dt_bias`` the inverse softplus of a dt log-uniform in [DT_MIN, DT_MAX]
A_MIN, A_MAX, DT_MIN, DT_MAX = 1e-6, 16.0, 1e-3, 1e-1
# the sizes of the CPU tests: every mechanism at widths a laptop traces in seconds
TINY = dict(
    vocab=512, max_len=40, d_model=64, n_layers=4, head_dim=16, n_heads=4, n_kv_heads=2, key_heads=2,
    value_heads=4, key_head_dim=16, value_head_dim=8, chunk=16, d_expert=32, d_shared=32, n_experts=16,
    top_k=4, experts_held=4, expert_offset=4, xent_chunk=32,
)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """Defaults are the published sizes of Qwen3-Next-80B-A3B-Instruct."""

    vocab: int = 151936
    max_len: int = 8192       # the sequences a step trains on (published limit: 262,144 positions)
    d_model: int = 2048
    n_layers: int = 48        # how many of the published layers run, from the first: whole periods
    period: int = 4           # full_attention_interval: layer i is full attention where (i + 1) % period == 0
    head_dim: int = 256       # gated attention
    n_heads: int = 16
    n_kv_heads: int = 2
    partial_rotary: float = 0.25   # partial_rotary_factor: the share of a head's coordinates that are turned
    rope_theta: float = 10000000.0
    key_heads: int = 16       # linear_num_key_heads
    value_heads: int = 32     # linear_num_value_heads
    key_head_dim: int = 128   # linear_key_head_dim
    value_head_dim: int = 128  # linear_value_head_dim
    conv_taps: int = 4        # linear_conv_kernel_dim
    chunk: int = 64
    d_expert: int = 512       # moe_intermediate_size: one routed expert's width
    d_shared: int = 512       # shared_expert_intermediate_size
    n_experts: int = 512      # the router's outputs
    top_k: int = 10
    experts_held: int = 512   # how many of them this chip holds ...
    expert_offset: int = 0    # ... from which on
    rms_eps: float = 1e-6
    aux_coef: float = 0.001
    remat: bool = True
    xent_chunk: int = 512

    def __post_init__(self):
        moe.check_share(self)
        if self.n_heads % self.n_kv_heads or self.value_heads % self.key_heads:
            raise ValueError(
                f"{self.n_kv_heads} key/value heads do not divide {self.n_heads} query heads, or "
                f"{self.key_heads} key heads {self.value_heads} value heads")
        if self.period < 2 or self.n_layers < 1 or self.n_layers % self.period:
            raise ValueError(f"n_layers={self.n_layers} is not whole periods of {self.period} layers")
        if self.chunk & (self.chunk - 1) or self.rotary_dim % 2:
            raise ValueError(f"chunk={self.chunk} is no power of two, or rotary_dim={self.rotary_dim} is odd")

    @classmethod
    def tiny(cls) -> "Qwen3NextConfig":
        return cls(**TINY)

    @property
    def periods(self) -> int:
        return self.n_layers // self.period

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary)

    @property
    def key_dim(self) -> int:
        return self.key_heads * self.key_head_dim

    @property
    def value_dim(self) -> int:
        return self.value_heads * self.value_head_dim

    @property
    def conv_dim(self) -> int:
        """The channels the convolution runs: q's, k's and v's, side by side."""
        return 2 * self.key_dim + self.value_dim

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """One mixer kind a layer that runs."""
        return tuple(FULL if (i + 1) % self.period == 0 else LINEAR for i in range(self.n_layers))


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def norm_init(d: int) -> common.Params:
    """The zero-centred norm's weight: the scale is ``1 + w``."""
    return {"w": jnp.zeros((d,), jnp.float32)}


def norm(p: common.Params, x: jax.Array, eps: float) -> jax.Array:
    return common.rmsnorm({"g": 1.0 + p["w"]}, x, eps)


def _delta_init(k, cfg: Qwen3NextConfig) -> common.Params:
    d, hv = cfg.d_model, cfg.value_heads
    lo, hi = jnp.log(DT_MIN), jnp.log(DT_MAX)
    dt = jnp.exp(jax.random.uniform(k[3], (hv,), jnp.float32) * (hi - lo) + lo)
    bound = cfg.conv_taps ** -0.5   # a depthwise convolution's fan-in is its taps
    return {
        "w_qkvz": matrix(k[0], (d, cfg.conv_dim + cfg.value_dim)),
        "w_ba": matrix(k[1], (d, 2 * hv)),
        "conv_w": jax.random.uniform(k[2], (cfg.conv_taps, cfg.conv_dim), jnp.float32, -bound, bound),
        "a_log": jnp.log(jax.random.uniform(k[4], (hv,), jnp.float32, A_MIN, A_MAX)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
        "o_norm": common.rmsnorm_init(cfg.value_head_dim),
        "wo": matrix(k[5], (cfg.value_dim, d)),
    }


def _attention_init(k, cfg: Qwen3NextConfig) -> common.Params:
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "wq": matrix(k[0], (d, cfg.n_heads * 2 * hd)),       # a head's query and its gate, side by side
        "wk": matrix(k[1], (d, cfg.n_kv_heads * hd)),
        "wv": matrix(k[2], (d, cfg.n_kv_heads * hd)),
        "q_norm": norm_init(hd), "k_norm": norm_init(hd),
        "wo": matrix(k[3], (cfg.n_heads * hd, d)),
    }


def _layer_init(rng: jax.Array, cfg: Qwen3NextConfig, kind: str) -> common.Params:
    k = jax.random.split(rng, 14)
    d = cfg.d_model
    return {
        "ln_mixer": norm_init(d), "ln_ffn": norm_init(d),
        "mixer": _delta_init(k[:6], cfg) if kind == LINEAR else _attention_init(k[:6], cfg),
        "router": matrix(k[6], (d, cfg.n_experts)),
        "shared": swiglu_init(k, d, cfg.d_shared, first=7),
        "shared_gate": matrix(k[10], (d, 1)),
        # the held experts stacked on a leading axis -> sharded over ep (parallel/sharding.py)
        "experts": swiglu_init(k, d, cfg.d_expert, (cfg.experts_held,), first=11),
    }


@functools.partial(jax.jit, static_argnums=1)
def init(rng: jax.Array, cfg: Qwen3NextConfig) -> common.Params:
    """One program for the whole tree. A layer's key is its index's, so layer
    ``i`` is ``blocks["linear"][i // 4, i % 4]`` or ``blocks["full"][i // 4]``."""
    keys = jax.random.split(rng, 3)
    layer_keys = jax.random.split(keys[1], cfg.n_layers).reshape(cfg.periods, cfg.period, -1)
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "blocks": {
            LINEAR: jax.vmap(jax.vmap(functools.partial(_layer_init, cfg=cfg, kind=LINEAR)))(layer_keys[:, :-1]),
            FULL: jax.vmap(functools.partial(_layer_init, cfg=cfg, kind=FULL))(layer_keys[:, -1])},
        "ln_f": norm_init(cfg.d_model),
        "lm_head": matrix(keys[2], (cfg.d_model, cfg.vocab)),
    }


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------


def _delta(p: common.Params, n: jax.Array, cfg: Qwen3NextConfig):
    """The Gated DeltaNet on the normed stream ``n`` [B, T, d]: (its output [B,
    T, d], what its scan says of itself: ``ops/gdn.scan_counters`` and the mean ``beta``)."""
    dtype = n.dtype
    hv, conv = cfg.value_heads, cfg.conv_dim
    w = p["w_qkvz"].astype(dtype)
    # q, k and v through the convolution as ONE stream of 8,192 channels, which the scan reads in place; z beside it
    # from its own columns (a slice of the weight, never of a stream); the bias is constant zeros, no leaf
    qkv = causal_conv(n @ w[:, :conv], p["conv_w"], jnp.zeros((conv,), jnp.float32))
    z = n @ w[:, conv:]
    ba = (n @ p["w_ba"].astype(dtype)).astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])     # one log decay a value head
    o, sums = gdn.gdn_with_sums(qkv, g, beta, cfg.key_heads, hv, cfg.key_head_dim, cfg.chunk)
    # each value head's norm on its own 128 lanes of [B, T, 4096] (no array by head), then the gate
    y = head_rmsnorm(p["o_norm"]["g"], o, hv, cfg.rms_eps).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    noted = {**gdn.scan_counters(sums), "beta_mean": jnp.mean(beta)}
    return y.astype(dtype) @ p["wo"].astype(dtype), noted


def qkv_gate(p: common.Params, n: jax.Array, cfg: Qwen3NextConfig):
    """The attention layer's products of the normed stream ``n`` [B, T, d]: q
    and its output gate [B, T, H * 256], k and v [B, T, 2 * 256], each made
    where ``attention_merged`` reads it; q and k normed over each head's own
    lanes and their first ``rotary_dim`` coordinates turned (half-split pairs),
    beside the kernels where the call that follows takes them on one chip."""
    dtype = n.dtype
    d, t = cfg.d_model, n.shape[1]
    h, kv, hd, rot = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rotary_dim
    # a head's columns of W_q are [query | gate]: both taken of the WEIGHT by head, so each product is in the
    # kernels' own layout
    wq = p["wq"].astype(dtype).reshape(d, h, 2, hd)
    q = head_rmsnorm(1.0 + p["q_norm"]["w"], n @ wq[:, :, 0].reshape(d, h * hd), h, cfg.rms_eps)
    gate = n @ wq[:, :, 1].reshape(d, h * hd)
    k = head_rmsnorm(1.0 + p["k_norm"]["w"], n @ p["wk"].astype(dtype), kv, cfg.rms_eps)
    v = n @ p["wv"].astype(dtype)
    if merged_in_place(q, k, v, h, kv, True, None, None) and chips_in_step() == 1:
        from distributedvolunteercomputing_tpu.ops import pallas_attention

        cos, sin = pallas_attention.rotary_tables(t, hd, cfg.rope_theta, rot)
        return (pallas_attention.rotary_merged(q, cos, sin, rot), gate,
                pallas_attention.rotary_merged(k, cos, sin, rot), v)
    turn = lambda a, heads: merge_heads(rope(split_heads(a, heads), base=cfg.rope_theta, layout="half", rotary_dim=rot))
    return turn(q, h), gate, turn(k, kv), v


def _attention(p: common.Params, n: jax.Array, cfg: Qwen3NextConfig):
    """(The mixer's output [B, T, d], the mean of its output gate.)"""
    q, gate, k, v = qkv_gate(p, n, cfg)
    a = attention_merged(q, k, v, cfg.n_heads, cfg.n_kv_heads, causal=True)     # 1/sqrt(256)
    gate = jax.nn.sigmoid(gate.astype(jnp.float32))
    return (a.astype(jnp.float32) * gate).astype(n.dtype) @ p["wo"].astype(n.dtype), jnp.mean(gate)


def _layer(p: common.Params, x: jax.Array, stats: Dict[str, jax.Array], cfg: Qwen3NextConfig, kind: str):
    """One layer: (x, running statistics) -> the same, and the layer's routes ``top_idx`` [S, k]."""
    b, t, d = x.shape
    n = norm(p["ln_mixer"], x, cfg.rms_eps)
    if kind == LINEAR:
        with jax.named_scope("gdn"):
            y, noted = _delta(p["mixer"], n, cfg)
        stats = {**stats, "gdn_carried": stats["gdn_carried"] + noted["carry_share"],
                 "gdn_decay_min": jnp.minimum(stats["gdn_decay_min"], noted["decay_min"]),
                 "gdn_beta": stats["gdn_beta"] + noted["beta_mean"]}
    else:
        with jax.named_scope("attention"):
            y, gate_mean = _attention(p["mixer"], n, cfg)
        stats = {**stats, "attn_gate": stats["attn_gate"] + gate_mean}
    x = x + y
    with jax.named_scope("moe"):
        dtype = x.dtype
        h = norm(p["ln_ffn"], x, cfg.rms_eps).reshape(b * t, d)
        top_idx, weights, probs = moe.route(p["router"], h, cfg.top_k, 1.0, score="softmax")
        ex = p["experts"]
        y, *dispatch = share_glu_experts(
            h, top_idx, weights, ex["w_gate"], ex["w_up"], ex["w_down"], cfg.expert_offset, cfg.n_experts)
        # the shared expert: every token, times a learnt scalar a token
        shared_gate = jax.nn.sigmoid((h @ p["shared_gate"].astype(dtype)).astype(jnp.float32))
        shared = (swiglu(p["shared"], h).astype(jnp.float32) * shared_gate).astype(dtype)
        x = x + (shared + y).reshape(b, t, d)
        noted, _ = moe.note_share(stats, top_idx, dispatch, cfg, probs=probs)
        stats = {**stats, **noted, "shared_gate": stats["shared_gate"] + jnp.mean(shared_gate)}
    return x, stats, top_idx


def _trunk(params: common.Params, tokens: jax.Array, cfg: Qwen3NextConfig):
    """Final hidden states [B, T, d], the statistics summed over the layers,
    and the layers' routes ``[L, S, k]`` in layer order."""
    x = params["wte"][tokens].astype(common.compute_dtype())

    def layer_of(kind: str, layers: int):
        def body(p, x, stats):
            return _layer(p, x, stats, cfg, kind)

        return common.remat_layer(body, layers) if cfg.remat else body

    linear_layer = layer_of(LINEAR, cfg.periods * (cfg.period - 1))
    full_layer = layer_of(FULL, cfg.periods)

    def linear_step(carry, p):
        x, stats, top_idx = linear_layer(p, *carry)
        return (x, stats), top_idx

    def period_step(carry, p):
        carry, first = jax.lax.scan(linear_step, carry, p[LINEAR])
        x, stats, last = full_layer(p[FULL], *carry)
        return (x, stats), jnp.concatenate([first, last[None]])

    zero = jnp.zeros((), jnp.float32)
    stats = {**moe.zero_share_stats(balanced=cfg.n_experts, chunks_extra=True),
             "gdn_carried": zero, "gdn_decay_min": zero, "gdn_beta": zero, "attn_gate": zero, "shared_gate": zero}
    (x, stats), routes = jax.lax.scan(period_step, (x, stats), params["blocks"])
    routes = routes.reshape(cfg.n_layers, tokens.size, cfg.top_k)
    return norm(params["ln_f"], x, cfg.rms_eps), stats, routes


def loss_and_routes(
    params: common.Params, batch: Dict[str, jax.Array], cfg: Qwen3NextConfig
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
    metrics = moe.share_metrics(loss, lm, aux, stats, tokens.size, cfg)
    # what the ``gdn.scan`` span carries: of the (delta layer, sequence, value head, chunk boundary) quadruples
    # the share across which the carried state still counts (``ops/kda.CARRY_FLOOR``), the lowest chunk-summed
    # log decay of the step, the mean beta; the attention layers' mean output gate and the layers' mean
    # shared-expert gate
    linear = cfg.periods * (cfg.period - 1)
    metrics["gdn_carry_share"] = stats["gdn_carried"] / linear
    metrics["gdn_decay_min"] = stats["gdn_decay_min"]
    metrics["gdn_beta_mean"] = stats["gdn_beta"] / linear
    metrics["attn_gate_mean"] = stats["attn_gate"] / cfg.periods
    metrics["shared_gate_mean"] = stats["shared_gate"] / cfg.n_layers
    return loss, metrics, routes


def spans(cfg: Qwen3NextConfig):
    """The spans the train loop records of this step: its routing (with the
    shared expert's mean gate), what its delta layers' scans carry from chunk to
    chunk and which form they took, and its attention layers' mean output gate."""
    return {"moe.route": moe.route_span(cfg, chunks_extra=True, more=("shared_gate_mean",)),
            "gdn.scan": common.StepSpan(("gdn_carry_share", "gdn_decay_min", "gdn_beta_mean"),
                                        noted={"gdn_form": ("gdn_scan", "form")}),
            "attention.gate": common.StepSpan(("attn_gate_mean",))}
