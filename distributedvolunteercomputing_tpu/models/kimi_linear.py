"""Kimi-Linear-48B-A3B-Instruct (moonshotai;
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct ``config.json``,
``model_type`` ``kimi_linear``): a 27-layer decoder, d 2,304, whose token mixers
are Kimi Delta Attention (KDA: linear attention with a state decayed by channel
and corrected by a delta rule; 20 layers) and, every fourth layer and the last,
multi-head latent attention WITHOUT any position encoding (7 layers). One
leading layer with a dense SwiGLU FFN of width 9,216, then 256 SwiGLU experts
of width 1,024 a layer, eight chosen per token by a sigmoid router with a
selection bias, beside one shared expert. 48 B parameters, 3 B of them at work
on a token.

By layer (the config counts layers from 1: ``kda_layers`` 1-3, 5-7, ...,
``full_attn_layers`` 4, 8, ..., 24, 27). Pre-norm residual blocks, RMSNorm eps
1e-5, no bias but the output gate's::

    n  = rmsnorm(x)
    KDA (32 heads, key and value head 128):
        [q | k | v] = silu(conv4(n W_qkv))      W_qkv: 2304 -> 3 x 4096; the convolution
                                                causal, depthwise, 4 taps a channel, no bias
        q = l2norm_head(q) / sqrt(128),  k = l2norm_head(k)
        g = -exp(A_log_h) softplus((n W_fa) W_fb + dt_bias)   the log decay, a KEY CHANNEL:
                                                W_fa: 2304 -> 128, W_fb: 128 -> 4096
        beta = sigmoid(n W_b)                   one a head
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T     S: [128, 128] a head, float32
        o_t = S_t^T q_t
        y = (rmsnorm_128(o) * sigmoid((n W_ga) W_gb + b_g)) W_o      one learned scale of 128 for every head
    latent attention (32 heads):
        q = n W_q  [32 x 192]                   a direct query (q_lora_rank null)
        [c | k_s] = n W_kva                     2304 -> 512 + 64; k_s is ONE vector a token
        [k_nope | v] = rmsnorm_512(c) W_kvb  [32 x (128 + 128)]
        k_h = [k_nope_h | k_s]                  192; NOTHING is rotated (mla_use_nope)
        a = causal softmax attention at 1/sqrt(192), VALUE HEAD 128
        y = a W_o                               4096 -> 2304
    h  = x + y;  n2 = rmsnorm(h)
    layer 1:  out = h + W2 (silu(W1 n2) * W3 n2)                  width 9,216
    else:     s = sigmoid(Wr n2) over 256, float32 at the highest precision
              T = top8(s + b)        b: the layer's selection bias; used HERE ONLY
              w_e = 2.446 s_e / (sum_T s + 1e-20)     (moe_renormalize; routed_scaling_factor)
              out = h + shared(n2) + sum_{e in T and held here} w_e expert_e(n2)

The shared expert is unweighted; ``num_expert_group`` 1: no group limits the
choice. Final RMSNorm, an untied head. Loss = mean next-token cross-entropy over
the vocabulary (slice); no auxiliary loss.

The delta rule runs as a chunked scan with its own backward (``ops/kda.py``,
chunks of 64; it takes q, k, v and the log decay as the convolution and the
products leave them, the heads side by side, and norms q and k, folds beta and
sums the decay on the chunk its step holds), the three convolutions each through
``ops/short_conv.causal_conv`` over its 4,096 channels (which wants a bias: it
is handed constant zeros that are no leaf), latent attention through
``attention_core`` with keys of 192 over values of 128 (the flash kernels take
a value head of its own width since PR 52), the held experts through
``ops/moe_dispatch.share_glu_experts``. The selection bias is the step's to
move, as ``models/glm4_moe_lite.py`` says of its own; the share's chunk is the
dispatch's default of three even shares, as there (latent attention's running
mean makes a sequence's tokens agree on their experts).

**The decay's leaves have their own initialisation**, the family's: ``A_log`` the
log of a uniform draw in [1, 16] a head, ``dt_bias`` the inverse softplus of a
``dt`` drawn log-uniform in [1e-3, 1e-1] a channel, the convolution's taps
uniform in +-1/sqrt(taps) (the depthwise ``Conv1d``'s own default). With
normal(0, 0.02) everywhere the decay would be exp(-0.69) a token on every
channel and a chunk's state gone before the next: a check on the initial
parameters would not see the recurrence (``models/nemotron_h.py``, PR 48).

The cut a chip makes without touching a width: ``n_layers`` (the first so
many), ``experts_held`` with ``expert_offset``, ``vocab``. Layers of one kind
that follow each other are one run, stacked and scanned
(``models/moe.run_layers``); every layer is rematerialised by
``models/common.remat_layer`` (a latent layer keeps its kernel's output and row
statistics, a KDA layer nothing: its backward runs the projections, the
convolution and the scan's forward again, which keeps the chunk-boundary
states its backward reads). Departures as in
``models/olmoe.py``: float32 parameters and bfloat16 compute on a TPU, the
router's product, the decay and ``beta`` in float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common, moe
from distributedvolunteercomputing_tpu.models.common import matrix, swiglu, swiglu_init
from distributedvolunteercomputing_tpu.ops import kda as kda_ops
from distributedvolunteercomputing_tpu.ops import moe_dispatch
from distributedvolunteercomputing_tpu.ops.attention import attention_core, merge_heads
from distributedvolunteercomputing_tpu.ops.short_conv import causal_conv

KDA, LATENT = "kda", "latent_attention"
DENSE, SPARSE = "dense", "sparse"
PUBLISHED_KDA_LAYERS = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26)
PUBLISHED_FULL_LAYERS = (4, 8, 12, 16, 20, 24, 27)
# what the weights' divisor adds to the chosen scores' sum (the family's public code's)
ROUTE_EPS = 1e-20
# the decay's initialisation (not in the published config; the configuration file's ``assumed.kda_init``):
# ``exp(A_log)`` uniform in [1, A_MAX], ``dt_bias`` the inverse softplus of a dt log-uniform in [DT_MIN, DT_MAX]
A_MAX, DT_MIN, DT_MAX = 16.0, 1e-3, 1e-1
# every expert layer carries the stepped bias; the chunk is three even shares as in models/glm4_moe_lite.py
SHARE_ROWS_SLACK = moe_dispatch.SHARE_ROWS_SLACK
# the sizes of the CPU tests: every mechanism at widths a laptop traces in seconds
TINY = dict(
    vocab=512, max_len=40, d_model=64, n_layers=5, kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
    kda_heads=2, kda_head_dim=16, gate_rank=8, chunk=16, n_heads=4, kv_lora_rank=16, qk_nope_dim=12,
    qk_rope_dim=4, v_head_dim=8, d_ff=128, d_expert=32, n_experts=16, top_k=4, experts_held=4,
    expert_offset=4, xent_chunk=32,
)


def _layer_numbers(value) -> Tuple[int, ...]:
    if isinstance(value, str):  # from the command line: 1,2,3
        value = [v for v in value.split(",") if v.strip()]
    elif isinstance(value, int):  # ... or one number
        value = (value,)
    return tuple(int(v) for v in value)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """Defaults are the published sizes of Kimi-Linear-48B-A3B-Instruct."""

    vocab: int = 163840
    max_len: int = 8192  # the sequences a step trains on (published limit: 1,048,576 positions)
    d_model: int = 2304
    n_layers: int = 27        # how many of the published layers run, from the first
    kda_layers: Any = PUBLISHED_KDA_LAYERS        # linear_attn_config: counted from 1
    full_attn_layers: Any = PUBLISHED_FULL_LAYERS
    kda_heads: int = 32       # linear_attn_config.num_heads
    kda_head_dim: int = 128   # linear_attn_config.head_dim: a head's keys AND its values
    conv_taps: int = 4        # short_conv_kernel_size
    gate_rank: int = 128      # the two low-rank gates' inner width (the family sets the head dim)
    chunk: int = 64
    n_heads: int = 32         # latent attention
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128    # a head's own key coordinates
    qk_rope_dim: int = 64     # the key part the heads share (never rotated: mla_use_nope)
    v_head_dim: int = 128
    dense_layers: int = 1     # first_k_dense_replace
    d_ff: int = 9216          # the dense FFN's width
    d_expert: int = 1024      # one routed expert's width, and one shared expert's
    n_shared: int = 1
    n_experts: int = 256      # the router's outputs
    top_k: int = 8
    experts_held: int = 256   # how many of them this chip holds ...
    expert_offset: int = 0    # ... from which on
    routed_scale: float = 2.446
    bias_gamma: float = 0.001  # what a step moves a selection bias by
    rms_eps: float = 1e-5
    remat: bool = True
    xent_chunk: int = 512

    def __post_init__(self):
        object.__setattr__(self, "kda_layers", _layer_numbers(self.kda_layers))
        object.__setattr__(self, "full_attn_layers", _layer_numbers(self.full_attn_layers))
        moe.check_share(self)
        listed = sorted(self.kda_layers + self.full_attn_layers)
        if self.n_layers < 1 or listed[:self.n_layers] != list(range(1, self.n_layers + 1)):
            raise ValueError(
                f"kda_layers and full_attn_layers must name each of the layers 1..{self.n_layers} once; "
                f"they name {listed}")
        if not 0 <= self.dense_layers <= self.n_layers or self.chunk & (self.chunk - 1):
            raise ValueError(f"dense_layers={self.dense_layers} of {self.n_layers} layers, chunk={self.chunk}")

    @classmethod
    def tiny(cls) -> "KimiLinearConfig":
        return cls(**TINY)

    @property
    def head_dim(self) -> int:
        """Latent attention's query/key head: its own coordinates and the shared part."""
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def kda_dim(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """One mixer kind a layer that runs."""
        return tuple(KDA if n in self.kda_layers else LATENT for n in range(1, self.n_layers + 1))

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


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def _kda_init(k, cfg: KimiLinearConfig) -> common.Params:
    d, inner, rank = cfg.d_model, cfg.kda_dim, cfg.gate_rank
    lo, hi = jnp.log(DT_MIN), jnp.log(DT_MAX)
    dt = jnp.exp(jax.random.uniform(k[4], (inner,), jnp.float32) * (hi - lo) + lo)
    bound = cfg.conv_taps ** -0.5   # a depthwise convolution's fan-in is its taps
    return {
        "w_qkv": matrix(k[0], (d, 3 * inner)),
        "conv_w": jax.random.uniform(k[1], (cfg.conv_taps, 3 * inner), jnp.float32, -bound, bound),
        "w_fa": matrix(k[2], (d, rank)), "w_fb": matrix(k[3], (rank, inner)),
        "a_log": jnp.log(jax.random.uniform(k[5], (cfg.kda_heads,), jnp.float32, 1.0, A_MAX)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
        "w_beta": matrix(k[6], (d, cfg.kda_heads)),
        "w_ga": matrix(k[7], (d, rank)), "w_gb": matrix(k[8], (rank, inner)),
        "gate_b": jnp.zeros((inner,), jnp.float32),
        "o_norm": common.rmsnorm_init(cfg.kda_head_dim),
        "wo": matrix(k[9], (inner, d)),
    }


def _latent_init(k, cfg: KimiLinearConfig) -> common.Params:
    d, h = cfg.d_model, cfg.n_heads
    return {
        "wq": matrix(k[0], (d, h * cfg.head_dim)),
        "wkv_a": matrix(k[1], (d, cfg.kv_lora_rank + cfg.qk_rope_dim)),
        "kv_a_norm": common.rmsnorm_init(cfg.kv_lora_rank),
        "wkv_b": matrix(k[2], (cfg.kv_lora_rank, h * (cfg.qk_nope_dim + cfg.v_head_dim))),
        "wo": matrix(k[3], (h * cfg.v_head_dim, d)),
    }


def _layer_init(rng: jax.Array, cfg: KimiLinearConfig, mixer: str, ffn: str) -> common.Params:
    k = jax.random.split(rng, 20)
    d = cfg.d_model
    p: common.Params = {"ln_mixer": common.rmsnorm_init(d), "ln_ffn": common.rmsnorm_init(d),
                        "mixer": _kda_init(k[:10], cfg) if mixer == KDA else _latent_init(k[:10], cfg)}
    if ffn == DENSE:
        p["mlp"] = swiglu_init(k, d, cfg.d_ff, first=10)
    else:
        p["router"] = matrix(k[13], (d, cfg.n_experts))
        p["bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)  # the step's, not the optimizer's
        p["shared"] = swiglu_init(k, d, cfg.n_shared * cfg.d_expert, first=14)
        # the held experts stacked on a leading axis -> sharded over ep (parallel/sharding.py)
        p["experts"] = swiglu_init(k, d, cfg.d_expert, (cfg.experts_held,), first=17)
    return p


@functools.partial(jax.jit, static_argnums=1)
def init(rng: jax.Array, cfg: KimiLinearConfig) -> common.Params:
    """One program for the whole tree. A layer's key is its index's; run ``r``
    holds its layers stacked, in order."""
    keys = jax.random.split(rng, 3)
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
        "lm_head": matrix(keys[2], (cfg.d_model, cfg.vocab)),
    }


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------


def _kda(p: common.Params, n: jax.Array, cfg: KimiLinearConfig):
    """The mixer on the normed stream ``n`` [B, T, d]: (its output [B, T, d],
    what its scan says of itself: ``ops/kda.scan_counters`` and the mean ``beta``)."""
    dtype = n.dtype
    b, t, _ = n.shape
    h, hd = cfg.kda_heads, cfg.kda_head_dim
    by_head = lambda a: a.reshape(b, t, h, hd)
    # each stream from its own columns of W_qkv through its own convolution (one stream of 12,288 channels is
    # 73 MB of the kernel's 64 MB of VMEM at its block of 256 positions); the bias is constant zeros, no leaf
    w_qkv, no_bias = p["w_qkv"].astype(dtype), jnp.zeros((h * hd,), jnp.float32)
    q, k, v = (causal_conv(n @ w_qkv[:, at:at + h * hd], p["conv_w"][:, at:at + h * hd], no_bias)
               for at in range(0, 3 * h * hd, h * hd))
    # the log decay with the heads side by side as the product leaves them: a head's rate on each of its channels
    f = (n @ p["w_fa"].astype(dtype)) @ p["w_fb"].astype(dtype)
    g = -jnp.repeat(jnp.exp(p["a_log"]), hd) * jax.nn.softplus(f.astype(jnp.float32) + p["dt_bias"])
    beta = jax.nn.sigmoid((n @ p["w_beta"].astype(dtype)).astype(jnp.float32))
    # q and k un-normed: the scan norms a head's vectors, folds beta and sums the decay on the chunk it holds
    # (ops/kda.py), and splitting the heads here is a reshape it takes back: nothing of a stream's size by head
    o, sums = kda_ops.kda_with_sums(by_head(q), by_head(k), by_head(v), by_head(g), beta, cfg.chunk)
    gate = jax.nn.sigmoid(((n @ p["w_ga"].astype(dtype)) @ p["w_gb"].astype(dtype)).astype(jnp.float32)
                          + p["gate_b"])
    y = common.rmsnorm(p["o_norm"], o, cfg.rms_eps).astype(jnp.float32) * by_head(gate)
    noted = {**kda_ops.scan_counters(sums), "beta_mean": jnp.mean(beta)}
    return y.astype(dtype).reshape(b, t, h * hd) @ p["wo"].astype(dtype), noted


def latent_qkv(p: common.Params, n: jax.Array, cfg: KimiLinearConfig):
    """The latent's products of the normed stream ``n`` [B, T, d]: q, k
    ``[B, H, T, 192]`` and v ``[B, H, T, 128]``; the one shared key part a token
    broadcast to every head; nothing rotated."""
    dtype = n.dtype
    b, t, _ = n.shape
    h, nope, rot = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q = (n @ p["wq"].astype(dtype)).reshape(b, t, h, nope + rot).transpose(0, 2, 1, 3)
    cks = n @ p["wkv_a"].astype(dtype)                                   # [B, T, latent + rot]
    c, k_shared = cks[..., :cfg.kv_lora_rank], cks[..., cfg.kv_lora_rank:]
    kv = common.rmsnorm(p["kv_a_norm"], c, cfg.rms_eps) @ p["wkv_b"].astype(dtype)
    kv = kv.reshape(b, t, h, nope + cfg.v_head_dim).transpose(0, 2, 1, 3)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_shared[:, None], (b, h, t, rot))], axis=-1)
    return q, k, kv[..., nope:]


def _latent(p: common.Params, n: jax.Array, cfg: KimiLinearConfig) -> jax.Array:
    q, k, v = latent_qkv(p, n, cfg)
    a = attention_core(q, k, v, causal=True)     # 1/sqrt(192): the whole key; a [B, H, T, 128]
    return merge_heads(a) @ p["wo"].astype(n.dtype)


def _layer(p: common.Params, x: jax.Array, stats: Dict[str, jax.Array], cfg: KimiLinearConfig,
           mixer: str, ffn: str):
    """One layer: (x, running statistics) -> the same, and for an expert layer
    its routes ``top_idx`` [S, k] and how many assignments chose each expert
    ``[E]`` (None for a dense layer)."""
    b, t, d = x.shape
    n = common.rmsnorm(p["ln_mixer"], x, cfg.rms_eps)
    if mixer == KDA:
        with jax.named_scope("kda"):
            y, noted = _kda(p["mixer"], n, cfg)
        stats = {**stats, "kda_carried": stats["kda_carried"] + noted["carry_share"],
                 "kda_decay_min": jnp.minimum(stats["kda_decay_min"], noted["decay_min"]),
                 "kda_beta": stats["kda_beta"] + noted["beta_mean"]}
    else:
        with jax.named_scope("attention"):
            y = _latent(p["mixer"], n, cfg)
    x = x + y
    h = common.rmsnorm(p["ln_ffn"], x, cfg.rms_eps)
    if ffn == DENSE:
        with jax.named_scope("mlp"):
            return x + swiglu(p["mlp"], h), stats, None
    with jax.named_scope("moe"):
        h = h.reshape(b * t, d)
        top_idx, weights, _ = moe.route(p["router"], h, cfg.top_k, cfg.routed_scale, p["bias"], ROUTE_EPS)
        ex = p["experts"]
        y, *dispatch = moe_dispatch.share_glu_experts(
            h, top_idx, weights, ex["w_gate"], ex["w_up"], ex["w_down"],
            cfg.expert_offset, cfg.n_experts, slack=SHARE_ROWS_SLACK,
        )
        x = x + (swiglu(p["shared"], h) + y).reshape(b, t, d)   # the shared expert: every token, unweighted
        noted, chosen = moe.note_share(stats, top_idx, dispatch, cfg, SHARE_ROWS_SLACK)
    return x, {**stats, **noted}, (top_idx, chosen)


def loss_and_routes(
    params: common.Params, batch: Dict[str, jax.Array], cfg: KimiLinearConfig
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """(loss, metrics, the experts every expert layer chose ``[L_sparse, S, k]``);
    see ``models/olmoe.loss_and_routes`` for what the routes are for."""
    tokens = batch["tokens"]
    x = params["wte"][tokens].astype(common.compute_dtype())
    runs = [(functools.partial(_layer, cfg=cfg, mixer=mixer, ffn=ffn), n, ffn == SPARSE)
            for mixer, ffn, n in cfg.runs]
    zero = jnp.zeros((), jnp.float32)
    stats = {**moe.zero_share_stats(chunks_extra=True),
             "kda_carried": zero, "kda_decay_min": zero, "kda_beta": zero}
    x, stats, routes, counts = moe.run_layers(runs, params["blocks"], x, stats, cfg.remat, tokens.size, cfg)
    x = common.rmsnorm(params["ln_f"], x, cfg.rms_eps)
    loss = common.lm_xent_chunked(
        x, params["lm_head"], batch["targets"], chunk=cfg.xent_chunk, head_layout="dv"
    )
    metrics = moe.share_metrics(
        loss, loss, jnp.zeros((), jnp.float32), stats, tokens.size, cfg, params, counts)
    # what the ``kda.scan`` span carries: of the (KDA layer, sequence, head, chunk boundary) quadruples the
    # share across which the carried state still counts (``ops/kda.CARRY_FLOOR``), the lowest chunk-summed
    # log decay of the step (how near float32's exponent a chunk's e^-G would be), the mean beta
    layers = max(cfg.layer_types.count(KDA), 1)
    metrics["kda_carry_share"] = stats["kda_carried"] / layers
    metrics["kda_decay_min"] = stats["kda_decay_min"]
    metrics["kda_beta_mean"] = stats["kda_beta"] / layers
    return loss, metrics, routes


def stepped(cfg: KimiLinearConfig):
    """What the train step needs to move the selection biases itself."""
    return moe.stepped(cfg.bias_gamma)


def spans(cfg: KimiLinearConfig):
    """The spans the train loop records of this step: its routing, and what
    its KDA layers' scans carry from chunk to chunk (``ops/kda.py`` has one form: none to note)."""
    return {"moe.route": moe.route_span(cfg, chunks_extra=True, stepped_bias=True),
            "kda.scan": common.StepSpan(("kda_carry_share", "kda_decay_min", "kda_beta_mean"))}
