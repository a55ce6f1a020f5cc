"""Xing4.0-29B-A4B (XingChen-AGI;
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B ``config.json``,
``model_type`` ``xing4_0``): a 40-layer decoder, d 3,584, that carries FOUR
residual streams a token and mixes them around every sublayer by maps made from
the token's own state, the residual one doubly stochastic (manifold-constrained
hyper-connections, mHC: arXiv:2512.24880, after Hyper-Connections,
arXiv:2409.19606). Every token mixer is multi-head latent attention with a key
of 128 + 64 = 192 over a value head of 128, rotary on the 64 under YaRN (factor
64 from 4,096). Two leading layers with a dense SwiGLU FFN of 9,216, then 64
SwiGLU experts of 1,024 a layer, four chosen per token by a sigmoid router with
a selection bias (``noaux_tc``), beside one shared expert. 29 B parameters, 4 B
of them at work on a token.

By SUBLAYER (the attention and the FFN of each layer: 80 in the model). With
``n = hc_mult = 4`` streams and ``C = 3584`` the residual state of a token is
``X in R^{n x C}``; ``X_0`` is the embedding copied to the four streams::

    u      = rmsnorm_{nC}(vec(X))                    eps hc_eps; a weight of nC
    Hpre~  = a_pre  (u Phi_pre)  + b_pre             Phi_pre:  [nC, n],   b_pre:  [n]
    Hpost~ = a_post (u Phi_post) + b_post            Phi_post: [nC, n],   b_post: [n]
    Hres~  = a_res  mat(u Phi_res) + b_res           Phi_res:  [nC, n^2], b_res:  [n, n]
    Hpre   = sigmoid(Hpre~)                          [n]
    Hpost  = 2 sigmoid(Hpost~)                       [n]
    Hres   = SK_20(exp(clip(Hres~, -30, 30)))        [n, n]; SK: rows over their sums, then
                                                     columns over theirs, 20 times
    x_in   = Hpre X                                  [C]: the sublayer's input
    y      = F(rmsnorm_C(x_in))                      F: latent attention, or the FFN
    X'     = Hres X + Hpost^T y                      [n x C]

The coefficients are 24 float32 numbers a token a sublayer. The final norm and
the head read the SUM of the four streams. ``F`` for attention (RMSNorm eps
1e-6, no biases)::

    cq = rmsnorm_768(W_qa n);  q = W_qb cq  [32 x (128 | 64)]
    [c | k_rope] = W_kva n                  3584 -> 512 + 64; ONE rotary key a token
    kv = W_kvb rmsnorm_512(c)  [32 x (128 | 128)]      k_nope | v
    rotary on q's 64 and on k_rope: interleaved pairs, YaRN's frequencies
        (``ops/attention.yarn_inv_freq``: theta 10,000, factor 64, 4,096, beta 32 / 1);
        cos and sin times mscale / mscale_all_dim's ratio = 1
    a = causal softmax attention at (0.1 ln 64 + 1)^2 / sqrt(192) = 2.0047 / sqrt(192),
        value head 128;  y = W_o a         W_o: 4096 -> 3584

``F`` for the FFN: layers 0-1 ``W2 (silu(W1 n) * W3 n)`` at 9,216; layers 2-39
``s = sigmoid(W_r n)`` over 64 in float32, ``T = top4(s + b)``, ``w_e = 2 s_e /
(sum_T s + 1e-20)``, ``shared(n) + sum_{e in T, held} w_e expert_e(n)``; the
bias ``b`` is the step's (``models/moe.balance``), as in
``models/glm4_moe_lite.py``. Loss = mean next-token cross-entropy over the
vocabulary (slice), an untied head; no auxiliary loss. The multi-token-prediction
module (``num_nextn_predict_layers`` 1) is not built (ROADMAP R5 (c)).

**The streams' layout: ``[B, T, n C]``, stream j on lanes ``j C .. (j + 1) C``.**
C = 3,584 is 28 whole 128-lane tiles, so a stream is a static, tile-aligned
slice of the last axis: ``Hpre X`` and ``Hres X + Hpost^T y`` are sums of such
slices times a token's own scalar (one fusion each), ``vec(X)`` is the array as
it lies (the norm's sum and ``u Phi`` read it with no reshape), and the tokens
stay on the sublanes as every product's rows want them. ``[B, T, n, C]`` would
put the 4 on the sublanes of a (16, 128) bf16 tile, three quarters of it
padding; ``[n, B, T, C]`` would make ``u Phi`` four products and the norm a sum
over two axes for nothing the flat form lacks. The streams are carried, and kept
at a rematerialised layer's boundary, in the compute dtype (bf16 on a TPU: 235 MB
at 8,192 tokens); the coefficient arithmetic and every mixing sum are float32
inside their fusions. ``u`` is never written: the norm's weight goes onto
``Phi``'s rows (a weight-sized product) and its ``1 / rms`` onto the product's 24
results, ``u Phi = rsqrt(mean(X^2) + eps) (X (g * Phi))``, the same sums in
another order. The product takes the streams in the compute dtype with float32
out of the MXU, as every other product of the model; the Sinkhorn steps run
with the tokens on the lanes (``[n, n, S]`` float32) and their backward is
autodiff through the 20 steps (``tests/test_xing4.py`` holds it to finite
differences), recomputed with the layer.

**Latent attention on the kernels' merged layout, the key padded to 256 lanes
through the weights.** ``glm4_moe_lite.qkv`` (adapted: ``pad``, ``inv_freq``,
``q_scale``; its defaults trace GLM's program as before) makes q and k
``[B, T, 32 x 256]``, a head's lanes ``[rope 64 | nope 128 | zeros 64]``, and v
``[B, T, 32 x 128]``; ``attention_merged`` hands them to the flash kernels where
they lie (``heads_a_block(256, 128)`` = 1). The zero lanes are columns of
``wq_b`` / ``[Wk ; E]``, never an activation's pad, and cost the kernels nothing
they did not already pay: a head of 192 occupies two 128-lane tiles in VMEM and
two passes of the MXU's 128-deep contraction either way. By head
(``models/kimi_linear.py``'s way at 192 over 128) q, k and v would be three
transposed copies of 100 MB a layer in each pass. The softmax scale that is not
``1 / sqrt(D)`` is folded into ``wq_b`` (``q_scale`` = 2.0047 sqrt(256 / 192):
the cores divide by the root of the 256 lanes they are handed); q is turned
beside the kernels as GLM's (the same VMEM count at 256 lanes and 8,192 rows).

**Measured** (TPU v5e, ``xing4-solo``: published layers 1-5, 8 of 64 experts,
an eighth of the vocabulary, one sequence of 4,096 tokens; PERF.md, Findings of
PR 70): a step is 239 ms, of which the scope ``hc`` reads 20.4 (5.8 forward, 2.8
recomputed, 11.7 backward) and the compiler's merged sibling fusions of the mix,
which carry no scope, another 8.7: about 30 ms where the path's required traffic
is 20.1 ms at 819 GB/s. With the mixes under autodiff the compiled step held
0.65e9 bytes more at 8,192 tokens. The mix after a sublayer reads the sublayer's
result in its backward, so a rematerialised expert layer runs its two forward
grouped products once more (nine a traced layer where GLM's has seven). At 8,192
tokens the step reads 19.30e9 bytes of arguments and temporaries and does not fit
a chip; at 4,096, 16.50e9, which loads and runs.

The cut a chip makes without touching a width, as ``models/glm4_moe_lite.py``:
``n_layers`` (the leading ones) with ``dense_layers``, ``experts_held`` with
``expert_offset``, ``vocab`` (a slice). Layers of one kind that follow each
other are one run, scanned (``models/moe.run_layers``, which carries whatever
``x`` is: here the four streams), every layer rematerialised by
``models/common.remat_layer`` as it stands: it keeps the layer's input (the
four streams) and the kernel's output. Departures as in ``models/olmoe.py``:
float32 parameters and bfloat16 compute on a TPU, the router's product in
float32 at the highest precision, rotary angles in float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributedvolunteercomputing_tpu.models import common, glm4_moe_lite, moe
from distributedvolunteercomputing_tpu.models.common import matrix, swiglu, swiglu_init
from distributedvolunteercomputing_tpu.ops.attention import attention_merged, yarn_inv_freq
from distributedvolunteercomputing_tpu.ops import moe_dispatch

LATENT = glm4_moe_lite.LATENT
DENSE, SPARSE = glm4_moe_lite.DENSE, glm4_moe_lite.SPARSE
ROUTE_EPS = glm4_moe_lite.ROUTE_EPS
LANES = 128
# the share's chunk: GLM's three even shares (the same router, bias rule and warm-up; models/glm4_moe_lite.py
# has the reading), not re-measured at this model's one sequence a step
SHARE_ROWS_SLACK = glm4_moe_lite.SHARE_ROWS_SLACK

# the running statistics of the residual maps, over the sublayers of a step (``hc.mix``)
_HC_STATS = ("hc_offdiag", "hc_err", "hc_pre_max", "hc_post")
# What the maps start from (the config gives none; the configuration file's ``assumed`` says why): the
# one-stream block (Hpre 1/n, Hpost 1, Hres near the identity) with seeded noise on every bias, so that the
# four streams part in the first sublayer and every later map matters to the loss.
INIT_A = 0.01
INIT_RES_DIAGONAL = 4.0
INIT_NOISE = 0.5


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    """Defaults are the published sizes of Xing4.0-29B-A4B."""

    vocab: int = 131072
    max_len: int = 8192  # the sequences a step trains on (published limit: 262,144 positions)
    d_model: int = 3584
    n_layers: int = 40
    n_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128    # a head's non-rotary query/key coordinates
    qk_rope_dim: int = 64     # its rotary ones; the key's are shared by the heads
    v_head_dim: int = 128
    dense_layers: int = 2     # first_k_dense_replace: leading layers with a dense FFN
    d_ff: int = 9216          # the dense FFN's width
    d_expert: int = 1024      # one routed expert's width, and one shared expert's
    n_shared: int = 1
    n_experts: int = 64       # the router's outputs
    top_k: int = 4
    experts_held: int = 64    # how many of them this chip holds ...
    expert_offset: int = 0    # ... from which on
    routed_scale: float = 2.0
    bias_gamma: float = 0.001  # what a step moves a selection bias by
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    yarn_factor: float = 64.0
    yarn_original_len: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    hc_mult: int = 4          # residual streams a token
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6      # the stream norm's
    hc_res_clamp_min: float = -30.0
    hc_res_clamp_max: float = 30.0
    remat: bool = True
    xent_chunk: int = 512

    def __post_init__(self):
        moe.check_share(self)
        if self.qk_rope_dim % 2 or self.n_layers < 1 or not 0 <= self.dense_layers <= self.n_layers:
            raise ValueError(f"qk_rope_dim={self.qk_rope_dim}, dense_layers={self.dense_layers} "
                             f"of n_layers={self.n_layers}")
        if self.hc_mult < 1 or self.hc_sinkhorn_iters < 0:
            raise ValueError(f"hc_mult={self.hc_mult}, hc_sinkhorn_iters={self.hc_sinkhorn_iters}")
        if self.yarn_mscale != self.yarn_mscale_all_dim:
            raise ValueError(
                f"mscale {self.yarn_mscale} over mscale_all_dim {self.yarn_mscale_all_dim} is not 1: "
                "a scale on the rotary tables is not built")

    @property
    def head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def head_pad(self) -> int:
        """Zero lanes behind a head of q and k: up to whole 128-lane tiles."""
        return -self.head_dim % LANES

    @property
    def softmax_scale(self) -> float:
        """``yarn_get_mscale(factor, mscale_all_dim)^2 / sqrt(head_dim)`` (DeepSeek-V3's)."""
        m = 0.1 * self.yarn_mscale_all_dim * math.log(self.yarn_factor) + 1.0 if self.yarn_factor > 1 else 1.0
        return m * m / math.sqrt(self.head_dim)

    @property
    def hc_maps(self) -> int:
        """Coefficients a token a sublayer: ``n`` in, ``n`` out, ``n^2`` across."""
        return self.hc_mult * (self.hc_mult + 2)

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return (LATENT,) * self.n_layers

    @property
    def runs(self) -> Tuple[Tuple[str, int], ...]:
        """(FFN kind, layers) of each run of equal layers, in order."""
        dense, sparse = self.dense_layers, self.n_layers - self.dense_layers
        return tuple((kind, n) for kind, n in ((DENSE, dense), (SPARSE, sparse)) if n)


def _hc_init(rng: jax.Array, cfg: Xing4Config) -> common.Params:
    """One sublayer's maps. ``phi``'s columns, ``b`` and ``a`` in the order
    pre (n) | post (n) | res (n^2, row-major: entry (i, j) at ``n i + j``)."""
    n = cfg.hc_mult
    k_phi, k_noise = jax.random.split(rng)
    centre = jnp.concatenate([
        jnp.full((n,), -math.log(n - 1.0) if n > 1 else 30.0),     # sigmoid -> 1 / n
        jnp.zeros((n,)),                                           # 2 sigmoid -> 1
        INIT_RES_DIAGONAL * jnp.eye(n).reshape(-1),
    ])
    return {
        "norm": common.rmsnorm_init(n * cfg.d_model),
        "phi": matrix(k_phi, (n * cfg.d_model, cfg.hc_maps)),
        "a": jnp.full((3,), INIT_A, jnp.float32),
        "b": (centre + INIT_NOISE * jax.random.normal(k_noise, (cfg.hc_maps,))).astype(jnp.float32),
    }


def _layer_init(rng: jax.Array, cfg: Xing4Config, ffn: str) -> common.Params:
    k = jax.random.split(rng, 17)
    d, h = cfg.d_model, cfg.n_heads
    p: common.Params = {
        "ln_mixer": common.rmsnorm_init(d), "ln_ffn": common.rmsnorm_init(d),
        "hc_mixer": _hc_init(k[15], cfg), "hc_ffn": _hc_init(k[16], cfg),
        "wq_a": matrix(k[0], (d, cfg.q_lora_rank)), "q_a_norm": common.rmsnorm_init(cfg.q_lora_rank),
        "wq_b": matrix(k[1], (cfg.q_lora_rank, h * cfg.head_dim)),
        "wkv_a": matrix(k[2], (d, cfg.kv_lora_rank + cfg.qk_rope_dim)),
        "kv_a_norm": common.rmsnorm_init(cfg.kv_lora_rank),
        "wkv_b": matrix(k[3], (cfg.kv_lora_rank, h * (cfg.qk_nope_dim + cfg.v_head_dim))),
        "wo": matrix(k[4], (h * cfg.v_head_dim, d)),
    }
    if ffn == DENSE:
        p["mlp"] = swiglu_init(k, d, cfg.d_ff, first=5)
    else:
        p["router"] = matrix(k[8], (d, cfg.n_experts))
        p["bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)  # the step's, not the optimizer's
        p["shared"] = swiglu_init(k, d, cfg.n_shared * cfg.d_expert, first=9)
        p["experts"] = swiglu_init(k, d, cfg.d_expert, (cfg.experts_held,), first=12)
    return p


@functools.partial(jax.jit, static_argnums=1)
def init(rng: jax.Array, cfg: Xing4Config) -> common.Params:
    """One program for the whole tree. A layer's key is its index's; run ``r``
    holds its layers stacked, in order."""
    keys = jax.random.split(rng, 3)
    layer_keys = jax.random.split(keys[1], cfg.n_layers)
    blocks, first = [], 0
    for ffn, n in cfg.runs:
        one = functools.partial(_layer_init, cfg=cfg, ffn=ffn)
        blocks.append(jax.vmap(one)(layer_keys[first:first + n]))
        first += n
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "blocks": blocks,
        "ln_f": common.rmsnorm_init(cfg.d_model),
        "lm_head": matrix(keys[2], (cfg.d_model, cfg.vocab)),
    }


def sinkhorn(m: jax.Array, iters: int) -> jax.Array:
    """``m`` [n, n, ...] positive (row i, column j, then whatever it is a batch
    over): each row divided by its sum, then each column by its, ``iters``
    times. The steps are written out and differentiated as written."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=1, keepdims=True)
        m = m / jnp.sum(m, axis=0, keepdims=True)
    return m


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _normed_product(x: jax.Array, phi: jax.Array, eps: float) -> jax.Array:
    """``rmsnorm(x) @ phi`` [.., K] float32 over the last axis of ``x`` [.., D]
    (compute dtype) with the norm's weight already on ``phi`` [D, K] (float32)
    and the normed ``x`` never written: ``rsqrt(mean(x^2) + eps) (x @ phi)``.
    Its backward is written out so that what a rematerialised layer's backward
    holds of it is ``x`` as it lies, the K results and the one ``1 / rms`` a
    token, and no float32 copy of ``x``."""
    return _normed_product_fwd(x, phi, eps)[0]


def _normed_product_fwd(x, phi, eps):
    xf = x.astype(jnp.float32)
    inv_rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    t = jnp.dot(x, phi.astype(x.dtype), preferred_element_type=jnp.float32)
    return t * inv_rms, (x, phi, t, inv_rms)


def _normed_product_bwd(eps, residuals, g):
    x, phi, t, inv_rms = residuals
    dt = (g * inv_rms).astype(x.dtype)
    # d(1 / rms) = -1/2 (1 / rms)^3 d mean(x^2), and d mean(x^2) = 2 x / D
    d_ms = -0.5 * jnp.sum(g * t, axis=-1, keepdims=True) * inv_rms ** 3
    dx = (jnp.dot(dt, phi.astype(x.dtype).T, preferred_element_type=jnp.float32)
          + d_ms * (2.0 / x.shape[-1]) * x.astype(jnp.float32))
    rows = x.reshape(-1, x.shape[-1])
    dphi = jnp.dot(rows.T, dt.reshape(-1, dt.shape[-1]), preferred_element_type=jnp.float32)
    return dx.astype(x.dtype), dphi


_normed_product.defvjp(_normed_product_fwd, _normed_product_bwd)


def hc_maps(p: common.Params, x: jax.Array, cfg: Xing4Config):
    """A sublayer's maps from the streams ``x`` [B, T, n C]: ``Hpre`` [B, T, n],
    ``Hpost`` [B, T, n], ``Hres`` [B, T, n, n], float32, and what the step
    notes of them."""
    b, t, _ = x.shape
    n = cfg.hc_mult
    # u Phi with u never written: the norm's weight on Phi's rows, its 1 / rms on the 24 results
    raw = _normed_product(x, p["norm"]["g"][:, None] * p["phi"], cfg.hc_eps)
    z = jnp.repeat(p["a"], np.array([n, n, n * n])) * raw + p["b"]
    z = z.reshape(b * t, cfg.hc_maps).T                           # the tokens on the lanes: [24, S]
    pre, post = jax.nn.sigmoid(z[:n]), 2.0 * jax.nn.sigmoid(z[n:2 * n])
    res = jnp.clip(z[2 * n:].reshape(n, n, b * t), cfg.hc_res_clamp_min, cfg.hc_res_clamp_max)
    res = sinkhorn(jnp.exp(res), cfg.hc_sinkhorn_iters)
    sums = jnp.concatenate([jnp.sum(res, axis=1), jnp.sum(res, axis=0)])
    noted = {
        "hc_offdiag": 1.0 - jnp.mean(jnp.trace(res)) / n,        # how much the streams mix
        "hc_err": jnp.max(jnp.abs(sums - 1.0)),                    # how far from doubly stochastic
        "hc_pre_max": jnp.max(pre), "hc_post": jnp.mean(post),
    }
    noted = jax.tree_util.tree_map(jax.lax.stop_gradient, noted)
    return pre.T.reshape(b, t, n), post.T.reshape(b, t, n), jnp.moveaxis(res, -1, 0).reshape(b, t, n, n), noted


def _streams(x: jax.Array, n: int):
    """The ``n`` streams of ``x`` [.., n C], float32: static, tile-aligned slices of the last axis."""
    c = x.shape[-1] // n
    return [x[..., j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]


# The two mixes are sums of a token's streams times the token's own scalars. Their backward passes are
# written out (the same sums the other way, and a dot over C for each scalar) so that they keep the streams
# and the sublayer's result as they lie, in the compute dtype, and the few coefficients: under autodiff every
# float32 copy and product of a stream was a residual of its own, 0.6 MB a token in the layer's backward.
@jax.custom_vjp
def hc_in(x: jax.Array, pre: jax.Array) -> jax.Array:
    """``Hpre X`` [B, T, C]: the sublayer's input, from the streams ``x``
    [B, T, n C] and ``pre`` [B, T, n] float32."""
    xs = _streams(x, pre.shape[-1])
    return sum(pre[..., j:j + 1] * xj for j, xj in enumerate(xs)).astype(x.dtype)


def _hc_in_bwd(residuals, g):
    x, pre = residuals
    gf = g.astype(jnp.float32)
    dx = jnp.concatenate([pre[..., j:j + 1] * gf for j in range(pre.shape[-1])], axis=-1)
    dpre = jnp.stack([jnp.sum(gf * xj, axis=-1) for xj in _streams(x, pre.shape[-1])], axis=-1)
    return dx.astype(x.dtype), dpre


hc_in.defvjp(lambda x, pre: (hc_in(x, pre), (x, pre)), _hc_in_bwd)


@jax.custom_vjp
def hc_out(x: jax.Array, y: jax.Array, post: jax.Array, res: jax.Array) -> jax.Array:
    """``Hres X + Hpost^T y`` [B, T, n C], from the streams ``x``, the
    sublayer's result ``y`` [B, T, C], ``post`` [B, T, n] and ``res``
    [B, T, n, n] (row i: the new stream i) float32."""
    n = post.shape[-1]
    xs, yf = _streams(x, n), y.astype(jnp.float32)
    return jnp.concatenate(
        [sum(res[..., i, j, None] * xj for j, xj in enumerate(xs)) + post[..., i:i + 1] * yf for i in range(n)],
        axis=-1).astype(x.dtype)


def _hc_out_bwd(residuals, g):
    x, y, post, res = residuals
    n = post.shape[-1]
    xs, gs, yf = _streams(x, n), _streams(g, n), y.astype(jnp.float32)
    dx = jnp.concatenate([sum(res[..., i, j, None] * gi for i, gi in enumerate(gs)) for j in range(n)], axis=-1)
    dy = sum(post[..., i:i + 1] * gi for i, gi in enumerate(gs))
    dpost = jnp.stack([jnp.sum(gi * yf, axis=-1) for gi in gs], axis=-1)
    dres = jnp.stack([jnp.stack([jnp.sum(gi * xj, axis=-1) for xj in xs], axis=-1) for gi in gs], axis=-2)
    return dx.astype(x.dtype), dy.astype(y.dtype), dpost, dres


hc_out.defvjp(lambda x, y, post, res: (hc_out(x, y, post, res), (x, y, post, res)), _hc_out_bwd)


def _note_hc(stats: Dict[str, jax.Array], noted: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {**stats,
            "hc_offdiag": stats["hc_offdiag"] + noted["hc_offdiag"], "hc_post": stats["hc_post"] + noted["hc_post"],
            "hc_err": jnp.maximum(stats["hc_err"], noted["hc_err"]),
            "hc_pre_max": jnp.maximum(stats["hc_pre_max"], noted["hc_pre_max"])}


def _attention(p: common.Params, x_in: jax.Array, cfg: Xing4Config) -> jax.Array:
    n = common.rmsnorm(p["ln_mixer"], x_in, cfg.rms_eps)
    inv_freq = yarn_inv_freq(cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_len,
                             cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    lanes = cfg.head_dim + cfg.head_pad
    q, k, v = glm4_moe_lite.qkv(p, n, cfg, pad=cfg.head_pad, inv_freq=inv_freq,
                                q_scale=cfg.softmax_scale * math.sqrt(lanes))   # the cores divide by sqrt(lanes)
    a = attention_merged(q, k, v, cfg.n_heads, cfg.n_heads, causal=True)
    return a @ p["wo"].astype(x_in.dtype)


def _layer(p: common.Params, x: jax.Array, stats: Dict[str, jax.Array], cfg: Xing4Config, ffn: str):
    """One layer: (the four streams [B, T, n C], running statistics) -> the
    same, and for an expert layer its routes ``top_idx`` [S, k] and how many
    assignments chose each expert ``[E]`` (None for a dense layer)."""
    b, t, _ = x.shape
    d = cfg.d_model
    with jax.named_scope("hc"):
        pre, post, res, noted = hc_maps(p["hc_mixer"], x, cfg)
        x_in = hc_in(x, pre)
    with jax.named_scope("attention"):
        y = _attention(p, x_in, cfg)
    with jax.named_scope("hc"):
        x = hc_out(x, y, post, res)
        stats = _note_hc(stats, noted)
        pre, post, res, noted = hc_maps(p["hc_ffn"], x, cfg)
        x_in = hc_in(x, pre)
        stats = _note_hc(stats, noted)
    if ffn == DENSE:
        with jax.named_scope("mlp"):
            y = swiglu(p["mlp"], common.rmsnorm(p["ln_ffn"], x_in, cfg.rms_eps))
        with jax.named_scope("hc"):
            return hc_out(x, y, post, res), stats, None
    with jax.named_scope("moe"):
        h = common.rmsnorm(p["ln_ffn"], x_in, cfg.rms_eps).reshape(b * t, d)
        top_idx, weights, _ = moe.route(p["router"], h, cfg.top_k, cfg.routed_scale, p["bias"], ROUTE_EPS)
        ex = p["experts"]
        y, *dispatch = moe_dispatch.share_glu_experts(
            h, top_idx, weights, ex["w_gate"], ex["w_up"], ex["w_down"],
            cfg.expert_offset, cfg.n_experts, slack=SHARE_ROWS_SLACK,
        )
        y = (swiglu(p["shared"], h) + y).reshape(b, t, d)   # the shared expert: every token, unweighted
        share, chosen = moe.note_share(stats, top_idx, dispatch, cfg, SHARE_ROWS_SLACK)
        stats = {**share, **{key: stats[key] for key in _HC_STATS}}
    with jax.named_scope("hc"):
        return hc_out(x, y, post, res), stats, (top_idx, chosen)


def loss_and_routes(
    params: common.Params, batch: Dict[str, jax.Array], cfg: Xing4Config
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """(loss, metrics, the experts every expert layer chose ``[L_sparse, S, k]``);
    see ``models/olmoe.loss_and_routes`` for what the routes are for."""
    tokens = batch["tokens"]
    n = cfg.hc_mult
    x = jnp.tile(params["wte"][tokens].astype(common.compute_dtype()), (1, 1, n))   # X_0: four copies
    runs = [(functools.partial(_layer, cfg=cfg, ffn=ffn), count, ffn == SPARSE) for ffn, count in cfg.runs]
    stats = {**moe.zero_share_stats(chunks_extra=True), **{key: jnp.zeros((), jnp.float32) for key in _HC_STATS}}
    x, stats, routes, counts = moe.run_layers(runs, params["blocks"], x, stats, cfg.remat, tokens.size, cfg)
    with jax.named_scope("hc"):
        x = sum(_streams(x, n)).astype(x.dtype)                                      # the head reads the sum
    x = common.rmsnorm(params["ln_f"], x, cfg.rms_eps)
    loss = common.lm_xent_chunked(
        x, params["lm_head"], batch["targets"], chunk=cfg.xent_chunk, head_layout="dv"
    )
    metrics = moe.share_metrics(
        loss, loss, jnp.zeros((), jnp.float32), stats, tokens.size, cfg, params, counts)
    sublayers = 2.0 * cfg.n_layers
    metrics.update({
        "hc_res_offdiag": stats["hc_offdiag"] / sublayers, "hc_sinkhorn_err": stats["hc_err"],
        "hc_pre_max": stats["hc_pre_max"], "hc_post_mean": stats["hc_post"] / sublayers,
    })
    return loss, metrics, routes


def stepped(cfg: Xing4Config):
    """What the train step needs to move the selection biases itself."""
    return moe.stepped(cfg.bias_gamma)


def spans(cfg: Xing4Config):
    """The spans the train loop records of this step."""
    return {
        "moe.route": moe.route_span(cfg, chunks_extra=True, stepped_bias=True),
        "hc.mix": common.StepSpan(
            ("hc_res_offdiag", "hc_sinkhorn_err", "hc_pre_max", "hc_post_mean"),
            {"hc_mult": int(cfg.hc_mult), "hc_sinkhorn_iters": int(cfg.hc_sinkhorn_iters)}),
    }
