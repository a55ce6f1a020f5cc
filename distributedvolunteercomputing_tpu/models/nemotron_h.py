"""NVIDIA-Nemotron-3-Nano-30B-A3B (nvidia;
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``,
``model_type`` ``nemotron_h``): 52 blocks, d 2,688, in the published order
``hybrid_override_pattern`` (``M`` a Mamba-2 state-space mixer, 23 of them;
``E`` an expert layer, 23; ``*`` attention, 6). EACH BLOCK IS ONE MIXER behind
one RMSNorm, ``x <- x + mixer(rmsnorm(x))``: there is no attention-plus-FFN
pair. RMSNorm eps 1e-5, no bias in any projection. 31.6 B parameters, 3.2 B of
them at work on a token.

    M:  [z | xBC | dt] = n W_in            4,096 | 4,096 + 2 x 8 x 128 | 64
        xBC = silu(conv(xBC) + b)          depthwise, causal, 4 taps a channel
        [x' | B | C] = xBC                 x' [64 heads, 64], B and C [8 groups, 128]
        dt = softplus(dt + dt_bias)        a head's scalar a token, no upper clamp
        S_t = exp(dt_t A) S_{t-1} + dt_t x'_t B_t^T,  y_t = S_t C_t + D x'_t
                                           A = -exp(A_log) a head; head h reads group h // 8
        y = rmsnorm over each group's 512 channels of (y * silu(z)), scale g [4,096]
        out = y W_out
    *:  q = n Wq [32 x 128], k = n Wk, v = n Wv [2 x 128]; query head i reads KV
        head i // 16; causal softmax at 1/sqrt(128); NO position encoding (the
        state-space blocks carry order); out = heads Wo
    E:  s = sigmoid(n Wr) over 128, float32 at the highest precision
        T = top6(s + b)           b: the block's selection bias; used HERE ONLY
        w_e = 2.5 s_e / (sum_T s + 1e-20)
        out = sum_{e in T and held here} w_e W2_e relu(W1_e n)^2  +  W2_s relu(W1_s n)^2
                                  experts WITHOUT a gate, width 1,856; shared 3,712

Final RMSNorm, an untied head. Loss = mean cross-entropy over the vocabulary
(slice); no auxiliary term (``aux_loss`` reads 0).

The recurrence runs as a chunked scan with its own backward
(``ops/ssd.py``, chunk 128; it takes x', B and C where the convolution leaves
them, side by side in one array), the convolution through ``ops/short_conv.causal_conv``,
the held experts through ``ops/moe_dispatch.share_glu_experts`` in its gate-less
kind (``act="relu2"``, no ``w_gate``: two grouped products a chunk forward and
five backward, as with a gate, the first of them over f columns and not 2 f), attention through ``ops/attention.attention_core`` in groups of
16. The selection bias is the step's to move, as ``models/lfm2.py`` says of its
own (``stepped``; ``SHARE_ROWS_SLACK_LEVELLED`` for the share's chunk).

**The state-space leaves have their own initialisation**, the family's:
``A_log = log(1..64)`` by head, ``D = 1``, ``dt_bias`` the inverse softplus of a
``dt`` drawn log-uniform in [``dt_min``, ``dt_max``] and floored at
``dt_floor``, from the seed; the convolution's taps and bias uniform in
+-1/sqrt(taps) (the depthwise ``Conv1d``'s own default, which the family's
initialiser leaves alone). With this repo's normal(0, 0.02) ``dt`` would be
softplus(0) = 0.69 and ``A`` about -1: every head's state gone within a dozen
positions, and nothing carried from chunk to chunk; and with taps of 0.02
``x'``, ``B`` and ``C`` would be about 0.03 each and the state's term of ``y`` a
thousandth of the ``D`` skip's: a check on the initial parameters would not see
the recurrence at all.

The cut a chip makes without touching a width: ``n_layers`` (the first so many
blocks of ``pattern``), ``experts_held`` with ``expert_offset``, ``vocab``.

**How the blocks are run.** In the published order an ``E`` always follows the
one or two mixers in front of it (``ME`` or ``M*E``), so no two neighbours are
equal and a run of equal BLOCKS would be one block long. The blocks are
therefore grouped into UNITS, an expert block with the mixers before it, and
units of one shape that follow each other are one run: ``params["blocks"]`` is a
list of runs, each the expert block's leaves (``ln``, ``router``, ``bias``,
``experts``, ``shared``: where ``models/moe`` looks for them) beside ``before``,
the list of its mixers' trees, all stacked on a leading axis over the run.
``models/moe.run_layers`` scans a run of several units over the one traced body
(the published model: 13 runs of 2 shapes; the benchmark's ``MEMEM*E``: a scan
over two ``ME`` and one ``M*E``). Every BLOCK is rematerialised by
``models/common.remat_layer`` by itself. An attention block keeps its kernel's
output and row statistics; a state-space block names nothing and keeps nothing
(its backward runs the projection, the convolution and the scan's forward
kernel again, which writes the chunk-boundary states its backward kernel reads:
0.54 GB a block at 4 x 8,192 tokens, alive for one block at a time).
Departures as in ``models/olmoe.py``: float32 parameters and bfloat16 compute on
a TPU, the router's product in float32 at the highest precision.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common, moe
from distributedvolunteercomputing_tpu.models.common import matrix
from distributedvolunteercomputing_tpu.ops import moe_dispatch
from distributedvolunteercomputing_tpu.ops.attention import attention_core, merge_heads, split_heads
from distributedvolunteercomputing_tpu.ops.short_conv import causal_conv
from distributedvolunteercomputing_tpu.ops.ssd import ssd

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
KIND_NAMES = {MAMBA: "mamba", EXPERTS: "experts", ATTENTION: "attention"}
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# what the weights' divisor adds to the chosen scores' sum (the family's public code's)
ROUTE_EPS = 1e-20
# every expert block built here carries the stepped bias: a levelled router's chunk
SHARE_ROWS_SLACK = moe_dispatch.SHARE_ROWS_SLACK_LEVELLED
EXPERT_ACT = "relu2"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Defaults are the published sizes of NVIDIA-Nemotron-3-Nano-30B-A3B."""

    vocab: int = 131072
    max_len: int = 8192  # the sequences a step trains on (published limit: 262,144 positions)
    d_model: int = 2688
    pattern: str = PUBLISHED_PATTERN  # one character a block: M, E or *
    n_layers: int = 0         # how many of the pattern's blocks run, from the first; 0: all
    n_heads: int = 32         # attention
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 64     # the state-space mixer: d_inner = mamba_heads x mamba_head_dim
    mamba_head_dim: int = 64
    n_groups: int = 8
    d_state: int = 128
    conv_taps: int = 4
    chunk: int = 128
    d_expert: int = 1856      # one routed expert's width
    d_shared: int = 3712      # the shared expert's
    n_experts: int = 128      # the router's outputs
    top_k: int = 6
    experts_held: int = 128   # how many of them this chip holds ...
    expert_offset: int = 0    # ... from which on
    routed_scale: float = 2.5
    bias_gamma: float = 0.001  # what a step moves a selection bias by
    rms_eps: float = 1e-5
    dt_min: float = 0.001     # time_step_min / _max / _floor: dt_bias's initialisation only
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    remat: bool = True
    xent_chunk: int = 512

    def __post_init__(self):
        unknown = sorted(set(self.pattern) - set(KIND_NAMES))
        if unknown or not 0 <= self.n_layers <= len(self.pattern) or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r} holds {unknown or 'no unknown kind'}; "
                             f"n_layers={self.n_layers} of its {len(self.pattern)} blocks")
        moe.check_share(self)
        if self.n_heads % self.n_kv_heads or self.mamba_heads % self.n_groups:
            raise ValueError(
                f"{self.n_kv_heads} key/value heads over {self.n_heads} query heads, "
                f"{self.n_groups} groups over {self.mamba_heads} state-space heads: neither may leave a rest")

    @property
    def depth(self) -> int:
        """How many blocks run."""
        return self.n_layers or len(self.pattern)

    @property
    def blocks(self) -> str:
        """The blocks that run, one character each."""
        return self.pattern[:self.depth]

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """One mixer kind a block, as the models that mix kinds list them."""
        return tuple(KIND_NAMES[k] for k in self.blocks)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def runs(self) -> Tuple[Tuple[str, int], ...]:
        """(unit, how many of it in a row), in order. A unit is an expert block
        with the mixers in front of it (``ME``, ``M*E``); mixers that no expert
        block follows (a cut's tail) are a unit by themselves."""
        units: List[str] = []
        unit = ""
        for kind in self.blocks:
            unit += kind
            if kind == EXPERTS:
                units.append(unit)
                unit = ""
        if unit:
            units.append(unit)
        out: List[List] = []
        for unit in units:
            if out and out[-1][0] == unit:
                out[-1][1] += 1
            else:
                out.append([unit, 1])
        return tuple((u, n) for u, n in out)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def _dt_bias_init(rng: jax.Array, cfg: NemotronHConfig) -> jax.Array:
    """The inverse softplus of a ``dt`` a head, log-uniform in [dt_min, dt_max], floored."""
    lo, hi = jnp.log(cfg.dt_min), jnp.log(cfg.dt_max)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(rng, (cfg.mamba_heads,), jnp.float32) * (hi - lo) + lo),
                     cfg.dt_floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def _conv_init(rng: jax.Array, shape: Tuple[int, ...], taps: int) -> jax.Array:
    """Uniform in +-1/sqrt(taps): a depthwise convolution's fan-in is its taps."""
    bound = taps ** -0.5
    return jax.random.uniform(rng, shape, jnp.float32, -bound, bound)


def _block_init(rng: jax.Array, cfg: NemotronHConfig, kind: str) -> common.Params:
    k = jax.random.split(rng, 6)
    d = cfg.d_model
    p: common.Params = {"ln": common.rmsnorm_init(d)}
    if kind == MAMBA:
        h = cfg.mamba_heads
        p.update({
            "w_in": matrix(k[0], (d, cfg.d_inner + cfg.conv_dim + h)),    # [z | xBC | dt]
            "conv_w": _conv_init(k[1], (cfg.conv_taps, cfg.conv_dim), cfg.conv_taps),
            "conv_b": _conv_init(k[2], (cfg.conv_dim,), cfg.conv_taps),
            "dt_bias": _dt_bias_init(k[3], cfg),
            "a_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
            "d_skip": jnp.ones((h,), jnp.float32),
            "norm": common.rmsnorm_init(cfg.d_inner),
            "w_out": matrix(k[4], (cfg.d_inner, d)),
        })
    elif kind == ATTENTION:
        hd = cfg.head_dim
        p.update({
            "wq": matrix(k[0], (d, cfg.n_heads * hd)), "wk": matrix(k[1], (d, cfg.n_kv_heads * hd)),
            "wv": matrix(k[2], (d, cfg.n_kv_heads * hd)), "wo": matrix(k[3], (cfg.n_heads * hd, d)),
        })
    else:
        p.update({
            "router": matrix(k[0], (d, cfg.n_experts)),
            "bias": jnp.zeros((cfg.n_experts,), jnp.float32),  # the step's, not the optimizer's
            "shared": {"w_up": matrix(k[1], (d, cfg.d_shared)), "w_down": matrix(k[2], (cfg.d_shared, d))},
            # the held experts stacked on a leading axis -> sharded over ep (parallel/sharding.py)
            "experts": {"w_up": matrix(k[3], (cfg.experts_held, d, cfg.d_expert)),
                        "w_down": matrix(k[4], (cfg.experts_held, cfg.d_expert, d))},
        })
    return p


def _unit_init(keys: jax.Array, cfg: NemotronHConfig, unit: str) -> common.Params:
    """One unit from its blocks' keys ``[len(unit), 2]``: the expert block's
    leaves at the top (where ``models/moe`` reads ``bias``), its mixers' under
    ``before``; a unit without an expert block holds ``before`` alone."""
    blocks = [_block_init(keys[i], cfg, kind) for i, kind in enumerate(unit)]
    if unit[-1] == EXPERTS:
        return {**blocks[-1], "before": blocks[:-1]}
    return {"before": blocks}


@functools.partial(jax.jit, static_argnums=1)
def init(rng: jax.Array, cfg: NemotronHConfig) -> common.Params:
    """One program for the whole tree. A block's key is its index's; run ``r``
    holds its units stacked, in order."""
    keys = jax.random.split(rng, 3)
    block_keys = jax.random.split(keys[1], cfg.depth)
    blocks, first = [], 0
    for unit, n in cfg.runs:
        span = n * len(unit)
        unit_keys = block_keys[first:first + span].reshape(n, len(unit), *block_keys.shape[1:])
        blocks.append(jax.vmap(functools.partial(_unit_init, cfg=cfg, unit=unit))(unit_keys))
        first += span
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "blocks": blocks,
        "ln_f": common.rmsnorm_init(cfg.d_model),
        "lm_head": matrix(keys[2], (cfg.d_model, cfg.vocab)),
    }


# ---------------------------------------------------------------------------
# the three mixers
# ---------------------------------------------------------------------------


def group_rmsnorm(g: jax.Array, y: jax.Array, groups: int, eps: float) -> jax.Array:
    """RMSNorm over each of the ``groups`` equal parts of the last axis, one
    learned scale a channel."""
    yf = y.astype(jnp.float32).reshape(*y.shape[:-1], groups, -1)
    yf = yf * jax.lax.rsqrt(jnp.mean(yf * yf, axis=-1, keepdims=True) + eps)
    return (yf.reshape(y.shape) * g).astype(y.dtype)


def _mamba(p: common.Params, x: jax.Array, cfg: NemotronHConfig):
    """``x + mixer(norm(x))`` and the scan's carry share (``ops/ssd.ssd``)."""
    dtype = x.dtype
    normed = common.rmsnorm(p["ln"], x, cfg.rms_eps)
    zxd = normed @ p["w_in"].astype(dtype)
    z, xbc, dt = jnp.split(zxd, [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
    xbc = causal_conv(xbc, p["conv_w"], p["conv_b"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    # the scan takes x', B and C where the convolution leaves them, side by side in ``xbc``
    y, carried = ssd(xbc, dt, p["a_log"], p["d_skip"], cfg.n_groups, cfg.d_state, cfg.chunk)
    y = group_rmsnorm(p["norm"]["g"], y * jax.nn.silu(z), cfg.n_groups, cfg.rms_eps)
    return x + y @ p["w_out"].astype(dtype), carried


def _attention(p: common.Params, x: jax.Array, cfg: NemotronHConfig) -> jax.Array:
    dtype = x.dtype
    n = common.rmsnorm(p["ln"], x, cfg.rms_eps)
    q = split_heads(n @ p["wq"].astype(dtype), cfg.n_heads)
    k = split_heads(n @ p["wk"].astype(dtype), cfg.n_kv_heads)
    v = split_heads(n @ p["wv"].astype(dtype), cfg.n_kv_heads)
    a = attention_core(q, k, v, causal=True)       # no rotary: the state-space blocks carry order
    return x + merge_heads(a) @ p["wo"].astype(dtype)


def _relu2_mlp(p: common.Params, h: jax.Array) -> jax.Array:
    dtype = h.dtype
    return jnp.square(jax.nn.relu(h @ p["w_up"].astype(dtype))) @ p["w_down"].astype(dtype)


def _experts(p: common.Params, x: jax.Array, stats: Dict[str, jax.Array], cfg: NemotronHConfig):
    """``x + experts(norm(x))``, the statistics with this block added, and its
    routes ``top_idx`` [S, k] with how many assignments chose each expert ``[E]``."""
    b, t, d = x.shape
    h = common.rmsnorm(p["ln"], x, cfg.rms_eps).reshape(b * t, d)
    top_idx, weights, _ = moe.route(p["router"], h, cfg.top_k, cfg.routed_scale, p["bias"], ROUTE_EPS)
    y, *dispatch = moe_dispatch.share_glu_experts(
        h, top_idx, weights, None, p["experts"]["w_up"], p["experts"]["w_down"],
        cfg.expert_offset, cfg.n_experts, act=EXPERT_ACT, slack=SHARE_ROWS_SLACK,
    )
    x = x + (_relu2_mlp(p["shared"], h) + y).reshape(b, t, d)   # the shared expert: every token, unweighted
    noted, chosen = moe.note_share(stats, top_idx, dispatch, cfg, SHARE_ROWS_SLACK)
    return x, {**stats, **noted}, (top_idx, chosen)


def _unit(p: common.Params, x: jax.Array, stats: Dict[str, jax.Array], cfg: NemotronHConfig,
          unit: str, n: int):
    """One unit of a run of ``n``: its mixers, then its expert block; every
    block rematerialised by itself. (x, running statistics) -> the same and the
    expert block's routes (None for a unit without one)."""
    remat = (lambda fn: common.remat_layer(fn, n)) if cfg.remat else (lambda fn: fn)
    for kind, bp in zip(unit, p["before"]):
        with jax.named_scope(KIND_NAMES[kind]):
            if kind == MAMBA:
                x, carried = remat(functools.partial(_mamba, cfg=cfg))(bp, x)
                stats = {**stats, "ssm_carried": stats["ssm_carried"] + carried}
            else:
                x = remat(functools.partial(_attention, cfg=cfg))(bp, x)
    if unit[-1] != EXPERTS:
        return x, stats, None
    with jax.named_scope("moe"):
        return remat(functools.partial(_experts, cfg=cfg))({k: v for k, v in p.items() if k != "before"}, x, stats)


def loss_and_routes(
    params: common.Params, batch: Dict[str, jax.Array], cfg: NemotronHConfig
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """(loss, metrics, the experts every expert block chose ``[L_sparse, S, k]``);
    see ``models/olmoe.loss_and_routes`` for what the routes are for."""
    tokens = batch["tokens"]
    x = params["wte"][tokens].astype(common.compute_dtype())
    runs = [(functools.partial(_unit, cfg=cfg, unit=unit, n=n), n, unit[-1] == EXPERTS)
            for unit, n in cfg.runs]
    stats = {**moe.zero_share_stats(act_zeros=True, chunks_extra=True),
             "ssm_carried": jnp.zeros((), jnp.float32)}
    # the blocks checkpoint themselves (``_unit``): a unit's checkpoint around them would keep nothing more
    x, stats, routes, counts = moe.run_layers(runs, params["blocks"], x, stats, False, tokens.size, cfg)
    x = common.rmsnorm(params["ln_f"], x, cfg.rms_eps)
    loss = common.lm_xent_chunked(
        x, params["lm_head"], batch["targets"], chunk=cfg.xent_chunk, head_layout="dv"
    )
    metrics = moe.share_metrics(
        loss, loss, jnp.zeros((), jnp.float32), stats, tokens.size, cfg, params, counts)
    # of the (state-space block, sequence, head, chunk boundary) quadruples, the share across
    # which the carried state still counts (``ops/ssd.CARRY_FLOOR``): the ``ssm.scan`` span's
    metrics["ssm_carry_share"] = stats["ssm_carried"] / max(cfg.blocks.count(MAMBA), 1)
    return loss, metrics, routes


def stepped(cfg: NemotronHConfig):
    """What the train step needs to move the selection biases itself."""
    return moe.stepped(cfg.bias_gamma)


def spans(cfg: NemotronHConfig):
    """The spans the train loop records of this step: its routing, and how
    much its scans carry from chunk to chunk, in which form (``ops/ssd.py``'s note)."""
    return {"moe.route": moe.route_span(cfg, act_zeros=True, chunks_extra=True, stepped_bias=True),
            "ssm.scan": common.StepSpan(("ssm_carry_share",), noted={"ssm_form": ("ssd_scan", "form")})}
