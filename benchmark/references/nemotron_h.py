"""NVIDIA-Nemotron-3-Nano-30B-A3B as published
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``,
``model_type`` ``nemotron_h``; the block equations are in
``models/nemotron_h.py``'s docstring and are followed here independently), plain.

Float32 ``jax.numpy`` at the highest matmul precision: no kernel, no sort, no
grouped matmul, NO CHUNKED ALGEBRA. Every block is one mixer behind one
RMSNorm, ``x + mixer(norm(x))``:

- ``M``: the input projection's ``[z | xBC | dt]``; the depthwise causal
  convolution as a sum of shifted copies, its bias, SiLU; the Mamba-2
  recurrence ONE POSITION AT A TIME, ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  B_t^T``, ``y_t = S_t C_t + D x_t`` (a ``lax.scan`` over the positions inside a
  ``lax.scan`` over stretches of ``SCAN_STRETCH`` of them whose body is
  checkpointed, so that the backward keeps a state a stretch and not a state a
  position: the stretches change no result and are not the program's chunks'
  algebra, there is no decay matrix and no product over a chunk here); the gate
  ``y * silu(z)`` BEFORE the RMSNorm over each group's channels; the output
  projection.
- ``*``: grouped-query attention, query head ``h`` reading key/value head
  ``h // (heads / kv heads)``, an explicit causal mask, no position encoding.
- ``E``: sigmoid scores over all E, the top ``k`` of ``scores + bias`` (the
  bias enters the choice and nothing else), weights the chosen scores over
  their sum (+1e-20) times ``routed_scaling_factor``; an expert is ``W_down
  relu(W_up n)^2``, no gate; the shared expert the same, on every token,
  unweighted. Every HELD expert runs on every token and is masked by the top-k
  one-hot times the weight; the experts this chip does not hold add nothing,
  here as in the program.

Final RMSNorm, an untied head over the vocabulary slice. Loss = mean token
cross-entropy; no auxiliary term. The selection bias gets no gradient: its
gradient leaf is zeros, as the program's is.

Computed in blocks so that 8,192 tokens fit beside the training state (none
changes a result): attention one query head and one block of ``ATTN_BLOCK``
queries at a time; the experts scanned one at a time with a carried sum; the
shared expert, the mixer's projections and the head in chunks of positions; the
recurrence an eighth of the heads at a time; every block checkpointed, the
mixer's three parts each by itself. The units of a run (the program stacks
them) run one after the other, each from its slice of the stack: a scan over
them would keep the stack's float32 parameters and gradients a second time
(``loss``).

Departures from the published description, here as in the program (the
configuration file's ``assumed`` says why): no rotary embedding in attention;
``d_inner`` = heads x head size; nothing trains the selection bias here (the
rule is the step's, ``models/moe.balance``); no auxiliary balancing term.

``routes`` (``[L_sparse, S, k]`` expert indices), where given, replaces the
reference's own top-k. ``variant`` swaps one term for what a mistaken
implementation would compute (``VARIANTS``).

It reads the program's parameter tree (``models/nemotron_h.py:init``: ``wte``,
``blocks`` = a list of runs of units, each the expert block's leaves beside
``before`` = its mixers' trees, stacked on a leading axis, ``ln_f``,
``lm_head``) because that is what the weights come in; nothing else is shared
with the code under test.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

ATTN_BLOCK = 1024    # queries a score block holds
HEAD_CHUNK = 2048    # positions a chunk of the head's log-probabilities holds
FFN_CHUNK = 2048     # positions a chunk of the shared expert's hidden activations holds
SCAN_STRETCH = 128   # positions between two kept states of the recurrence's backward

# one term of the block equations computed as a mistaken implementation would
VARIANTS = ("no_state_between_chunks", "groups_by_modulo", "dt_without_bias", "no_skip_D",
            "gate_after_norm", "norm_over_all_channels", "conv_bias_left_out", "conv_of_3_taps",
            "no_silu_after_conv", "relu_for_relu2", "gated_expert", "rope_applied",
            "softmax_for_sigmoid", "weights_not_renormalised", "no_routed_scaling", "bias_in_weights",
            "shared_expert_weighted")

# published key (scalar) -> attribute of the program's NemotronHConfig
_PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model",
    "num_hidden_layers": "depth",
    "hybrid_override_pattern": "pattern",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "mamba_num_heads": "mamba_heads",
    "mamba_head_dim": "mamba_head_dim",
    "n_groups": "n_groups",
    "ssm_state_size": "d_state",
    "conv_kernel": "conv_taps",
    "chunk_size": "chunk",
    "moe_intermediate_size": "d_expert",
    "moe_shared_expert_intermediate_size": "d_shared",
    "num_experts_per_tok": "top_k",
    "n_routed_experts": "experts_held",
    "vocab_size": "vocab",
    "norm_eps": "rms_eps",
    "layer_norm_epsilon": "rms_eps",
    "routed_scaling_factor": "routed_scale",
    "time_step_min": "dt_min",
    "time_step_max": "dt_max",
    "time_step_floor": "dt_floor",
}
# what the program cannot vary, so the file must say what the program does
_FIXED = {"attention_bias": False, "use_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
          "use_conv_bias": True, "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
          "norm_topk_prob": True, "n_group": 1, "topk_group": 1, "tie_word_embeddings": False,
          "n_shared_experts": 1, "sliding_window": None, "model_type": "nemotron_h"}


# the state-space leaves' initialisation the program has (``models/nemotron_h.init``), as the file must name it
SSM_INIT = "a_log=log(1..H), d=1, dt_bias=softplus^-1(loguniform), conv=uniform(1/sqrt(taps))"


def _routed(file_cfg: Dict[str, Any]) -> int:
    """The router's outputs: the published count where the file's
    ``n_routed_experts`` is the share held here."""
    return int(file_cfg.get("published", {}).get("n_routed_experts", file_cfg["n_routed_experts"]))


def check_config(program_config: Any, file_cfg: Dict[str, Any]) -> None:
    """The registry's configuration must be the file's, key for key."""
    name = file_cfg["name"]

    def same(what, have, want):
        if have != want:
            raise ValueError(f"configuration {name}: the program runs {what}={have!r}, the file says {want!r}")

    c = program_config
    for pub, attr in _PUBLISHED_TO_PROGRAM.items():
        same(f"{attr} ({pub})", getattr(c, attr), file_cfg[pub])
    same("n_experts (the router's outputs)", c.n_experts, _routed(file_cfg))
    same("expert_offset", c.expert_offset, int(file_cfg["expert_offset"]))
    same("the blocks that run (assumed.blocks_run)", c.blocks, file_cfg["assumed"]["blocks_run"]["value"])
    same("max_len (assumed.seq_len)", c.max_len, int(file_cfg["assumed"]["seq_len"]["value"]))
    same("bias_gamma (assumed.expert_bias)", c.bias_gamma,
         float(file_cfg["assumed"]["expert_bias"]["gamma"]))
    same("d_inner (assumed.d_inner)", c.d_inner, int(file_cfg["assumed"]["d_inner"]["value"]))
    for pub, want in _FIXED.items():
        if file_cfg.get(pub, want) != want:
            raise ValueError(f"configuration {name}: {pub}={file_cfg[pub]!r} is not what is built")
    if file_cfg["assumed"]["rotary"]["value"] != "none":
        raise ValueError(f"configuration {name}: the program's attention applies no rotary embedding")
    if file_cfg["assumed"]["aux_coefficients"]["load_balancing"] != 0:
        raise ValueError(f"configuration {name}: the program has no auxiliary loss")
    if file_cfg["assumed"]["ssm_init"]["value"] != SSM_INIT:
        raise ValueError(f"configuration {name}: the state-space leaves' initialisation is models/nemotron_h.init's")


def sizes(file_cfg: Dict[str, Any]) -> Dict[str, int]:
    """What the FLOP arithmetic and the data generator need."""
    return {
        "n_layer": int(file_cfg["num_hidden_layers"]),
        "d_model": file_cfg["hidden_size"],
        "seq_len": int(file_cfg["assumed"]["seq_len"]["value"]),
        "vocab": file_cfg["vocab_size"],
    }


def hyper(file_cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "heads": int(file_cfg["num_attention_heads"]),
        "kv_heads": int(file_cfg["num_key_value_heads"]),
        "head_dim": int(file_cfg["head_dim"]),
        "ssm_heads": int(file_cfg["mamba_num_heads"]),
        "ssm_head_dim": int(file_cfg["mamba_head_dim"]),
        "groups": int(file_cfg["n_groups"]),
        "state": int(file_cfg["ssm_state_size"]),
        "taps": int(file_cfg["conv_kernel"]),
        "chunk": int(file_cfg["chunk_size"]),       # read by the variant ``no_state_between_chunks`` only
        "blocks": str(file_cfg["assumed"]["blocks_run"]["value"]),
        "theta": float(file_cfg["rope_theta"]),     # read by the variant ``rope_applied`` only
        "eps": float(file_cfg["norm_eps"]),
        "top_k": int(file_cfg["num_experts_per_tok"]),
        "offset": int(file_cfg["expert_offset"]),
        "scale": float(file_cfg["routed_scaling_factor"]),
    }


def _rmsnorm(g: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _by_positions(fn, x, chunk: int) -> jax.Array:
    """``fn`` (position-wise, [B, c, d] -> [B, c, e]; of several arrays where
    ``x`` is a tuple of them) over ``x`` a chunk of positions at a time, the
    body checkpointed."""
    many = isinstance(x, tuple)
    xs = x if many else (x,)
    b, t = xs[0].shape[:2]
    if t % chunk:
        return fn(*xs)
    xs = tuple(jnp.moveaxis(v.reshape(b, t // chunk, chunk, v.shape[-1]), 1, 0) for v in xs)
    _, out = jax.lax.scan(lambda c, xc: (c, jax.checkpoint(fn)(*xc)), None, xs)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, -1)


# ---------------------------------------------------------------------------
# M
# ---------------------------------------------------------------------------


def _recurrence(x, dt, a, b, c, reset_every: int):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``,
    ``S_0 = 0``, one position at a time. The heads come on TWO axes ``[I, J]``
    so that a group's ``B`` and ``C`` reach its heads by broadcasting: ``x``
    [Z, T, I, J, P], ``dt`` [Z, T, I, J], ``a`` [I, J], ``b`` and ``c``
    [Z, T, I or 1, J or 1, N]. The ``I`` slices of heads run one after the
    other, each checkpointed (the heads do not meet: what a slice's backward
    keeps, two states a position over a stretch, is an I-th of all heads').
    ``reset_every``: a mistaken implementation's, the state set to zero every
    so many positions (0: never)."""
    z, t, hi, hj, p = x.shape
    n = b.shape[-1]
    stretch = SCAN_STRETCH if t % SCAN_STRETCH == 0 else t
    at = jnp.arange(t).reshape(t // stretch, stretch)

    def by_stretch(v):
        return jnp.moveaxis(v, 1, 0).reshape(t // stretch, stretch, *v.shape[:1], *v.shape[2:])

    @jax.checkpoint
    def one_slice(x, dt, a, b, c):
        """``x`` [Z, T, J, P], ``dt`` [Z, T, J], ``a`` [J], ``b`` and ``c`` [Z, T, J or 1, N]."""

        def position(s, now):
            x_t, dt_t, b_t, c_t, i = now
            if reset_every:
                s = jnp.where(i % reset_every == 0, 0.0, s)
            s = jnp.exp(dt_t * a)[..., None, None] * s + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
            return s, jnp.sum(s * c_t[..., None, :], axis=-1)

        @jax.checkpoint  # the backward pass recomputes a stretch's states from the one at its start
        def one_stretch(s, xs):
            return jax.lax.scan(position, s, xs)

        xs = (by_stretch(x), by_stretch(dt), by_stretch(b), by_stretch(c), at)
        _, y = jax.lax.scan(one_stretch, jnp.zeros((z, hj, p, n), x.dtype), xs)
        return jnp.moveaxis(y.reshape(t, z, hj, p), 0, 1)

    b, c = (jnp.broadcast_to(v, (z, t, hi, v.shape[3], n)) for v in (b, c))
    slices = tuple(jnp.moveaxis(v, 2, 0) for v in (x, dt)) + (a,) + tuple(jnp.moveaxis(v, 2, 0) for v in (b, c))
    _, y = jax.lax.scan(lambda carry, one: (carry, one_slice(*one)), None, slices)
    return jnp.moveaxis(y, 0, 2)


def _causal_conv(u: jax.Array, w: jax.Array) -> jax.Array:
    """``c_t = sum_j w[j] u_{t - (K - 1 - j)}``, zeros before the start; ``u`` [Z, T, C], ``w`` [K, C]."""
    k, t = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * w[j] for j in range(k))


def _mamba(p: Dict[str, Any], n: jax.Array, hp: Dict[str, Any], variant: Optional[str]) -> jax.Array:
    """The mixer on the normed stream ``n`` [Z, T, d], in three parts each
    checkpointed by itself (what comes before the recurrence, the recurrence,
    what comes after it), so that the block's backward holds one part's
    ``[T, 10,304]`` float32 products at a time."""
    z_, t, _ = n.shape
    h, hd, g, ns = hp["ssm_heads"], hp["ssm_head_dim"], hp["groups"], hp["state"]
    d_inner = h * hd
    # head h = i (H / G) + j reads group i = h // (H / G); the mistaken h = i G + j reads group j = h % G
    two = (h // g, g) if variant == "groups_by_modulo" else (g, h // g)

    @jax.checkpoint
    def before(n, w_in, taps, conv_b, dt_bias):
        # the three streams of [z | xBC | dt], each from its own columns (one [T, 10,304] product fewer to hold)
        gate = _by_positions(lambda v: v @ w_in[:, :d_inner], n, FFN_CHUNK)
        xbc = _by_positions(lambda v: v @ w_in[:, d_inner:-h], n, FFN_CHUNK)
        dt = n @ w_in[:, -h:]
        if variant == "conv_of_3_taps":
            taps = taps[1:]
        xbc = _causal_conv(xbc, taps)
        if variant != "conv_bias_left_out":
            xbc = xbc + conv_b
        if variant != "no_silu_after_conv":
            xbc = jax.nn.silu(xbc)
        b = xbc[..., d_inner:d_inner + g * ns].reshape(z_, t, g, ns)
        c = xbc[..., d_inner + g * ns:].reshape(z_, t, g, ns)
        dt = jax.nn.softplus(dt if variant == "dt_without_bias" else dt + dt_bias)
        return gate, xbc[..., :d_inner].reshape(z_, t, *two, hd), b, c, dt.reshape(z_, t, *two)

    gate, x, b, c, dt = before(n, p["w_in"], p["conv_w"], p["conv_b"], p["dt_bias"])
    # a group's B and C reach its heads by broadcasting along the other head axis
    b, c = (b[:, :, None], c[:, :, None]) if variant == "groups_by_modulo" else (b[:, :, :, None], c[:, :, :, None])
    y = _recurrence(x, dt, -jnp.exp(p["a_log"]).reshape(two), b, c,
                    hp["chunk"] if variant == "no_state_between_chunks" else 0)
    groups = 1 if variant == "norm_over_all_channels" else g

    def after(y, x, gate):
        rows = y.shape[1]
        if variant != "no_skip_D":
            y = y + (p["d_skip"].reshape(*two, 1) * x.reshape(z_, rows, *two, hd)).reshape(z_, rows, d_inner)

        def normed(v):
            v = v.reshape(z_, rows, groups, -1)
            return (v / jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True) + hp["eps"])).reshape(z_, rows, d_inner)

        if variant == "gate_after_norm":
            y = normed(y) * p["norm"]["g"] * jax.nn.silu(gate)
        else:
            y = normed(y * jax.nn.silu(gate)) * p["norm"]["g"]
        return y @ p["w_out"]

    return _by_positions(after, (y.reshape(z_, t, d_inner), x.reshape(z_, t, d_inner), gate), FFN_CHUNK)


# ---------------------------------------------------------------------------
# *
# ---------------------------------------------------------------------------


def _turn(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over all of ``x`` [..., T, D], half-split pairs (i, i + D/2)."""
    t, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention_head(q: jax.Array, k: jax.Array, v: jax.Array, scale: float) -> jax.Array:
    """ONE head, [B, T, D] each -> [B, T, D]: a block of queries at a time
    against every key, an explicit mask ``j <= i``."""
    b, t, d = q.shape
    block = ATTN_BLOCK if t % ATTN_BLOCK == 0 else t
    j = jnp.arange(t)[None, :]

    @jax.checkpoint  # the backward pass recomputes a block's [block, T] scores
    def one_block(qb, i0):
        i = i0 + jnp.arange(block)[:, None]
        scores = qb @ jnp.swapaxes(k, -1, -2) * scale
        return jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1) @ v

    blocks = jnp.moveaxis(q.reshape(b, t // block, block, d), 1, 0)
    starts = jnp.arange(t // block) * block
    _, out = jax.lax.scan(lambda c, qi: (c, one_block(*qi)), None, (blocks, starts))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, d)


def _attention(p: Dict[str, Any], n: jax.Array, hp: Dict[str, Any], variant: Optional[str]) -> jax.Array:
    heads, kv, hd = hp["heads"], hp["kv_heads"], hp["head_dim"]
    d = n.shape[-1]
    k = jnp.moveaxis((n @ p["wk"]).reshape(*n.shape[:2], kv, hd), 2, 0)       # [KV, B, T, D]
    v = jnp.moveaxis((n @ p["wv"]).reshape(*n.shape[:2], kv, hd), 2, 0)
    if variant == "rope_applied":
        k = _turn(k, hp["theta"])
    by_head = (jnp.moveaxis(p["wq"].reshape(d, heads, hd), 1, 0), p["wo"].reshape(heads, hd, d),
               jnp.arange(heads) // (heads // kv))

    @jax.checkpoint
    def one_head(wq, wo, group):
        q = n @ wq
        if variant == "rope_applied":
            q = _turn(q, hp["theta"])
        return _attention_head(q, k[group], v[group], 1.0 / math.sqrt(hd)) @ wo

    total, _ = jax.lax.scan(lambda acc, w: (acc + one_head(*w), None), jnp.zeros_like(n), by_head)
    return total


# ---------------------------------------------------------------------------
# E
# ---------------------------------------------------------------------------


def _relu2_mlp(h: jax.Array, w_up: jax.Array, w_down: jax.Array, variant: Optional[str]) -> jax.Array:
    up = h @ w_up
    hidden = jax.nn.relu(up)
    if variant != "relu_for_relu2":
        hidden = hidden * hidden
    if variant == "gated_expert":   # nothing to gate with: the up product once more
        hidden = hidden * up
    return hidden @ w_down


def _experts(p: Dict[str, jax.Array], h: jax.Array, weight: jax.Array, variant) -> jax.Array:
    """Every held expert on every token of ``h`` [S, d], each scaled by its
    column of ``weight`` [S, held]: a scan over single experts that carries
    their sum, the body checkpointed."""

    @jax.checkpoint
    def one(w, col):
        return col[:, None] * _relu2_mlp(h, w["w_up"], w["w_down"], variant)

    total, _ = jax.lax.scan(lambda acc, w_col: (acc + one(*w_col), None),
                            jnp.zeros_like(h), (dict(p), weight.T))
    return total


def _expert_block(p: Dict[str, Any], n: jax.Array, routes: Optional[jax.Array], hp: Dict[str, Any],
                  variant: Optional[str]):
    b, t, d = n.shape
    flat = n.reshape(b * t, d)
    logits = flat @ p["router"]
    scores = jax.nn.softmax(logits, -1) if variant == "softmax_for_sigmoid" else jax.nn.sigmoid(logits)
    biased = scores + jax.lax.stop_gradient(p["bias"])
    if routes is None:
        _, routes = jax.lax.top_k(biased, hp["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(routes, scores.shape[-1], dtype=scores.dtype), axis=1)
    weight = chosen * (biased if variant == "bias_in_weights" else scores)
    if variant != "weights_not_renormalised":
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    if variant != "no_routed_scaling":
        weight = hp["scale"] * weight
    held = p["experts"]["w_up"].shape[0]
    y = _experts(p["experts"], flat, weight[:, hp["offset"]:hp["offset"] + held], variant)
    shared = _by_positions(
        lambda v: _relu2_mlp(v, p["shared"]["w_up"], p["shared"]["w_down"], variant), n, FFN_CHUNK)
    shared = shared.reshape(b * t, d)
    if variant == "shared_expert_weighted":  # as one more chosen expert, at the mean of the chosen weights
        shared = shared * (jnp.sum(weight, axis=-1, keepdims=True) / hp["top_k"])
    return (shared + y).reshape(b, t, d), routes


def _head_loss(x: jax.Array, g: jax.Array, w: jax.Array, targets: jax.Array, eps: float) -> jax.Array:
    b, t, d = x.shape
    chunk = HEAD_CHUNK if t % HEAD_CHUNK == 0 else t

    @jax.checkpoint
    def one(xc, tc):
        logp = jax.nn.log_softmax(_rmsnorm(g, xc, eps) @ w, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tc[..., None], axis=-1))

    xs = jnp.moveaxis(x.reshape(b, t // chunk, chunk, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(b, t // chunk, chunk), 1, 0)
    total, _ = jax.lax.scan(lambda acc, xt: (acc + one(*xt), None), jnp.zeros((), x.dtype), (xs, ts))
    return total / (b * t)


def _units(blocks: str):
    """The units of the blocks that run: an expert block with the mixers
    before it (``ME``, ``M*E``); trailing mixers are a unit of their own."""
    units, unit = [], ""
    for kind in blocks:
        unit += kind
        if kind == "E":
            units.append(unit)
            unit = ""
    return units + ([unit] if unit else [])


def loss(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array, hp: Dict[str, Any],
         routes: Optional[jax.Array] = None, with_routes: bool = False,
         variant: Optional[str] = None):
    """Mean next-token cross-entropy, float32 throughout. ``with_routes`` also
    returns the ``[L_sparse, S, k]`` routes used."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        eps = hp["eps"]

        # every block checkpointed: the backward pass keeps one block's activations
        @jax.checkpoint
        def mamba(x, p):
            return x + _mamba(p, _rmsnorm(p["ln"]["g"], x, eps), hp, variant)

        @jax.checkpoint
        def attention(x, p):
            return x + _attention(p, _rmsnorm(p["ln"]["g"], x, eps), hp, variant)

        @jax.checkpoint
        def experts(x, p, given):
            y, used = _expert_block(p, _rmsnorm(p["ln"]["g"], x, eps), given, hp, variant)
            return x + y, used

        def unit_body(unit):
            def body(x, p, given):
                for kind, bp in zip(unit, p["before"]):
                    x = mamba(x, bp) if kind == "M" else attention(x, bp)
                if unit[-1] != "E":
                    return x, None
                return experts(x, {k: v for k, v in p.items() if k != "before"}, given)
            return body

        # the units in order, and how many of each shape follow each other: the runs the tree is stacked in
        units = _units(hp["blocks"])
        x = params["wte"][tokens]
        used, first, at = [], 0, 0
        for run in params["blocks"]:
            n = jax.tree_util.tree_leaves(run)[0].shape[0]
            unit = units[at]
            at += n
            body = unit_body(unit)
            sparse = unit[-1] == "E"
            given = routes[first:first + n] if sparse and routes is not None else None
            # One unit after the other, NOT a scan over the run: compiled for a described v5e
            # (experiments/check_memory.py, PR 48) a scan over the two stacked ME units holds the run's
            # 1.1 GB of float32 parameters and as much of gradients again inside the loop, 4.13 GB of
            # temporaries where the units in a row take 3.11, and the check has 16.9 GB for 16.80 and 15.78.
            outs = []
            for i in range(n):
                x, out = body(x, jax.tree_util.tree_map(lambda a: a[i], run), None if given is None else given[i])
                outs.append(out)
            if sparse:
                used.append(jnp.stack(outs))
                first += n
        total = _head_loss(x, params["ln_f"]["g"], params["lm_head"], targets, eps)
        if not with_routes:
            return total
        k = hp["top_k"]
        return total, jnp.concatenate(used) if used else jnp.zeros((0, tokens.size, k), jnp.int32)


def make_loss_and_grad(file_cfg: Dict[str, Any]):
    """``(params, tokens, targets[, routes]) -> (loss, grads)`` for this configuration."""
    hp = hyper(file_cfg)

    def fn(params, tokens, targets, routes=None) -> Tuple[jax.Array, Any]:
        return jax.value_and_grad(loss)(params, tokens, targets, hp, routes)

    return fn
