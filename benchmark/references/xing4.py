"""Xing4.0-29B-A4B as published
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B ``config.json``,
``model_type`` ``xing4_0``), plain, written from the papers' equations: the
residual path from manifold-constrained hyper-connections (arXiv:2512.24880,
after arXiv:2409.19606), latent attention, the router and YaRN's softmax scale
from DeepSeek-V3 (arXiv:2412.19437) and YaRN (arXiv:2309.00071).

Float32 ``jax.numpy`` at the highest matmul precision: no kernel, no sort, no
grouped matmul, no loss through a custom derivative. A token carries ``n``
residual streams, here as ``X`` [B, T, n, C]; ``X_0`` is the embedding on each.
Every SUBLAYER (a layer's attention, then its FFN) has maps of its own::

    u     = vec(X) / sqrt(mean(vec(X)^2) + hc_eps) * g          over all n C coordinates
    z     = a (u Phi) + b                                       Phi: [n C, n + n + n^2]
    Hpre  = sigmoid(z_pre) [n];  Hpost = 2 sigmoid(z_post) [n]
    Hres  = K_iters(exp(clip(z_res, min, max))) [n, n]          K: each row over its sum, then
                                                                each column over its, iters times
    x_in  = sum_j Hpre_j X_j
    y     = F(rmsnorm(x_in))
    X'_i  = sum_j Hres_ij X_j + Hpost_i y

(``a`` one scalar for each of the three maps). The final norm and the head read
``sum_j X_j``. ``F`` is latent attention (as ``references/glm4_moe_lite.py``
has it, at a key of ``nope + rot`` over a value head of its own width, the
rotary frequencies YaRN's, the scores at ``m^2 / sqrt(nope + rot)`` with ``m =
0.1 mscale_all_dim ln(factor) + 1``), a dense SwiGLU FFN (a layer with ``mlp``)
or the experts (one with ``router``: sigmoid scores over all E, the top ``k``
of ``scores + bias``, weights the chosen scores over their sum (+1e-20) times
``routed_scaling_factor``, the shared expert unweighted beside them; every HELD
expert runs on every token and is masked by the top-k one-hot times the
weight). Loss = mean token cross-entropy; no auxiliary term; the selection
bias's gradient leaf is zeros, as the program's is.

Computed in blocks so that 8,192 tokens fit beside the gradient trees (none
changes a result): the maps and both mixes a chunk of ``HC_CHUNK`` positions at
a time (a float32 ``[8192, 14336]`` array is 470 MB), each chunk's body
checkpointed; attention one head and one block of ``ATTN_BLOCK`` queries at a
time; the dense FFN in chunks of positions; the experts one at a time with a
carried sum; the head in chunks; every layer checkpointed; a run of equal
layers is a ``lax.scan`` over the one layer body.

Not built, here as in the program: the multi-token-prediction module.

``routes`` (``[L_sparse, S, k]``), where given, replaces the reference's own
top-k. ``variant`` swaps one term for what a mistaken implementation would
compute (``VARIANTS``).

It reads the program's parameter tree (``models/xing4.py:init``: ``wte``,
``blocks`` = a list of runs stacked on a leading layer axis, a layer's
``hc_mixer`` / ``hc_ffn`` = ``{norm: {g}, phi, a, b}`` with the columns of
``phi`` and ``b`` ordered pre | post | res row-major, ``ln_f``, ``lm_head``)
because that is what the weights come in; nothing else is shared with the code
under test.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

ATTN_BLOCK = 1024   # queries a score block holds
HEAD_CHUNK = 2048   # positions a chunk of the head's log-probabilities holds
FFN_CHUNK = 2048    # positions a chunk of the dense FFN's gate and up products holds
HC_CHUNK = 1024     # positions a chunk of the residual maps and mixes holds

# one term of the equations computed as a mistaken implementation would
HC_VARIANTS = ("no_sinkhorn", "rows_only", "sinkhorn_1_iter", "res_transposed", "post_not_doubled",
               "pre_softmax", "static_maps", "no_stream_norm", "no_clip", "streams_mean_at_the_end",
               "one_map_a_layer")
ATTENTION_VARIANTS = ("no_yarn_scale", "yarn_scale_on_rope_only", "plain_rope_frequencies",
                      "rope_key_per_head", "rope_on_whole_head", "scale_by_128")
ROUTER_VARIANTS = ("weights_not_renormalised", "no_routed_scaling", "bias_in_weights", "shared_expert_weighted")
VARIANTS = HC_VARIANTS + ATTENTION_VARIANTS + ROUTER_VARIANTS

# published key (scalar) -> attribute of the program's Xing4Config
_PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "first_k_dense_replace": "dense_layers",
    "intermediate_size": "d_ff",
    "moe_intermediate_size": "d_expert",
    "n_shared_experts": "n_shared",
    "num_experts_per_tok": "top_k",
    "n_routed_experts": "experts_held",
    "vocab_size": "vocab",
    "rms_norm_eps": "rms_eps",
    "routed_scaling_factor": "routed_scale",
    "rope_theta": "rope_theta",
    "hc_mult": "hc_mult",
    "hc_sinkhorn_iters": "hc_sinkhorn_iters",
    "hc_eps": "hc_eps",
    "mhc_h_res_clamp_min": "hc_res_clamp_min",
    "mhc_h_res_clamp_max": "hc_res_clamp_max",
}
_YARN_TO_PROGRAM = {
    "factor": "yarn_factor", "original_max_position_embeddings": "yarn_original_len",
    "beta_fast": "yarn_beta_fast", "beta_slow": "yarn_beta_slow", "mscale": "yarn_mscale",
    "mscale_all_dim": "yarn_mscale_all_dim",
}
# what the program cannot vary, so the file must say what the program does
_FIXED = {"attention_bias": False, "norm_topk_prob": True, "topk_method": "noaux_tc", "n_group": 1,
          "topk_group": 1, "tie_word_embeddings": False, "num_nextn_predict_layers": 0, "hidden_act": "silu",
          "scoring_func": "sigmoid", "moe_layer_freq": 1, "ep_size": 1}


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
    yarn = file_cfg["rope_scaling"]
    if yarn.get("type") != "yarn":
        raise ValueError(f"configuration {name}: rope_scaling {yarn.get('type')!r} is not what is built")
    for pub, attr in _YARN_TO_PROGRAM.items():
        same(f"{attr} (rope_scaling.{pub})", getattr(c, attr), yarn[pub])
    same("n_experts (the router's outputs)", c.n_experts, _routed(file_cfg))
    same("expert_offset", c.expert_offset, int(file_cfg["expert_offset"]))
    same("max_len (assumed.seq_len)", c.max_len, int(file_cfg["assumed"]["seq_len"]["value"]))
    same("bias_gamma (assumed.expert_bias)", c.bias_gamma,
         float(file_cfg["assumed"]["expert_bias"]["gamma"]))
    for pub, want in _FIXED.items():
        if file_cfg.get(pub, want) != want:
            raise ValueError(f"configuration {name}: {pub}={file_cfg[pub]!r} is not what is built")
    same("num_key_value_heads (latent attention: a key and a value a query head)", c.n_heads,
         int(file_cfg["num_key_value_heads"]))
    if file_cfg["assumed"]["rotary_pairing"]["value"] != "interleaved":
        raise ValueError(f"configuration {name}: the program turns neighbouring pairs (interleaved)")
    if file_cfg["assumed"]["aux_coefficients"]["load_balancing"] != 0:
        raise ValueError(f"configuration {name}: the program has no auxiliary loss")


def sizes(file_cfg: Dict[str, Any]) -> Dict[str, int]:
    """What the FLOP arithmetic and the data generator need."""
    return {
        "n_layer": int(file_cfg["num_hidden_layers"]),
        "d_model": file_cfg["hidden_size"],
        "seq_len": int(file_cfg["assumed"]["seq_len"]["value"]),
        "vocab": file_cfg["vocab_size"],
    }


def hyper(file_cfg: Dict[str, Any]) -> Dict[str, Any]:
    yarn = file_cfg["rope_scaling"]
    return {
        "heads": int(file_cfg["num_attention_heads"]),
        "latent": int(file_cfg["kv_lora_rank"]),
        "nope": int(file_cfg["qk_nope_head_dim"]),
        "rot": int(file_cfg["qk_rope_head_dim"]),
        "v_dim": int(file_cfg["v_head_dim"]),
        "theta": float(file_cfg["rope_theta"]),
        "eps": float(file_cfg["rms_norm_eps"]),
        "top_k": int(file_cfg["num_experts_per_tok"]),
        "offset": int(file_cfg["expert_offset"]),
        "scale": float(file_cfg["routed_scaling_factor"]),
        "yarn_factor": float(yarn["factor"]), "yarn_len": int(yarn["original_max_position_embeddings"]),
        "beta_fast": float(yarn["beta_fast"]), "beta_slow": float(yarn["beta_slow"]),
        "mscale": float(yarn["mscale"]), "mscale_all_dim": float(yarn["mscale_all_dim"]),
        "streams": int(file_cfg["hc_mult"]), "sinkhorn_iters": int(file_cfg["hc_sinkhorn_iters"]),
        "hc_eps": float(file_cfg["hc_eps"]),
        "clamp": (float(file_cfg["mhc_h_res_clamp_min"]), float(file_cfg["mhc_h_res_clamp_max"])),
    }


def _rmsnorm(g: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 at a factor of 1 or less)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(rot: int, hp: Dict[str, Any]) -> jax.Array:
    """``[rot / 2]``: pair i's angle a position. A pair that turns more than
    ``beta_fast`` times within the original context keeps ``theta^(-2i/rot)``,
    one that turns fewer than ``beta_slow`` times is slowed by ``factor``, the
    pairs between change over linearly in i (arXiv:2309.00071 section 3.2,
    with the public code's whole-number bounds)."""
    def pair_that_turns(times: float) -> float:
        return rot * math.log(hp["yarn_len"] / (times * 2 * math.pi)) / (2 * math.log(hp["theta"]))

    low = max(math.floor(pair_that_turns(hp["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(hp["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    plain = hp["theta"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    slowed = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return plain * (1.0 - slowed) + plain / hp["yarn_factor"] * slowed


def _turn(x: jax.Array, freq: jax.Array, times: float = 1.0) -> jax.Array:
    """``x`` [..., T, R]: the pair of coordinates (2i, 2i+1) of position ``t``
    turned by the angle ``t freq_i``, as a 2 x 2 rotation of each pair (times
    ``times``: a scale on cos and sin)."""
    t, r = x.shape[-2], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]          # [T, R/2]
    pairs = x.reshape(*x.shape[:-1], r // 2, 2)
    rotation = times * jnp.stack([jnp.stack([jnp.cos(angle), -jnp.sin(angle)], axis=-1),
                                  jnp.stack([jnp.sin(angle), jnp.cos(angle)], axis=-1)], axis=-2)
    return jnp.sum(rotation * pairs[..., None, :], axis=-1).reshape(x.shape)


def _attention_head(q: jax.Array, k: jax.Array, v: jax.Array, scale: float) -> jax.Array:
    """ONE head, ``q`` and ``k`` [B, T, D], ``v`` [B, T, Dv] -> [B, T, Dv]: a
    block of queries at a time against every key, an explicit mask ``j <= i``."""
    b, t, d = q.shape
    block = ATTN_BLOCK if t % ATTN_BLOCK == 0 else t
    j = jnp.arange(t)[None, :]

    @jax.checkpoint  # the backward pass recomputes a block's [block, T] scores
    def one_block(qb, i0):
        i = i0 + jnp.arange(block)[:, None]
        scores = qb @ jnp.swapaxes(k, -1, -2) * scale                    # [B, block, T]
        return jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1) @ v

    blocks = jnp.moveaxis(q.reshape(b, t // block, block, d), 1, 0)
    starts = jnp.arange(t // block) * block
    _, out = jax.lax.scan(lambda c, qi: (c, one_block(*qi)), None, (blocks, starts))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, v.shape[-1])


def _swiglu(h: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    gate, up = jnp.split(h @ jnp.concatenate([w_gate, w_up], axis=-1), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down


def _experts(p: Dict[str, jax.Array], h: jax.Array, weight: jax.Array) -> jax.Array:
    """Every held expert on every token of ``h`` [S, d], each scaled by its
    column of ``weight`` [S, held]: a scan over single experts that carries
    their sum, the body checkpointed."""

    @jax.checkpoint
    def one(w, col):
        return col[:, None] * _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])

    total, _ = jax.lax.scan(lambda acc, w_col: (acc + one(*w_col), None),
                            jnp.zeros_like(h), (dict(p), weight.T))
    return total


def _by_positions(fn, chunk: int, *arrays: jax.Array):
    """``fn`` (position-wise over axis 1 of every array, returning one array or
    a tuple of them) a chunk of positions at a time, the body checkpointed."""
    t = arrays[0].shape[1]
    if t % chunk or t == chunk:
        return fn(*arrays)
    cut = lambda a: jnp.moveaxis(a.reshape(a.shape[0], t // chunk, chunk, *a.shape[2:]), 1, 0)
    _, out = jax.lax.scan(lambda c, xs: (c, jax.checkpoint(fn)(*xs)), None, tuple(cut(a) for a in arrays))
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(a.shape[1], t, *a.shape[3:])
    return jax.tree_util.tree_map(join, out)


def stochastic(m: jax.Array, iters: int) -> jax.Array:
    """``m`` [..., n, n] positive: every row over its sum, then every column
    over its, ``iters`` times."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def residual_maps(p: Dict[str, Any], x: jax.Array, hp: Dict[str, Any], variant: Optional[str] = None):
    """A sublayer's maps from the streams ``x`` [B, T, n, C]: ``Hpre`` [B, T, n],
    ``Hpost`` [B, T, n], ``Hres`` [B, T, n, n]."""
    b, t, n, c = x.shape
    u = x.reshape(b, t, n * c)
    if variant != "no_stream_norm":
        u = _rmsnorm(p["norm"]["g"], u, hp["hc_eps"])
    a_pre, a_post, a_res = p["a"][0], p["a"][1], p["a"][2]
    if variant == "static_maps":
        a_pre = a_post = a_res = 0.0
    raw = u @ p["phi"]                                                          # [B, T, n + n + n^2]
    z_pre = a_pre * raw[..., :n] + p["b"][:n]
    z_post = a_post * raw[..., n:2 * n] + p["b"][n:2 * n]
    z_res = (a_res * raw[..., 2 * n:] + p["b"][2 * n:]).reshape(b, t, n, n)
    pre = jax.nn.softmax(z_pre, axis=-1) if variant == "pre_softmax" else jax.nn.sigmoid(z_pre)
    post = (1.0 if variant == "post_not_doubled" else 2.0) * jax.nn.sigmoid(z_post)
    if variant != "no_clip":
        z_res = jnp.clip(z_res, *hp["clamp"])
    m = jnp.exp(z_res)
    if variant == "rows_only":
        res = m / jnp.sum(m, axis=-1, keepdims=True)
    elif variant != "no_sinkhorn":
        res = stochastic(m, 1 if variant == "sinkhorn_1_iter" else hp["sinkhorn_iters"])
    else:
        res = m
    if variant == "res_transposed":
        res = jnp.swapaxes(res, -1, -2)
    return pre, post, res


def _latent_attention(p: Dict[str, Any], n: jax.Array, hp: Dict[str, Any],
                      variant: Optional[str]) -> jax.Array:
    """The mixer's result on the normed input ``n`` [B, T, d] (no residual): as
    ``references/glm4_moe_lite.py``'s, one head's q, k and v alive at a time."""
    b, t, d = n.shape
    heads, latent, nope, rot, v_dim = hp["heads"], hp["latent"], hp["nope"], hp["rot"], hp["v_dim"]
    first = n @ jnp.concatenate([p["wq_a"], p["wkv_a"]], axis=-1)
    cq, c, k_rot = jnp.split(first, [p["wq_a"].shape[-1], p["wq_a"].shape[-1] + latent], axis=-1)
    cq = _rmsnorm(p["q_a_norm"]["g"], cq, hp["eps"])
    c = _rmsnorm(p["kv_a_norm"]["g"], c, hp["eps"])
    m_all = _mscale(hp["yarn_factor"], hp["mscale_all_dim"])
    scale = m_all * m_all / math.sqrt(nope if variant == "scale_by_128" else nope + rot)
    on_tables = _mscale(hp["yarn_factor"], hp["mscale"]) / m_all              # 1 as published
    if variant in ("no_yarn_scale", "yarn_scale_on_rope_only"):
        scale = 1.0 / math.sqrt(nope + rot)
    if variant == "yarn_scale_on_rope_only":
        on_tables = m_all
    plain = variant == "plain_rope_frequencies"

    def freq(r):
        return hp["theta"] ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r) if plain else yarn_frequencies(r, hp)

    by_head = (jnp.moveaxis(p["wq_b"].reshape(-1, heads, nope + rot), 1, 0),      # [H, q rank, nope + rot]
               jnp.moveaxis(p["wkv_b"].reshape(-1, heads, nope + v_dim), 1, 0),   # [H, latent, nope + v]
               p["wo"].reshape(heads, v_dim, d))                                  # [H, v, d]

    @jax.checkpoint
    def one_head(wq, wkv, wo):
        q, kv = cq @ wq, c @ wkv
        k_nope, v = kv[..., :nope], kv[..., nope:]
        # a rotary key of the head's own, taken from W_kvb's output (its value's first coordinates), or the shared one
        k_own = v[..., :rot] if variant == "rope_key_per_head" else k_rot
        if variant == "rope_on_whole_head":
            q = _turn(q, freq(nope + rot), on_tables)
            k = _turn(jnp.concatenate([k_nope, k_own], axis=-1), freq(nope + rot), on_tables)
        else:
            q = jnp.concatenate([q[..., :nope], _turn(q[..., nope:], freq(rot), on_tables)], axis=-1)
            k = jnp.concatenate([k_nope, _turn(k_own, freq(rot), on_tables)], axis=-1)
        return _attention_head(q, k, v, scale) @ wo

    total, _ = jax.lax.scan(lambda acc, w: (acc + one_head(*w), None), jnp.zeros_like(n), by_head)
    return total


def _ffn(p: Dict[str, Any], n2: jax.Array, routes: Optional[jax.Array], hp: Dict[str, Any],
         variant: Optional[str]):
    """The FFN's result on the normed input ``n2`` [B, T, d] (no residual) and
    the routes it used (``[S, k]``; None for a dense layer)."""
    b, t, d = n2.shape
    if "mlp" in p:
        m = p["mlp"]
        return _by_positions(lambda h: _swiglu(h, m["w_gate"], m["w_up"], m["w_down"]), FFN_CHUNK, n2), None
    n2 = n2.reshape(b * t, d)
    scores = jax.nn.sigmoid(n2 @ p["router"])                                   # [S, E]
    biased = scores + jax.lax.stop_gradient(p["bias"])
    if routes is None:
        _, routes = jax.lax.top_k(biased, hp["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(routes, scores.shape[-1], dtype=scores.dtype), axis=1)  # [S, E]
    weight = chosen * (biased if variant == "bias_in_weights" else scores)
    if variant != "weights_not_renormalised":
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    if variant != "no_routed_scaling":
        weight = hp["scale"] * weight
    held = p["experts"]["w_gate"].shape[0]
    y = _experts(p["experts"], n2, weight[:, hp["offset"]:hp["offset"] + held])
    s = p["shared"]
    shared = _swiglu(n2, s["w_gate"], s["w_up"], s["w_down"])
    if variant == "shared_expert_weighted":  # as one more chosen expert, at the mean of the chosen weights
        shared = shared * (jnp.sum(weight, axis=-1, keepdims=True) / hp["top_k"])
    return (shared + y).reshape(b, t, d), routes


def _sublayer(p_hc: Dict[str, Any], g: jax.Array, x: jax.Array, fn, hp: Dict[str, Any],
              variant: Optional[str]):
    """``x`` [B, T, n, C] through one sublayer: the maps and the input a chunk
    of positions at a time, ``fn`` on the whole sequence, the mix a chunk at a
    time. ``fn``: normed input [B, T, C] -> (its result, whatever else)."""
    def maps_and_input(xc):
        pre, post, res = residual_maps(p_hc, xc, hp, variant)
        return jnp.einsum("btj,btjc->btc", pre, xc), post, res

    x_in, post, res = _by_positions(maps_and_input, HC_CHUNK, x)
    y, more = fn(_rmsnorm(g, x_in, hp["eps"]))

    def mix(xc, yc, postc, resc):
        return jnp.einsum("btij,btjc->btic", resc, xc) + postc[..., None] * yc[..., None, :]

    return _by_positions(mix, HC_CHUNK, x, y, post, res), more


def _block(p: Dict[str, Any], x: jax.Array, routes: Optional[jax.Array], hp: Dict[str, Any],
           variant: Optional[str] = None):
    """One layer on the streams ``x`` [B, T, n, C], its FFN's kind read off
    ``p``; returns the routes it used (``[S, k]``; None for a dense layer)."""
    x, _ = _sublayer(p["hc_mixer"], p["ln_mixer"]["g"], x,
                     lambda n: (_latent_attention(p, n, hp, variant), None), hp, variant)
    p_hc = p["hc_mixer"] if variant == "one_map_a_layer" else p["hc_ffn"]
    return _sublayer(p_hc, p["ln_ffn"]["g"], x, lambda n2: _ffn(p, n2, routes, hp, variant), hp, variant)


def _head_loss(x: jax.Array, g: jax.Array, w: jax.Array, targets: jax.Array, eps: float) -> jax.Array:
    """Mean cross-entropy of the final norm and the head ``w`` [d, V], a chunk
    of positions at a time."""
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


def loss(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array, hp: Dict[str, Any],
         routes: Optional[jax.Array] = None, with_routes: bool = False,
         variant: Optional[str] = None):
    """Mean next-token cross-entropy, float32 throughout. ``with_routes`` also
    returns the ``[L_sparse, S, k]`` routes used."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)

        @jax.checkpoint  # the backward pass keeps one layer's streams
        def layer(x, p, given):
            return _block(p, x, given, hp, variant)

        emb = params["wte"][tokens]
        x = jnp.broadcast_to(emb[:, :, None, :], (*emb.shape[:2], hp["streams"], emb.shape[-1]))
        used, first = [], 0
        for run in params["blocks"]:
            n = jax.tree_util.tree_leaves(run)[0].shape[0]
            sparse = "router" in run
            given = routes[first:first + n] if sparse and routes is not None else None
            if n == 1:
                x, out = layer(x, jax.tree_util.tree_map(lambda a: a[0], run),
                               None if given is None else given[0])
                out = None if out is None else out[None]
            elif given is None:
                x, out = jax.lax.scan(lambda x, p: layer(x, p, None), x, run)
            else:
                x, out = jax.lax.scan(lambda x, pg: layer(x, *pg), x, (run, given))
            if sparse:
                used.append(out)
                first += n
        x = jnp.mean(x, axis=2) if variant == "streams_mean_at_the_end" else jnp.sum(x, axis=2)
        total = _head_loss(x, params["ln_f"]["g"], params["lm_head"], targets, hp["eps"])
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
