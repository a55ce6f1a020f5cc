"""What the expert families share (``models/laguna.py``, ``smallthinker.py``,
``lfm2.py``, ``glm4_moe_lite.py``, ``sdar_moe.py``; ``models/olmoe.py`` holds every expert and
takes the balancing term only): a config brings ``top_k``, ``n_experts``,
``experts_held`` and ``expert_offset``, its expert layers call
``ops/moe_dispatch.share_glu_experts`` for the held experts' part of the sum,
and this module keeps

- the check that the held experts are a slice of the router's (``check_share``);
- the router (``route``: sigmoid scores, or a softmax over all E), with the selection bias that the STEP moves
  where a layer carries the leaf ``bias`` [E] beside ``router`` [d, E];
- the share's running statistics over the expert layers (``zero_share_stats``,
  ``note_share``), the balancing term of a router trained by an auxiliary loss
  (``balance_loss``), the step's metrics (``share_metrics``) and the
  declaration of the ``moe.route`` span that carries them (``route_span``);
- the runs of equal layers (``run_layers``) and the stepped bias's rule
  (``balance``, ``stepped``) for the families that have them.

What a family has is read from the statistics' own keys and from the arguments
it passes, never from which family is calling.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common
from distributedvolunteercomputing_tpu.ops.moe_dispatch import share_rows_bound

# the step's metric that ``stepped`` reads: per expert layer, how many of the
# step's assignments chose each expert ``[L_sparse, E]``
COUNTS = "moe_expert_counts"


def check_share(cfg) -> None:
    """``top_k`` of the router's outputs, and the held experts a slice of them."""
    if not 1 <= cfg.top_k <= cfg.n_experts:
        raise ValueError(f"top_k={cfg.top_k} must be in [1, n_experts={cfg.n_experts}]")
    if not (0 <= cfg.expert_offset and 1 <= cfg.experts_held
            and cfg.expert_offset + cfg.experts_held <= cfg.n_experts):
        raise ValueError(
            f"experts {cfg.expert_offset}..{cfg.expert_offset + cfg.experts_held} "
            f"are not a slice of the {cfg.n_experts}")


def route(p_router: jax.Array, h: jax.Array, top_k: int, routed_scale: float,
          bias: Optional[jax.Array] = None, eps: float = 0.0, score: str = "sigmoid"):
    """Router of one layer: ``h`` [S, d] -> (top_idx [S, k], weights [S, k]
    float32, scores [S, E] float32). Scores from a float32 product at the
    highest precision, each expert's own ``sigmoid`` or (``score`` "softmax",
    SDAR's Qwen3-MoE router) a softmax over all E; the weights are the chosen
    experts' own scores,
    normalised to sum to 1 (+``eps`` in the divisor, as the family's public
    code has it: 1e-6 LFM2, 1e-20 GLM-4.7-Flash, none Laguna), times
    ``routed_scale``. A selection ``bias`` [E] is added for the CHOICE of the
    k and for nothing else."""
    logits = jnp.dot(
        h.astype(jnp.float32), p_router, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    scores = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[score](logits)
    if bias is None:
        top_scores, top_idx = jax.lax.top_k(scores, top_k)
    else:
        _, top_idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
        top_scores = jnp.take_along_axis(scores, top_idx, axis=-1)
    scaled = routed_scale * top_scores
    total = jnp.sum(top_scores, axis=-1, keepdims=True)
    weights = scaled / ((total + eps) if eps else total)
    return top_idx, weights, scores


def zero_share_stats(balanced: int = 0, act_zeros: bool = False,
                     chunks_extra: bool = False) -> Dict[str, jax.Array]:
    """The share's statistics before the first expert layer. ``balanced``: the
    router's outputs E where an auxiliary loss balances it; ``act_zeros``:
    ReLU-gated experts; ``chunks_extra``: a chunk sized by the family's own
    ``slack``, whose second chunks are worth counting."""
    zero = jnp.zeros((), jnp.float32)
    stats = {}
    if balanced:
        stats["choices"] = jnp.zeros((balanced,), jnp.float32)  # sum over layers of f_e
        stats["probs"] = jnp.zeros((balanced,), jnp.float32)    # sum over layers of P_e
    stats.update({
        "load_max": zero,    # fullest held expert of any layer, rows
        "rows_held": zero,   # assignments on held experts, all layers
        "rows_moved": zero,  # rows the dispatch gathered to its grouped matmuls, all layers
        "dropped": zero,     # held assignments no grouped matmul computed
    })
    if act_zeros:
        stats["act_zeros"] = zero     # entries of the held rows' gate that the ReLU set to zero
    if chunks_extra:
        stats["chunks_extra"] = zero  # chunks the dispatch ran beyond one a layer, all layers
    return stats


def note_share(stats: Dict[str, jax.Array], top_idx: jax.Array, dispatch, cfg,
               slack: Optional[float] = None, probs: Optional[jax.Array] = None,
               scores: Optional[jax.Array] = None):
    """The running statistics with one expert layer added, and how many of its
    assignments chose each expert ``[E]``. ``dispatch``: what
    ``ops/moe_dispatch.share_glu_experts`` returned beside ``y``
    (``group_sizes``, ``dropped``, ``moved``, ``act_zeros``), at ``slack``.
    Statistics with ``choices`` / ``probs`` take the router's distribution
    over all E ``[S, E]``: ``probs`` as it is, or ``scores`` normalised here."""
    group_sizes, dropped, moved, act_zeros = dispatch
    chosen = jnp.sum(jax.nn.one_hot(top_idx, cfg.n_experts, dtype=jnp.float32), axis=(0, 1))
    load = group_sizes.astype(jnp.float32)
    new = {}
    if "choices" in stats:
        new["choices"] = stats["choices"] + chosen / top_idx.shape[0]
        if scores is not None:
            probs = scores / jnp.sum(scores, -1, keepdims=True)
        new["probs"] = stats["probs"] + jnp.mean(probs, axis=0)
    new.update({
        "load_max": jnp.maximum(stats["load_max"], jnp.max(load)),
        "rows_held": stats["rows_held"] + jnp.sum(load),
        "rows_moved": stats["rows_moved"] + moved.astype(jnp.float32),
        "dropped": stats["dropped"] + dropped.astype(jnp.float32),
    })
    if "act_zeros" in stats:
        new["act_zeros"] = stats["act_zeros"] + act_zeros.astype(jnp.float32)
    if "chunks_extra" in stats:
        # ``moved`` is whole chunks of ``cap`` rows (all S k, at most one chunk's, with every expert held)
        cap = share_rows_bound(top_idx.shape[0], cfg.top_k, cfg.experts_held, cfg.n_experts, slack)
        new["chunks_extra"] = stats["chunks_extra"] + ((moved + cap - 1) // cap - 1).astype(jnp.float32)
    return new, chosen


def balance_loss(stats: Dict[str, jax.Array], n_layers: int, n_experts: int) -> jax.Array:
    """``E sum_e f_e P_e`` of the ``n_layers`` expert layers the statistics
    hold: means over layers and tokens first, product after."""
    return n_experts * jnp.sum((stats["choices"] / n_layers) * (stats["probs"] / n_layers))


def run_layers(runs, blocks, x: jax.Array, stats: Dict[str, jax.Array], remat: bool,
               n_tokens: int, cfg):
    """``x`` through the model's RUNS of equal layers. ``runs``: per run its
    one layer's body ``(p, x, stats) -> (x, stats, (top_idx, chosen) | None)``,
    its length and whether its layers route; ``blocks``: per run the layers'
    parameters stacked on a leading axis. A run of one layer is traced by
    itself, a longer one is a ``lax.scan`` over the one body, rematerialised
    by ``common.remat_layer`` as it stands. Returns ``x``, the statistics, the
    expert layers' routes ``[L_sparse, S, k]`` and their experts' assignment
    counts ``[L_sparse, E]``."""
    routes, counts = [], []
    for (body, n, sparse), p in zip(runs, blocks):
        layer = common.remat_layer(body, n) if remat else body
        if n == 1:  # its own shape: no loop around it
            x, stats, out = layer(jax.tree_util.tree_map(lambda a: a[0], p), x, stats)
            out = jax.tree_util.tree_map(lambda a: a[None], out)
        else:
            def step(carry, p, layer=layer):
                x, stats, out = layer(p, *carry)
                return (x, stats), out

            (x, stats), out = jax.lax.scan(step, (x, stats), p)
        if sparse:
            routes.append(out[0])
            counts.append(out[1])
    if routes:
        routes, counts = jnp.concatenate(routes), jnp.concatenate(counts)
    else:
        routes = jnp.zeros((0, n_tokens, cfg.top_k), jnp.int32)
        counts = jnp.zeros((0, cfg.n_experts), jnp.float32)
    return x, stats, routes, counts


def biases(params: common.Params) -> jax.Array:
    """Every expert layer's selection bias ``[L_sparse, E]``, in layer order."""
    runs = [p["bias"] for p in params["blocks"] if "bias" in p]
    return jnp.concatenate(runs) if runs else jnp.zeros((1, 1), jnp.float32)  # a dense cut: one 0


def bias_steps(counts: jax.Array) -> jax.Array:
    """``sign(mean(c) - c_e)`` a layer ``[L_sparse, E]``: +1 for an expert the
    step sent less than an even share, -1 for more, 0 for exactly it."""
    return jnp.sign(jnp.mean(counts, axis=-1, keepdims=True) - counts)


def share_metrics(loss: jax.Array, lm: jax.Array, aux: jax.Array, stats: Dict[str, jax.Array],
                  n_tokens: int, cfg, params: Optional[common.Params] = None,
                  counts: Optional[jax.Array] = None) -> Dict[str, jax.Array]:
    """The step's metrics of a model that holds a share of its experts; with
    ``counts`` (``run_layers``'s, and the ``params`` they were counted under),
    those of routers that carry the stepped bias as well."""
    metrics = {
        "loss": loss, "lm_loss": lm, "aux_loss": aux,
        # over the held experts: the fullest of any layer and the even share of
        # a layer's S k assignments; the assignments on held experts and the
        # rows the dispatch gathered for them, summed over the expert layers;
        # held assignments no grouped matmul computed
        "moe_load_max": stats["load_max"],
        "moe_load_mean": jnp.asarray(n_tokens * cfg.top_k / cfg.n_experts, jnp.float32),
        "moe_rows_held": stats["rows_held"],
        "moe_rows_moved": stats["rows_moved"],
        "moe_dropped": stats["dropped"],
    }
    if "act_zeros" in stats:
        # of the held assignments' d_expert hidden activations each, the share
        # whose gate the ReLU set to exactly zero (what a sparse down-projection
        # could skip); the mask is the activation's own
        metrics["moe_act_zero_share"] = stats["act_zeros"] / jnp.maximum(
            stats["rows_held"] * cfg.d_expert, 1.0)
    if "chunks_extra" in stats:
        # how many chunks beyond one a layer the family's bound cost this step
        metrics["moe_chunks_extra"] = stats["chunks_extra"]
    if counts is not None:
        bias = biases(params)
        # the selection biases this step chose with, over layers and experts,
        # and how many of them the step's rule then moves
        metrics["moe_bias_max"] = jnp.max(bias)
        metrics["moe_bias_min"] = jnp.min(bias)
        metrics["moe_bias_moved"] = jnp.sum(bias_steps(counts) != 0).astype(jnp.float32)
        metrics[COUNTS] = counts  # the step's own: ``stepped`` reads it, the loop never sees it
    return metrics


def route_span(cfg, share: bool = True, act_zeros: bool = False, chunks_extra: bool = False,
               stepped_bias: bool = False, more: Tuple[str, ...] = ()) -> common.StepSpan:
    """The declaration of a family's ``moe.route`` span (``ModelBundle.spans``).
    Its keys: what the family's step returns of ``share_metrics``'s (``share``:
    it counts the rows of a held share; the next two as it starts its
    statistics, ``zero_share_stats``; ``stepped_bias``: its step moves
    selection biases) and ``more`` of its own. Its attributes: ``experts_held``
    where the config says how many, which stream the layer's router reads
    (``router_site``: its input, before attention, or what attention made of
    it) and, for a config that lists its layers' token mixers, how many of each kind."""
    keys = ["moe_load_max", "moe_load_mean", "moe_dropped"]
    if share:
        keys += ["moe_rows_held", "moe_rows_moved"]
    if chunks_extra:
        keys.append("moe_chunks_extra")
    if act_zeros:
        keys.append("moe_act_zero_share")
    if stepped_bias:
        keys += ["moe_bias_max", "moe_bias_min", "moe_bias_moved"]
    attrs = {}
    if getattr(cfg, "experts_held", None) is not None:
        attrs["experts_held"] = int(cfg.experts_held)
    attrs["router_site"] = getattr(cfg, "router_site", "post_attention")
    layer_types = getattr(cfg, "layer_types", ())
    attrs.update({f"mixers_{kind}": layer_types.count(kind) for kind in sorted(set(layer_types))})
    return common.StepSpan((*keys, "aux_loss", "lm_loss", *more), attrs)


def is_bias(path: Tuple) -> bool:
    return getattr(path[-1], "key", None) == "bias"


def balance(params: common.Params, counts: jax.Array, gamma: float) -> common.Params:
    """``params`` with every selection bias moved one step of the rule
    ``b_e <- b_e + gamma sign(mean(c) - c_e)`` (auxiliary-loss-free balancing,
    arXiv:2408.15664); the other leaves as they came. ``counts``
    ``[L_sparse, E]`` in layer order, as the step's metrics give them."""
    blocks, first = [], 0
    for p in params["blocks"]:
        if "bias" in p:
            n = p["bias"].shape[0]
            p = {**p, "bias": p["bias"] + gamma * bias_steps(counts[first:first + n])}
            first += n
        blocks.append(p)
    return {**params, "blocks": blocks}


def stepped(gamma: float):
    """What the train step needs to move the selection biases itself
    (``models/common.SteppedLeaves``)."""
    return common.SteppedLeaves(
        signal=COUNTS,
        owns=lambda params: jax.tree_util.tree_map_with_path(lambda path, _: is_bias(path), params),
        rule=lambda params, counts: balance(params, counts, gamma),
    )
