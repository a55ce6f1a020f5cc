"""Parameter partition rules: Megatron-style tensor parallelism by path.

The model zoo stores params as plain nested dicts/lists (models/*.py), so
partition specs are assigned by matching the pytree *path* against a small
generic rule table that covers every transformer in the zoo:

- column-parallel (shard the OUTPUT feature dim over ``tp``): qkv / wq / wk /
  wv / wg projections, mlp_in / w_gate / w_up — the matmul that *fans out*;
- row-parallel (shard the INPUT feature dim over ``tp``): attn_out / wo /
  mlp_out / w_down — the matmul that *fans in*, after which XLA emits an
  all-reduce of the activations over ICI;
- everything else (embeddings, norms, biases of row-parallel layers, LoRA
  adapters — rank ~8, not worth slicing) is replicated.

What a transformer layer costs over ``tp`` in a rematerialised train step
(read off the compiled ``dp=2,tp=2`` gpt2_large step, tests/test_tpu_compile.py,
and its trace, PERF.md section 5): FOUR all-reduces of a replica's [B/dp, T, d]
activations — after attn_out and mlp_out in the forward, and for the input
cotangent of each column-parallel product (mlp_in, qkv) in the backward. That
is the layout's price, and since PR 57 about half of it is paid beside other
work: a model whose block couples no two rows (gpt2) runs each replica's rows
as TWO independent streams inside the one scanned, rematerialised layer body
(``models/common.scan_blocks``, ``ops/attention.tp_streams``), so each of the
four is two all-reduces of [B/2dp, T, d], and the step is compiled with
asynchronous collectives where the mesh has a ``tp`` axis
(``parallel/train_step.step_compiler_options``): at each of the four sites the
stream that comes first has its all-reduce running as a start / done pair
beside the other stream's products and attention kernel; the stream that comes
second has nothing of the layer left to hide behind and stays on the
instruction stream. With one row a replica, an odd count, or ``tp`` 1 there is
one stream and the jaxpr is the one it was; with ``tp`` 1 the compiled program
too, while a one-stream step over ``tp`` > 1 (those counts, and every model
``scan_blocks`` is not told to split) is still compiled with the ``tp``
options: its all-reduces become pairs with nothing of their own rows to run
beside (what that costs: PERF.md section 6, PR 57). A fifth, after
attn_out again in the backward's recomputed forward, was ours and is gone:
where ``tp`` > 1 the layer's checkpoint keeps attn_out's reduced result
(``models/common.remat_layer``, ``ops/attention.keep_tp_reduced``; mlp_out's
needs nothing, the recomputed forward never wants the layer's output). The
fused ``qkv`` leaf adds no activation traffic
to it, though its stored sharding alone would: [d, 3d] cut over ``tp`` on 3d
in contiguous parts gives chip 0 of a pair all of q and half of k, while
attention runs heads 0..H/tp-1 of each of q, k and v there. The stored leaf
keeps that layout (other volunteers average it leaf by leaf, checkpoints hold
it); the head-aligned view — [d, 3, H, hd] laid out over ``tp`` on H — is
taken inside the step by ``models/common.qkv_heads``, so what crosses the
link for it is the weight's shards (an all-gather of the bf16 weight in each
forward, one of its gradient), never q, k, v or their cotangents.

This is the build-side TP addition documented in SURVEY.md §2 (reference is
volunteer-DP only; TP within a slice is what `pjit` gives us for free).

A rule only applies when the sharded dim is divisible by the mesh axis size;
otherwise that dim silently falls back to replicated (e.g. GPT-2's vocab
50257 is prime — the tied embedding stays replicated on any mesh).
"""

from __future__ import annotations

import re
from typing import Any, List, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (path regex, spec). First match wins; paths look like "blocks/qkv/w"
# (stacked scan-over-layers layout: leaves carry a leading n_layers axis; a
# model scanned by period, models/smallthinker.py, has "blocks/global/wq"
# [P, d, q] and "blocks/sliding/wq" [P, 3, d, q]: the period axes lead).
# Column-parallel weights are [L, d_in, d_out] → sharded on d_out; their
# biases [L, d_out] → sharded on d_out. Row-parallel weights are sharded on
# d_in; their biases are full-size → replicated. Specs below are written for
# the TRAILING dims and right-aligned by _fit_spec, so the same rule covers a
# stacked leaf and an unstacked one (e.g. lm_head, which has no layer axis).
_RULES: List[Tuple[str, P]] = [
    # OLMoE's SwiGLU expert stacks [E, d, f] / [E, f, d] (models/olmoe.py):
    # before the dense w_gate / w_up / w_down rules, which the names also match.
    (r".*/experts/(w_gate|w_up)$", P("ep", None, "tp")),
    (r".*/experts/w_down$", P("ep", "tp", None)),
    (r".*/(qkv|mlp_in)/w$", P(None, "tp")),
    (r".*/(qkv|mlp_in)/b$", P("tp")),
    (r".*/(attn_out|mlp_out)/w$", P("tp", None)),
    (r".*/(wq|wk|wv|wg|w_gate|w_up)$", P(None, "tp")),  # wg: Laguna's per-head gate [d, H]
    # latent attention's second products fan out by head (models/glm4_moe_lite.py); wq_a / wkv_a,
    # whose outputs a norm reads whole (and the one rotary key every head shares), stay replicated
    (r".*/(wq_b|wkv_b)$", P(None, "tp")),
    (r".*/(wo|w_down)$", P("tp", None)),
    (r".*/lm_head$", P(None, "tp")),
    # MoE expert stacks [E, d, f] / [E, f, d]: experts over ep, per-expert
    # hidden dim over tp (column- then row-parallel, as for the dense FFN).
    (r".*/moe_in$", P("ep", None, "tp")),
    (r".*/moe_out$", P("ep", "tp", None)),
]


def _path_str(path: Tuple[Any, ...]) -> str:
    parts = []
    for entry in path:
        if hasattr(entry, "key"):
            parts.append(str(entry.key))
        elif hasattr(entry, "idx"):
            parts.append(str(entry.idx))
        else:
            parts.append(str(entry))
    return "/".join(parts)


def _fit_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """RIGHT-align the spec to the leaf's rank (leading dims replicated) and
    drop axes that don't divide. Right-alignment is what makes one rule serve
    both stacked [L, d_in, d_out] block weights and unstacked [d_in, d_out]
    ones: the feature dims are always the trailing dims."""
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pad = len(shape) - len(spec)
    out = []
    for dim in range(len(shape)):
        axis = spec[dim - pad] if dim >= pad else None
        if axis is not None and shape[dim] % axis_sizes.get(axis, 1) != 0:
            axis = None
        out.append(axis)
    return P(*out)


def partition_spec_for_path(path_str: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    spec = P()
    for pattern, rule_spec in _RULES:
        if re.match(pattern, "/" + path_str):
            spec = _fit_spec(rule_spec, shape, mesh)  # full rank after fit
            break
    # Pipeline parallelism: every per-layer leaf under a STACKED "blocks"
    # subtree carries the layer axis first; with a pp axis active that axis
    # is sharded over pp, so each stage holds only its own layers' params
    # (parallel/pipeline.py consumes them under shard_map). Composes with
    # the tp rules (e.g. [L, d_in, d_out] -> ("pp", None, "tp")). Unstacked
    # legacy paths ("blocks/3/qkv/w") are left alone.
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pp = axis_sizes.get("pp", 1)
    if (
        pp > 1
        and "blocks/" in path_str
        and re.search(r"blocks/\d+(/|$)", path_str) is None
        and shape
        and shape[0] % pp == 0
    ):
        padded = list(spec) if len(spec) == len(shape) else [None] * len(shape)
        if padded[0] is None:
            padded[0] = "pp"
            spec = P(*padded)
    return spec


def make_param_shardings(mesh: Mesh, params: Any) -> Any:
    """Pytree of NamedSharding matching ``params``, rules applied by path."""

    def assign(path, leaf):
        spec = partition_spec_for_path(_path_str(path), leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(assign, params)


def _dp_sharded_specs(mesh: Mesh, params: Any) -> Any:
    """Each leaf's rule spec plus ``dp`` on the first still-replicated dim the
    dp axis divides (leaves with no such dim keep their rule spec). The shared
    placement rule behind ZeRO-1 (optimizer moments) and FSDP (params)."""
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = axis_sizes.get("dp", 1)

    def assign(path, leaf):
        spec = partition_spec_for_path(_path_str(path), leaf.shape, mesh)
        padded = list(spec) + [None] * (len(leaf.shape) - len(spec))
        if dp > 1:
            for dim in range(len(leaf.shape)):
                if padded[dim] is None and leaf.shape[dim] % dp == 0:
                    padded[dim] = "dp"
                    break
        while padded and padded[-1] is None:  # P(None) and P() compare unequal
            padded.pop()
        return NamedSharding(mesh, P(*padded))

    return jax.tree_util.tree_map_with_path(assign, params)


def make_zero1_opt_shardings(mesh: Mesh, params: Any) -> Any:
    """ZeRO-1 shardings for params-shaped optimizer moments.

    Rationale: params stay replicated over dp (grads psum in backward — the
    genre's data-parallel contract), but Adam's mu/nu never enter a matmul,
    so nothing forces them replicated; sharding them over dp cuts optimizer
    memory per chip by the dp factor (AdamW: from 2x params to 2x/dp). GSPMD
    then emits reduce-scatter(grads) + all-gather(updated params) around the
    elementwise update — the ZeRO-1 communication pattern — from annotations
    alone. Composes with tp/pp rules: a [L, d_in, d_out] qkv leaf on a
    dp2/pp2/tp2 mesh ends up P("pp", "dp", "tp")."""
    return _dp_sharded_specs(mesh, params)


def make_fsdp_param_shardings(mesh: Mesh, params: Any) -> Any:
    """FSDP (ZeRO-3) shardings: the PARAMS themselves sharded over dp (same
    first-free-dim rule), so weights + grads + optimizer state all live at
    1/dp per chip — the regime where Llama-7B-scale models fit a slice.

    GSPMD inserts the FSDP communication pattern from these annotations: an
    all-gather materializes each weight just before its matmul (fwd and bwd),
    and the gradient reduction becomes a reduce-scatter back to the shards.
    The train step re-constrains updated params each step
    (make_sharded_train_step(fsdp=True)) so the sharding persists. Trades
    per-step all-gather bandwidth (ICI-resident on a TPU slice) for dp-fold
    memory — the standard TPU fully-sharded recipe."""
    return _dp_sharded_specs(mesh, params)


def batch_sharding(mesh: Mesh, seq_axis: bool = False) -> Any:
    """Sharding for a batch dict: leading dim over dp, optionally dim 1 over sp.

    Every leaf of the zoo's batches is [B, ...] (images, tokens, targets,
    masks), so one spec fits all leaves; token-model leaves are [B, T] and
    long-context runs additionally split T over ``sp``.
    """
    spec = P("dp", "sp") if seq_axis else P("dp")
    return NamedSharding(mesh, spec)
