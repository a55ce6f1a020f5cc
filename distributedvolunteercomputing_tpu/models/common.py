"""Shared building blocks for the plain-JAX functional model zoo.

Models are pure functions over explicit param pytrees (nested dicts of
jnp arrays). That keeps the whole zoo uniform for the three things this
framework does with params: shard them with ``pjit``, average them on host
across volunteers, and checkpoint them — no framework Module state to
special-case.

Params are stored float32; matmul-heavy compute casts to bfloat16 on TPU so
the MXU runs at full rate. Reference parity: the CUDA train_step genre uses
AMP the same way (SURVEY.md L1/L5).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributedvolunteercomputing_tpu.ops import attention as attention_ops
from distributedvolunteercomputing_tpu.utils import traced

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SteppedLeaves:
    """Leaves of the parameters that the STEP moves, by the model's own rule
    and from the step's own metrics, and the optimizer does not: a state that
    no gradient reaches (a router's selection bias, which load balancing
    without an auxiliary loss raises and lowers by the experts' loads). A
    model's bundle names them (``ModelBundle.stepped``); every builder of a
    step takes them (``stepped=``) and hands them to ``train_step_body``, the
    one place they act. A bundle that names none compiles to the program it
    compiled to before there was such a thing.

    ``signal``: the key of the loss function's metrics that the rule reads. It
    need not be a scalar; the step takes it out of the metrics it returns.
    ``owns(params)``: a tree of bools shaped like ``params``, True on the
    leaves the rule owns. Their gradient is zeroed before the optimizer sees
    it (no share of a global-norm clip) and whatever the optimizer makes of
    them is discarded: an owned leaf after the step is ``rule``'s.
    ``rule(params, signal)``: a tree shaped like ``params`` whose owned leaves
    are the new values, from the parameters as they were BEFORE the update."""

    signal: str
    owns: Callable[[Any], Any]
    rule: Callable[[Any, Any], Any]


@dataclasses.dataclass(frozen=True)
class StepSpan:
    """A span the train loop records of a model's step, by the name the
    bundle gives it (``ModelBundle.spans``; a family module's ``spans(cfg)``):
    every ``ROUTE_EVERY`` steps and at each log point, with whichever of
    ``keys`` the step's metrics hold, as floats. The loop carries what is
    declared here and reads none of it.

    ``attrs``: attributes known when the bundle is built (what the config
    says), on every such span as they are.
    ``noted``: attribute -> (kind, label) of ``utils/traced.py``: the values
    that label took in the notes of that kind since the trainer was built,
    sorted and joined by "+" (which form a scan's op chose when the step was
    traced); left out while there was no such note."""

    keys: Tuple[str, ...]
    attrs: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    noted: Mapping[str, Tuple[str, str]] = dataclasses.field(default_factory=dict)


# bf16 on TPU keeps the MXU at full rate; f32 on CPU keeps tests exact enough
# to compare against numpy references.
def compute_dtype() -> jnp.dtype:
    from distributedvolunteercomputing_tpu.utils.jaxenv import tpu_backend

    if tpu_backend():
        return jnp.bfloat16
    return jnp.float32


def dense_init(rng: jax.Array, d_in: int, d_out: int, scale: Optional[float] = None) -> Params:
    if scale is None:
        scale = 1.0 / (d_in ** 0.5)
    w_rng, _ = jax.random.split(rng)
    return {
        "w": (jax.random.normal(w_rng, (d_in, d_out), jnp.float32) * scale),
        "b": jnp.zeros((d_out,), jnp.float32),
    }


def dense(p: Params, x: jax.Array, dtype: Optional[jnp.dtype] = None) -> jax.Array:
    dtype = dtype or compute_dtype()
    y = jnp.dot(x.astype(dtype), p["w"].astype(dtype))
    return y + p["b"].astype(dtype)


def qkv_heads(p: Params, x: jax.Array, n_heads: int) -> Tuple[str, Tuple[jax.Array, jax.Array, jax.Array]]:
    """``"merged"`` and q, k, v in that layout, from a qkv leaf ``{"w": [d, 3d],
    "b": [3d]}`` (columns q|k|v, each head-major) and the normed input
    [B, T, d]: [B, T, d] each, a head a run of ``hd`` lanes as its product
    leaves it. Three products off the weight's three column ranges, so that no
    [B, T, 3d] result is cut in three and no array by head is written on the
    way to the kernels (``attention_ops.attention_merged`` reads this layout in
    place).

    Under a traced step whose mesh divides the heads over ``tp``
    (``attention_ops.heads_tp``) the weight is first viewed as [d, 3, H, hd]
    and laid out over ``tp`` on H, so each of the three is a column-parallel
    product by the q, k or v columns of a chip's own heads, born where the
    per-shard kernels and the row-parallel ``attn_out`` read it. The stored
    leaf is sharded on 3d in contiguous parts (chip 0 of a pair holds all of q
    and half of k), so as one [d, 3d] product half of q and of v, activations,
    would cross the link in every pass of every layer; off the view only the
    weight's and the bias's shards do. With no step mesh, ``tp`` 1 or manual,
    or heads that ``tp`` does not divide, nothing is viewed or laid out."""
    tp = attention_ops.heads_tp()
    # every TRACED qkv projection off one leaf (swarm.qkv_projection, beside swarm.attention_core), by the
    # chips the step's mesh could divide the heads over: the layout is "merged" wherever this runs
    traced.note("qkv_projection", tp=tp)
    dtype = compute_dtype()
    d = x.shape[-1]
    w, b, x = p["w"].astype(dtype), p["b"].astype(dtype), x.astype(dtype)
    if tp > 1 and n_heads % tp == 0:
        w = attention_ops.constrain_in_step(w.reshape(d, 3, n_heads, d // n_heads), P(None, None, "tp", None))
        b = attention_ops.constrain_in_step(b.reshape(3, n_heads, d // n_heads), P(None, "tp", None))
        return "merged", tuple(jnp.dot(x, w[:, s].reshape(d, d)) + b[s].reshape(d) for s in range(3))
    return "merged", tuple(jnp.dot(x, w[:, s * d:(s + 1) * d]) + b[s * d:(s + 1) * d] for s in range(3))


def fused_qkv_attention(p: Params, x: jax.Array, n_heads: int, causal: bool = False) -> jax.Array:
    """Self-attention of the normed input [B, T, d] through a fused qkv leaf,
    [B, T, d] as the output projection reads it. q, k and v where their
    products leave them (``qkv_heads``) go to ``attention_ops.attention_merged``,
    whose kernels read and write that layout wherever the shapes allow, on one
    chip and per shard of ``tp`` alike, and which is ``split_heads`` +
    ``attention_core`` + ``merge_heads`` wherever not: that fallback is where
    attention by head now lives for these models (a chip's heads of 64 an odd
    number, say)."""
    _, (q, k, v) = qkv_heads(p, x, n_heads)
    return attention_ops.attention_merged(q, k, v, n_heads, n_heads, causal=causal)


def matrix(rng: jax.Array, shape: Tuple[int, ...], scale: float = 0.02) -> jax.Array:
    return jax.random.normal(rng, shape, jnp.float32) * scale


def swiglu_init(keys: Sequence[jax.Array], d: int, f: int, lead: Tuple[int, ...] = (),
                first: int = 0) -> Params:
    """A gated FFN ``d -> f -> d`` from ``keys[first]``, ``[first + 1]``,
    ``[first + 2]``, each taken as its leaf is drawn; ``lead``: the leading
    axes of a stack of them (a chip's held experts)."""
    return {"w_gate": matrix(keys[first], (*lead, d, f)), "w_up": matrix(keys[first + 1], (*lead, d, f)),
            "w_down": matrix(keys[first + 2], (*lead, f, d))}


def swiglu(p: Params, h: jax.Array) -> jax.Array:
    dtype = h.dtype
    act = jax.nn.silu(h @ p["w_gate"].astype(dtype)) * (h @ p["w_up"].astype(dtype))
    return act @ p["w_down"].astype(dtype)


def embed_init(rng: jax.Array, vocab: int, d: int, scale: float = 0.02) -> jax.Array:
    return matrix(rng, (vocab, d), scale)


def layernorm_init(d: int) -> Params:
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    # LN statistics in f32 for stability even when activations are bf16.
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"] + p["b"]).astype(x.dtype)


def rmsnorm_init(d: int) -> Params:
    return {"g": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * p["g"]).astype(x.dtype)


def softmax_xent(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None,
                 denominator: Optional[float] = None) -> jax.Array:
    """Mean cross-entropy; ``labels`` are int ids; optional 0/1 mask, or
    per-token weights over a ``denominator`` of the caller's."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        if denominator is not None:
            return jnp.sum(nll * mask) / denominator
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def stacked_init(layer_init, rng: jax.Array, n_layers: int) -> Params:
    """Init ``n_layers`` identical layers as ONE stacked pytree (leading axis
    = layer). The zoo's transformers scan over this stack (``lax.scan``)
    instead of unrolling a Python loop, so the XLA program contains each
    block's HLO once — smaller programs, faster compiles, and the layout the
    TPU sharding rules (parallel/sharding.py) expect for block weights."""
    keys = jax.random.split(rng, n_layers)
    return jax.vmap(layer_init)(keys)


def remat_layer(body, layers: int = 1, calls: int = 1):
    """``body`` (one layer) rematerialised in the backward pass: the one place
    that decides what a rematerialised layer keeps. Beside the layer's inputs
    that is what costs a layer input's worth of memory and a long wait to make
    again: what the flash kernel produced in the layer, its output and
    log-sum-exp rows (``pallas_attention.KEPT_NAMES``: a kernel call's worth of
    time), so the recomputed forward runs no kernel; and, where the traced step's
    mesh divides the layer over ``tp``, the row-parallel attention product's
    result after its sum over ``tp`` (``attention_ops.keep_tp_reduced``, named
    in ``gpt2._block``: an all-reduce over the link that nothing hides), so
    the recomputed forward holds no collective. On one chip that second keep
    would save a product of 0.14 ms for the same memory and is not made: a
    layer there keeps what it kept, and one on the XLA core names nothing and
    its program is a bare ``jax.checkpoint``'s. The expert models' ``wo`` /
    ``w_down`` results are not named: no cell runs them over ``tp`` and their
    room at 8,192-16,384 tokens a row is smaller than this stack. ``layers``:
    how many layers run this one trace (a scan's length), and ``calls``: how
    often ``body`` runs what it traced once (``scan_blocks``' row streams, times
    the passes of a loop around the scan), for ``swarm.remat_kept``'s bytes."""
    from distributedvolunteercomputing_tpu.ops.pallas_attention import KEPT_NAMES

    # a name that no value of the trace carries (TP_REDUCED where tp is 1) keeps nothing
    policy = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES, attention_ops.TP_REDUCED)
    fn = jax.checkpoint(body, policy=policy)

    def layer(*args):
        with attention_ops.keeping_kernel_results(layers, calls):
            return fn(*args)

    return layer


def scan_blocks(body, blocks: Params, x: jax.Array, remat: bool = True, with_outputs: bool = False,
                rows_independent: bool = False, passes: int = 1):
    """Run ``x`` through stacked ``blocks`` with ``lax.scan``; ``body`` is
    ``(layer_params, x) -> x``. With ``remat`` each layer's activations are
    rematerialized in backward (``remat_layer`` per scan step), the standard
    O(sqrt)-free layerwise remat that keeps HBM at one layer's activations.
    ``with_outputs``: ``body`` returns ``(x, y)`` and the layers' ``y`` come
    back stacked beside the final ``x``.

    ``rows_independent``: the caller's word that ``body`` couples no two rows
    of ``x`` (a dense block; not a layer whose dispatch chunks or statistics
    run over the batch). Where the traced step's mesh divides a layer over
    ``tp`` and each replica's rows are even (``attention_ops.tp_streams``) the
    scan then carries ``x`` as a PAIR of row halves, split once here and
    merged once after the last layer, and ONE body, under one checkpoint,
    applies ``body`` to each half in turn: two chains that share only the
    weights, so the compiler can run one's all-reduce over ``tp`` beside the
    other's products and kernel (parallel/train_step.py compiles such a step
    with asynchronous collectives), forward and backward. The checkpoint keeps
    of each half what it kept of the whole. Elsewhere the jaxpr is the one it
    was (the step over a ``tp`` axis is compiled with that axis's options
    whether or not a model splits).

    ``passes``: how often an enclosing loop runs this scan over the same
    ``blocks`` (a looped model's passes, ``models/ouro.py``: the scan is traced
    once inside the outer loop's body), for ``swarm.remat_kept``'s bytes."""
    streams = 1
    if rows_independent:
        streams = attention_ops.tp_streams(x.shape[0])
        # every TRACED scan of layers that couple no rows: 2 streams, or the 1 it fell back to (swarm.tp_streams)
        traced.note("tp_streams", streams=streams)
    if streams > 1:
        if with_outputs:
            raise ValueError("row streams carry no layer outputs")
        one = jax.jit(body)  # the halves have one shape: traced once, called for each
        x = attention_ops.split_rows(x, streams)

        def body(p, hs):
            return tuple(one(p, h) for h in hs)

    fn = remat_layer(body, jax.tree_util.tree_leaves(blocks)[0].shape[0], streams * passes) if remat else body

    def step(h, p):
        return fn(p, h) if with_outputs else (fn(p, h), None)

    x, ys = jax.lax.scan(step, x, blocks)
    if streams > 1:
        x = attention_ops.merge_rows(x)
    return (x, ys) if with_outputs else x


def _project_vocab(x: jax.Array, head: jax.Array, head_layout: str) -> jax.Array:
    # f32 accumulation out of the MXU regardless of the bf16 inputs.
    eq = "...d,vd->...v" if head_layout == "vd" else "...d,dv->...v"
    return jnp.einsum(eq, x, head.astype(x.dtype), preferred_element_type=jnp.float32)


def _xent_chunks(x, head, labels, weights, divisor, chunk: int, head_layout: str,
                 with_dx: bool = False, with_dhead: bool = False, with_dweights: bool = False):
    """ONE ``lax.scan`` over the ``chunk``-sized slices of T: ``(loss, dx, dhead,
    dweights)``, the loss alone (one vocabulary-sized product a chunk) unless a
    gradient is asked for. A chunk that is asked makes ``dlogits`` while its
    logits are in hand and spends it at once: ``dx``'s rows (in ``x``'s dtype,
    stacked by the scan) and ``dhead``'s sum (float32, carried by the loop, in
    ``head``'s dtype at the end), both of the loss as it is returned,
    ``nll . weights / divisor``; ``dweights`` is the chunk's ``nll / divisor``
    as it stands (float32, stacked as ``dx`` is), no product.
    The products take what autodiff's took (read off the compiled steps of
    ``olmoe-solo`` and ``medium-solo``): the float32 ``dlogits`` against the
    operand in compute dtype, float32 out of the MXU."""
    b, t, _ = x.shape
    n = t // chunk
    head_c = head.astype(x.dtype)  # once, not a chunk
    contract_v = 0 if head_layout == "vd" else 1

    def by_chunk(a):  # [B, T, ...] -> [n, B, chunk, ...]: scan's leading axis is the chunk index
        return jnp.moveaxis(a.reshape(b, n, chunk, *a.shape[2:]), 1, 0)

    def body(carry, chunk_in):
        nll_sum, dhead = carry
        xc, lc, *wc = chunk_in
        logits = _project_vocab(xc, head_c, head_layout)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = logz - gold  # a token's, before its weight
        dwc = nll / divisor if with_dweights else None
        if wc:
            nll = nll * wc[0]
        dxc = None
        if with_dx or with_dhead:
            hit = lc[..., None] == jnp.arange(logits.shape[-1])
            scale = (wc[0] / divisor)[..., None] if wc else 1.0 / divisor
            dlogits = (jnp.exp(logits - logz[..., None]) - hit) * scale
        if with_dx:
            dxc = jax.lax.dot_general(dlogits, head_c, (((2,), (contract_v,)), ((), ())),
                                      preferred_element_type=jnp.float32).astype(x.dtype)
        if with_dhead:
            lhs, rhs = (dlogits, xc) if head_layout == "vd" else (xc, dlogits)
            dhead = dhead + jax.lax.dot_general(lhs, rhs, (((0, 1), (0, 1)), ((), ())),
                                                preferred_element_type=jnp.float32)
        return (nll_sum + jnp.sum(nll), dhead), (dxc, dwc)

    chunks = (by_chunk(x), by_chunk(labels)) + (() if weights is None else (by_chunk(weights),))
    zero = jnp.zeros((), jnp.float32)
    (nll_sum, dhead), (dx, dweights) = jax.lax.scan(
        body, (zero, jnp.zeros(head.shape, jnp.float32) if with_dhead else None), chunks)
    if with_dx:
        dx = jnp.moveaxis(dx, 0, 1).reshape(x.shape)
    if with_dweights:
        dweights = jnp.moveaxis(dweights, 0, 1).reshape(b, t)
    return nll_sum / divisor, dx, dhead.astype(head.dtype) if with_dhead else None, dweights


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _xent_scalar(x, head, labels, weights, divisor, chunk, head_layout):
    return _xent_chunks(x, head, labels, weights, divisor, chunk, head_layout)[0]


def _xent_scalar_fwd(x, head, labels, weights, divisor, chunk, head_layout):
    # symbolic_zeros: each argument comes with whether it is differentiated at all. A frozen head (an
    # adapter-only finetune) costs no [d, V] accumulator and no third product; weights nobody learns through
    # (a mask, a noise schedule's 1 / t) cost no stack of the tokens' own losses.
    loss, dx, dhead, dweights = _xent_chunks(
        x.value, head.value, labels.value, None if weights is None else weights.value, divisor.value,
        chunk, head_layout, with_dx=x.perturbed, with_dhead=head.perturbed,
        with_dweights=weights is not None and weights.perturbed)
    # the divisor's own: d (sum / divisor) = -loss / divisor (a mask's sum that something is learnt through)
    ddivisor = -loss / divisor.value if divisor.perturbed else None
    return loss, (dx, dhead, dweights, ddivisor)


def _xent_scalar_bwd(chunk, head_layout, residuals, g):
    # the gradients of the loss times the scalar cotangent (the literal 1.0 under value_and_grad: no pass);
    # a loss nobody's cotangent reaches never gets here (the backward pass skips an all-zero cotangent)
    dx, dhead, dweights, ddivisor = (None if r is None else r * g.astype(r.dtype) for r in residuals)
    return dx, dhead, None, dweights, ddivisor


_xent_scalar.defvjp(_xent_scalar_fwd, _xent_scalar_bwd, symbolic_zeros=True)


@jax.named_scope("loss_head")  # names the head's ops in a profiler trace
def lm_xent_chunked(
    x: jax.Array,
    head: jax.Array,
    labels: jax.Array,
    mask: Optional[jax.Array] = None,
    chunk: int = 128,
    head_layout: str = "vd",
    denominator: Optional[float] = None,
) -> jax.Array:
    """Mean LM cross-entropy WITHOUT materializing the [B, T, V] f32 logits.

    For GPT-2-small shapes (B=8, T=1024, V=50257) the full logits tensor is
    1.6 GB f32 — and its backward residuals double that. This scans over T in
    ``chunk``-sized slices, so peak memory is one [B, chunk, V] buffer (~206 MB
    at chunk=128), and keeps nothing of a chunk for a backward pass: the head
    is the last thing a forward does and its result is one scalar, so where the
    loss is differentiated (a ``jax.custom_vjp``) the SAME loop makes each
    chunk's ``dlogits`` from the logits in hand and both gradient products from
    it, three vocabulary-sized products a chunk, and the backward pass is the
    two gradients times the scalar cotangent. An undifferentiated call
    (evaluation) runs the loop with the logits product alone.

    ``head`` is the projection matrix: [V, d] (``head_layout="vd"``, tied
    embeddings — GPT-2/BERT) or [d, V] (``"dv"``, a separate lm_head — Llama).
    ``mask`` is an optional 0/1 token mask (MLM objective), over whose sum the
    loss is the mean; with a ``denominator`` it is per-token WEIGHTS and the loss
    is the weighted sum over that divisor (a denoising loss: masked tokens by
    ``1 / t`` over B x L; a looped model's passes by their exit probabilities,
    ``models/ouro.py``). The weights are differentiated where something is learnt
    through them (``d loss / d weights = nll / divisor``, which each chunk has in
    hand: under the same ``symbolic_zeros`` rule as ``x`` and ``head``, so a mask
    or a schedule that nothing learns through adds nothing to the program); the
    labels are not.
    """
    b, t, _ = x.shape
    if t % chunk != 0:
        chunk = t  # tiny test configs: single chunk, same math, same rule
    weights = None if mask is None else mask.astype(jnp.float32)
    # the divisor depends on the batch alone: known before the loop, so a chunk's dlogits is final
    if denominator is not None:
        divisor = denominator
    elif weights is not None:
        divisor = jnp.maximum(jnp.sum(weights), 1.0)
    else:
        divisor = b * t
    return _xent_scalar(x, head, labels, weights, jnp.asarray(divisor, jnp.float32), chunk, head_layout)


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))


def count_params(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def split_keys(rng: jax.Array, n: int) -> Tuple[jax.Array, ...]:
    return tuple(jax.random.split(rng, n))
