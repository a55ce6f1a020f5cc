"""Attention ops shared by the transformer zoo (BERT / GPT-2 / Llama).

Plain-XLA reference path: one fused einsum-softmax-einsum that XLA maps onto
the MXU. The pallas flash kernel (ops/pallas_attention.py) and the ring
attention sequence-parallel path (parallel/ring_attention.py) are drop-in
replacements for ``attention_core``; ``auto`` routing picks
between the XLA core and the kernel by what it can observe (backend, mask,
shape, dtype, the traced step's mesh), from crossovers measured on the v5e.

Softmax statistics run in float32 even when q/k/v are bfloat16 — MXU matmuls
in bf16, reductions in f32, the standard TPU recipe.
"""

from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from distributedvolunteercomputing_tpu.utils import traced

# Active sequence-parallel context: (mesh, axis_name, impl) or None. When
# set, the attention core routes to the chosen SP implementation so the
# model code is unchanged between single-device and sp-sharded runs. Set by
# make_sharded_train_step at TRACE time (it wraps the step body), or manually.
# impl: "ring" (K/V rotate via ppermute — works for any head count, memory
# O(T/sp) per device) or "ulysses" (all-to-all swaps seq<->heads around a
# full-sequence attention — fewer collective hops on ICI; needs H % sp == 0).
_seq_ctx = None


@contextlib.contextmanager
def sequence_parallel(mesh, axis: str = "sp", impl: str = "ring"):
    if impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown sequence-parallel impl {impl!r}")
    global _seq_ctx
    prev = _seq_ctx
    _seq_ctx = (mesh, axis, impl)
    try:
        yield
    finally:
        _seq_ctx = prev

# The mesh the enclosing train step is traced for, or None. Set at TRACE time
# by parallel/train_step.py (like _seq_ctx above). A Pallas kernel is a custom
# call that GSPMD cannot partition (on a TPU the lowering refuses: "Mosaic
# kernels cannot be automatically partitioned"), so under a mesh of several
# chips the kernel runs per shard (``_flash_per_shard``).
_mesh_ctx = None


@contextlib.contextmanager
def step_mesh(mesh):
    global _mesh_ctx
    prev = _mesh_ctx
    _mesh_ctx = mesh
    try:
        yield
    finally:
        _mesh_ctx = prev


# Attention core selection: "xla" (fused einsum-softmax-einsum), "flash"
# (pallas kernel, ops/pallas_attention.py), or "auto": flash on a TPU for
# mask-free square attention from the sequence length at which it was
# measured to win, xla otherwise (masked attention, rectangular causal, short
# sequences, every other backend). ``set_attention_impl`` forces either core.
_impl = "auto"
# Crossover for auto routing, for the dtypes it was measured in (MEASURED, PR 27,
# TPU v5e: experiments/attention_sweep.py; the table is in PERF.md,
# Findings of PR 27): forward + backward of both cores inside a rematerialised
# layer, causal, head dim 64. bf16: the kernel wins at T=512 (8.6 against
# 11.5 ms a layer), 1,024 (9.8 / 19.1), 2,048 (11.7 / 33.1) and 4,096
# (15.1 / 99.1) and loses at T=256 (8.8 / 7.9); f32: wins at 512 (5.9 / 7.2)
# and 1,024 (6.3 / 12.4), not measured below. Other dtypes keep the XLA core.
_AUTO_FLASH_MIN_T = {"bfloat16": 512, "float32": 512}
# Head dims the kernel was measured at (128: 3.2 against 6.6 ms a layer at
# T=1,024; 256, latent attention's head: PERF.md, Findings of PR 42; 192, a
# latent key of 128 + 64 over a value head of 128: PERF.md, Findings of PR 52);
# others keep the XLA core.
_AUTO_FLASH_HEAD_DIMS = (64, 128, 192, 256)

# Every TRACED attention call is noted (``utils/traced.py``) as "attention_core"
# with impl, T, D, dtype, window ("none" for full attention), kv_heads, layout,
# rotary and computed_over_band (a windowed flash call's computed pairs over
# its band's: whether the strips engaged): a volunteer counts them
# (swarm.attention_core), so its summary says how many of the step's
# attention calls took the fused core, how many of those were handed the projections' own [B, T, H * D] arrays (``layout``
# "merged": a head of whole tiles, or of 64 as half of one; "heads" is
# [B, H, T, D]) and where the call's rotary turn ran
# (``rotary``): "kernel" where the forward kernel turns each q block on the
# tile (k, and the backward's resident q and its dq, by one merged-layout pass
# each beside the kernels), "outside" where ``attention_merged`` ran ``rope``
# on [B, H, T, D] before the core, "none" where the call was given no rotary
# description (a model that turns its own parts before ``attention_core``,
# GLM's and Kimi's latent keys, reads "none").
# What ``attention_merged`` says of the call it hands to ``attention_core``.
_observed_rotary = "none"


def _note_core(impl: str, t: int, d: int, dtype, window: Optional[int], kv_heads: int, layout: str,
               rotary: str) -> None:
    traced.note("attention_core", impl=impl, T=t, D=d, dtype=jnp.dtype(dtype).name,
                window="none" if window is None else window, kv_heads=kv_heads, layout=layout, rotary=rotary,
                computed_over_band=_computed_over_band(impl, t, d, dtype, window, rotary))


def _computed_over_band(impl: str, t: int, d: int, dtype, window: Optional[int], rotary: str):
    """(query, key) pairs the flash kernels' forward computes of a windowed
    call over the pairs inside its band, to two places (``pallas_attention.
    window_tiles`` at the call's own blocks): 2.0 where every visited tile is a
    masked whole one a window wide, about 1 + strip / window where the tiles an
    edge crosses run as strips. "none" for a call with no window or on the XLA
    core."""
    if impl != "flash" or window is None:
        return "none"
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    tiles = pa.window_tiles(t, window, *pa.choose_blocks(t, t, d, dtype, window, rotary == "kernel"))
    return round(tiles["fwd"] / tiles["band"], 2)


# While remat_layer traces a body: the bytes kept of each kernel call in it
# and of each value named by ``keep_tp_reduced``.
_kept_ctx = None
# The name of a row-parallel product's result after its sum over ``tp``, kept
# by a rematerialised layer where the traced step's mesh divides the layer.
TP_REDUCED = "tp_reduced"


@contextlib.contextmanager
def keeping_kernel_results(layers: int, calls: int = 1):
    """Around the trace of one rematerialised layer body that ``layers``
    layers run: gathers what ``_flash_per_shard`` says a chip keeps of each
    kernel call in it, and ``keep_tp_reduced`` of each value it named, and
    notes the sum as "remat_kept" with (layers, bytes): how many layers run
    that one trace (a scan's length) and the bytes one chip keeps of them a
    step (swarm.remat_kept), ``calls`` times over where the body runs ``calls``
    times what it traced once (a row stream each). A layer that ran the XLA
    core on one chip names nothing, keeps nothing and is not noted."""
    global _kept_ctx
    prev, _kept_ctx = _kept_ctx, []
    kept = _kept_ctx
    try:
        yield
    finally:
        _kept_ctx = prev
    if kept:
        traced.note("remat_kept", layers=layers, bytes=layers * calls * sum(kept))


def chips_in_step() -> int:
    """How many chips the program being traced is laid out over: the size of
    the traced step's mesh, or, where no step has announced one, every device
    there is (a bare ``jit`` may be handed arrays sharded over all of them).
    A kernel with no per-shard form runs where this is 1."""
    return jax.device_count() if _mesh_ctx is None else _mesh_ctx.size


def heads_tp() -> int:
    """Over how many chips the traced step's mesh can divide a projection's
    heads: the size of its ``tp`` axis, or 1 with no step mesh or where an
    enclosing ``shard_map`` (a pipeline stage, Ulysses) has already made
    ``tp`` manual, so that what the trace sees is one chip's share."""
    if _mesh_ctx is None or "tp" in jax.sharding.get_abstract_mesh().manual_axes:
        return 1
    return _mesh_ctx.shape.get("tp", 1)


def tp_streams(rows: int) -> int:
    """As how many independent row streams a layer should run a batch of
    ``rows`` rows: 2 where the traced step's mesh divides the layer over ``tp``
    (each row-parallel product then ends in an all-reduce over the link, which
    only OTHER rows' work can run beside) and every ``dp`` replica holds an
    even number of rows; 1 elsewhere, and the jaxpr is the one it was (a step
    over ``tp`` is still compiled with ``parallel/train_step``'s options for
    that axis, one stream or two)."""
    if heads_tp() == 1:
        return 1
    return 2 if rows % (2 * _mesh_ctx.shape.get("dp", 1)) == 0 else 1


def split_rows(x: jax.Array, streams: int):
    """``x`` [B, ...] as ``streams`` arrays [B / streams, ...], each holding
    the same share of EVERY ``dp`` replica's rows and laid out over ``dp``
    itself: ``x[:B / 2]`` of a dp-sharded batch would put one stream on each
    replica and move activations across ``dp``. ``merge_rows`` undoes it."""
    dp = _mesh_ctx.shape.get("dp", 1)
    parts = x.reshape(dp, streams, x.shape[0] // (dp * streams), *x.shape[1:])
    return tuple(_rows_over_dp(parts[:, s].reshape(-1, *x.shape[1:])) for s in range(streams))


def merge_rows(parts) -> jax.Array:
    """The rows ``split_rows`` took apart, in the order they had."""
    dp = _mesh_ctx.shape.get("dp", 1)
    rest = parts[0].shape[1:]
    stacked = jnp.stack([p.reshape(dp, -1, *rest) for p in parts], axis=1)
    return _rows_over_dp(stacked.reshape(-1, *rest))


def _rows_over_dp(x: jax.Array) -> jax.Array:
    free = jax.sharding.PartitionSpec.UNCONSTRAINED
    return constrain_in_step(x, jax.sharding.PartitionSpec("dp", *[free] * (x.ndim - 1)))


def keep_tp_reduced(x: jax.Array) -> jax.Array:
    """``x``, a row-parallel product's [B, T, d] result after its sum over
    ``tp``, named so that the layer's checkpoint keeps it: rebuilding it in the
    backward costs the product and an all-reduce over the link that no compute
    hides. With ``tp`` 1 the same keep would buy a product alone for a layer
    input's worth of memory, so ``x`` comes back unnamed and the program is
    the text it was."""
    if heads_tp() == 1:
        return x
    if _kept_ctx is not None:  # a chip's share: the rows of its dp (and sp) part, whole over tp
        rows_over = _mesh_ctx.shape.get("dp", 1) * _mesh_ctx.shape.get("sp", 1)
        _kept_ctx.append(x.size * jnp.dtype(x.dtype).itemsize // rows_over)
    return checkpoint_name(x, TP_REDUCED)


def constrain_in_step(x: jax.Array, spec) -> jax.Array:
    """``x`` laid out by ``spec`` over the traced step's mesh (inside an
    enclosing ``shard_map`` the mesh to name is the context's own, as in
    ``_flash_per_shard``)."""
    ctx = jax.sharding.get_abstract_mesh()
    mesh = ctx if ctx.manual_axes else _mesh_ctx
    return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, spec))


def set_attention_impl(name: str) -> None:
    """Select the attention core for subsequent TRACES.

    The impl is read at trace time: computations already jitted (and cached
    by shape) keep whatever core they were traced with — call this before
    the first train step, not between steps.
    """
    global _impl
    if name not in ("auto", "xla", "flash"):
        raise ValueError(f"unknown attention impl {name!r}")
    _impl = name


def get_attention_impl() -> str:
    return _impl


def _route_to_flash(q: jax.Array, k: jax.Array, causal: bool, mask, window=None, turned: bool = False,
                    block_diffusion: Optional[int] = None) -> bool:
    if mask is not None:  # flash path has no additive-mask support
        return False
    tq, tk, d = q.shape[-2], k.shape[-2], q.shape[-1]
    if q.shape[1] % k.shape[1]:
        return False  # the XLA core says why
    if causal and tq != tk:
        # The flash kernel's causal mask is top-left aligned (row i sees keys
        # 0..i); this XLA core is bottom-right aligned for Tq != Tk. Only the
        # square case agrees, so rectangular causal always takes the XLA path.
        return False
    if _impl != "flash":
        from distributedvolunteercomputing_tpu.utils.jaxenv import tpu_backend

        min_t = _AUTO_FLASH_MIN_T.get(jnp.dtype(q.dtype).name)
        if (
            _impl != "auto" or not tpu_backend() or min_t is None or tq != tk
            or tq < min_t or d not in _AUTO_FLASH_HEAD_DIMS
        ):
            return False
        if _mesh_ctx is None and jax.device_count() > 1:
            # Several chips and no step has said how its arrays are laid out
            # (a bare jit over sharded parameters, say): Mosaic refuses a
            # kernel that GSPMD would have to partition, the XLA core is
            # partitioned like any other op.
            return False
    from distributedvolunteercomputing_tpu.ops.pallas_attention import choose_blocks

    if choose_blocks(tq, tk, d, q.dtype, window, turned, block_diffusion) is None:  # one head (and a turned call's tables) does not fit VMEM
        return False
    return _shard_axes(q, k) is not None


def _shard_axes(q: jax.Array, k: Optional[jax.Array] = None):
    """How the kernel's call is divided under the traced step's mesh: the
    (batch axis, head axis) names to shard over (either may be None), or None
    where batch or heads (the key/value heads, of which there may be fewer)
    do not divide, so that the XLA core, which GSPMD partitions itself, must
    take the call."""
    if _mesh_ctx is None:
        return (None, None)
    sizes = dict(zip(_mesh_ctx.axis_names, _mesh_ctx.devices.shape))
    dp, tp = sizes.get("dp", 1), sizes.get("tp", 1)
    if q.shape[0] % dp or (q if k is None else k).shape[1] % tp:
        return None
    return ("dp" if dp > 1 else None, "tp" if tp > 1 else None)


def _flash_per_shard(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool, window: Optional[int] = None,
    block_diffusion: Optional[int] = None,
) -> jax.Array:
    """The kernel on each chip's own batch rows (dp) and heads (tp): attention
    mixes neither, so no q, k or v crosses a chip (a chip's query heads read
    its own key/value heads: both are cut in contiguous parts). Mosaic takes a
    kernel only where every mesh axis is manual, so the ``shard_map`` is manual
    over all the axes that an enclosing one (pipeline stages, Ulysses) has not
    already made manual."""
    from jax.sharding import PartitionSpec as P

    from distributedvolunteercomputing_tpu.ops.pallas_attention import flash_attention, kept_bytes

    def core(q, k, v):  # traced once, at one chip's shapes
        if _kept_ctx is not None:
            _kept_ctx.append(kept_bytes(q, k, window, v, block_diffusion=block_diffusion))
        # blocks: pallas_attention.choose_blocks of the (shard's) shape
        return flash_attention(q, k, v, causal=causal, window=window, block_diffusion=block_diffusion)

    spec = P(*_shard_axes(q, k), None, None)
    return _per_shard(core, (q, k, v), (spec, spec, spec), spec)


def _per_shard(core, args, in_specs, out_spec):
    """``core(*args)`` on each chip's own part under the traced step's mesh
    (the specs are not read where the step has one chip)."""
    if _mesh_ctx is None or _mesh_ctx.size == 1:
        return core(*args)
    # Inside an enclosing shard_map the mesh to name is the context's own
    # (the same axes, some already manual).
    ctx = jax.sharding.get_abstract_mesh()
    outer = set(ctx.manual_axes)
    return jax.shard_map(
        core, mesh=ctx if outer else _mesh_ctx, in_specs=in_specs,
        out_specs=out_spec, axis_names=set(_mesh_ctx.axis_names) - outer, check_vma=False,
    )(*args)


class Rotary(NamedTuple):
    """A model's rotary embedding as ``rope`` is told of it."""

    base: float = 10000.0
    layout: str = "interleaved"
    rotary_dim: Optional[int] = None
    inv_freq: Optional[jax.Array] = None
    scale: float = 1.0
    positions: Optional[jax.Array] = None  # [T]; the row index where none are given


def block_diffusion_mask(t: int, bd: int) -> jax.Array:
    """``[t, t]`` bool, True where query row i sees key row j under the
    block-diffusion mask over ``[x_0 ; x_t]`` (``pallas_attention.bd_keep``:
    clean rows first, then the noised copy, ``t / 2`` each, blocks of ``bd``)."""
    from distributedvolunteercomputing_tpu.ops.pallas_attention import bd_keep

    rows = jnp.arange(t, dtype=jnp.int32)
    return bd_keep(rows[:, None], rows[None, :], t // 2, bd)


def attention_merged(
    q: jax.Array,  # [B, T, H * D] as the query projection made it
    k: jax.Array,  # [B, T, Hkv * D]
    v: jax.Array,  # [B, T, Hkv * Dv]
    heads: int,
    kv_heads: int,
    causal: bool = False,
    window: Optional[int] = None,
    rotary: Optional[Rotary] = None,  # turns q and k (both by the same positions: 0..T-1, or its own)
    block_diffusion: Optional[int] = None,  # rows [x_0 ; x_t] in blocks of so many positions; not causal
) -> jax.Array:
    """Attention from the projections' arrays to the output projection's,
    [B, T, H * Dv]: ``merge_heads(attention_core(rope(split_heads(q)),
    rope(split_heads(k)), split_heads(v)))``, which is what runs wherever the
    shapes do not allow better. Where the call would take the flash kernel
    (``_route_to_flash``) and ``merged_in_place`` says so, the kernels read q,
    k and v and write the output where they lie. That is: both head widths
    whole 128-lane tiles and the rotary layout "half" (or none), a head being a
    block of the last axis and the rotary pairs turned by a roll along a head's
    own lanes (q on the forward kernel's tile; k, and the backward's q and dq,
    by one pass each in the same layout); or a head that is a whole part of a
    tile (GPT-2's 64: two heads a 128-lane block, each run on its own lanes
    inside a grid step), q, k and v alike, every key/value head its own, as
    plain causal or full attention with no rotary, window or block-diffusion
    mask, a chip's heads whole blocks. Either way no transpose, no array D/2
    wide and no float32 copy of a head-shaped array stands between a projection
    and its kernel. The shapes (and the step's mesh) decide; nothing else does."""
    global _observed_rotary
    b, t, _ = q.shape
    d = q.shape[-1] // heads
    qs, ks = _by_head(q, k, heads, kv_heads)
    if not merged_in_place(q, k, v, heads, kv_heads, causal, window, rotary, block_diffusion):
        qh, kh, vh = split_heads(q, heads), split_heads(k, kv_heads), split_heads(v, kv_heads)
        if rotary is not None:
            qh, kh = rope(qh, **rotary._asdict()), rope(kh, **rotary._asdict())
        prev, _observed_rotary = _observed_rotary, "none" if rotary is None else "outside"
        try:
            return merge_heads(attention_core(
                qh, kh, vh, causal=causal, window=window, block_diffusion=block_diffusion))
        finally:
            _observed_rotary = prev
    _note_core("flash", t, d, q.dtype, window, kv_heads, "merged", "none" if rotary is None else "kernel")
    return _flash_merged_per_shard(q, k, v, d, _shard_axes(qs, ks), causal, window, rotary, block_diffusion)


def _by_head(q, k, heads: int, kv_heads: int):
    """What ``_route_to_flash`` and ``_shard_axes`` read of merged q and k by head, [B, n, T, D]."""
    b, d = q.shape[0], q.shape[-1] // heads
    return tuple(jax.ShapeDtypeStruct((b, n, x.shape[1], d), x.dtype) for n, x in ((heads, q), (kv_heads, k)))


def merged_in_place(q, k, v, heads: int, kv_heads: int, causal: bool, window: Optional[int],
                    rotary: Optional[Rotary], block_diffusion: Optional[int] = None) -> bool:
    """Whether ``attention_merged`` hands this call (arrays, or their shapes
    and dtypes) to the kernels on the projections' own layout: a head of whole
    128-lane tiles with any mask and a "half" rotary, or a head that is a whole
    part of a tile (64: two a block) as plain causal or full attention, its
    blocks whole on every chip's share of the heads."""
    from distributedvolunteercomputing_tpu.ops.pallas_attention import heads_a_block

    d, dv = q.shape[-1] // heads, v.shape[-1] // kv_heads
    qs, ks = _by_head(q, k, heads, kv_heads)
    axes = _shard_axes(qs, ks)
    tp = 1 if axes is None or axes[1] is None else _mesh_ctx.shape["tp"]
    per = heads_a_block(d, dv, heads // tp, kv_heads // tp)  # of one chip's heads
    return (
        _seq_ctx is None and per > 0
        and (per == 1 or (rotary is None and window is None and block_diffusion is None))
        and (rotary is None or rotary.layout == "half")
        and (window is None or (causal and window >= 1))
        and _route_to_flash(qs, ks, causal, None, window, turned=rotary is not None,
                            block_diffusion=block_diffusion)
    )


def _flash_merged_per_shard(q, k, v, d: int, axes, causal, window, rotary: Optional[Rotary],
                            block_diffusion: Optional[int] = None):
    """``_flash_per_shard`` for the merged layout, heads of ``d`` lanes: a
    chip's heads are a contiguous part of the last axis (``tp`` of ``axes`` =
    ``_shard_axes`` cuts whole heads, the key/value heads with their query
    heads), the tables are whole on every chip."""
    from jax.sharding import PartitionSpec as P

    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    tables, rotary_dim = (), None
    if rotary is not None:
        rotary_dim = d if rotary.rotary_dim is None else rotary.rotary_dim
        tables = pa.rotary_tables(
            q.shape[1], d, rotary.base, rotary_dim, rotary.inv_freq, rotary.scale, rotary.positions)

    def core(q, k, v, *tables):  # traced once, at one chip's shapes
        shard = (q.shape[-1] // d, k.shape[-1] // d)  # this chip's query and key/value heads
        if _kept_ctx is not None:
            _kept_ctx.append(pa.kept_bytes(q, k, window, v, shard, block_diffusion))
        cos, sin = tables if tables else (None, None)
        if tables:
            k = pa.rotary_merged(k, cos, sin, rotary_dim)
        return pa.flash_attention_merged(
            q, k, v, cos, sin, shard, causal, window, rotary_dim, None, block_diffusion)

    spec = P(axes[0], None, axes[1])
    return _per_shard(core, (q, k, v, *tables), (spec, spec, spec) + (P(None, None),) * len(tables), spec)


def attention_core(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, Hkv, Tk, D]: Hkv divides H, query head h reads h // (H / Hkv)
    v: jax.Array,  # [B, Hkv, Tk, Dv]: the value head's own width, which is the output's
    causal: bool = False,
    mask: Optional[jax.Array] = None,  # [B, 1|H, Tq, Tk] additive-able bool
    window: Optional[int] = None,  # causal, square: query i sees keys i - window < j <= i
    block_diffusion: Optional[int] = None,  # not causal, square: ``block_diffusion_mask``
) -> jax.Array:
    plain = window is None and block_diffusion is None and q.shape[1] == k.shape[1]  # what the sp paths compute
    if _seq_ctx is not None and mask is None and q.shape[-2] == k.shape[-2] and plain:
        mesh, axis, impl = _seq_ctx
        if impl == "ulysses":
            from distributedvolunteercomputing_tpu.parallel.ulysses import (
                ulysses_attention_bhtd,
            )

            return ulysses_attention_bhtd(q, k, v, mesh, axis, causal)
        from distributedvolunteercomputing_tpu.parallel.ring_attention import ring_attention_bhtd

        return ring_attention_bhtd(q, k, v, mesh, axis, causal)
    return attention_core_local(q, k, v, causal, mask, window, block_diffusion)


def attention_core_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
    window: Optional[int] = None,
    block_diffusion: Optional[int] = None,
) -> jax.Array:
    """The single-device core (flash kernel or fused XLA), with no
    sequence-parallel routing — also the inner attention the Ulysses path
    runs per head-group after its all-to-all."""
    h, h_kv = q.shape[1], k.shape[1]
    if h % h_kv:
        raise ValueError(f"{h_kv} key/value heads do not divide {h} query heads")
    if window is not None and (not causal or q.shape[-2] != k.shape[-2] or window < 1):
        raise ValueError("a window needs causal attention over a square sequence")
    if mask is not None and h != h_kv:
        raise ValueError("a mask over grouped key/value heads is not built")
    if block_diffusion is not None and (
            causal or window is not None or q.shape[-2] != k.shape[-2] or q.shape[-2] % (2 * block_diffusion)):
        raise ValueError("a block-diffusion mask is over [x_0 ; x_t], two halves of whole blocks, and is its own mask")
    flash = _route_to_flash(q, k, causal, mask, window, block_diffusion=block_diffusion)
    _note_core("flash" if flash else "xla", q.shape[-2], q.shape[-1], q.dtype, window, h_kv, "heads", _observed_rotary)
    if flash:
        return _flash_per_shard(q, k, v, causal, window, block_diffusion)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    if h != h_kv:
        # a group's query heads beside their key/value head: [B, Hkv, G, T, D]
        b, _, tq, d = q.shape
        qg = q.reshape(b, h_kv, h // h_kv, tq, d)
        logits = jnp.einsum("bngqd,bnkd->bngqk", qg, k).astype(jnp.float32) * scale
    else:
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        if window is not None:
            causal_mask &= ~jnp.tril(jnp.ones((tq, tk), bool), k=-window)
        logits = jnp.where(causal_mask, logits, -1e30)
    if block_diffusion is not None:  # the mask as an explicit array: small sizes, and the kernels' yardstick
        logits = jnp.where(block_diffusion_mask(logits.shape[-1], block_diffusion), logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    if h != h_kv:
        return jnp.einsum("bngqk,bnkd->bngqd", probs, v).reshape(*q.shape[:-1], v.shape[-1])
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, t, d = x.shape
    return x.reshape(b, t, n_heads, d // n_heads).transpose(0, 2, 1, 3)


def merge_heads(x: jax.Array) -> jax.Array:
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def yarn_inv_freq(
    rotary_dim: int, base: float, factor: float, original_max_len: int,
    beta_fast: float = 32.0, beta_slow: float = 1.0,
) -> jax.Array:
    """YaRN's inverse frequencies (Peng et al. 2023, arXiv:2309.00071, as the
    public ``_compute_yarn_parameters`` computes them), ``[rotary_dim / 2]``
    float32: a frequency that turns more than ``beta_fast`` times within the
    original context keeps its value (extrapolation), one that turns fewer
    than ``beta_slow`` times is divided by ``factor`` (interpolation), and the
    dimensions between are blended linearly."""
    import math

    def correction_dim(rotations: float) -> float:
        return rotary_dim * math.log(original_max_len / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001  # the public code's guard against a zero-width ramp
    pos_freqs = base ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    ramp = jnp.clip(
        (jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    extrapolated = 1.0 - ramp  # 1 where the frequency is kept as it is
    return (1.0 / (factor * pos_freqs)) * (1.0 - extrapolated) + (1.0 / pos_freqs) * extrapolated


def rope(
    x: jax.Array,
    positions: Optional[jax.Array] = None,
    base: float = 10000.0,
    layout: str = "interleaved",
    rotary_dim: Optional[int] = None,
    inv_freq: Optional[jax.Array] = None,
    scale: float = 1.0,
) -> jax.Array:
    """Rotary position embedding over the last dim of ``x`` [B, H, T, D].

    ``rotary_dim`` (default D) rotates the first ``rotary_dim`` coordinates of
    each head and passes the rest through (partial rotary); ``inv_freq``
    ``[rotary_dim / 2]`` replaces ``base ** (-2i / rotary_dim)`` (YaRN:
    ``yarn_inv_freq``); ``scale`` multiplies cos and sin (YaRN's attention
    factor).

    ``layout`` says which two coordinates a frequency rotates together:
    ``"interleaved"`` pairs (2i, 2i+1) (the RoFormer paper; what the Llama
    proxy here trains with), ``"half"`` pairs (i, i + D/2) (``rotate_half`` in
    the public GPT-NeoX / OLMoE implementations). The two are one rotation
    under a fixed permutation of each head's coordinates, so a model trained
    from scratch may use either; weights published for one need that one."""
    if layout not in ("interleaved", "half"):
        raise ValueError(f"unknown rope layout {layout!r}")
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        rotated = rope(x[..., :rotary_dim], positions, base, layout, None, inv_freq, scale)
        return jnp.concatenate([rotated, x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    t = x.shape[-2]
    if positions is None:
        positions = jnp.arange(t)
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d) if inv_freq is None else inv_freq
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [T, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32)
    if layout == "half":
        x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.astype(x.dtype)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    rx1 = x1 * cos - x2 * sin
    rx2 = x1 * sin + x2 * cos
    out = jnp.stack([rx1, rx2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)
