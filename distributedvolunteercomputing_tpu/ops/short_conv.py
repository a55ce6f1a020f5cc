"""The gated short convolution of a ``conv`` token mixer (LFM2,
``models/lfm2.py``): from the input projection's three streams ``[B | C | u]``
``[batch, T, 3 d]`` and the depthwise taps ``w`` ``[K, d]`` (K = 3)::

    g   = B * u
    c_t = sum_j w[j] g_{t - (K - 1 - j)}      causal: zeros before the sequence's start
    y   = C * c                                [batch, T, d]

Two gates and K - 1 shifted multiply-adds: one read of the three streams and
one write, forward; backward one more read (the cotangent) and one write of
the streams' cotangent.

Two forms of the same arithmetic:

- ``short_conv_xla``: plain ``jax.numpy`` (shifts by pad and slice), what the
  CPU, a step over several chips and every shape the kernel does not take run;
  differentiated by JAX.
- the Pallas kernels ``dvc_short_conv_fwd`` / ``dvc_short_conv_bwd`` on one
  TPU chip. Why a kernel for five elementwise operations: XLA's fusions carry
  no name of ours into a device trace (an ``XLA Ops`` event is the
  instruction's text, ``%fusion.N``; ``benchmark/moe_trace.py``), and XLA is
  free to fold the gates into the projections beside them, so neither the
  convolution's milliseconds nor its passes over memory could be read; a
  kernel has a name, and moves exactly the bytes counted above. It computes in
  float32 from the compute dtype's streams and rounds once, on the way out.

A block is ``block_t`` positions of one sequence over all ``d`` channels; the
K - 1 positions before it (after it, for the cotangent in the backward) come
from the neighbouring block's edge, read as a block of ``_EDGE`` rows.

The second convolution of the repo (``causal_conv``: a Mamba-2 mixer's,
``models/nemotron_h.py``) is the same depthwise causal sum over ONE stream, with
a bias and SiLU and no gates: ``y = silu(conv(u) + b)``, K = 4, 6,144
channels. The taps were static already (any K up to ``_EDGE``); what the blocks
read of their neighbours, the shifts, the grid and the kernels' NAMES
(``dvc_short_conv_fwd`` / ``_bwd``: what ``conv.device_ms`` reads) are shared,
and the two kernel BODIES are its own (``_fwd_kernel_stream`` /
``_bwd_kernel_stream``): a gate-less body inside the gated one would be a flag on
every line of it, and the gated bodies stay the text LFM2's step compiled from.
Its backward needs the activation's slope on the K - 1 rows AFTER the block
too, so it rebuilds the convolution there from the next block's edge and its
own last rows.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedvolunteercomputing_tpu.ops.attention import chips_in_step
from distributedvolunteercomputing_tpu.utils.jaxenv import tpu_backend

LANES = 128
_EDGE = 16          # rows of a neighbour's edge block: one bf16 sublane tile
BLOCK_T = 256       # positions a block holds (PERF.md, Findings of PR 39)
_VMEM_LIMIT = 64 * 1024 * 1024


def short_conv_xla(bcu: jax.Array, taps: jax.Array) -> jax.Array:
    """The plain form, in ``bcu``'s dtype."""
    b_, c_, u_ = jnp.split(bcu, 3, axis=-1)
    g = b_ * u_
    k, t = taps.shape[0], g.shape[1]
    w = taps.astype(g.dtype)
    padded = jnp.pad(g, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * w[j] for j in range(k))
    return c_ * conv


def choose_block(t: int, d: int, taps: int) -> Optional[int]:
    """Positions a kernel block holds for a ``[*, t, 3 d]`` input, or None
    where the kernel does not take the shape: channels in whole lane tiles, a
    sequence in whole blocks, an edge wide enough for the taps."""
    if d % LANES or not 2 <= taps <= _EDGE:
        return None
    for block in (BLOCK_T, 128, 64, 32):
        if t % block == 0:
            return block
    return None


def _shifted(x: jax.Array, edge_rows, by: int, later: bool) -> jax.Array:
    """``x`` [block, d] moved ``by`` rows: ``out[i] = x[i - by]``, the first
    ``by`` rows from the previous block's last (``later``: ``out[i] = x[i +
    by]``, the last ``by`` rows from the next block's first); ``edge_rows`` are
    those ``by`` rows, in order."""
    n = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    out = pltpu.roll(x, (n - by) if later else by, 0)
    for i, edge in enumerate(edge_rows):
        out = jnp.where(row == ((n - by + i) if later else i), edge, out)
    return out


def _streams(ref, d: int):
    f32 = jnp.float32
    return (ref[:, 0:d].astype(f32), ref[:, d:2 * d].astype(f32), ref[:, 2 * d:3 * d].astype(f32))


def _conv_of_block(g, prev_g, w, taps: int):
    """(sum_j w[j] g shifted by K-1-j, [g shifted by 1, by 2, ...])."""
    shifted = [
        _shifted(g, [prev_g[_EDGE - by + i:_EDGE - by + i + 1] for i in range(by)], by, False)
        for by in range(1, taps)
    ]
    conv = g * w[taps - 1:taps]
    for by, s in enumerate(shifted, start=1):
        conv = conv + s * w[taps - 1 - by:taps - by]
    return conv, shifted


def _fwd_kernel(x_ref, prev_ref, w_ref, o_ref, *, d: int, taps: int):
    b_, c_, u_ = _streams(x_ref, d)
    pb, _, pu = _streams(prev_ref, d)
    prev_g = jnp.where(pl.program_id(1) > 0, pb * pu, 0.0)
    conv, _ = _conv_of_block(b_ * u_, prev_g, w_ref[...], taps)
    o_ref[...] = (c_ * conv).astype(o_ref.dtype)


def _bwd_kernel(x_ref, prev_ref, next_ref, dy_ref, dy_next_ref, w_ref, dx_ref, dw_ref, *,
                d: int, taps: int, n_blocks: int):
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    f32 = jnp.float32
    w = w_ref[...]
    b_, c_, u_ = _streams(x_ref, d)
    pb, _, pu = _streams(prev_ref, d)
    g = b_ * u_
    prev_g = jnp.where(pl.program_id(1) > 0, pb * pu, 0.0)
    conv, shifted = _conv_of_block(g, prev_g, w, taps)
    dy = dy_ref[...].astype(f32)
    da = dy * c_                                     # the convolution's cotangent
    next_da = jnp.where(pl.program_id(1) < n_blocks - 1,
                        dy_next_ref[...].astype(f32) * next_ref[:, d:2 * d].astype(f32), 0.0)
    dg = da * w[taps - 1:taps]
    for by in range(1, taps):
        later = _shifted(da, [next_da[i:i + 1] for i in range(by)], by, True)
        dg = dg + later * w[taps - 1 - by:taps - by]
    dx_ref[:, 0:d] = (dg * u_).astype(dx_ref.dtype)
    dx_ref[:, d:2 * d] = (dy * conv).astype(dx_ref.dtype)
    dx_ref[:, 2 * d:3 * d] = (dg * b_).astype(dx_ref.dtype)
    for by, s in enumerate([g] + shifted):
        dw_ref[taps - 1 - by:taps - by, :] += jnp.sum(da * s, axis=0, keepdims=True)


def _params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


def _specs(block: int, width: int, n_blocks: int):
    """(a block, the last ``_EDGE`` rows before it, the first ``_EDGE`` rows
    after it) of a ``[batch, T, width]`` array; a sequence's first block reads
    its own edge for the former and its last for the latter, and the kernel
    puts zeros there."""
    per = block // _EDGE
    here = pl.BlockSpec((None, block, width), lambda i, j: (i, j, 0))
    prev = pl.BlockSpec((None, _EDGE, width), lambda i, j: (i, jnp.maximum(j * per - 1, 0), 0))
    nxt = pl.BlockSpec(
        (None, _EDGE, width), lambda i, j: (i, jnp.minimum((j + 1) * per, n_blocks * per - 1), 0))
    return here, prev, nxt


def _kernel_fwd(bcu: jax.Array, taps: jax.Array, block: int, interpret: bool) -> jax.Array:
    batch, t, d3 = bcu.shape
    d, k = d3 // 3, taps.shape[0]
    here, prev, _ = _specs(block, d3, t // block)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, taps=k),
        grid=(batch, t // block),
        in_specs=[here, prev, pl.BlockSpec((k, d), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, t, d), bcu.dtype),
        compiler_params=_params(interpret), interpret=interpret, name="dvc_short_conv_fwd",
    )(bcu, bcu, taps.astype(jnp.float32))


def _kernel_bwd(bcu: jax.Array, taps: jax.Array, dy: jax.Array, block: int,
                interpret: bool) -> Tuple[jax.Array, jax.Array]:
    batch, t, d3 = bcu.shape
    d, k = d3 // 3, taps.shape[0]
    n_blocks = t // block
    here, prev, nxt = _specs(block, d3, n_blocks)
    dy_here, _, dy_next = _specs(block, d, n_blocks)
    whole = pl.BlockSpec((k, d), lambda i, j: (0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, taps=k, n_blocks=n_blocks),
        grid=(batch, n_blocks),
        in_specs=[here, prev, nxt, dy_here, dy_next, whole],
        out_specs=[here, whole],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((k, d), jnp.float32)],
        compiler_params=_params(interpret), interpret=interpret, name="dvc_short_conv_bwd",
    )(bcu, bcu, bcu, dy, dy, taps.astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def short_conv_kernel(bcu: jax.Array, taps: jax.Array, block: int, interpret: bool) -> jax.Array:
    return _kernel_fwd(bcu, taps, block, interpret)


def _vjp_fwd(bcu, taps, block, interpret):
    return _kernel_fwd(bcu, taps, block, interpret), (bcu, taps)


def _vjp_bwd(block, interpret, res, dy):
    bcu, taps = res
    dx, dw = _kernel_bwd(bcu, taps, dy, block, interpret)
    return dx, dw.astype(taps.dtype)


short_conv_kernel.defvjp(_vjp_fwd, _vjp_bwd)


# ---------------------------------------------------------------------------
# one stream, a bias, an activation, no gates
# ---------------------------------------------------------------------------

def _silu_slope(z: jax.Array) -> jax.Array:
    """SiLU's derivative from the pre-activation."""
    s = jax.nn.sigmoid(z)
    return s * (1.0 + z * (1.0 - s))


def causal_conv_xla(u: jax.Array, taps: jax.Array, bias: jax.Array) -> jax.Array:
    """The plain form, in ``u``'s dtype: ``silu(sum_j taps[j] u_{t-(K-1-j)} + bias)``."""
    k, t = taps.shape[0], u.shape[1]
    w = taps.astype(u.dtype)
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * w[j] for j in range(k))
    return jax.nn.silu(conv + bias.astype(u.dtype))


def _fwd_kernel_stream(x_ref, prev_ref, w_ref, b_ref, o_ref, *, taps: int):
    f32 = jnp.float32
    prev = jnp.where(pl.program_id(1) > 0, prev_ref[...].astype(f32), 0.0)
    conv, _ = _conv_of_block(x_ref[...].astype(f32), prev, w_ref[...], taps)
    o_ref[...] = jax.nn.silu(conv + b_ref[...]).astype(o_ref.dtype)


def _bwd_kernel_stream(x_ref, prev_ref, next_ref, dy_ref, dy_next_ref, w_ref, b_ref,
                      dx_ref, dw_ref, db_ref, *, taps: int, n_blocks: int):
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    f32 = jnp.float32
    w, bias = w_ref[...], b_ref[...]
    u = x_ref[...].astype(f32)
    n = u.shape[0]
    prev = jnp.where(pl.program_id(1) > 0, prev_ref[...].astype(f32), 0.0)
    conv, shifted = _conv_of_block(u, prev, w, taps)
    da = dy_ref[...].astype(f32) * _silu_slope(conv + bias)              # the convolution's cotangent
    # the same on the next block's first rows, whose convolution reaches back into this block
    conv_next, _ = _conv_of_block(next_ref[...].astype(f32), u[n - _EDGE:], w, taps)
    next_da = jnp.where(pl.program_id(1) < n_blocks - 1,
                        dy_next_ref[...].astype(f32) * _silu_slope(conv_next + bias), 0.0)
    du = da * w[taps - 1:taps]
    for by in range(1, taps):
        later = _shifted(da, [next_da[i:i + 1] for i in range(by)], by, True)
        du = du + later * w[taps - 1 - by:taps - by]
    dx_ref[...] = du.astype(dx_ref.dtype)
    for by, s in enumerate([u] + shifted):
        dw_ref[taps - 1 - by:taps - by, :] += jnp.sum(da * s, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(da, axis=0, keepdims=True)


def _stream_fwd(u, taps, bias, block: int, interpret: bool) -> jax.Array:
    batch, t, d = u.shape
    k = taps.shape[0]
    here, prev, _ = _specs(block, d, t // block)
    return pl.pallas_call(
        functools.partial(_fwd_kernel_stream, taps=k),
        grid=(batch, t // block),
        in_specs=[here, prev, pl.BlockSpec((k, d), lambda i, j: (0, 0)),
                  pl.BlockSpec((1, d), lambda i, j: (0, 0))],
        out_specs=here,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        compiler_params=_params(interpret), interpret=interpret, name="dvc_short_conv_fwd",
    )(u, u, taps.astype(jnp.float32), bias.astype(jnp.float32)[None])


def _stream_bwd(u, taps, bias, dy, block: int, interpret: bool):
    batch, t, d = u.shape
    k = taps.shape[0]
    n_blocks = t // block
    here, prev, nxt = _specs(block, d, n_blocks)
    whole = pl.BlockSpec((k, d), lambda i, j: (0, 0))
    row = pl.BlockSpec((1, d), lambda i, j: (0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel_stream, taps=k, n_blocks=n_blocks),
        grid=(batch, n_blocks),
        in_specs=[here, prev, nxt, here, nxt, whole, row],
        out_specs=[here, whole, row],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((k, d), jnp.float32),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        compiler_params=_params(interpret), interpret=interpret, name="dvc_short_conv_bwd",
    )(u, u, u, dy, dy, taps.astype(jnp.float32), bias.astype(jnp.float32)[None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def causal_conv_kernel(u: jax.Array, taps: jax.Array, bias: jax.Array, block: int, interpret: bool) -> jax.Array:
    return _stream_fwd(u, taps, bias, block, interpret)


def _stream_vjp_fwd(u, taps, bias, block, interpret):
    return _stream_fwd(u, taps, bias, block, interpret), (u, taps, bias)


def _stream_vjp_bwd(block, interpret, res, dy):
    u, taps, bias = res
    du, dw, db = _stream_bwd(u, taps, bias, dy, block, interpret)
    return du, dw.astype(taps.dtype), db[0].astype(bias.dtype)


causal_conv_kernel.defvjp(_stream_vjp_fwd, _stream_vjp_bwd)


# What the stream kernels' backward holds in VMEM a position and channel of its block (the compiler's own count:
# 79.07 MB for a block of 256 positions of 8,192 channels, PR 67): the five double-buffered bf16 blocks and the
# float32 copies, shifts and slopes made of them.
_STREAM_VMEM_BYTES = 38


def choose_stream_block(t: int, d: int, taps: int) -> Optional[int]:
    """``choose_block`` for ONE stream of ``d`` channels, halved until the
    backward kernel's block fits its VMEM: 256 positions up to 6,898 channels
    (every stream before Qwen3-Next's: 4,096 and 6,144), 128 for its 8,192."""
    block = choose_block(t, d, taps)
    while block is not None and block > 32 and block * d * _STREAM_VMEM_BYTES > _VMEM_LIMIT:
        block //= 2
    return block


def causal_conv(u: jax.Array, taps: jax.Array, bias: jax.Array) -> jax.Array:
    """``silu(conv(u) + bias)`` over ``u`` [batch, T, d]: the kernels on one TPU
    chip where they take the shape, the plain form elsewhere, as ``short_conv``."""
    block = choose_stream_block(u.shape[1], u.shape[2], taps.shape[0])
    if block is None or not tpu_backend() or chips_in_step() > 1:
        return causal_conv_xla(u, taps, bias)
    return causal_conv_kernel(u, taps, bias, block, False)


def short_conv(bcu: jax.Array, taps: jax.Array) -> jax.Array:
    """``C * conv(B * u)``: the kernel on one TPU chip where it takes the
    shape, the plain form elsewhere (Mosaic refuses a kernel that GSPMD would
    have to partition, and the plain form is partitioned like any other op)."""
    block = choose_block(bcu.shape[1], bcu.shape[2] // 3, taps.shape[0])
    if block is None or not tpu_backend() or chips_in_step() > 1:
        return short_conv_xla(bcu, taps)
    return short_conv_kernel(bcu, taps, block, False)
