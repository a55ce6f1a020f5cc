"""The Mamba-2 recurrence of a state-space token mixer (``models/nemotron_h.py``)
as a CHUNKED SCAN with its own backward. For each head, with a state ``S`` in
``R^{P x N}`` that starts at zero::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

``x`` [batch, T, H, P], ``dt`` [batch, T, H] (positive, float32), ``A`` =
``-exp(a_log)`` [H], ``B`` and ``C`` [batch, T, G, N] (head ``h`` reads group
``h // (H / G)``), ``D`` [H]. Position by position this is T dependent steps of
rank-one updates; in chunks of Q positions (Q = 128, the model's
``chunk_size``) it is matrix products and a carried state. With ``cum_i`` the
running sum of ``dt A`` inside a chunk (inclusive), ``xd = dt x``::

    y   = ((C B^T) o L) xd + exp(cum) o (C S_prev^T)     L_ij = exp(cum_i - cum_j), j <= i
    S   = exp(cum_Q) S_prev + (exp(cum_Q - cum) o xd)^T B

All decays, ``L`` and the state in float32; the products' operands in the
compute dtype, accumulated in float32. ``ssd`` takes the mixer's streams where
the convolution leaves them: ONE array ``xbc`` [batch, T, H P + 2 G N] with
``x'`` in its first H P lanes (head h in lanes h P ..), ``B`` in the next G N
and ``C`` in the last, and gives ``y`` [batch, T, H P] the same way round. Two
forms, the same arithmetic behind that one entry, each with its own backward:
the reverse scan over the chunks that carries the state's cotangent ``dS``
(``_chunk_bwd`` spells it out), reading each chunk's incoming state as the
forward wrote it (float32: the residual beside the inputs).

- ``lax.scan`` over the chunks in ``jax.numpy`` (``plain_core``: ``_plain_fwd``
  / ``_plain_bwd`` over ``(xd, cum, B, C) -> y``): the CPU, a step over several
  chips, every shape the kernels do not take. It works by head, [Z, H, T, P] and
  [Z, G, T, N]: ``_plain_ssd`` splits ``xbc``, makes ``xd = dt x'`` and the skip
  in passes of their own and transposes there and back, all JAX's to
  differentiate.
- the Pallas kernels ``dvc_ssd_fwd`` / ``dvc_ssd_bwd`` on one TPU chip
  (``kernel_core``): a grid of (sequence, group, chunk) with the chunks in order
  (reversed backward), TOKEN-MAJOR. A grid step reads the group's [Q, R P] lanes
  of ``x'`` and its [Q, N] of ``B`` and of ``C`` straight out of ``xbc`` (three
  BlockSpecs over the one array: no split, reshape or transpose of it exists),
  ``cum`` and ``dt`` as [R, Q] float32 rows of [Z, T / Q, H, Q], and ``D`` by
  lane; it makes ``xd = (dt x')`` in float32 and rounds it to the compute dtype
  before any product, adds ``D x'`` to ``y`` before the one store, and backward
  returns ``dx'`` (both its terms), ``dB``, ``dC``, ``dcum``, ``d dt`` (a row sum
  over a head's lanes) and a sequence's ``sum dy x'`` for ``dD``. ``dx'`` is
  written into an array of ``xbc``'s shape, and ``dB`` and ``dC`` are put into
  their lanes of it in place (two updates of G N lanes: a Pallas result takes
  one BlockSpec, and a concatenation would move all H P + 2 G N lanes again).
  The group's H / G heads share ``C B^T``; they are taken a lane tile at a time,
  ``heads_a_tile`` side by side (two heads of 64 in 128 lanes), their states
  turned, [N, R P] float32, resident in VMEM from chunk to chunk. A device
  trace shows the kernels under these names (``benchmark/ssd_trace.py``).

A sequence that is no whole number of chunks is padded with positions whose
``dt`` is 0 (no decay, no input) and cut again. Which form a traced scan took
is noted as "ssd_scan" (``utils/traced.py``; the ``ssm.scan`` span's ``ssm_form``).

MEASURED (PR 49, TPU v5e, nemotron3-nano-solo-8k: 64 heads of 64 in 8 groups,
state 128, chunks of 128, 2 x 8,192 tokens, bf16; PERF.md, Findings of PR 49).
Until PR 49 the kernels took ``dt x'``, ``B``, ``C`` by head ([Z, H, T, 64],
[Z, G, T, 128]) and gave ``y`` back so: six transposes and three elementwise
passes a block each way, and a head of 64 in half of a 128-lane tile. The scan as
the mixer calls it, from ``xbc`` to ``y`` (``experiments/ssd_sweep.py``): 8.41 ms
-> 1.50 forward, 15.44 -> 4.01 forward with every gradient; in the step the
kernels 1.46 -> 1.25 ms a forward call and 2.11 -> 2.21 backward (which now
makes ``dt x'``, the skip and their cotangents), ``ssm.device_ms`` 15.08 -> 14.10
with nothing by head left around them, ``step.device_ms`` 424.97 -> 376.27,
``tok_s_chip`` 38,585-38,677 -> 43,652-43,751 (+13.1%). What the kernels' time
is: a head's cum and dt down the rows (``_as_column``: a masked sum along
lanes, 0.07 us a head and quantity; turning the [R, Q] block instead, or a tile
of copies of a row, reads a third slower: a column that comes out of a
transpose pays for every broadcast along lanes) and, backward, the sums along
lanes (folding two into one took 0.6 ms off a call); making ``m^T`` instead of
turning ``m`` took 0.8 ms off, the states turned 0.3, the cotangent updated in
place instead of concatenated 0.4.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedvolunteercomputing_tpu.ops.attention import chips_in_step
from distributedvolunteercomputing_tpu.ops.lanes import by_head, of_head
from distributedvolunteercomputing_tpu.utils import traced
from distributedvolunteercomputing_tpu.utils.jaxenv import tpu_backend

CHUNK = 128
# a chunk's whole decay under which the state carried into it counts as forgotten
CARRY_FLOOR = 1e-3
_VMEM_LIMIT = 64 * 1024 * 1024
_F32 = jnp.float32

_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


def _dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=_F32)


# ---------------------------------------------------------------------------
# one chunk, every sequence, group and head at once: the plain form's body
# ---------------------------------------------------------------------------
# s, ds [Z, G, R, P, N] float32 (R heads a group); xd, dy [Z, G, R, Q, P];
# cum [Z, G, R, Q] float32; b, c [Z, G, Q, N]


def _decays(cum: jax.Array):
    """(L [.., Q, Q] masked, exp(cum), exp(cum_Q - cum), exp(cum_Q))."""
    q = cum.shape[-1]
    tri = jnp.tril(jnp.ones((q, q), bool))
    lower = jnp.exp(jnp.where(tri, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    last = cum[..., -1:]
    return lower, jnp.exp(cum), jnp.exp(last - cum), jnp.exp(last[..., 0])


def _chunk_fwd(s, xd, cum, b, c):
    dtype = xd.dtype
    lower, e, w, whole = _decays(cum)
    cb = jnp.einsum("zgin,zgjn->zgij", c, b, preferred_element_type=_F32)
    m = (cb[:, :, None] * lower).astype(dtype)
    y = jnp.einsum("zgrij,zgrjp->zgrip", m, xd, preferred_element_type=_F32)
    y = y + e[..., None] * jnp.einsum("zgin,zgrpn->zgrip", c, s.astype(dtype),
                                      preferred_element_type=_F32)
    xw = (w[..., None] * xd).astype(dtype)
    s_new = whole[..., None, None] * s + jnp.einsum(
        "zgrjp,zgjn->zgrpn", xw, b, preferred_element_type=_F32)
    return y.astype(dtype), s_new


def _chunk_bwd(ds, s, xd, cum, b, c, dy):
    """Cotangents (dxd, dcum, db, dc) of one chunk and of the state that
    entered it, from ``dy`` and the cotangent ``ds`` of the state that left."""
    dtype = xd.dtype
    lower, e, w, whole = _decays(cum)
    cb = jnp.einsum("zgin,zgjn->zgij", c, b, preferred_element_type=_F32)
    m = cb[:, :, None] * lower
    sb, dsb = s.astype(dtype), ds.astype(dtype)
    dm = jnp.einsum("zgrip,zgrjp->zgrij", dy, xd, preferred_element_type=_F32)
    z = dm * m
    dcb = jnp.sum(dm * lower, axis=2).astype(dtype)
    y_in = jnp.einsum("zgin,zgrpn->zgrip", c, sb, preferred_element_type=_F32)
    from_ds = jnp.einsum("zgjn,zgrpn->zgrjp", b, dsb, preferred_element_type=_F32)
    dxd = jnp.einsum("zgrij,zgrip->zgrjp", m.astype(dtype), dy,
                     preferred_element_type=_F32) + w[..., None] * from_ds
    dye = (e[..., None] * dy).astype(dtype)
    xw = (w[..., None] * xd).astype(dtype)
    dw = w * jnp.sum(xd * from_ds, axis=-1)
    dc = (jnp.einsum("zgrip,zgrpn->zgin", dye, sb, preferred_element_type=_F32)
          + jnp.einsum("zgij,zgjn->zgin", dcb, b, preferred_element_type=_F32))
    db = (jnp.einsum("zgrjp,zgrpn->zgjn", xw, dsb, preferred_element_type=_F32)
          + jnp.einsum("zgij,zgin->zgjn", dcb, c, preferred_element_type=_F32))
    dcum = jnp.sum(z, axis=-1) - jnp.sum(z, axis=-2) + e * jnp.sum(dy * y_in, axis=-1) - dw
    dlast = whole * jnp.sum(ds * s, axis=(-1, -2)) + jnp.sum(dw, axis=-1)
    dcum = dcum.at[..., -1].add(dlast)
    ds_prev = whole[..., None, None] * ds + jnp.einsum(
        "zgrip,zgin->zgrpn", dye, c, preferred_element_type=_F32)
    return dxd.astype(dtype), dcum, db.astype(dtype), dc.astype(dtype), ds_prev


def _rows_by_chunk(rows, g: int, nc: int):
    """[Z, H, T, P] -> [nc, Z, G, R, Q, P]: the chunk axis leading, the heads by group."""
    z, h, t, p = rows.shape
    return jnp.moveaxis(rows.reshape(z, g, h // g, nc, t // nc, p), 3, 0)


def _by_chunk(xd, cum, b, c):
    """The core's arguments with the chunk axis leading and the heads by group:
    xd [nc, Z, G, R, Q, P], cum [nc, Z, G, R, Q], b / c [nc, Z, G, Q, N]."""
    z, h = xd.shape[:2]
    nc, q = cum.shape[1], cum.shape[3]
    g, n = b.shape[1], b.shape[3]
    return (_rows_by_chunk(xd, g, nc),
            jnp.moveaxis(cum, 1, 0).reshape(nc, z, g, h // g, q),
            jnp.moveaxis(b.reshape(z, g, nc, q, n), 2, 0),
            jnp.moveaxis(c.reshape(z, g, nc, q, n), 2, 0))


def _rows_back(a, shape):
    """[nc, Z, G, R, Q, P] -> [Z, H, T, P] (or the groups' [nc, Z, G, Q, N] -> [Z, G, T, N])."""
    lead = a.ndim - 3
    return jnp.moveaxis(a, 0, lead).reshape(shape)


def _plain_fwd(xd, cum, b, c):
    """(y [Z, H, T, P], the state entering each chunk [Z, nc, H, P, N] float32)."""
    z, h, t, p = xd.shape
    g, n = b.shape[1], b.shape[3]
    args = _by_chunk(xd, cum, b, c)

    def step(s, chunk):
        y, s_new = _chunk_fwd(s, *chunk)
        return s_new, (y, s)

    _, (y, states) = jax.lax.scan(step, jnp.zeros((z, g, h // g, p, n), _F32), args)
    return _rows_back(y, xd.shape), jnp.moveaxis(states, 0, 1).reshape(z, -1, h, p, n)


def _plain_bwd(xd, cum, b, c, states, dy):
    z, h, t, p = xd.shape
    nc = cum.shape[1]
    g, n = b.shape[1], b.shape[3]
    r = h // g
    args = _by_chunk(xd, cum, b, c)
    dys = _rows_by_chunk(dy, g, nc)
    states = jnp.moveaxis(states.reshape(z, nc, g, r, p, n), 1, 0)

    def step(ds, chunk):
        s, dy_c, *rest = chunk
        dxd, dcum, db, dc, ds_prev = _chunk_bwd(ds, s, *rest, dy_c)
        return ds_prev, (dxd, dcum, db, dc)

    _, (dxd, dcum, db, dc) = jax.lax.scan(
        step, jnp.zeros((z, g, r, p, n), _F32), (states, dys, *args), reverse=True)
    return (_rows_back(dxd, xd.shape), jnp.moveaxis(dcum.reshape(nc, z, h, -1), 0, 1),
            _rows_back(db, b.shape), _rows_back(dc, c.shape))


# ---------------------------------------------------------------------------
# the kernels: one (sequence, group, chunk) a grid step, token-major
# ---------------------------------------------------------------------------
# A grid step sees the group's rows of one chunk as the mixer has them: x' / y /
# dy / dx' [Q, R P] (head r in lanes r P .. r P + P - 1), B / C / dB / dC [Q, N],
# cum / dt and their cotangents [R, Q] float32, and the group's states TURNED,
# [N, R P] float32 (head r in the lanes it has in x'). The heads are taken a
# lane TILE at a time: ``per`` heads side by side in ``per P`` lanes (two heads
# of 64 in a tile of 128). What all of a tile's heads share is one product over
# the whole tile (``C S^T``, ``B dS^T``, the state's update and the sums into dB
# / dC); what differs by head (``m_h``) is a product over the tile a head, of
# which a select on the lane keeps the head's part. No operand is turned in the
# loop over the tiles: with the states as [N, R P] their products take ``B^T`` /
# ``C^T``, turned once a grid step, and the backward makes ``m_h^T`` from
# ``B C^T`` and the upper triangle as the forward makes ``m_h``.


def _chunk_masks(q: int):
    """(row == lane, row >= lane, row <= lane) of a [Q, Q]."""
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return row == col, row >= col, row <= col


def _as_column(row_vec, eye):
    """[1, Q] -> [Q, 1], exactly: the diagonal of its broadcast, summed along lanes."""
    q = eye.shape[0]
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row_vec, (q, q)), 0.0), axis=1, keepdims=True)


def _last_as_column(row_vec):
    """[1, Q] -> [Q, 1], every entry the vector's last: Mosaic broadcasts a
    [1, 1] along sublanes or lanes, not both, so the chunk's whole sum is made
    a column here and broadcasts along lanes from there."""
    q = row_vec.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return jnp.sum(jnp.where(lane == q - 1, jnp.broadcast_to(row_vec, (q, q)), 0.0), axis=1, keepdims=True)


def _as_row(col_vec, eye):
    q = eye.shape[0]
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(col_vec, (q, q)), 0.0), axis=0, keepdims=True)


def heads_a_tile(heads_a_group: int, head_dim: int) -> int:
    """How many heads the kernels take side by side: those that fill a tile of
    128 lanes (one where a head is a tile or more), at most the group's."""
    return min(heads_a_group, max(1, 128 // head_dim))


def _tile_decays(cum_ref, dt_ref, first: int, per: int, width: int, eye):
    """Of the ``per`` heads from ``first`` of the group: each head's cum along
    the lanes [1, Q] and down the rows [Q, 1]; by lane of the tile, down the
    rows [Q, 1 or width]: ``dt``, ``exp(cum)`` and ``exp(cum_Q - cum)``; by lane
    of the tile [1, 1 or width]: ``exp(cum_Q)``."""
    q, p = eye.shape[0], width // per
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, width), 1)
    rows, cols, dts, es, ws, wholes = [], [], [], [], [], []
    for h in range(first, first + per):
        row = cum_ref[h:h + 1, :]
        col = _as_column(row, eye)
        rows.append(row)
        cols.append(col)
        dts.append(_as_column(dt_ref[h:h + 1, :], eye))
        es.append(jnp.exp(col))
        ws.append(jnp.exp(_last_as_column(row) - col))
        wholes.append(jnp.exp(row[:, q - 1:]))
    return (rows, cols, lane, by_head(dts, lane, p), by_head(es, lane, p), by_head(ws, lane, p),
            by_head(wholes, lane[:1], p))


def _fwd_kernel(x_ref, b_ref, c_ref, cum_ref, dt_ref, d_ref, y_ref, st_ref, s_scr, *, per: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    dtype = x_ref.dtype
    bm, cm = b_ref[...], c_ref[...]
    q, heads = bm.shape[0], cum_ref.shape[0]
    width = x_ref.shape[1] // heads * per
    eye, tri, _ = _chunk_masks(q)
    cb = _dot(cm, bm, _NT)                                        # [Q, Q]
    b_t = bm.T                                                    # [N, Q]
    for first in range(0, heads, per):
        at = slice(first // per * width, (first // per + 1) * width)
        rows, cols, lane, dt, e, w, whole = _tile_decays(cum_ref, dt_ref, first, per, width, eye)
        s = s_scr[:, at]                                          # [N, width]: the tile's states, turned
        st_ref[:, at] = s
        xf = x_ref[:, at].astype(_F32)
        xd = (xf * dt).astype(dtype)
        y = by_head([_dot((cb * jnp.exp(jnp.where(tri, col - row, -jnp.inf))).astype(dtype), xd, _NN)
                      for row, col in zip(rows, cols)], lane, width // per)
        y = y + e * _dot(cm, s.astype(dtype), _NN) + d_ref[:, at] * xf
        y_ref[:, at] = y.astype(dtype)
        xw = (w * xd.astype(_F32)).astype(dtype)
        s_scr[:, at] = whole * s + _dot(b_t, xw, _NN)


def _bwd_kernel(x_ref, b_ref, c_ref, cum_ref, dt_ref, d_ref, st_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dcum_ref, ddt_ref, dd_ref, ds_scr, *, per: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    dtype = x_ref.dtype
    bm, cm = b_ref[...], c_ref[...]
    q, heads = bm.shape[0], cum_ref.shape[0]
    width = x_ref.shape[1] // heads * per
    p = width // per
    eye, tri, upper = _chunk_masks(q)
    last_lane = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    cb, cb_t = _dot(cm, bm, _NT), _dot(bm, cm, _NT)
    c_t = cm.T                                                                # [N, Q]
    dcb = jnp.zeros((q, q), _F32)
    db = jnp.zeros(bm.shape, _F32)
    dc = jnp.zeros(cm.shape, _F32)
    for first in range(0, heads, per):
        at = slice(first // per * width, (first // per + 1) * width)
        rows, cols, lane, dt, e, w, whole = _tile_decays(cum_ref, dt_ref, first, per, width, eye)
        s, ds = st_ref[:, at], ds_scr[:, at]                                  # [N, width]
        sb, dsb = s.astype(dtype), ds.astype(dtype)
        xf, dyb = x_ref[:, at].astype(_F32), dy_ref[:, at]
        dyf = dyb.astype(_F32)
        xd = (xf * dt).astype(dtype)
        xdf = xd.astype(_F32)
        y_in = _dot(cm, sb, _NN)                                              # [Q, width]
        from_ds = _dot(bm, dsb, _NN)
        dw = w * xdf * from_ds                                                # before its sum over a head's lanes
        by_lane = e * dyf * y_in - dw
        zs, dxds = [], []
        for i, (row, col) in enumerate(zip(rows, cols)):
            lower = jnp.exp(jnp.where(tri, col - row, -jnp.inf))
            dm = _dot(of_head(dyf, lane, i, p).astype(dtype), xd, _NT)       # [Q, Q]: over head i's lanes
            zs.append(dm * (cb * lower))
            dcb = dcb + dm * lower
            m_t = cb_t * jnp.exp(jnp.where(upper, row - col, -jnp.inf))       # m^T, made as m is, not turned
            dxds.append(_dot(m_t.astype(dtype), dyb, _NN))
        dxd = by_head(dxds, lane, p) + w * from_ds
        dye = (e * dyf).astype(dtype)
        dc = dc + _dot(dye, sb, _NT)
        db = db + _dot((w * xdf).astype(dtype), dsb, _NT)
        # of the chunk's whole sum, by lane before a head's lanes are summed: through the state kept, through w
        to_last = whole * jnp.sum(ds * s, axis=0, keepdims=True) + jnp.sum(dw, axis=0, keepdims=True)
        to_dt = dxd * xf
        for i, z in enumerate(zs):
            h = first + i
            mine = of_head(by_lane, lane, i, p)
            if z.shape == mine.shape:   # a chunk as wide as a tile: one sum along lanes for both
                by_row = jnp.sum(z + mine, axis=1, keepdims=True)
            else:
                by_row = jnp.sum(z, axis=1, keepdims=True) + jnp.sum(mine, axis=1, keepdims=True)
            dlast = jnp.sum(of_head(to_last, lane[:1], i, p), axis=1, keepdims=True)
            dcum = _as_row(by_row, eye) - jnp.sum(z, axis=0, keepdims=True)
            dcum_ref[h:h + 1, :] = jnp.where(last_lane, dcum + dlast, dcum)
            ddt_ref[h:h + 1, :] = _as_row(jnp.sum(of_head(to_dt, lane, i, p), axis=1, keepdims=True), eye)
        dx_ref[:, at] = (d_ref[:, at] * dyf + dt * dxd).astype(dtype)
        dd_ref[:, at] += jnp.sum(dyf * xf, axis=0, keepdims=True)
        ds_scr[:, at] = whole * ds + _dot(c_t, dye, _NN)
    dcb = dcb.astype(dtype)
    dc_ref[...] = (dc + _dot(dcb, bm, _NN)).astype(dtype)
    db_ref[...] = (db + _dot(dcb, cm, _TN)).astype(dtype)


def _params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


def _specs(shapes, backward: bool):
    """BlockSpecs for the grid (Z, G, nc), the chunks last to first in the
    backward: a group's rows of a [Z, T, lanes] array whose lanes hold H P of
    ``x'`` (and, in ``xbc``, G N of ``B`` and G N of ``C`` after them: ``rows``,
    ``b``, ``c``), its [Z, T, G N] rows (``group``), cum / dt [Z, nc, H, Q], the
    states, turned, [Z, nc, N, H P], ``D`` by lane [1, 1, H P] (``skip``) and a row a
    sequence [Z, 1, H P] (``skip_sum``)."""
    g, r, q, p, n, nc = shapes

    def at(k):
        return (nc - 1 - k) if backward else k

    first_b = g * r * p // n
    rows = pl.BlockSpec((None, q, r * p), lambda i, j, k: (i, at(k), j))
    b = pl.BlockSpec((None, q, n), lambda i, j, k: (i, at(k), first_b + j))
    c = pl.BlockSpec((None, q, n), lambda i, j, k: (i, at(k), first_b + g + j))
    group = pl.BlockSpec((None, q, n), lambda i, j, k: (i, at(k), j))
    cum = pl.BlockSpec((None, None, r, q), lambda i, j, k: (i, at(k), j, 0))
    states = pl.BlockSpec((None, None, n, r * p), lambda i, j, k: (i, at(k), 0, j))
    skip = pl.BlockSpec((None, 1, r * p), lambda i, j, k: (0, 0, j))
    skip_sum = pl.BlockSpec((None, 1, r * p), lambda i, j, k: (i, 0, j))
    return rows, b, c, group, cum, states, skip, skip_sum


def _shapes(xbc, cum, groups: int, state: int):
    z, t, lanes = xbc.shape
    nc, h, q = cum.shape[1:]
    p = (lanes - 2 * groups * state) // h
    return z, t, h, p, nc, (groups, h // groups, q, p, state, nc)


def _kernel_fwd(xbc, cum, dt, d, groups: int, state: int, interpret: bool):
    """(y [Z, T, H P] with the skip, the state entering each chunk, turned:
    [Z, nc, N, H P] float32) from ``xbc`` [Z, T, H P + 2 G N] as the
    convolution leaves it, cum and dt [Z, nc, H, Q] float32, ``d`` [1, 1, H P]
    float32 (a head's ``D`` in each of its lanes)."""
    z, t, h, p, nc, shapes = _shapes(xbc, cum, groups, state)
    rows, b, c, _, cums, states, skip, _ = _specs(shapes, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, per=heads_a_tile(h // groups, p)),
        grid=(z, groups, nc),
        in_specs=[rows, b, c, cums, cums, skip],
        out_specs=[rows, states],
        out_shape=[jax.ShapeDtypeStruct((z, t, h * p), xbc.dtype),
                   jax.ShapeDtypeStruct((z, nc, state, h * p), _F32)],
        scratch_shapes=[pltpu.VMEM((state, h // groups * p), _F32)],
        compiler_params=_params(interpret), interpret=interpret, name="dvc_ssd_fwd",
    )(xbc, xbc, xbc, cum, dt, d)


def _kernel_bwd(xbc, cum, dt, d, st, dy, groups: int, state: int, interpret: bool):
    """Cotangents (d xbc [Z, T, H P + 2 G N] with dx' in its lanes and those of
    B and C left unwritten, dB and dC [Z, T, G N], dcum and d dt [Z, nc, H, Q]
    float32, a sequence's sum of ``dy x'`` [Z, 1, H P] float32)."""
    z, t, h, p, nc, shapes = _shapes(xbc, cum, groups, state)
    rows, b, c, group, cums, states, skip, skip_sum = _specs(shapes, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, per=heads_a_tile(h // groups, p)),
        grid=(z, groups, nc),
        in_specs=[rows, b, c, cums, cums, skip, states, rows],
        out_specs=[rows, group, group, cums, cums, skip_sum],
        out_shape=[jax.ShapeDtypeStruct(xbc.shape, xbc.dtype),
                   jax.ShapeDtypeStruct((z, t, groups * state), xbc.dtype),
                   jax.ShapeDtypeStruct((z, t, groups * state), xbc.dtype),
                   jax.ShapeDtypeStruct(cum.shape, _F32),
                   jax.ShapeDtypeStruct(cum.shape, _F32),
                   jax.ShapeDtypeStruct((z, 1, h * p), _F32)],
        scratch_shapes=[pltpu.VMEM((state, h // groups * p), _F32)],
        compiler_params=_params(interpret), interpret=interpret, name="dvc_ssd_bwd",
    )(xbc, xbc, xbc, cum, dt, d, st, dy)


# ---------------------------------------------------------------------------
# the core in each form, and what is around it
# ---------------------------------------------------------------------------

PLAIN, KERNEL, INTERPRET = "plain", "kernel", "interpret"

@jax.custom_vjp
def plain_core(xd: jax.Array, cum: jax.Array, b: jax.Array, c: jax.Array) -> jax.Array:
    """``y`` [Z, H, T, P] without the skip, from ``xd = dt x`` [Z, H, T, P],
    the chunks' running sums ``cum`` [Z, T / Q, H, Q] float32 and ``b``, ``c``
    [Z, G, T, N]: the plain form."""
    return _plain_fwd(xd, cum, b, c)[0]


def _plain_core_fwd(xd, cum, b, c):
    y, states = _plain_fwd(xd, cum, b, c)
    return y, (xd, cum, b, c, states)


plain_core.defvjp(_plain_core_fwd, lambda res, dy: _plain_bwd(*res, dy))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def kernel_core(xbc: jax.Array, cum: jax.Array, dt: jax.Array, d: jax.Array,
                groups: int, state: int, interpret: bool) -> jax.Array:
    """``y`` [Z, T, H P] WITH the skip, from the mixer's streams where the
    convolution leaves them (``xbc`` [Z, T, H P + 2 G N]: ``x'``, ``B``, ``C``
    side by side), ``cum`` and ``dt`` [Z, T / Q, H, Q] float32 and ``D`` by lane
    [1, 1, H P] float32: the kernels, which make ``dt x'`` and add ``D x'``."""
    return _kernel_fwd(xbc, cum, dt, d, groups, state, interpret)[0]


def _kernel_core_fwd(xbc, cum, dt, d, groups, state, interpret):
    y, states = _kernel_fwd(xbc, cum, dt, d, groups, state, interpret)
    return y, (xbc, cum, dt, d, states)


def _kernel_core_bwd(groups, state, interpret, res, dy):
    dxbc, db, dc, dcum, ddt, dd = _kernel_bwd(*res, dy, groups, state, interpret)
    first_b = dxbc.shape[-1] - 2 * groups * state
    dxbc = jax.lax.dynamic_update_slice_in_dim(dxbc, db, first_b, axis=2)
    dxbc = jax.lax.dynamic_update_slice_in_dim(dxbc, dc, first_b + groups * state, axis=2)
    return dxbc, dcum, ddt, jnp.sum(dd, axis=0, keepdims=True)


kernel_core.defvjp(_kernel_core_fwd, _kernel_core_bwd)


def kernel_takes(heads: int, groups: int, head_dim: int, state: int, chunk: int) -> bool:
    """Whether the kernels take the shape: a group's heads in whole sublane
    tiles of cum and in whole lane tiles of ``x'`` (a head a whole number of
    tiles, or a whole number of heads a tile), a chunk and a state in whole
    lane tiles, ``B`` a whole number of its blocks into ``xbc``."""
    if heads % groups or chunk % 128 or state % 128 or (heads * head_dim) % state:
        return False
    r = heads // groups
    return (r % 8 == 0 and (r * head_dim) % 128 == 0 and (head_dim % 128 == 0 or 128 % head_dim == 0)
            and r % heads_a_tile(r, head_dim) == 0)


def choose_form(heads: int, groups: int, head_dim: int, state: int, chunk: int) -> str:
    """The kernels on one TPU chip where they take the shape, the plain form
    elsewhere (Mosaic refuses a kernel that GSPMD would have to partition)."""
    if tpu_backend() and chips_in_step() == 1 and kernel_takes(heads, groups, head_dim, state, chunk):
        return KERNEL
    return PLAIN


def _plain_ssd(xbc, dt, cum, d, groups: int, state: int):
    """The plain form behind ``ssd``: the streams split and turned to the
    scan's [Z, H, T, P] / [Z, G, T, N], ``dt x'`` and the skip in passes of
    their own."""
    z, t, h = dt.shape
    x, b, c = jnp.split(xbc, [xbc.shape[-1] - 2 * groups * state, xbc.shape[-1] - groups * state], axis=-1)
    x = x.reshape(z, t, h, -1)
    skip = (d.astype(_F32)[:, None] * x.astype(_F32)).astype(x.dtype)
    xd = (x.astype(_F32) * dt.astype(_F32)[..., None]).astype(x.dtype).transpose(0, 2, 1, 3)
    b, c = (a.reshape(z, t, groups, state).transpose(0, 2, 1, 3) for a in (b, c))
    return (plain_core(xd, cum, b, c).transpose(0, 2, 1, 3) + skip).reshape(z, t, -1)


def ssd(xbc: jax.Array, dt: jax.Array, a_log: jax.Array, d: jax.Array, groups: int, state: int,
        chunk: int = CHUNK, form: str = "") -> Tuple[jax.Array, jax.Array]:
    """The recurrence at the top of this module over the mixer's streams as
    the convolution leaves them, ``xbc`` [batch, T, H P + 2 G N] (``x'``, then
    ``B``, then ``C``), with ``dt`` [batch, T, H]: (``y`` [batch, T, H P] in
    ``xbc``'s dtype, the share of the (sequence, head, chunk after the first)
    triples whose whole-chunk decay ``exp(sum dt A)`` is over ``CARRY_FLOOR``:
    where the state carried across the boundary still counts at the chunk's
    end; 0 for a sequence of one chunk)."""
    z, t, h = dt.shape
    p = (xbc.shape[-1] - 2 * groups * state) // h
    form = form or choose_form(h, groups, p, state, chunk)
    # which form a TRACED scan took: the ``ssm.scan`` span's ``ssm_form`` (``models/nemotron_h.spans``)
    traced.note("ssd_scan", form=form, heads=h, groups=groups, head_dim=p, state=state, chunk=chunk)
    pad = (-t) % chunk
    if pad:
        xbc, dt = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (xbc, dt))
    nc = (t + pad) // chunk
    dt = dt.astype(_F32)
    a = dt * -jnp.exp(a_log.astype(_F32))                                   # [Z, T, H]
    cum = jnp.cumsum(a.reshape(z, nc, chunk, h), axis=2).transpose(0, 1, 3, 2)
    if form == PLAIN:
        y = _plain_ssd(xbc, dt, cum, d, groups, state)
    else:
        y = kernel_core(xbc, cum, dt.reshape(z, nc, chunk, h).transpose(0, 1, 3, 2),
                        jnp.repeat(d.astype(_F32), p)[None, None], groups, state, form == INTERPRET)
    carried = jax.lax.stop_gradient(cum[:, 1:, :, -1]) > jnp.log(CARRY_FLOOR)
    share = jnp.mean(carried.astype(_F32)) if nc > 1 else jnp.zeros((), _F32)
    return (y[:, :t] if pad else y), share
