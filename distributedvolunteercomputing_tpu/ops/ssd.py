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
compute dtype, accumulated in float32. What is differentiated by hand is the
CORE ``(xd, cum, B, C) -> y``: its backward is the reverse scan over the chunks
that carries the state's cotangent ``dS`` (``_chunk_bwd``), reading each
chunk's incoming state as the forward wrote it (``[batch, T / Q, H, P, N]``
float32: the residual beside the inputs). The running sum, ``dt x``, ``A`` and
the ``D`` skip around the core are plain ``jax.numpy`` and JAX's to
differentiate.

Two forms of the core, the same arithmetic:

- ``lax.scan`` over the chunks in ``jax.numpy`` (``_plain_fwd`` / ``_plain_bwd``):
  the CPU, a step over several chips, every shape the kernels do not take;
- the Pallas kernels ``dvc_ssd_fwd`` / ``dvc_ssd_bwd`` on one TPU chip: a grid
  of (sequence, group, chunk) with the chunks in order (reversed backward), the
  group's ``H / G`` heads in one grid step (they share ``C B^T`` and the group's
  ``dB`` / ``dC``), each head's state [P, N] float32 resident in VMEM from
  chunk to chunk. A device trace shows them under these names
  (``benchmark/ssd_trace.py``).

A sequence that is no whole number of chunks is padded with positions whose
``dt`` is 0 (no decay, no input) and cut again.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedvolunteercomputing_tpu.ops.attention import chips_in_step
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
# the kernels: one (sequence, group, chunk) a grid step
# ---------------------------------------------------------------------------


def _chunk_masks(q: int):
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return row == col, row >= col


def _as_column(row_vec, eye):
    """[1, Q] -> [Q, 1], exactly: the diagonal of its broadcast, summed along lanes."""
    q = eye.shape[0]
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row_vec, (q, q)), 0.0), axis=1, keepdims=True)


def _last_as_column(row_vec, rows: int):
    """[1, Q] -> [rows, 1], every entry the vector's last: Mosaic broadcasts a
    [1, 1] along sublanes or lanes, not both, so the chunk's whole sum is made
    a column here and broadcasts along lanes from there."""
    q = row_vec.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, q), 1)
    return jnp.sum(jnp.where(lane == q - 1, jnp.broadcast_to(row_vec, (rows, q)), 0.0),
                   axis=1, keepdims=True)


def _as_row(col_vec, eye):
    q = eye.shape[0]
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(col_vec, (q, q)), 0.0), axis=0, keepdims=True)


def _fwd_kernel(xd_ref, cum_ref, b_ref, c_ref, y_ref, st_ref, s_scr, *, heads: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    dtype = xd_ref.dtype
    bm, cm = b_ref[...], c_ref[...]
    q = bm.shape[0]
    eye, tri = _chunk_masks(q)
    cb = _dot(cm, bm, _NT)                                        # [Q, Q]
    for h in range(heads):
        s = s_scr[h]
        st_ref[h] = s
        row = cum_ref[h:h + 1, :]
        col = _as_column(row, eye)
        last = _last_as_column(row, q)
        m = (cb * jnp.exp(jnp.where(tri, col - row, -jnp.inf))).astype(dtype)
        xh = xd_ref[h]
        y = _dot(m, xh, _NN) + jnp.exp(col) * _dot(cm, s.astype(dtype), _NT)
        y_ref[h] = y.astype(dtype)
        xw = (jnp.exp(last - col) * xh.astype(_F32)).astype(dtype)
        s_scr[h] = jnp.exp(_last_as_column(row, s.shape[0])) * s + _dot(xw, bm, _TN)


def _bwd_kernel(xd_ref, cum_ref, b_ref, c_ref, st_ref, dy_ref,
                dxd_ref, dcum_ref, db_ref, dc_ref, ds_scr, *, heads: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    dtype = xd_ref.dtype
    bm, cm = b_ref[...], c_ref[...]
    q = bm.shape[0]
    eye, tri = _chunk_masks(q)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    cb = _dot(cm, bm, _NT)
    dcb = jnp.zeros((q, q), _F32)
    db = jnp.zeros(bm.shape, _F32)
    dc = jnp.zeros(cm.shape, _F32)
    for h in range(heads):
        s, ds = st_ref[h], ds_scr[h]
        sb, dsb = s.astype(dtype), ds.astype(dtype)
        row = cum_ref[h:h + 1, :]
        col = _as_column(row, eye)
        last = _last_as_column(row, q)
        lower = jnp.exp(jnp.where(tri, col - row, -jnp.inf))
        m = cb * lower
        e, w = jnp.exp(col), jnp.exp(last - col)
        whole = jnp.exp(_last_as_column(row, s.shape[0]))                      # [P, 1]
        xh, dyh = xd_ref[h], dy_ref[h]
        xf, dyf = xh.astype(_F32), dyh.astype(_F32)
        dm = _dot(dyh, xh, _NT)
        z = dm * m
        dcb = dcb + dm * lower
        y_in = _dot(cm, sb, _NT)
        from_ds = _dot(bm, dsb, _NT)
        dxd_ref[h] = (_dot(m.astype(dtype), dyh, _TN) + w * from_ds).astype(dtype)
        dye = (e * dyf).astype(dtype)
        xw = (w * xf).astype(dtype)
        dw = w * jnp.sum(xf * from_ds, axis=1, keepdims=True)                 # [Q, 1]
        dc = dc + _dot(dye, sb, _NN)
        db = db + _dot(xw, dsb, _NN)
        by_row = (jnp.sum(z, axis=1, keepdims=True)
                  + e * jnp.sum(dyf * y_in, axis=1, keepdims=True) - dw)
        dlast = (jnp.sum(whole * jnp.sum(ds * s, axis=1, keepdims=True), axis=0, keepdims=True)
                 + jnp.sum(dw, axis=0, keepdims=True))
        dcum = _as_row(by_row, eye) - jnp.sum(z, axis=0, keepdims=True)
        dcum_ref[h:h + 1, :] = jnp.where(lane == q - 1, dcum + dlast, dcum)
        ds_scr[h] = whole * ds + _dot(dye, cm, _TN)
    dcb = dcb.astype(dtype)
    dc_ref[...] = (dc + _dot(dcb, bm, _NN)).astype(dtype)
    db_ref[...] = (db + _dot(dcb, cm, _TN)).astype(dtype)


def _params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


def _specs(shapes, backward: bool):
    """BlockSpecs of (rows [Z, H, T, P], cum [Z, nc, H, Q], group rows
    [Z, G, T, N], states [Z, nc, H, P, N]) for the grid (Z, G, nc); the chunks
    run last to first in the backward."""
    r, q, p, n, nc = shapes

    def at(k):
        return (nc - 1 - k) if backward else k

    rows = pl.BlockSpec((None, r, q, p), lambda i, g, k: (i, g, at(k), 0))
    cum = pl.BlockSpec((None, None, r, q), lambda i, g, k: (i, at(k), g, 0))
    group = pl.BlockSpec((None, None, q, n), lambda i, g, k: (i, g, at(k), 0))
    states = pl.BlockSpec((None, None, r, p, n), lambda i, g, k: (i, at(k), g, 0, 0))
    return rows, cum, group, states


def _kernel_fwd(xd, cum, b, c, interpret: bool):
    z, h, t, p = xd.shape
    nc, q = cum.shape[1], cum.shape[3]
    g, n = b.shape[1], b.shape[3]
    r = h // g
    rows, cums, group, states = _specs((r, q, p, n, nc), False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=r),
        grid=(z, g, nc),
        in_specs=[rows, cums, group, group],
        out_specs=[rows, states],
        out_shape=[jax.ShapeDtypeStruct(xd.shape, xd.dtype),
                   jax.ShapeDtypeStruct((z, nc, h, p, n), _F32)],
        scratch_shapes=[pltpu.VMEM((r, p, n), _F32)],
        compiler_params=_params(interpret), interpret=interpret, name="dvc_ssd_fwd",
    )(xd, cum, b, c)


def _kernel_bwd(xd, cum, b, c, st, dy, interpret: bool):
    z, h, t, p = xd.shape
    nc, q = cum.shape[1], cum.shape[3]
    g, n = b.shape[1], b.shape[3]
    r = h // g
    rows, cums, group, states = _specs((r, q, p, n, nc), True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=r),
        grid=(z, g, nc),
        in_specs=[rows, cums, group, group, states, rows],
        out_specs=[rows, cums, group, group],
        out_shape=[jax.ShapeDtypeStruct(xd.shape, xd.dtype),
                   jax.ShapeDtypeStruct(cum.shape, _F32),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype)],
        scratch_shapes=[pltpu.VMEM((r, p, n), _F32)],
        compiler_params=_params(interpret), interpret=interpret, name="dvc_ssd_bwd",
    )(xd, cum, b, c, st, dy)


# ---------------------------------------------------------------------------
# the core and what is around it
# ---------------------------------------------------------------------------

PLAIN, KERNEL, INTERPRET = "plain", "kernel", "interpret"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def ssd_core(xd: jax.Array, cum: jax.Array, b: jax.Array, c: jax.Array, form: str) -> jax.Array:
    """``y`` [Z, H, T, P] without the skip, from ``xd = dt x`` [Z, H, T, P],
    the chunks' running sums ``cum`` [Z, T / Q, H, Q] float32 and ``b``, ``c``
    [Z, G, T, N]; ``form``: which of the two forms computes it."""
    return _core_fwd(xd, cum, b, c, form)[0]


def _core_fwd(xd, cum, b, c, form):
    if form == PLAIN:
        y, states = _plain_fwd(xd, cum, b, c)
    else:
        y, states = _kernel_fwd(xd, cum, b, c, form == INTERPRET)
    return y, (xd, cum, b, c, states)


def _core_bwd(form, res, dy):
    if form == PLAIN:
        return _plain_bwd(*res, dy)
    return tuple(_kernel_bwd(*res, dy, form == INTERPRET))


ssd_core.defvjp(_core_fwd, _core_bwd)


def kernel_takes(heads: int, groups: int, head_dim: int, state: int, chunk: int) -> bool:
    """Whether the kernels take the shape: a group's heads in whole sublane
    tiles, a chunk and a state in whole lane tiles."""
    return (heads % groups == 0 and (heads // groups) % 8 == 0 and chunk % 128 == 0
            and state % 128 == 0 and head_dim % 8 == 0)


def choose_form(heads: int, groups: int, head_dim: int, state: int, chunk: int) -> str:
    """The kernels on one TPU chip where they take the shape, the plain form
    elsewhere (Mosaic refuses a kernel that GSPMD would have to partition)."""
    if tpu_backend() and chips_in_step() == 1 and kernel_takes(heads, groups, head_dim, state, chunk):
        return KERNEL
    return PLAIN


def ssd(x: jax.Array, dt: jax.Array, a_log: jax.Array, b: jax.Array, c: jax.Array, d: jax.Array,
        chunk: int = CHUNK, form: str = "") -> Tuple[jax.Array, jax.Array]:
    """The recurrence at the top of this module: (``y`` [batch, T, H, P] in
    ``x``'s dtype, the share of the (sequence, head, chunk after the first)
    triples whose whole-chunk decay ``exp(sum dt A)`` is over ``CARRY_FLOOR``:
    where the state carried across the boundary still counts at the chunk's
    end; 0 for a sequence of one chunk)."""
    z, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    form = form or choose_form(h, g, p, n, chunk)
    skip = (d.astype(_F32)[:, None] * x.astype(_F32)).astype(x.dtype)
    pad = (-t) % chunk
    if pad:
        x, dt, b, c = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, dt, b, c))
    nc = (t + pad) // chunk
    a = dt.astype(_F32) * -jnp.exp(a_log.astype(_F32))                      # [Z, T, H]
    cum = jnp.cumsum(a.reshape(z, nc, chunk, h), axis=2).transpose(0, 1, 3, 2)
    xd = (x.astype(_F32) * dt.astype(_F32)[..., None]).astype(x.dtype).transpose(0, 2, 1, 3)
    y = ssd_core(xd, cum, b.transpose(0, 2, 1, 3), c.transpose(0, 2, 1, 3), form)
    y = y.transpose(0, 2, 1, 3)[:, :t] + skip
    carried = jax.lax.stop_gradient(cum[:, 1:, :, -1]) > jnp.log(CARRY_FLOOR)
    share = jnp.mean(carried.astype(_F32)) if nc > 1 else jnp.zeros((), _F32)
    return y, share
