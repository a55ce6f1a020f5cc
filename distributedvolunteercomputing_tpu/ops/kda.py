"""Kimi Delta Attention (``models/kimi_linear.py``): a linear-attention token
mixer whose state is decayed BY CHANNEL and corrected by a delta rule, as a
CHUNKED SCAN with its own backward. For each head, with a state ``S`` in
``R^{K x V}`` (key x value, float32) that starts at zero::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T      o_t = S_t^T q_t

``alpha_t = exp(g_t)`` in (0, 1) a key channel, ``beta_t`` in (0, 1) a head.
Read as steps: decay the state by channel; ``u_t = beta_t (v_t - S^T k_t)``,
what the state does not hold yet along the new key; ``S += k_t u_t^T``. A
token's correction depends on every earlier token's, so a chunk of C tokens is
no running sum (``ops/ssd.py``) but a triangular system. With ``G`` the running
sum of ``g`` inside the chunk (inclusive), ``kb = beta k``, ``vb = beta v`` and
``S_0`` the state that enters::

    L_ij = sum_c kb_ic k_jc exp(G_ic - G_jc)   j < i      (I + L) U = vb - (kb e^G) S_0
    B_ij = sum_c q_ic  k_jc exp(G_ic - G_jc)   j <= i     O = (q e^G) S_0 + B U
    S_C  = Diag(e^{G_C}) S_0 + (k e^{G_C - G})^T U

**The exponent.** ``exp(G_i - G_j)`` does not factor into ``(k e^G)(k e^-G)^T``
over a chunk: a chunk's summed log decay passes -88 with decays the model is
initialised with, and ``e^{-G}`` overflows float32. Here the pairs (i, j) are
taken by LEVEL, the highest bit in which i and j differ: at level h the pair
lies in one block of 2 h rows, i in its second half and j in its first, and
both exponents are taken relative to the last row r of the first half:
``exp(G_i - G_r) exp(G_r - G_j)``, neither above 0. log2(C) levels, each one
masked product over the chunk; exact whatever the decay (the reference row is
read through a one-hot product in bfloat16: any value does that both factors
share, and it is at most |G| / 256 off). ``(I + L)^-1`` is made by the same
levels, from the diagonal outwards: forward substitution in blocks, two small
products a level (``_inverse``, which says why not the shorter product of
``I + (-L)^(2^k)``).

``kda`` takes the mixer's streams token-major as the projections and the
convolution leave them: ``q``, ``k`` [batch, T, H, K] UN-NORMED, ``v`` [batch,
T, H, V], ``g`` [batch, T, H, K] float32, ``beta`` [batch, T, H]. It sees them
with the heads side by side again ([batch, T, H K]: where the caller split the
heads by a reshape, none is left) and nothing of a stream's size is laid out
again, widened or worked by head outside the loops. ``core`` is a ``lax.scan``
over the chunks' numbers whose step takes its chunk [batch, C, H, ..] out of the
streams IN PLACE (``lax.dynamic_slice`` along T: a chunk of a sequence is one
block of C x H K values) and does on the chunk it holds what the equations'
operands are made of (``_prepare``): a head's ``q`` at length K^-1/2 and ``k``
at length 1 (float32, back in the streams' dtype), ``kb = beta k`` and ``vb =
beta v`` (float32 products, rounded once), ``G`` the running sum of ``g`` down
the chunk's rows (a lower-triangular product at float32's own precision). One
chunk of one head is then plain matrices (``_chunk_fwd`` / ``_chunk_bwd``),
mapped over sequences and heads, and ``o``'s chunk is written into [batch, T, H
V] the same way. The backward is the reverse scan that carries the state's
cotangent, recomputes a chunk from the raw streams and the state that entered
it (float32, kept by the forward: [T / C, batch, H, V, K]), runs ``_chunk_bwd``
and hands its cotangents to JAX's transpose of the chunk's preparation: the
streams' cotangents come out raw, in place, and no formula of the preparation's
is derived by hand. XLA compiles it for the CPU, one chip and a step over
several chips alike (a slice along T of streams sharded over sequences and heads
stays on its device); there is no kernel, because the same chunk functions as
Pallas kernels (a head's state resident in VMEM) were slower on a v5e than XLA's
loops, alone and in the step (``PERF.md``, PR 52). A device trace shows the scan
as ``while`` loops that carry the heads' states (``benchmark/kda_trace.py``).

Until PR 55 the norms, beta's fold and the decay's running sum were float32
passes over whole streams by head around the core, and the core was handed its
chunks stacked ``[T / C, batch, C, H, ..]``: on the chip those passes and copies
took as long as the loops (``PERF.md``, PR 55; ``experiments/kda_sweep.py``
times both). A sequence that is no whole number of chunks is padded with
positions that neither decay nor write (``g`` 0, ``beta`` 0) and cut again.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.utils.jaxenv import tpu_backend

CHUNK = 64
# a chunk's whole decay, at the channel that decays least, under which the state carried into it counts as forgotten
CARRY_FLOOR = 1e-3
# what the l2 norm of a head's query and key adds under its root (the family's public kernels')
L2_EPS = 1e-6
_F32 = jnp.float32
_BF16 = jnp.bfloat16

_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


def _dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    if a.dtype == _BF16 and not tpu_backend():
        # XLA's CPU dot takes bf16 x bf16 -> f32 in some layouts only; a bf16 pair's product is exact in float32
        a, b = a.astype(_F32), b.astype(_F32)
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=_F32)


def _dot32(a: jax.Array, b: jax.Array) -> jax.Array:
    """a @ b of float32 operands at float32's own precision."""
    return jax.lax.dot_general(a, b, (_NN, ((), ())), precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


# ---------------------------------------------------------------------------
# one chunk of one head: plain matrices
# ---------------------------------------------------------------------------
# q, k, kb [C, K] and vb, do [C, V] in the compute dtype, gc [C, K] float32 (the
# chunk's running sum of g), the state TURNED: st, dst [V, K] float32.


def _grid(c: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0), jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _pairs(row, col, shift: int):
    """The pairs (i, j) of level ``h = 2^shift``: i after j, in one block of 2 h rows and in different halves of it."""
    return (row > col) & ((row >> shift) != (col >> shift)) & ((row >> (shift + 1)) == (col >> (shift + 1)))


def _level_decays(gc):
    """Per level ``h = 2^shift`` of a chunk: (exp(G_i - R_i) for a row as the
    later of a pair, exp(R'_j - G_j) as the earlier), each [C, K], neither
    exponent above 0 but for the reference's rounding. ``R'_j`` is ``G`` at the
    last row of j's block of h rows, ``R_i`` at the last row of the block before
    i's (0 before the chunk's first): one row for the pairs of the level, read
    for every level at once through ONE one-hot product of ``G`` in bfloat16
    (any value does that both factors share)."""
    c = gc.shape[0]
    row, col = _grid(c)
    hits = []
    for shift in _shifts(c):
        last = ((row >> shift) << shift) + ((1 << shift) - 1)
        hits += [col == last - (1 << shift), col == last]
    one_hot = jnp.where(jnp.concatenate(hits, axis=0), 1.0, 0.0).astype(_BF16)       # [2 levels C, C]
    refs = _dot(one_hot, gc.astype(_BF16), _NN)
    at = lambda i: refs[i * c:(i + 1) * c]
    return [(jnp.exp(gc - at(2 * s)), jnp.exp(at(2 * s + 1) - gc)) for s in _shifts(c)]


def _shifts(c: int):
    return range(c.bit_length() - 1)


def _within(q, k, kb, decays):
    """(L strictly lower, as its levels' parts: each [C, C] float32 and zero
    outside its level's pairs; B lower with its diagonal, [C, C] float32); a
    level's two left factors side by side down the rows of one product."""
    dtype = q.dtype
    c = q.shape[0]
    row, col = _grid(c)
    qf, kf, kbf = q.astype(_F32), k.astype(_F32), kb.astype(_F32)
    levels = []
    b = jnp.where(row == col, jnp.sum(qf * kf, axis=1, keepdims=True), 0.0)
    for shift, (later, earlier) in zip(_shifts(c), decays):
        left = jnp.concatenate([kbf * later, qf * later], axis=0).astype(dtype)
        both = _dot(left, (kf * earlier).astype(dtype), _NT)                     # [2 C, C]
        pairs = _pairs(row, col, shift)
        levels.append(jnp.where(pairs, both[:c], 0.0))
        b = b + jnp.where(pairs, both[c:], 0.0)
    return levels, b


def _inverse(levels, exact: bool):
    """``(I + L)^-1`` of a strictly lower ``L`` [C, C] given as its levels'
    parts, by blocks from the diagonal outwards: with ``D`` the inverse of the
    diagonal blocks of h rows, a block of 2 h rows ``[[A, 0], [L_h, B]]`` has the
    inverse ``[[A^-1, 0], [-B^-1 L_h A^-1, B^-1]]``, which over the whole chunk
    is ``D - D L_h D``: two products a level, forward substitution in blocks.
    (The shorter ``prod_k (I + (-L)^(2^k))`` sums powers of ``L`` that reach 1e8
    where a chunk's keys resemble each other, and cancels them in float32: PR
    52's first step on the chip that held such a chunk ended in NaN.)
    ``exact``: float32's own precision (a float32 compute dtype); else the
    products' operands are rounded to bfloat16 as every other product's are
    (forward substitution does not amplify that: on the chip the result reads
    as far from the float32 recurrence as with three bfloat16 passes a product,
    5.5e-3 of the largest value, at a third of the passes)."""
    c = levels[0].shape[0]
    row, col = _grid(c)
    dot = _dot32 if exact else (lambda a, b: _dot(a.astype(_BF16), b.astype(_BF16), _NN))
    d = jnp.where(row == col, 1.0, 0.0)
    for shift, lower_h in enumerate(levels):
        # at the first level D is the identity
        d = d - (lower_h if shift == 0 else dot(d, dot(lower_h, d)))
    return d


def _chunk_decays(gc):
    """(exp(G), exp(G_C - G), exp(G_C) [1, K])."""
    last = gc[gc.shape[0] - 1:, :]
    return jnp.exp(gc), jnp.exp(last - gc), jnp.exp(last)


def _chunk_fwd(st, q, k, kb, vb, gc):
    """(o [C, V] in the compute dtype, the state that leaves [V, K] float32)."""
    dtype = q.dtype
    levels, b = _within(q, k, kb, _level_decays(gc))
    t = _inverse(levels, dtype == _F32)
    e, ew, ec = _chunk_decays(gc)
    stb = st.astype(dtype)
    x = vb.astype(_F32) - _dot((kb.astype(_F32) * e).astype(dtype), stb, _NT)
    ub = _dot(t.astype(dtype), x.astype(dtype), _NN).astype(dtype)
    o = _dot((q.astype(_F32) * e).astype(dtype), stb, _NT) + _dot(b.astype(dtype), ub, _NN)
    st_new = ec * st + _dot(ub, (k.astype(_F32) * ew).astype(dtype), _TN)
    return o.astype(dtype), st_new


def _chunk_bwd(dst, st, q, k, kb, vb, gc, do):
    """Cotangents (dq, dk, dkb [C, K], dvb [C, V] float32, dgc [C, K] float32,
    that of the state that entered [V, K]) from ``do`` and the cotangent ``dst``
    of the state that left; the chunk's forward computed again from ``st``."""
    dtype = q.dtype
    c = q.shape[0]
    row, col = _grid(c)
    qf, kf, kbf = q.astype(_F32), k.astype(_F32), kb.astype(_F32)
    decays = _level_decays(gc)
    levels, b = _within(q, k, kb, decays)
    t = _inverse(levels, dtype == _F32)
    e, ew, ec = _chunk_decays(gc)
    stb, dstb = st.astype(dtype), dst.astype(dtype)
    qt, kbt, kh = qf * e, kbf * e, kf * ew
    x = vb.astype(_F32) - _dot(kbt.astype(dtype), stb, _NT)
    ub = _dot(t.astype(dtype), x.astype(dtype), _NN).astype(dtype)
    # o = qt S + B U;  S' = ec S + kh^T U
    du = _dot(b.astype(dtype), do, _TN) + _dot(kh.astype(dtype), dstb, _NT)
    db = jnp.where(row >= col, _dot(do, ub, _NT), 0.0)
    dqt = _dot(do, stb, _NN)
    dkh = _dot(ub, dstb, _NN)
    # U = (I + L)^-1 X:  dX = (I + L)^-T dU,  dL = -dX U^T;  X = vb - kbt S
    dx = _dot(t.astype(dtype), du.astype(dtype), _TN)
    dxb = dx.astype(dtype)
    dlower = jnp.where(row > col, -_dot(dxb, ub, _NT), 0.0)
    dkbt = -_dot(dxb, stb, _NN)
    dst_prev = _dot(do, qt.astype(dtype), _TN) + ec * dst - _dot(dxb, kbt.astype(dtype), _TN)
    diag = jnp.sum(jnp.where(row == col, db, 0.0), axis=1, keepdims=True)
    dq = dqt * e + diag * kf
    dk = dkh * ew + diag * qf
    dkb = dkbt * e
    dg = dkbt * kbt + dqt * qt - dkh * kh
    for shift, (later, earlier) in zip(_shifts(c), decays):
        kbl, ql, kr = kbf * later, qf * later, kf * earlier
        pairs = _pairs(row, col, shift)
        # a level's two cotangents down the rows of one product each way, as its two left factors are
        d_both = jnp.concatenate([jnp.where(pairs, dlower, 0.0), jnp.where(pairs, db, 0.0)], axis=0).astype(dtype)
        d_left = _dot(d_both, kr.astype(dtype), _NN)                             # [2 C, K]: d(kb later), d(q later)
        dkbl, dql = d_left[:c], d_left[c:]
        dkr = _dot(d_both, jnp.concatenate([kbl, ql], axis=0).astype(dtype), _TN)
        dkb, dq, dk = dkb + dkbl * later, dq + dql * later, dk + dkr * earlier
        dg = dg + dkbl * kbl + dql * ql - dkr * kr
    # the chunk's whole sum G_C: through kh's exponent and through the decay of the state kept
    to_last = jnp.sum(dkh * kh, axis=0, keepdims=True) + ec * jnp.sum(dst * st, axis=0, keepdims=True)
    at_last = jax.lax.broadcasted_iota(jnp.int32, dg.shape, 0) == c - 1
    dg = jnp.where(at_last, dg + to_last, dg)
    return dq, dk, dkb, dx, dg, dst_prev


# ---------------------------------------------------------------------------
# a chunk as the mixer's streams hold it: what the chunk functions' operands are made of
# ---------------------------------------------------------------------------


def _l2norm(x: jax.Array, scale: float = 1.0) -> jax.Array:
    """A head's vector (the last axis) at length ``scale``: float32, back in ``x``'s dtype."""
    xf = x.astype(_F32)
    return (xf * (scale * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + L2_EPS))).astype(x.dtype)


def _prepare(q, k, v, g, beta):
    """One chunk of one head as the streams hold it (``q``, ``k`` [C, K]
    un-normed, ``v`` [C, V], the log decay ``g`` [C, K], ``beta`` [C, 1]) -> the
    chunk functions' operands (q, k, kb [C, K], vb [C, V] in the streams' dtype,
    gc [C, K] float32): every product and sum float32, each result rounded once."""
    q, k = _l2norm(q, q.shape[-1] ** -0.5), _l2norm(k)
    b = beta.astype(_F32)
    kb = (k.astype(_F32) * b).astype(k.dtype)
    vb = (v.astype(_F32) * b).astype(v.dtype)
    # the running sum down the chunk's rows: a lower-triangular product at float32's own precision
    row, col = _grid(q.shape[0])
    gc = _dot32(jnp.where(row >= col, 1.0, 0.0), g.astype(_F32))
    return q, k, kb, vb, gc


def _head_fwd(st, q, k, v, g, beta):
    """(the state that leaves [V, K], the chunk's summed log decay [K], o [C, V]) of one raw chunk of one head."""
    q, k, kb, vb, gc = _prepare(q, k, v, g, beta)
    o, st_new = _chunk_fwd(st, q, k, kb, vb, gc)
    return st_new, gc[-1], o


def _head_bwd(dst, st, q, k, v, g, beta, do):
    """(The cotangent of the state that entered, the raw chunk's: dq, dk [C, K],
    dv [C, V], dg [C, K], dbeta [C, 1], each in its stream's dtype): the
    preparation computed again and differentiated by JAX around ``_chunk_bwd``,
    whose cotangents leave it in the operands' dtypes."""
    prepared, back = jax.vjp(_prepare, q, k, v, g, beta)
    *d, dst_prev = _chunk_bwd(dst, st, *prepared, do)
    return (dst_prev, *back(tuple(a.astype(p.dtype) for a, p in zip(d, prepared))))


# ---------------------------------------------------------------------------
# a scan over the chunks, the chunk's functions over sequences and heads
# ---------------------------------------------------------------------------
# streams [Z, T, H K or H V] as the projections and the convolution leave them, beta
# [Z, T, H], T a whole number of chunks: a step takes its chunk [Z, C, ..] out of them
# in place and writes its results' chunk the same way; states [nc, Z, H, V, K]


def _over_heads(fn, values: Tuple[int, int], streams: Tuple[int, int]):
    """``fn`` over Z and H. Its arguments are ``values[0]`` values a head [Z, H,
    ..] then ``streams[0]`` streams' chunks [Z, C, H, ..]; its results
    ``values[1]`` and ``streams[1]`` of the same, in that order."""
    def axes(at: int, stream_axis: int):
        return (0,) * values[at] + (stream_axis,) * streams[at]
    return jax.vmap(jax.vmap(fn, in_axes=axes(0, 1), out_axes=axes(1, 1)), in_axes=axes(0, 0), out_axes=0)


def _chunk_indices(nc: int):
    """The chunks' numbers, UNSIGNED: a signed index is first tested for counting from the end, and behind that
    test the TPU compiler no longer sees that a chunk starts at a multiple of its rows (an update in place of
    rows that may straddle tiles took 11 us a chunk of a stream where an aligned one takes under 4: my chip runs, PR 55)."""
    return jnp.arange(nc, dtype=jnp.uint32)


def _chunk_of(a, i, chunk: int, heads: int):
    """Chunk ``i`` of a stream [Z, T, H D] by head: [Z, C, H, D] (``beta``, [Z, T, H]: D = 1)."""
    return jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk, axis=1).reshape(a.shape[0], chunk, heads, -1)


def _put_chunk(a, block, i, chunk: int):
    """The stream ``a`` [Z, T, H D] with ``block`` [Z, C, H, D] as its chunk ``i``."""
    return jax.lax.dynamic_update_slice_in_dim(a, block.reshape(a.shape[0], chunk, -1), i * chunk, axis=1)


def _scan_fwd(q, k, v, g, beta, heads: int, chunk: int):
    """(o [Z, T, H V], every chunk's summed log decay [nc, Z, H, K] float32, the
    state entering each chunk [nc, Z, H, V, K] float32)."""
    z, t, _ = q.shape
    step_fn = _over_heads(_head_fwd, (1, 2), (5, 1))

    def step(carry, i):
        st, o = carry
        st_new, total, o_i = step_fn(st, *(_chunk_of(a, i, chunk, heads) for a in (q, k, v, g, beta)))
        return (st_new, _put_chunk(o, o_i, i, chunk)), (total, st)

    start = (jnp.zeros((z, heads, v.shape[-1] // heads, q.shape[-1] // heads), _F32), jnp.zeros_like(v))
    (_, o), (sums, states) = jax.lax.scan(step, start, _chunk_indices(t // chunk))
    return o, sums, states


def _scan_bwd(q, k, v, g, beta, states, do, heads: int, chunk: int):
    step_fn = _over_heads(_head_bwd, (2, 1), (6, 5))
    streams = (q, k, v, g, beta)

    def step(carry, xs):
        dst, grads = carry
        i, st = xs
        dst_prev, *d = step_fn(dst, st, *(_chunk_of(a, i, chunk, heads) for a in (*streams, do)))
        return (dst_prev, tuple(_put_chunk(a, d_i, i, chunk) for a, d_i in zip(grads, d))), None

    start = (jnp.zeros(states.shape[1:], _F32), tuple(jnp.zeros_like(a) for a in streams))
    (_, grads), _ = jax.lax.scan(step, start, (_chunk_indices(states.shape[0]), states), reverse=True)
    return grads


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def core(q, k, v, g, beta, heads: int, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """(``o`` [Z, T, H V], the chunks' summed log decays [nc, Z, H, K] float32:
    no gradient) from the mixer's streams with the heads side by side: ``q``,
    ``k`` un-normed and ``g`` [Z, T, H K], ``v`` [Z, T, H V], ``beta`` [Z, T, H]."""
    return _scan_fwd(q, k, v, g, beta, heads, chunk)[:2]


def _core_fwd(q, k, v, g, beta, heads, chunk):
    o, sums, states = _scan_fwd(q, k, v, g, beta, heads, chunk)
    return (o, sums), (q, k, v, g, beta, states)


core.defvjp(_core_fwd, lambda heads, chunk, res, d: _scan_bwd(*res, d[0], heads, chunk))


# ---------------------------------------------------------------------------
# the entry
# ---------------------------------------------------------------------------


def kda_with_sums(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
                  chunk: int = CHUNK) -> Tuple[jax.Array, jax.Array]:
    """``kda``'s ``o`` [batch, T, H, V] in ``q``'s dtype and every chunk's summed
    log decay [batch, T / C, H, K] float32 (no gradient: what the counters read)."""
    z, t, h, _ = q.shape
    if chunk < 2 or chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk} is no power of two: the pairs of a chunk are taken by level")
    # the heads side by side again, as the projections hold them: where the caller split them by a reshape, no copy
    streams = tuple(a.reshape(z, t, -1) for a in (q, k, v, g, beta))
    pad = (-t) % chunk
    if pad:
        streams = tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in streams)
    o, sums = core(*streams, h, chunk)
    return o[:, :t].reshape(z, t, h, -1), jnp.moveaxis(jax.lax.stop_gradient(sums), 0, 1)


def carry_share(sums: jax.Array) -> jax.Array:
    """Of the (sequence, head, chunk after the first) triples, the share whose
    chunk's whole decay, at the channel that decays least, is over
    ``CARRY_FLOOR``: where the state carried across the boundary still counts
    at the chunk's end; 0 for a sequence of one chunk."""
    if sums.shape[1] < 2:
        return jnp.zeros((), _F32)
    return jnp.mean((jnp.max(sums[:, 1:], axis=-1) > jnp.log(CARRY_FLOOR)).astype(_F32))


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
        chunk: int = CHUNK) -> Tuple[jax.Array, jax.Array]:
    """The recurrence at the top of this module over the mixer's streams,
    token-major: ``q``, ``k`` [batch, T, H, K] as the convolution leaves them
    (UN-NORMED: the scan takes a head's ``q`` at length K^-1/2 and its ``k`` at
    length 1), ``v`` [batch, T, H, V], the log decay ``g`` [batch, T, H, K] (at
    most 0) and ``beta`` [batch, T, H]: (``o`` [batch, T, H, V] in ``q``'s dtype,
    ``carry_share`` of the chunks' sums)."""
    o, sums = kda_with_sums(q, k, v, g, beta, chunk)
    return o, carry_share(sums)


def scan_counters(sums: jax.Array) -> Dict[str, jax.Array]:
    """What a step says of one mixer's scan from its chunks' summed log decays."""
    return {"carry_share": carry_share(sums), "decay_min": jnp.min(sums)}
