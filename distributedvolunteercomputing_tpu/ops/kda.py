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

``kda`` takes the streams token-major as the projections and the convolution
leave them, ``q``, ``k`` [batch, T, H, K], ``v`` [batch, T, H, V], ``g``
[batch, T, H, K] float32, ``beta`` [batch, T, H]. One chunk of one head is plain
matrices (``_chunk_fwd`` / ``_chunk_bwd``); ``core`` is a ``lax.scan`` over the
chunks with those functions mapped over sequences and heads, and its backward
the reverse scan that carries the state's cotangent and recomputes a chunk from
the state that entered it (float32, kept by the forward: [batch, T / C, H, V,
K]). XLA compiles it for the CPU, one chip and a step over several chips alike;
there is no kernel, because the same chunk functions as Pallas kernels (a head's
state resident in VMEM) were slower on a v5e than XLA's loops, alone and in the
step (``PERF.md``, PR 52). A device trace shows the scan as ``while`` loops that
carry the heads' states (``benchmark/kda_trace.py``).

``beta`` is folded into ``kb`` and ``vb`` and ``g`` summed by chunk outside the
core, in passes that JAX differentiates. A sequence that is no whole number of
chunks is padded with positions that neither decay nor write (``g`` 0, ``beta``
0) and cut again.
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
# a scan over the chunks, the chunk's functions over sequences and heads
# ---------------------------------------------------------------------------
# streams [Z, T, H, K or V], T a whole number of chunks; states [Z, nc, H, V, K]


def _over_heads(fn, n_states: int, n_streams: int):
    """``fn`` of ``n_states`` states [Z, H, V, K] then ``n_streams`` streams [Z, C, H, *]: over Z and H."""
    def axes(stream_axis):
        return (0,) * n_states + (stream_axis,) * n_streams
    return jax.vmap(jax.vmap(fn, in_axes=axes(1)), in_axes=axes(0))


def _by_chunk(a, chunk: int):
    """[Z, T, H, D] -> [nc, Z, C, H, D]."""
    z, t, h, d = a.shape
    return jnp.moveaxis(a.reshape(z, t // chunk, chunk, h, d), 1, 0)


def _from_chunks(a):
    """[nc, Z, H, C, D] (the mapped functions' results) -> [Z, T, H, D]."""
    nc, z, h, c, d = a.shape
    return jnp.moveaxis(a, (0, 3), (1, 2)).reshape(z, nc * c, h, d)


def _scan_fwd(q, k, kb, vb, gc, chunk: int):
    """(o [Z, T, H, V], the state entering each chunk [Z, nc, H, V, K] float32)."""
    z, t, h, dk = q.shape
    step_fn = _over_heads(_chunk_fwd, 1, 5)

    def step(st, xs):
        o, st_new = step_fn(st, *xs)
        return st_new, (o, st)

    xs = tuple(_by_chunk(a, chunk) for a in (q, k, kb, vb, gc))
    _, (o, states) = jax.lax.scan(step, jnp.zeros((z, h, vb.shape[-1], dk), _F32), xs)
    return _from_chunks(o), jnp.moveaxis(states, 0, 1)


def _scan_bwd(q, k, kb, vb, gc, states, do, chunk: int):
    z, t, h, dk = q.shape
    step_fn = _over_heads(_chunk_bwd, 2, 6)

    def step(dst, xs):
        *out, dg, dst_prev = step_fn(dst, *xs)
        # the streams' cotangents leave a chunk in the streams' dtype: what the scan stacks is half as wide
        return dst_prev, (*(d.astype(a.dtype) for d, a in zip(out, (q, k, kb, vb))), dg)

    xs = (jnp.moveaxis(states, 1, 0), *(_by_chunk(a, chunk) for a in (q, k, kb, vb, gc, do)))
    _, outs = jax.lax.scan(step, jnp.zeros((z, h, vb.shape[-1], dk), _F32), xs, reverse=True)
    return tuple(_from_chunks(a) for a in outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def core(q, k, kb, vb, gc, chunk: int) -> jax.Array:
    """``o`` [Z, T, H, V] from ``q``, ``k``, ``kb = beta k`` [Z, T, H, K], ``vb =
    beta v`` [Z, T, H, V] and the chunks' running sums ``gc`` [Z, T, H, K]
    float32."""
    return _scan_fwd(q, k, kb, vb, gc, chunk)[0]


def _core_fwd(q, k, kb, vb, gc, chunk):
    o, states = _scan_fwd(q, k, kb, vb, gc, chunk)
    return o, (q, k, kb, vb, gc, states)


core.defvjp(_core_fwd, lambda chunk, res, do: _scan_bwd(*res, do, chunk))


# ---------------------------------------------------------------------------
# the entry
# ---------------------------------------------------------------------------


def kda_with_sums(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
                  chunk: int = CHUNK) -> Tuple[jax.Array, jax.Array]:
    """``kda``'s ``o`` [batch, T, H, V] in ``q``'s dtype and every chunk's summed
    log decay [batch, T / C, H, K] float32 (no gradient: what the counters read)."""
    z, t, h, dk = q.shape
    if chunk < 2 or chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk} is no power of two: the pairs of a chunk are taken by level")
    pad = (-t) % chunk
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    tp = t + pad
    nc = tp // chunk
    b = beta.astype(_F32)[..., None]
    kb = (k.astype(_F32) * b).astype(k.dtype)
    vb = (v.astype(_F32) * b).astype(v.dtype)
    gc = jnp.cumsum(g.astype(_F32).reshape(z, nc, chunk, h, dk), axis=2)
    sums = jax.lax.stop_gradient(gc[:, :, -1])
    o = core(q, k, kb, vb, gc.reshape(z, tp, h, dk), chunk)
    return (o[:, :t] if pad else o), sums


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
    token-major: ``q`` (scaled), ``k`` [batch, T, H, K], ``v`` [batch, T, H, V],
    the log decay ``g`` [batch, T, H, K] (at most 0) and ``beta`` [batch, T, H]:
    (``o`` [batch, T, H, V] in ``q``'s dtype, ``carry_share`` of the chunks' sums)."""
    o, sums = kda_with_sums(q, k, v, g, beta, chunk)
    return o, carry_share(sums)


def scan_counters(sums: jax.Array) -> Dict[str, jax.Array]:
    """What a step says of one mixer's scan from its chunks' summed log decays."""
    return {"carry_share": carry_share(sums), "decay_min": jnp.min(sums)}
