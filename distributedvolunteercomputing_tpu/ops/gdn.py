"""The gated delta rule under ONE decay a head (``models/qwen3_next.py``'s Gated
DeltaNet mixer), several value heads reading one key head: the SECOND chunk form
of the delta rule beside ``ops/kda.py``'s decay by channel, as a chunked scan
with its own backward. For value head j, with a state ``S`` in ``R^{K x V}``
(float32) that starts at zero, its key head ``j // R`` (R value heads a key
head) and ``g_t`` at most 0, one number a value head a token::

    S <- exp(g_t) S;   u_t = beta_t (v_t - S^T k_t);   S <- S + k_t u_t^T;   o_t = S^T q_t

``ops/kda.py``'s recurrence with ``Diag(alpha)`` a scalar. Over a chunk of C
tokens, with ``G`` the running sum of ``g`` inside the chunk (inclusive),
``D_ij = exp(G_i - G_j)`` for j <= i and ``S_0`` the state that enters::

    L = tril(beta_i (k k^T)_ij D_ij, -1)        (I + L) U = beta (v - e^G (k S_0))
    B = tril((q k^T)_ij D_ij)                   O = e^G (q S_0) + B U
    S_C = e^{G_C} S_0 + (e^{G_C - G} k)^T U

**Why a form of its own, beside ``ops/kda.py`` and not inside it.** A decay that
is one number a head needs none of what most of that module's chunk functions
do: ``exp(G_i - G_j)`` is a ``[C, C]`` matrix of values at most 1, taken of a
DIFFERENCE (no ``e^{-G}`` anywhere, so no exponent to keep in range and no
levels: a chunk whose summed log decay is far below -88 is exact), and the
in-chunk matrices are one product each where KDA's are six masked ones. And the
unscaled ``k k^T`` and ``q k^T`` of a chunk do not depend on the value head: they
are made ONCE A KEY HEAD and both of its value heads scale them by their own
``D`` (and ``beta``), so q and k stay at the key heads' count everywhere, never
repeated to the value heads' at a stream's size. What the two forms share is
imported from ``ops/kda.py`` as it stands (that module's text, and Kimi-Linear's
lowered step, are untouched): the products' helpers, the l2 norm of a head's q
and k on the chunk the step holds, the triangular inverse by blocks
(``_inverse``: ``L`` is handed as its levels' parts, cut from the one matrix by
masks), the map over sequences and heads, the in-place chunk reads and writes
and the unsigned chunk numbers, the chunk size and the carry counter.

``gdn_with_sums`` takes the mixer's streams as the convolution and the
projections leave them: ``qkv`` [batch, T, 2 Hk K + Hv V], q's, k's and v's
channels side by side as ONE array (the convolution's output: a step takes its
chunk's rows of all three out of it in place, and the backward writes ONE
cotangent the same way; no slice of a stream's size is ever made), the log decay
``g`` and ``beta`` [batch, T, Hv] float32. q and k come UN-NORMED; the step norms
them on the chunk it holds (q to length K^-1/2, k to length 1), sums the decay
down the chunk's rows and folds beta in float32. The backward is the reverse
scan that carries the states' cotangent, recomputes a chunk from the raw streams,
the states that entered it (float32, kept by the forward: [T / C, batch, Hv, V,
K]) and the chunk's ``T = (I + L)^-1`` (in the compute dtype, kept by the forward
too: [T / C, batch, Hk, R, C, C], a scan's ``xs``) and hands its cotangents to
JAX's transpose of the norms and the running sum. There is no kernel: XLA's
loops, on every backend, as ``ops/kda.py`` says of its own. A sequence that is no
whole number of chunks is padded with positions that neither decay nor write
(``g`` 0, ``beta`` 0) and cut again.

**What of a chunk leaves the backward loop, what does not leave either loop, and
why (PR 68).** Of a chunk, everything but ``S_0`` is a function of the streams
alone, and the inverse's levels (two [C, C] x [C, C] products each, ten fusions a
step) were 46 of the loops' 115 ms in ``qwen3-next-solo-8k``. The backward needs
the same ``T`` the forward made: the layer is rematerialised, so the recomputed
forward hands it over by chunk (67 MB in bfloat16 at the cell's shape, alive for
one layer's backward) and the backward loop makes no inverse: 6.6 ms less a
layer's backward on the chip. What was ALSO tried, and is not here because the
chip said no (``PERF.md`` section 6, PR 68; ``experiments/results/
pr68_gdn_sweep.jsonl``): making a pass's in-chunk matrices, norms and inverses for
all its chunks at once outside the ``lax.scan``, as batched products under one
more ``jax.vmap`` (in blocks of 1 to 32 chunks), the loops keeping only what
reads or writes the carried state. The loops fell from 115 to 46 ms a step and
the step ROSE from 416 to 498 ms: inside a loop a chunk's [batch, Hk, R, C, C]
matrices stay in the chip's vector memory from one small fusion to the next and a
level's product over 64 matrices takes 5 us; outside, the same product over a
block's 2,048 matrices reads and writes them through HBM and takes 0.18 us a
matrix where the loop's takes 0.08. The inverse alone outside (forward 10.9 ms a
layer at its best block of 16, against 9.8 with it inside) loses too. So the
loops keep their chunk's own work, and only what a later pass would make AGAIN
is handed over.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.ops import kda
from distributedvolunteercomputing_tpu.ops.kda import _NN, _NT, _TN, _dot, _dot32, _grid
from distributedvolunteercomputing_tpu.utils import traced

CHUNK = kda.CHUNK
_F32 = jnp.float32
# the one form this module has, as the ``gdn.scan`` span's ``gdn_form`` names it (``utils/traced.py``, kind "gdn_scan")
FORM = "scalar_decay_xla"


# ---------------------------------------------------------------------------
# one chunk of one VALUE head: plain matrices
# ---------------------------------------------------------------------------
# q, k [C, K] normed, in the compute dtype; a = k k^T and bq = q k^T [C, C] float32,
# unscaled, its key head's; v, do [C, V]; gc [C] (the chunk's running sum of g) and
# beta [C] float32; the state TURNED as ops/kda.py's: st, dst [V, K] float32.


def _decay(gc):
    """``D_ij = exp(G_i - G_j)`` for j <= i and 0 above the diagonal, [C, C]: no exponent over 0."""
    row, col = _grid(gc.shape[0])
    return jnp.where(row >= col, jnp.exp(jnp.minimum(gc[:, None] - gc[None, :], 0.0)), 0.0)


def _levels(lower):
    """A strictly lower ``L`` as ``ops/kda._inverse`` takes it: its levels' parts, each zero outside its level's pairs."""
    row, col = _grid(lower.shape[0])
    return [jnp.where(kda._pairs(row, col, shift), lower, 0.0) for shift in kda._shifts(lower.shape[0])]


def _in_chunk(gc, beta, a, bq):
    """(D, L strictly lower, B lower with its diagonal: [C, C] float32) of a value head's ``gc``, ``beta`` [C] and its key head's products."""
    row, col = _grid(gc.shape[0])
    d = _decay(gc)
    return d, jnp.where(row > col, beta[:, None] * a * d, 0.0), bq * d


def _around(st, t, q, k, v, gc, beta):
    """What both passes make of a chunk around its ``T = (I + L)^-1`` [C, C] (in
    the compute dtype) and the state that enters: (e^G, e^{G_C - G} [C, 1] and
    e^{G_C}; [q S_0 ; k S_0] [2 C, V]; v - e^G (k S_0); U [C, V] in the compute
    dtype)."""
    dtype = q.dtype
    e, ew, ec = jnp.exp(gc)[:, None], jnp.exp(gc[-1] - gc)[:, None], jnp.exp(gc[-1])
    held = _dot(jnp.concatenate([q, k], axis=0), st.astype(dtype), _NT)        # [q S_0 ; k S_0]
    w = v.astype(_F32) - e * held[q.shape[0]:]
    u = _dot(t, (beta[:, None] * w).astype(dtype), _NN).astype(dtype)
    return e, ew, ec, held, w, u


def _value_head_fwd(st, v, gc, beta, a, bq, q, k):
    """(o [C, V] in the compute dtype, the state that leaves [V, K] float32, the
    chunk's ``T`` [C, C] in the compute dtype: what the backward takes over)."""
    dtype = q.dtype
    _, lower, b = _in_chunk(gc, beta, a, bq)
    t = kda._inverse(_levels(lower), dtype == _F32).astype(dtype)
    e, ew, ec, held, _, u = _around(st, t, q, k, v, gc, beta)
    o = e * held[:q.shape[0]] + _dot(b.astype(dtype), u, _NN)
    st_new = ec * st + _dot(u, (k.astype(_F32) * ew).astype(dtype), _TN)
    return o.astype(dtype), st_new, t


def _value_head_bwd(dst, st, t, v, gc, beta, do, a, bq, q, k):
    """Cotangents (that of the state that entered [V, K]; dv [C, V], dgc, dbeta
    [C] float32; this value head's part of dq, dk [C, K] and of da, dbq [C, C],
    float32) from ``do`` and the cotangent ``dst`` of the state that left; the
    chunk's forward computed again from ``st`` around the forward's own ``t``."""
    dtype = q.dtype
    c = q.shape[0]
    row, col = _grid(c)
    d, lower, b = _in_chunk(gc, beta, a, bq)
    e, ew, ec, held, w, u = _around(st, t, q, k, v, gc, beta)
    kf = k.astype(_F32)
    stb, dstb, dof = st.astype(dtype), dst.astype(dtype), do.astype(_F32)
    kh = kf * ew
    # o = e (q S) + B U;  S' = ec S + kh^T U
    du = _dot(b.astype(dtype), do, _TN) + _dot(kh.astype(dtype), dstb, _NT)
    db = jnp.where(row >= col, _dot(do, u, _NT), 0.0)
    dkh = _dot(u, dstb, _NN)
    # U = (I + L)^-1 X:  dX = (I + L)^-T dU,  dL = -dX U^T;  X = beta (v - e (k S))
    dx = _dot(t, du.astype(dtype), _TN)
    dlower = jnp.where(row > col, -_dot(dx.astype(dtype), u, _NT), 0.0)
    # the two reads of the state, [q S ; k S], and their cotangents down the rows of one product each way
    d_held = jnp.concatenate([e * dof, -(beta[:, None] * e) * dx], axis=0).astype(dtype)
    dqk = _dot(d_held, stb, _NN)                                                # [2 C, K]
    dst_prev = ec * dst + _dot(d_held, jnp.concatenate([q, k], axis=0), _TN)
    dq, dk = dqk[:c], dqk[c:] + dkh * ew
    dbeta = jnp.sum(dx * w, axis=1) + jnp.sum(dlower * a * d, axis=1)
    # D enters L and B elementwise: dD D = dL L + dB B; G through D's rows and columns, e^G, e^{G_C - G} and e^{G_C}
    through_d = dlower * lower + db * b
    de = jnp.sum(dof * held[:c], axis=1) - beta * jnp.sum(dx * held[c:], axis=1)
    dew = jnp.sum(dkh * kh, axis=1)
    dgc = jnp.sum(through_d, axis=1) - jnp.sum(through_d, axis=0) + de * e[:, 0] - dew
    dgc = dgc.at[c - 1].add(jnp.sum(dew) + ec * jnp.sum(dst * st))
    return dst_prev, beta[:, None] * dx, dgc, dbeta, dq, dk, beta[:, None] * d * dlower, db * d


# ---------------------------------------------------------------------------
# one chunk of one KEY head with its value heads, as the mixer's streams hold it
# ---------------------------------------------------------------------------
# q, k [C, K] un-normed; v, o, do [C, R V]; g, beta [C, R]; the states [R, V, K], their chunk's t [R, C, C]


def _prepare(q, k, g):
    """(q at length K^-1/2, k at length 1, in the streams' dtype; gc [C, R]
    float32: the running sum of ``g`` down the chunk's rows, a lower-triangular
    product at float32's own precision)."""
    row, col = _grid(q.shape[0])
    return (kda._l2norm(q, q.shape[-1] ** -0.5), kda._l2norm(k),
            _dot32(jnp.where(row >= col, 1.0, 0.0), g.astype(_F32)))


def _by_value_head(a, r: int):
    """[C, R V] -> [R, C, V]."""
    return jnp.moveaxis(a.reshape(a.shape[0], r, -1), 1, 0)


def _side_by_side(a):
    """[R, C, V] -> [C, R V]."""
    return jnp.moveaxis(a, 0, 1).reshape(a.shape[1], -1)


def _group_fwd(st, q, k, v, g, beta):
    """(the states that leave [R, V, K], the chunk's summed log decays [R], its T [R, C, C]; o [C, R V])."""
    r = st.shape[0]
    q, k, gc = _prepare(q, k, g)
    a, bq = _dot(k, k, _NT), _dot(q, k, _NT)             # once a key head, unscaled
    o, st_new, t = jax.vmap(_value_head_fwd, in_axes=(0, 0, 1, 1, None, None, None, None))(
        st, _by_value_head(v, r), gc, beta.astype(_F32), a, bq, q, k)
    return st_new, gc[-1], t, _side_by_side(o)


def _group_bwd(dst, st, t, q, k, v, g, beta, do):
    """(The cotangent of the states that entered; the raw chunk's dq, dk [C, K],
    dv [C, R V], dg, dbeta [C, R], each in its stream's dtype): the preparation
    differentiated by JAX around the value heads' hand-written cotangents, whose
    parts of dq, dk, d(k k^T) and d(q k^T) are summed over the key head's value
    heads before the two products that bring them back to q and k."""
    r = st.shape[0]
    (qn, kn, gc), back = jax.vjp(_prepare, q, k, g)
    dtype = qn.dtype
    a, bq = _dot(kn, kn, _NT), _dot(qn, kn, _NT)
    dst_prev, dv, dgc, dbeta, dq, dk, da, dbq = jax.vmap(
        _value_head_bwd, in_axes=(0, 0, 0, 0, 1, 1, 0, None, None, None, None))(
        dst, st, t, _by_value_head(v, r), gc, beta.astype(_F32), _by_value_head(do, r), a, bq, qn, kn)
    da, dbq = jnp.sum(da, axis=0), jnp.sum(dbq, axis=0)
    # a = k k^T, bq = q k^T: [da + da^T ; dbq] k is (dk's part, dq's), and dbq^T q the rest of dk's
    back_k = _dot(jnp.concatenate([da + da.T, dbq], axis=0).astype(dtype), kn, _NN)
    c = q.shape[0]
    dqn = jnp.sum(dq, axis=0) + back_k[c:]
    dkn = jnp.sum(dk, axis=0) + back_k[:c] + _dot(dbq.astype(dtype), qn, _TN)
    dq_raw, dk_raw, dg = back((dqn.astype(dtype), dkn.astype(dtype), jnp.moveaxis(dgc, 0, 1)))
    return (dst_prev, dq_raw, dk_raw, _side_by_side(dv).astype(v.dtype), dg.astype(g.dtype),
            jnp.moveaxis(dbeta, 0, 1).astype(beta.dtype))


# ---------------------------------------------------------------------------
# a scan over the chunks, the chunk's functions over sequences and key heads
# ---------------------------------------------------------------------------
# qkv [Z, T, 2 Hk K + Hv V], g and beta [Z, T, Hv], T a whole number of chunks; the
# states [nc, Z, Hv, V, K], carried [Z, Hv, V, K] and seen by key head [Z, Hk, R, V, K]


def _layout(qkv, key_heads: int, value_heads: int, key_dim: int):
    """(q's and k's channels ``Hk K``, v's ``Hv V``, the carried states seen by key head [Z, Hk, R, V, K])."""
    kd = key_heads * key_dim
    vd = qkv.shape[-1] - 2 * kd
    return kd, vd, (qkv.shape[0], key_heads, value_heads // key_heads, vd // value_heads, key_dim)


def _chunk_by_key_head(qkv, i, chunk: int, kd: int, key_heads: int):
    """Chunk ``i`` of ``qkv`` [Z, T, 2 Hk K + Hv V], taken in place, by key head: q, k [Z, C, Hk, K], v [Z, C, Hk, R V]."""
    rows = jax.lax.dynamic_slice_in_dim(qkv, i * chunk, chunk, axis=1)
    z = rows.shape[0]
    return tuple(a.reshape(z, chunk, key_heads, -1) for a in (rows[..., :kd], rows[..., kd:2 * kd], rows[..., 2 * kd:]))


def _scan_fwd(qkv, g, beta, key_heads: int, value_heads: int, key_dim: int, chunk: int):
    """(o [Z, T, Hv V], every chunk's summed log decay [nc, Z, Hv] float32; what
    the backward takes over: the states entering each chunk [nc, Z, Hv, V, K]
    float32 and every chunk's T [nc, Z, Hk, R, C, C] in ``qkv``'s dtype)."""
    z, t, _ = qkv.shape
    kd, vd, by_key_head = _layout(qkv, key_heads, value_heads, key_dim)
    step_fn = kda._over_heads(_group_fwd, (1, 3), (5, 1))

    def step(carry, i):
        st, o = carry
        st_new, total, t_i, o_i = step_fn(st.reshape(by_key_head), *_chunk_by_key_head(qkv, i, chunk, kd, key_heads),
                                          kda._chunk_of(g, i, chunk, key_heads), kda._chunk_of(beta, i, chunk, key_heads))
        return (st_new.reshape(st.shape), kda._put_chunk(o, o_i, i, chunk)), (total.reshape(z, value_heads), st, t_i)

    start = (jnp.zeros((z, value_heads, vd // value_heads, key_dim), _F32), jnp.zeros((z, t, vd), qkv.dtype))
    (_, o), (sums, *kept) = jax.lax.scan(step, start, kda._chunk_indices(t // chunk))
    return o, sums, kept


def _scan_bwd(qkv, g, beta, states, inverses, do, key_heads: int, value_heads: int, key_dim: int, chunk: int):
    z = qkv.shape[0]
    kd, _, by_key_head = _layout(qkv, key_heads, value_heads, key_dim)
    step_fn = kda._over_heads(_group_bwd, (3, 1), (6, 5))

    def step(carry, xs):
        dst, (dqkv, dg, dbeta) = carry
        i, st, t_i = xs
        dst_prev, dq, dk, dv, dg_i, dbeta_i = step_fn(
            dst.reshape(by_key_head), st.reshape(by_key_head), t_i, *_chunk_by_key_head(qkv, i, chunk, kd, key_heads),
            *(kda._chunk_of(a, i, chunk, key_heads) for a in (g, beta, do)))
        # the chunk's rows of the ONE cotangent, q's, k's and v's channels side by side as the stream holds them
        rows = jnp.concatenate([a.reshape(z, chunk, -1) for a in (dq, dk, dv)], axis=-1)
        grads = (jax.lax.dynamic_update_slice_in_dim(dqkv, rows, i * chunk, axis=1),
                 kda._put_chunk(dg, dg_i, i, chunk), kda._put_chunk(dbeta, dbeta_i, i, chunk))
        return (dst_prev.reshape(dst.shape), grads), None

    start = (jnp.zeros(states.shape[1:], _F32), tuple(jnp.zeros_like(a) for a in (qkv, g, beta)))
    (_, grads), _ = jax.lax.scan(step, start, (kda._chunk_indices(states.shape[0]), states, inverses), reverse=True)
    return grads


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def core(qkv, g, beta, key_heads: int, value_heads: int, key_dim: int, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """(``o`` [Z, T, Hv V], the chunks' summed log decays [nc, Z, Hv] float32:
    no gradient) from the mixer's streams: ``qkv`` [Z, T, 2 Hk K + Hv V] with q
    and k un-normed, ``g`` and ``beta`` [Z, T, Hv]."""
    return _scan_fwd(qkv, g, beta, key_heads, value_heads, key_dim, chunk)[:2]


def _core_fwd(qkv, g, beta, key_heads, value_heads, key_dim, chunk):
    o, sums, kept = _scan_fwd(qkv, g, beta, key_heads, value_heads, key_dim, chunk)
    return (o, sums), (qkv, g, beta, *kept)


core.defvjp(_core_fwd, lambda key_heads, value_heads, key_dim, chunk, res, d:
            _scan_bwd(*res, d[0], key_heads, value_heads, key_dim, chunk))


# ---------------------------------------------------------------------------
# the entry
# ---------------------------------------------------------------------------


def gdn_with_sums(qkv: jax.Array, g: jax.Array, beta: jax.Array, key_heads: int, value_heads: int,
                  key_dim: int, chunk: int = CHUNK) -> Tuple[jax.Array, jax.Array]:
    """The recurrence at the top of this module over the mixer's streams,
    token-major: ``qkv`` [batch, T, 2 Hk K + Hv V] (q, k UN-NORMED, then v, side
    by side as the convolution leaves them; value head j reads key head ``j //
    (Hv / Hk)``), the log decay ``g`` (at most 0) and ``beta`` [batch, T, Hv]
    float32: (``o`` [batch, T, Hv V] in ``qkv``'s dtype, every chunk's summed log
    decay [batch, T / C, Hv] float32: no gradient, what the counters read)."""
    t = qkv.shape[1]
    if chunk < 2 or chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk} is no power of two: the chunk's inverse is taken by level")
    if value_heads % key_heads or (qkv.shape[-1] - 2 * key_heads * key_dim) % value_heads:
        raise ValueError(f"{value_heads} value heads over {key_heads} key heads of {key_dim} do not divide "
                         f"{qkv.shape[-1]} channels")
    traced.note("gdn_scan", form=FORM)
    streams = (qkv, g.astype(_F32), beta.astype(_F32))
    pad = (-t) % chunk
    if pad:
        streams = tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in streams)
    o, sums = core(*streams, key_heads, value_heads, key_dim, chunk)
    return o[:, :t], jnp.moveaxis(jax.lax.stop_gradient(sums), 0, 1)


def scan_counters(sums: jax.Array) -> Dict[str, jax.Array]:
    """What a step says of one mixer's scan from its chunks' summed log decays
    [batch, T / C, Hv]: ``ops/kda.scan_counters`` of a head whose one decay is
    its slowest channel's (``carry_share``: of the (sequence, head, chunk after
    the first) triples, the share whose chunk's whole decay is over
    ``ops/kda.CARRY_FLOOR``; ``decay_min``: the lowest chunk-summed log decay)."""
    return kda.scan_counters(sums[..., None])
