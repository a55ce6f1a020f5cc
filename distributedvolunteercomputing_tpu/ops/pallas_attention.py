"""Flash attention as Pallas TPU kernels (forward + one fused backward).

The reference's hot path is a CUDA ``train_step`` (BASELINE.json:5); its TPU
equivalent for the transformer zoo is attention that never materialises the
[Tq, Tk] score matrix in HBM. Forward is a block-wise online-softmax kernel;
backward recomputes the probabilities from the saved logsumexp and produces
dq, dk and dv in ONE kernel, wired up through ``jax.custom_vjp``.

Design (speeds: PERF.md, Findings of PR 27, measured on a TPU v5e):

- **One head's whole sequence is resident in VMEM** and the kernels loop
  over its blocks themselves. The forward's grid is (batch, head, q-block)
  with the head's K and V as one block (fetched once per head: the block
  index does not change between q-blocks); the backward's grid is (batch,
  head, k-block) with the head's q, dO, lse and delta resident. Grid steps
  are hundreds, not the tens of thousands a 128 x 128 grid makes at
  T=1,024, and a step's cost is its arithmetic. ``choose_blocks`` picks the
  block sizes from (Tq, Tk, D, dtype) and refuses shapes whose resident
  set does not fit VMEM (the router then keeps the XLA core).
- **Causal blocks that are wholly masked are never visited** (the in-kernel
  loops end at the diagonal), and blocks wholly below the diagonal run a
  body with no mask arithmetic at all; only the blocks the diagonal crosses
  (and a ragged last block) pay for ``iota``/compare/select.
- **Softmax statistics are rows, not 128-lane broadcasts.** lse and delta
  live in HBM as ``[B, H, nq, 1, bq]`` (one row per q-block). The backward
  computes transposed scores ``k @ q^T`` so that a row broadcasts along
  sublanes, which is free; the forward turns its column into a row once per
  q-block.
- **Grouped key/value heads** (``k.shape[1]`` divides ``q.shape[1]``): query
  head ``h`` reads K/V head ``h // group`` through the block index map, so a
  group's K and V are fetched once while its query heads follow each other.
  The backward writes dk and dv per QUERY head and the caller sums each
  group's (one reduce over [B, Hkv, group, T, D]); with equal head counts the
  program is what it was.
- **A window** (``window=w``: query i sees keys j with i - w < j <= i) moves
  the START of the in-kernel loops as the diagonal moves their end: blocks
  wholly outside the band are never visited, blocks wholly inside run the
  unmasked body, and only the blocks an edge crosses pay for the mask. The
  windowed calls carry their own kernel names (``dvc_flash_win_fwd`` /
  ``dvc_flash_win_bwd``) so that a device trace tells them from the full ones.
- **The value head has its own width** (``v.shape[-1]``, latent attention's 128
  under keys of 128 + 64): the v, o, dO and dv blocks and the output's
  accumulator take it, the scores and dq / dk the key width. With equal widths
  the program is what it was.
- **Matmuls run in the INPUT dtype** with f32 accumulation, so bf16 inputs
  hit the MXU at its bf16 rate. Scores, statistics and accumulators are
  f32; the probabilities (and ds) are rounded to the input dtype before
  their matmuls, exactly as the XLA core rounds them (ops/attention.py).

On non-TPU backends the kernels run in interpret mode, so the same code
path is unit-testable on the CPU mesh (tests/conftest.py forces
JAX_PLATFORMS=cpu). Numerics are validated against ops/attention.py's
plain-XLA core in tests/test_pallas_attention.py.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedvolunteercomputing_tpu.utils.jaxenv import tpu_backend

NEG_INF = -1e30
LANES = 128
# What one kernel may hold in VMEM. A v5e core has 128 MiB; the compiler's
# default scoped limit is 16 MiB, so every call states its own.
VMEM_BUDGET_BYTES = 64 * 1024 * 1024
# The largest block a sequence is cut into. Measured, PR 27, TPU v5e
# (PERF.md, block sweep): at T=1,024 one 1,024 x 1,024 block a head (the
# forward is then a plain softmax: no running statistics to rescale) and
# 512 x 512 blocks (a quarter of the square skipped as masked) are within 3%
# of each other forward + backward, and everything smaller loses to the
# per-block cost of the running statistics; at T=2,048 blocks of 1,024 win.
PREFERRED_BLOCK = 1024
# The names ``_fa_fwd`` gives the kernel's output and its log-sum-exp rows:
# what a rematerialised layer keeps of a call (models/common.remat_layer).
KEPT_NAMES = ("attention_out", "attention_lse")


def _interpret_default() -> bool:
    return not tpu_backend()


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(tq: int, tk: int, d: int, dtype, block_q: int, block_k: int) -> int:
    """Upper estimate of the backward kernel's VMEM (the larger of the two):
    the resident head (q, dO, dq, double-buffered; the f32 dq accumulator),
    the streamed k/v/dk/dv blocks and their accumulators, the statistics'
    rows (a [1, bq] row fills 8 sublanes) and the score-shaped f32
    temporaries (s, p, dp, ds and two rounded copies). The head dim pads to
    128 lanes in VMEM."""
    item = jnp.dtype(dtype).itemsize
    dl = _round_up(d, LANES)
    tq_p, bk = _round_up(tq, block_q), min(block_k, _round_up(tk, 8))
    resident = 3 * 2 * tq_p * dl * item + tq_p * dl * 4
    streamed = 4 * 2 * bk * dl * item + 2 * bk * dl * 4
    stats = 2 * 2 * 8 * tq_p * 4
    tiles = 6 * block_q * bk * 4
    return resident + streamed + stats + tiles


def choose_blocks(
    tq: int, tk: int, d: int, dtype, window: Optional[int] = None
) -> Optional[Tuple[int, int]]:
    """(block_q, block_k) for a shape, or None where the kernel cannot hold
    one head in VMEM. With a ``window`` the blocks are no larger than the
    window where the sequence allows it: every block a query block visits is
    then crossed by an edge (two blocks visited for one block's worth of pairs
    inside the band), and still smaller blocks lost to the per-block cost.
    Measured, PR 33, TPU v5e, window 512 at T=8,192, 64 heads over 8, forward
    + backward (experiments/laguna_attention_sweep.py): 512 x 512 38.4 ms,
    256 x 512 44.2, 1,024 x 1,024 49.4, 256 x 256 53.8, 128 x 128 85.2.

    Mosaic wants a block's last two dims to be multiples of the dtype's
    tile ((8, 128) f32, (16, 128) bf16) or the whole dim; the statistics'
    row puts block_q on the lane axis. So a sequence that is a multiple of
    128 takes the largest of 1,024, 512, 256, 128 that divides it, and any
    other sequence is one block (padded to the sublane tile) up to 1,024 and
    padded to whole 1,024-blocks beyond."""

    largest = PREFERRED_BLOCK
    if window is not None:
        largest = max(128, min(PREFERRED_BLOCK, window))

    def pick(t: int) -> int:
        for b in (PREFERRED_BLOCK, 512, 256, 128):
            if b <= largest and t % b == 0:
                return b
        return _round_up(t, 16) if t <= PREFERRED_BLOCK else PREFERRED_BLOCK

    bq, bk = pick(tq), pick(tk)
    if vmem_bytes(tq, tk, d, dtype, bq, bk) > VMEM_BUDGET_BYTES:
        return None
    return bq, bk


def _pad_seq(x: jax.Array, block: int) -> jax.Array:
    t = x.shape[2]
    pad = (-t) % block
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def _dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """dot_general with f32 accumulation, operands kept in THEIR dtype —
    sub-f32 inputs hit the MXU at native rate (see module docstring)."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


def _compiler_params(interpret: bool, semantics, vmem: int):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=int(min(max(2 * vmem, 32 * 1024 * 1024), 100 * 1024 * 1024)),
    )


def _loop(lo, hi, body) -> None:
    """``for j in range(lo, hi): body(j)`` over refs; bounds may be traced."""
    jax.lax.fori_loop(lo, hi, lambda j, c: (body(j), c)[1], 0)


def _mask_scores(s, q0, kpos: jax.Array, q_axis: int, *, causal: bool, tk_valid: int, ragged: bool,
                 window: Optional[int] = None):
    """The scores of a masked block with NEG_INF where they do not count: key
    positions ``kpos`` (an iota the shape of the block) outside the valid
    keys, under causal masking after their query (queries run from ``q0``
    along ``q_axis``), and under a window ``window`` or more keys before it.
    The valid-key compare is emitted only where padded keys exist
    (``ragged``)."""
    keep = kpos < tk_valid if ragged else None
    if causal:
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, kpos.shape, q_axis)
        keep = (kpos <= qpos) if keep is None else keep & (kpos <= qpos)
        if window is not None:
            keep = keep & (kpos > qpos - window)
    return s if keep is None else jnp.where(keep, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, block_q, block_k, tk_valid, n_k, window=None,
):
    iq = pl.program_id(2)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[...]  # [bq, D], input dtype
    _masked = functools.partial(
        _mask_scores, causal=causal, tk_valid=tk_valid, ragged=tk_valid % block_k != 0,
        window=window,
    )

    def step(jk, masked: bool):
        start = pl.multiple_of(jk * block_k, block_k)
        kblk = k_ref[pl.ds(start, block_k), :]
        vblk = v_ref[pl.ds(start, block_k), :]
        s = _dot(q, kblk, _NT) * scale  # f32 [bq, bk]
        if masked:
            col = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = _masked(s, iq * block_q, col, 0)
        m_prev = m_scr[...]  # [bq, LANES], every lane the same
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, 0:1])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr[:, 0:1] + _dot(p.astype(vblk.dtype), vblk, _NN)

    # Blocks wholly visible (and wholly inside the valid keys) need no mask;
    # the ones the diagonal crosses, or a ragged last one, do; the rest of a
    # causal row's blocks are never visited.
    n_full = tk_valid // block_k
    n_end = n_k
    if causal:
        n_full = jnp.minimum(n_full, (iq * block_q) // block_k)
        n_end = jnp.minimum(n_k, ((iq + 1) * block_q - 1) // block_k + 1)
    if window is None:
        _loop(0, n_full, lambda jk: step(jk, False))
        _loop(n_full, n_end, lambda jk: step(jk, True))
    else:
        # The band's far edge: the first block that holds a key some row of
        # this q-block sees, then the first block every row sees all of.
        row0, row1 = iq * block_q, (iq + 1) * block_q - 1
        lo = jnp.maximum(row0 - window + 1, 0) // block_k
        inside = jnp.maximum(row1 - window + block_k, 0) // block_k  # ceil((row1 - w + 1) / bk)
        a = jnp.clip(inside, lo, n_end)
        b = jnp.clip(n_full, a, n_end)
        _loop(lo, a, lambda jk: step(jk, True))
        _loop(a, b, lambda jk: step(jk, False))
        _loop(b, n_end, lambda jk: step(jk, True))

    l = l_scr[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc_scr[...] / l_safe[:, 0:1]).astype(o_ref.dtype)
    # The statistic is a column here and a row in HBM: one transpose per
    # q-block instead of a 128-lane broadcast written for every row.
    lse_ref[...] = jnp.transpose(m_scr[...] + jnp.log(l_safe))[0:1, :]


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
    bq: int, bk: int, interpret: bool, window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(out [B, H, Tq, D], lse [B, H, nq, 1, bq] over the padded rows)."""
    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[3]  # the value head's own width: o's, and the accumulator's
    group = h // k.shape[1]  # query heads per key/value head
    scale = 1.0 / (d ** 0.5)
    qp, kp, vp = _pad_seq(q, bq), _pad_seq(k, bk), _pad_seq(v, bk)
    tq_p, tk_p = qp.shape[2], kp.shape[2]
    n_q, n_k = tq_p // bq, tk_p // bk

    qspec = pl.BlockSpec((None, None, bq, d), lambda i, j, iq: (i, j, iq, 0))
    if group == 1:
        kvspec = pl.BlockSpec((None, None, tk_p, d), lambda i, j, iq: (i, j, 0, 0))
    else:
        kvspec = pl.BlockSpec((None, None, tk_p, d), lambda i, j, iq: (i, j // group, 0, 0))
    ospec, vspec = qspec, kvspec
    if dv != d:  # a value head narrower (or wider) than the key head: v and o in blocks of its width
        ospec = pl.BlockSpec((None, None, bq, dv), qspec.index_map)
        vspec = pl.BlockSpec((None, None, tk_p, dv), kvspec.index_map)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk, tk_valid=tk, n_k=n_k, window=window,
        ),
        grid=(b, h, n_q),
        in_specs=[qspec, kvspec, vspec],
        out_specs=[
            ospec,
            pl.BlockSpec((None, None, None, 1, bq), lambda i, j, iq: (i, j, iq, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq_p, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, n_q, 1, bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # running max
            pltpu.VMEM((bq, LANES), jnp.float32),  # running denominator
            pltpu.VMEM((bq, dv), jnp.float32),     # un-normalized output
        ],
        compiler_params=_compiler_params(
            interpret, ("parallel", "parallel", "parallel"),
            vmem_bytes(tq, tk, d, q.dtype, bq, bk),
        ),
        interpret=interpret,
        name="dvc_flash_fwd" if window is None else "dvc_flash_win_fwd",
    )(qp, kp, vp)
    return out[:, :, :tq], lse


# ---------------------------------------------------------------------------
# backward: one kernel per k-block, the head's q / dO / statistics resident
# ---------------------------------------------------------------------------


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_scr, dk_scr, dv_scr,
    *, scale, causal, block_q, block_k, tk_valid, n_q, n_k, window=None,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)
    kblk = k_ref[...]  # [bk, D]
    vblk = v_ref[...]
    _masked = functools.partial(
        _mask_scores, causal=causal, tk_valid=tk_valid, ragged=tk_valid % block_k != 0,
        window=window,
    )

    def step(iq, masked: bool):
        start = pl.multiple_of(iq * block_q, block_q)
        qblk = q_ref[pl.ds(start, block_q), :]
        doblk = do_ref[pl.ds(start, block_q), :]
        # Transposed scores [bk, bq]: the statistics are rows [1, bq] and
        # broadcast along sublanes; dv and dk need no transposed operand.
        s_t = _dot(kblk, qblk, _NT) * scale
        if masked:
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
            s_t = _masked(s_t, iq * block_q, kpos, 1)
        p_t = jnp.exp(s_t - lse_ref[iq])  # f32 [bk, bq]
        dv_scr[...] += _dot(p_t.astype(doblk.dtype), doblk, _NN)
        dp_t = _dot(vblk, doblk, _NT)
        ds_t = (p_t * (dp_t - delta_ref[iq])).astype(qblk.dtype)
        # dk and dq accumulate unscaled; the scale is applied once at the end.
        dk_scr[...] += _dot(ds_t, qblk, _NN)
        dq_scr[pl.ds(start, block_q), :] += _dot(ds_t, kblk, _TN)

    # Padded q rows need no mask: their dO and delta are zero, so they add
    # nothing. Padded keys (a ragged last block) must not receive a share of
    # a row's probability, so every step of that block is masked.
    first, n_masked_end = 0, 0
    if causal:
        first = (ik * block_k) // block_q
        n_masked_end = jnp.minimum(n_q, ((ik + 1) * block_k + block_q - 2) // block_q)
    if tk_valid % block_k:
        n_masked_end = jnp.where(ik == n_k - 1, n_q, n_masked_end)
    if window is None:
        _loop(first, n_masked_end, lambda iq: step(iq, True))
        _loop(n_masked_end, n_q, lambda iq: step(iq, False))
    else:
        # Queries past the band's far edge never see this k-block: the loop
        # ends with the last q-block that holds a row seeing its last key;
        # q-blocks whose every row sees every key of it run unmasked.
        key0, key1 = ik * block_k, (ik + 1) * block_k - 1
        last = jnp.minimum(n_q, (key1 + window - 1) // block_q + 1)
        a = jnp.minimum(n_masked_end, last)
        b = jnp.clip((key0 + window) // block_q, a, last)
        _loop(first, a, lambda iq: step(iq, True))
        _loop(a, b, lambda iq: step(iq, False))
        _loop(b, last, lambda iq: step(iq, True))

    dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(ik == n_k - 1)
    def finalize():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_backward(causal: bool, bq: int, bk: int, interpret: bool, residuals, g,
                    window: Optional[int] = None):
    q, k, v, out, lse = residuals
    do = g
    b, h, tq, d = q.shape
    h_kv, tk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // h_kv
    scale = 1.0 / (d ** 0.5)

    # delta_i = sum_d dO_i O_i — the softmax-jacobian diagonal term.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    qp, kp, vp, dop = _pad_seq(q, bq), _pad_seq(k, bk), _pad_seq(v, bk), _pad_seq(do, bq)
    tq_p, tk_p = qp.shape[2], kp.shape[2]
    n_q, n_k = tq_p // bq, tk_p // bk
    delta = jnp.pad(delta, ((0, 0), (0, 0), (0, tq_p - tq))).reshape(b, h, n_q, 1, bq)

    head = pl.BlockSpec((None, None, tq_p, d), lambda i, j, ik: (i, j, 0, 0))
    rows = pl.BlockSpec((None, None, n_q, 1, bq), lambda i, j, ik: (i, j, 0, 0, 0))
    kblock = pl.BlockSpec((None, None, bk, d), lambda i, j, ik: (i, j, ik, 0))
    # A group's query heads read one K/V head and each writes its own dk, dv.
    kv_in = kblock if group == 1 else pl.BlockSpec(
        (None, None, bk, d), lambda i, j, ik: (i, j // group, ik, 0))
    vhead, vblock, v_in = head, kblock, kv_in
    if dv != d:  # do, v and dv in blocks of the value head's width
        vhead = pl.BlockSpec((None, None, tq_p, dv), head.index_map)
        vblock = pl.BlockSpec((None, None, bk, dv), kblock.index_map)
        v_in = pl.BlockSpec((None, None, bk, dv), kv_in.index_map)
    dq, dk, dv_ = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk, tk_valid=tk, n_q=n_q, n_k=n_k, window=window,
        ),
        grid=(b, h, n_k),
        in_specs=[head, kv_in, v_in, vhead, rows, rows],
        out_specs=[head, kblock, vblock],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, tk_p, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, tk_p, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq_p, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(
            # dq accumulates across the k-blocks of a head: that dim is
            # sequential.
            interpret, ("parallel", "parallel", "arbitrary"),
            vmem_bytes(tq, tk, d, q.dtype, bq, bk),
        ),
        interpret=interpret,
        name="dvc_flash_bwd" if window is None else "dvc_flash_win_bwd",
    )(qp, kp, vp, dop, lse, delta)
    dk, dv_ = dk[:, :, :tk], dv_[:, :, :tk]
    if group > 1:
        dk, dv_ = (
            jnp.sum(a.reshape(b, h_kv, group, tk, a.shape[-1]), axis=2, dtype=jnp.float32).astype(a.dtype)
            for a in (dk, dv_)
        )
    return dq[:, :, :tq], dk, dv_


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _resolve(q, k, block_q, block_k, interpret, causal=True, window=None) -> Tuple[int, int, bool]:
    """The call's block sizes and mode: explicit blocks are clipped to the
    (tile-rounded) sequence, missing ones come from ``choose_blocks``."""
    tq, tk, d = q.shape[2], k.shape[2], q.shape[3]
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"{k.shape[1]} key/value heads do not divide {q.shape[1]} query heads")
    if window is not None and (not causal or tq != tk or window < 1):
        raise ValueError("a window needs causal attention over a square sequence")
    if block_q is None or block_k is None:
        chosen = choose_blocks(tq, tk, d, q.dtype, window)
        if chosen is None:
            raise ValueError(
                f"flash attention keeps one head in VMEM; Tq={tq}, Tk={tk}, D={d} "
                f"needs more than {VMEM_BUDGET_BYTES} bytes"
            )
        block_q = chosen[0] if block_q is None else block_q
        block_k = chosen[1] if block_k is None else block_k
    bq = min(block_q, _round_up(tq, 8))
    bk = min(block_k, _round_up(tk, 8))
    return bq, bk, _interpret_default() if interpret is None else interpret


def kept_bytes(q: jax.Array, k: jax.Array, window: Optional[int] = None,
               v: Optional[jax.Array] = None) -> int:
    """Bytes of the two residuals named by ``KEPT_NAMES`` for one call at
    ``choose_blocks``' blocks, as the chip lays them out: the output with its
    head dim padded to whole lanes (a D=64 output takes a D=128 one's room:
    the compiled step's kept stack is ``bf16[L,B,H,T,64]`` tiled (8, 128)),
    and the f32 log-sum-exp rows of whole q-blocks."""
    bq, _, _ = _resolve(q, k, None, None, False, True, window)
    b, h, tq, d = q.shape
    if v is not None:  # the output is as wide as the value head
        d = v.shape[3]
    return b * h * (tq * _round_up(d, LANES) * jnp.dtype(q.dtype).itemsize + 4 * _round_up(tq, bq))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, Hkv, Tk, D], Hkv dividing H
    v: jax.Array,  # [B, Hkv, Tk, Dv]: the value head may be another width than the key head; o is as wide
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Drop-in for ops.attention.attention_core (no additive mask support).

    Block sizes default to ``choose_blocks`` of the shape. Causal masking is
    top-left aligned: row i attends keys 0..i. For Tq != Tk this differs
    from attention_core's bottom-right alignment — the router in
    ops/attention.py only sends square causal shapes here. Query head ``h``
    reads key/value head ``h // (H / Hkv)``. With ``window`` (causal, square)
    row i attends keys ``i - window < j <= i``.
    """
    bq, bk, interp = _resolve(q, k, block_q, block_k, interpret, causal, window)
    return _flash_forward(q, k, v, causal, bq, bk, interp, window)[0]


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret, window):
    bq, bk, interp = _resolve(q, k, block_q, block_k, interpret, causal, window)
    out, lse = _flash_forward(q, k, v, causal, bq, bk, interp, window)
    # Named so that a checkpoint's policy can keep them (KEPT_NAMES): with the
    # kernel's two results saved, a rematerialised layer's recomputed forward
    # has no use for the kernel and its call there is dead code.
    out, lse = checkpoint_name(out, KEPT_NAMES[0]), checkpoint_name(lse, KEPT_NAMES[1])
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, window, residuals, g):
    q, k = residuals[0], residuals[1]
    bq, bk, interp = _resolve(q, k, block_q, block_k, interpret, causal, window)
    return _flash_backward(causal, bq, bk, interp, residuals, g, window)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
