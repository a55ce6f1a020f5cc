"""Flash attention as Pallas TPU kernels (forward + backward).

The reference's hot path is a CUDA ``train_step`` (BASELINE.json:5); its TPU
equivalent for the transformer zoo is attention that never materialises the
[Tq, Tk] score matrix in HBM. Forward is a block-wise online-softmax kernel;
backward is the standard two-kernel flash recomputation (dq from k-blocks,
dk/dv from q-blocks) using the saved logsumexp, wired up through
``jax.custom_vjp``.

Design (speed not measured on the current code; numerics checked on the
v5e by chip_smoke.py):

- **K/V stream through the GRID** (innermost "arbitrary" dimension) with
  online-softmax state in VMEM scratch, instead of pulling the whole key
  sequence into VMEM per grid step. VMEM footprint is O(block) not O(T),
  and the Mosaic program is one small k-block body regardless of sequence
  length.
- **Matmuls run in the INPUT dtype** (``preferred_element_type=f32``
  accumulation), so bf16 inputs hit the MXU at its bf16 rate. bf16 x bf16
  products are exact in the f32 accumulator, so the bf16 path loses no
  precision on the score matmul; the p @ v / gradient matmuls round p/ds
  to the input dtype (the standard flash trade, applied only when inputs
  are sub-f32).
- Causal blocks that are fully masked skip their compute via ``pl.when``
  (the grid still visits them — index-remapping them away is not worth
  the complexity at these shapes).

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedvolunteercomputing_tpu.utils.jaxenv import tpu_backend

NEG_INF = -1e30
# Per-row softmax stats (lse, delta) are carried with a broadcast 128-lane
# trailing dim: Mosaic requires the last block dim to be 128-divisible or
# full, and a [T]-shaped row vector satisfies neither (same layout as the
# in-tree jax.experimental.pallas.ops.tpu.flash_attention).
LANES = 128


def _interpret_default() -> bool:
    return not tpu_backend()


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


def _to_input_dtype(p: jax.Array, like: jax.Array) -> jax.Array:
    """Round a f32 intermediate to the input dtype for the next matmul —
    only when the inputs are sub-f32 (bf16 path); f32 stays exact."""
    return p.astype(like.dtype) if like.dtype != jnp.float32 else p


def _compiler_params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        # b, h, q-blocks run in any order; the k-stream dim is sequential
        # (its scratch carry makes steps order-dependent).
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, block_q, block_k, tk_valid, n_k,
):
    iq, jk = pl.program_id(2), pl.program_id(3)

    @pl.when(jk == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Last k block this q block attends (causal rows end at (iq+1)*bq - 1).
    last_jk = n_k - 1
    if causal:
        last_jk = jnp.minimum(last_jk, ((iq + 1) * block_q - 1) // block_k)

    @pl.when(jk <= last_jk)
    def compute():
        q = q_ref[0, 0]  # [bq, D], input dtype
        kblk = k_ref[0, 0]  # [bk, D]
        s = _dot(q, kblk, ((1,), (1,))) * scale  # f32 [bq, bk]
        col = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = col < tk_valid
        if causal:
            row = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            mask = mask & (col <= row)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0:1]
        l_prev = l_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_prev * corr + jnp.sum(p, axis=1, keepdims=True), l_scr.shape
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        acc_scr[...] = acc_scr[...] * corr + _dot(
            _to_input_dtype(p, v_ref), v_ref[0, 0], ((1,), (0,))
        )

    @pl.when(jk == n_k - 1)
    def finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(
            m_scr[:, 0:1] + jnp.log(l_safe), lse_ref.shape[2:]
        )


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
    block_q: int, block_k: int, interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    b, h, tq, d = q.shape
    tk = k.shape[2]
    bq, bk = min(block_q, tq), min(block_k, tk)
    scale = 1.0 / (d ** 0.5)

    qp, kp, vp = _pad_seq(q, bq), _pad_seq(k, bk), _pad_seq(v, bk)
    tq_p, tk_p = qp.shape[2], kp.shape[2]
    n_k = tk_p // bk

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, tk_valid=tk, n_k=n_k,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, tq_p // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda i, j, iq, jk: (i, j, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda i, j, iq, jk: (i, j, jk, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda i, j, iq, jk: (i, j, jk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda i, j, iq, jk: (i, j, iq, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda i, j, iq, jk: (i, j, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, tq_p, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # running max
            pltpu.VMEM((bq, LANES), jnp.float32),  # running denominator
            pltpu.VMEM((bq, d), jnp.float32),      # un-normalized output
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :tq], lse[:, :, :tq, 0]


# ---------------------------------------------------------------------------
# backward: dq kernel (streams k blocks) and dkv kernel (streams q blocks)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale, causal, block_q, block_k, tk_valid, n_k,
):
    iq, jk = pl.program_id(2), pl.program_id(3)

    @pl.when(jk == 0)
    def init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    last_jk = n_k - 1
    if causal:
        last_jk = jnp.minimum(last_jk, ((iq + 1) * block_q - 1) // block_k)

    @pl.when(jk <= last_jk)
    def compute():
        q = q_ref[0, 0]
        kblk = k_ref[0, 0]
        vblk = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0:1]
        delta = delta_ref[0, 0][:, 0:1]
        s = _dot(q, kblk, ((1,), (1,))) * scale
        col = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = col < tk_valid
        if causal:
            row = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            mask = mask & (col <= row)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk] f32
        dp = _dot(do, vblk, ((1,), (1,)))
        ds = p * (dp - delta)
        dq_scr[...] = dq_scr[...] + _dot(
            _to_input_dtype(ds, k_ref), kblk, ((1,), (0,))
        )

    @pl.when(jk == n_k - 1)
    def finalize():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale, causal, block_q, block_k, tk_valid, n_q,
):
    jk, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # Causal: q blocks strictly before this k block's first row see nothing.
    first_iq = (jk * block_k) // block_q if causal else 0

    @pl.when(iq >= first_iq)
    def compute():
        kblk = k_ref[0, 0]
        vblk = v_ref[0, 0]
        qblk = q_ref[0, 0]
        doblk = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0:1]
        delta = delta_ref[0, 0][:, 0:1]
        s = _dot(qblk, kblk, ((1,), (1,))) * scale
        col = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = col < tk_valid
        if causal:
            row = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            mask = mask & (col <= row)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk] f32
        p_in = _to_input_dtype(p, v_ref)
        dv_scr[...] = dv_scr[...] + _dot(p_in, doblk, ((0,), (0,)))
        dp = _dot(doblk, vblk, ((1,), (1,)))
        ds = p * (dp - delta)
        ds_in = _to_input_dtype(ds, q_ref)
        # dk accumulates ds.T @ q; scale applied once at finalize.
        dk_scr[...] = dk_scr[...] + _dot(ds_in, qblk, ((0,), (0,)))

    @pl.when(iq == n_q - 1)
    def finalize():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(
    causal: bool, block_q: int, block_k: int, interpret: bool,
    residuals, g,
):
    q, k, v, out, lse = residuals
    do = g
    b, h, tq, d = q.shape
    tk = k.shape[2]
    bq, bk = min(block_q, tq), min(block_k, tk)
    scale = 1.0 / (d ** 0.5)

    # delta_i = sum_d dO_i O_i — the softmax-jacobian diagonal term.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    qp, kp, vp = _pad_seq(q, bq), _pad_seq(k, bk), _pad_seq(v, bk)
    dop = _pad_seq(do, bq)
    tq_p, tk_p = qp.shape[2], kp.shape[2]
    n_q, n_k = tq_p // bq, tk_p // bk
    pad_q = tq_p - tq
    if pad_q:
        # Padded q rows must not contribute to dk/dv: exp(NEG_INF - 0) would
        # be 1, so give them lse=+large instead so p == 0 exactly.
        lse_p = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)), constant_values=1e30)
        delta_p = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q)))
    else:
        lse_p, delta_p = lse, delta
    lse_p = jnp.broadcast_to(lse_p[..., None], (*lse_p.shape, LANES))
    delta_p = jnp.broadcast_to(delta_p[..., None], (*delta_p.shape, LANES))

    # dq: grid (b, h, q-blocks, k-stream)
    qspec = pl.BlockSpec((1, 1, bq, d), lambda i, j, iq, jk: (i, j, iq, 0))
    kstream = pl.BlockSpec((1, 1, bk, d), lambda i, j, iq, jk: (i, j, jk, 0))
    vecq = pl.BlockSpec((1, 1, bq, LANES), lambda i, j, iq, jk: (i, j, iq, 0))
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk, tk_valid=tk, n_k=n_k,
        ),
        grid=(b, h, n_q, n_k),
        in_specs=[qspec, kstream, kstream, qspec, vecq, vecq],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, h, tq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(qp, kp, vp, dop, lse_p, delta_p)

    # dk/dv: grid (b, h, k-blocks, q-stream)
    kspec = pl.BlockSpec((1, 1, bk, d), lambda i, j, jk, iq: (i, j, jk, 0))
    qstream = pl.BlockSpec((1, 1, bq, d), lambda i, j, jk, iq: (i, j, iq, 0))
    vecq_s = pl.BlockSpec((1, 1, bq, LANES), lambda i, j, jk, iq: (i, j, iq, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk, tk_valid=tk, n_q=n_q,
        ),
        grid=(b, h, tk_p // bk, n_q),
        in_specs=[qstream, kspec, kspec, qstream, vecq_s, vecq_s],
        out_specs=[kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tk_p, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, tk_p, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(qp, kp, vp, dop, lse_p, delta_p)

    return dq[:, :, :tq], dk[:, :, :tk], dv[:, :, :tk]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, H, Tk, D]
    v: jax.Array,  # [B, H, Tk, D]
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Drop-in for ops.attention.attention_core (no additive mask support).

    Causal masking is top-left aligned: row i attends keys 0..i. For
    Tq != Tk this differs from attention_core's bottom-right alignment —
    the router in ops/attention.py only sends square causal shapes here.
    """
    out, _ = _flash_forward(
        q, k, v, causal, block_q, block_k,
        _interpret_default() if interpret is None else interpret,
    )
    return out


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(
        q, k, v, causal, block_q, block_k,
        _interpret_default() if interpret is None else interpret,
    )
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, residuals, g):
    return _flash_backward(
        causal, block_q, block_k,
        _interpret_default() if interpret is None else interpret,
        residuals, g,
    )


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_mha(
    q: jax.Array,  # [B, T, d_model] (already projected)
    k: jax.Array,
    v: jax.Array,
    n_heads: int,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Multi-head wrapper matching ops.attention.multi_head_attention."""
    from distributedvolunteercomputing_tpu.ops.attention import merge_heads, split_heads

    out = flash_attention(
        split_heads(q, n_heads), split_heads(k, n_heads), split_heads(v, n_heads),
        causal, block_q, block_k,
    )
    return merge_heads(out)
