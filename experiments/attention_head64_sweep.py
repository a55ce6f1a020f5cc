"""A head of 64 as half of a 128-lane block: the flash kernels on the merged
layout against the same kernels by head, alone, on the chip.

``ops/pallas_attention``'s two kernels read ``[B, T, H * 64]`` in blocks of 128
lanes, two heads side by side (``heads_a_block``), each head run in turn inside
a grid step. A half can be taken two ways, and this script times both beside
the by-head call (``[B, H, T, 64]``, a block's 64 lanes padded to 128 in VMEM
and in HBM) they replace:

- ``zeroed`` (what ``ops/pallas_attention`` ships): no slice. The other head's
  lanes are zeroed in q (forward) or in k and v (backward), every product runs
  128 lanes deep or wide, and the result's lanes are chosen by
  ``jnp.where(lane < 64, ..)`` (``ops/lanes.by_head``).
- ``sliced`` (carried here: ``_sliced_fwd_kernel`` / ``_sliced_bwd_kernel``, the
  causal one-head bodies on 64 lanes): static lane slices of every load and
  store, as ``pallas_attention._delta_kernel`` takes them. Slower on the chip at
  every shape (``experiments/results/attention_head64_sweep.jsonl``), which is
  why it is here and not there. A VIEW of a ref's lanes (``ref.at[:, 64:128]``)
  is refused by Mosaic ("Slice shape along dimension 2 must be aligned to
  tiling (128), but is 64"); slices in the loads and stores themselves compile.

Forward alone and forward + backward (delta included: XLA's reduce by head, the
``dvc_attn_delta`` kernel merged), bfloat16, causal at the shapes of
``medium-solo`` ``[16, 16, 1024, 64]``, of a chip of ``large-solo-4chip``
``[8, 10, 1024, 64]`` and of ``lfm2-solo-8k`` ``[4, 32 over 8, 8192, 64]``. LFM2's
grouped key/value heads have no pair form yet: its merged rows run the 32
query heads against key/value heads REPEATED to 32 (what a pair kernel reads
today; a grouped pair would fetch a quarter of it), beside the grouped by-head
call its cell runs. Then NOT causal (``*.full``; the shipped form only, the
sliced bodies here are causal ones), as ``common.fused_qkv_attention`` calls
the pair kernels for BERT and ViT, which no cell runs: BERT's layer ``[8, 12,
512, 64]``, ``medium-solo``'s shape with every key seen, and ViT's 197 patches,
one padded block. Each merged result is held to the by-head one (largest
absolute difference of o, dq, dk, dv). One JSON line a shape and form, all in
``chiprun_out/attention_head64_sweep.jsonl``:

    chiprun -- python experiments/attention_head64_sweep.py

On the CPU the kernels are interpreted (paths, not speeds): give ``--tiny``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedvolunteercomputing_tpu.ops import attention as A
from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

# (name, B, H, Hkv, T, D, causal)
SHAPES = [
    ("medium-solo", 16, 16, 16, 1024, 64, True),
    ("large-solo-4chip.shard", 8, 10, 10, 1024, 64, True),
    ("lfm2-solo-8k", 4, 32, 8, 8192, 64, True),
    ("bert-512.full", 8, 12, 12, 512, 64, False),
    ("medium-solo.full", 16, 16, 16, 1024, 64, False),
    ("vit-197.full", 16, 12, 12, 197, 64, False),
]


def by_head(h, hkv, interpret, causal):
    """(forward, forward + backward) of the by-head kernels on merged inputs'
    heads: q, k, v arrive [B, H, T, D] (the split is not timed)."""
    def blocks(q, k):
        return pa._resolve(q, k, None, None, interpret, causal)[:2]

    def fwd(q, k, v, cot):
        return pa._flash_forward(q, k, v, causal, *blocks(q, k), interpret)[0]

    def both(q, k, v, cot):
        bq, bk = blocks(q, k)
        out, lse = pa._flash_forward(q, k, v, causal, bq, bk, interpret)
        return (out, *pa._flash_backward(causal, bq, bk, interpret, (q, k, v, out, lse), cot))

    return fwd, both


def merged(h, halves, interpret, causal):
    """The same of the pair kernels on [B, T, H * D], a half taken ``halves``' way."""
    forward, backward = (pa._flash_forward, pa._flash_backward) if halves == "zeroed" else (_sliced_forward, _sliced_backward)

    def blocks(q, k):
        return pa._resolve(q, k, None, None, interpret, causal, None, (h, h))[:2]

    def fwd(q, k, v, cot):
        return forward(q, k, v, causal, *blocks(q, k), interpret, None, (h, h))[0]

    def both(q, k, v, cot):
        bq, bk = blocks(q, k)
        out, lse = forward(q, k, v, causal, bq, bk, interpret, None, (h, h))
        return (out, *backward(causal, bq, bk, interpret, (q, k, v, out, lse), cot, None, (h, h)))

    return fwd, both


# -- the form not taken: static lane slices (causal, whole blocks: the sweep's shapes) ----------------


def _sliced_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, bq, bk, n_k, d):
    """``pallas_attention._fwd_kernel``'s causal one-head body, once a head of
    the block, on that head's 64 lanes of every ref."""
    iq = pl.program_id(2)
    rows = []
    for h in range(q_ref.shape[-1] // d):
        at = slice(h * d, (h + 1) * d)
        m_scr[...] = jnp.full_like(m_scr, pa.NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q = q_ref[:, at]

        def step(jk, masked):
            start = pl.multiple_of(jk * bk, bk)
            kblk, vblk = k_ref[pl.ds(start, bk), at], v_ref[pl.ds(start, bk), at]
            s = pa._dot(q, kblk, pa._NT) * scale
            if masked:
                col = jk * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = pa._mask_scores(s, iq * bq, col, 0, causal=True, tk_valid=n_k * bk, ragged=False)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new[:, 0:1])
            corr = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_scr[...] = m_new
            acc_scr[...] = acc_scr[...] * corr[:, 0:1] + pa._dot(p.astype(vblk.dtype), vblk, pa._NN)

        n_full = jnp.minimum(n_k, (iq * bq) // bk)
        pa._loop(0, n_full, lambda jk: step(jk, False))
        pa._loop(n_full, jnp.minimum(n_k, ((iq + 1) * bq - 1) // bk + 1), lambda jk: step(jk, True))
        l = l_scr[...]
        o_ref[:, at] = (acc_scr[...] / l[:, 0:1]).astype(o_ref.dtype)
        rows.append(jnp.transpose(m_scr[...] + jnp.log(l))[0:1, :])
    lse_ref[...] = jnp.stack(rows)


def _sliced_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                       dq_scr, dk_scr, dv_scr, *, scale, bq, bk, n_q, n_k, d):
    """``pallas_attention._bwd_kernel``'s causal one-head body, the same way."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    for h in range(k_ref.shape[-1] // d):
        at = slice(h * d, (h + 1) * d)
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        kblk, vblk = k_ref[:, at], v_ref[:, at]

        def step(iq, masked):
            start = pl.multiple_of(iq * bq, bq)
            qblk, doblk = q_ref[pl.ds(start, bq), at], do_ref[pl.ds(start, bq), at]
            s_t = pa._dot(kblk, qblk, pa._NT) * scale
            if masked:
                kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
                s_t = pa._mask_scores(s_t, iq * bq, kpos, 1, causal=True, tk_valid=n_k * bk, ragged=False)
            p_t = jnp.exp(s_t - lse_ref[h, iq])
            dv_scr[...] += pa._dot(p_t.astype(doblk.dtype), doblk, pa._NN)
            ds_t = (p_t * (pa._dot(vblk, doblk, pa._NT) - delta_ref[h, iq])).astype(qblk.dtype)
            dk_scr[...] += pa._dot(ds_t, qblk, pa._NN)
            dq_scr[pl.ds(start, bq), at] += pa._dot(ds_t, kblk, pa._TN)

        n_masked_end = jnp.minimum(n_q, ((ik + 1) * bk + bq - 2) // bq)
        pa._loop((ik * bk) // bq, n_masked_end, lambda iq: step(iq, True))
        pa._loop(n_masked_end, n_q, lambda iq: step(iq, False))
        dk_ref[:, at] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[:, at] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(ik == n_k - 1)
    def finalize():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _sliced_forward(q, k, v, causal, bq, bk, interpret, window, heads):
    b, t, hd = q.shape
    h, d, per = heads[0], hd // heads[0], pa.LANES // (hd // heads[0])
    n = t // bq
    block = lambda rows, where: pl.BlockSpec((None, rows, per * d), where)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_sliced_fwd_kernel, scale=d ** -0.5, bq=bq, bk=bk, n_k=t // bk, d=d),
        grid=(b, h // per, n),
        in_specs=[block(bq, lambda i, j, iq: (i, iq, j)), block(t, lambda i, j, iq: (i, 0, j)),
                  block(t, lambda i, j, iq: (i, 0, j))],
        out_specs=[block(bq, lambda i, j, iq: (i, iq, j)),
                   pl.BlockSpec((None, per, None, 1, bq), lambda i, j, iq: (i, j, iq, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct((b, h, n, 1, bq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, pa.LANES), jnp.float32), pltpu.VMEM((bq, pa.LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pa._compiler_params(interpret, ("parallel",) * 3, pa.vmem_bytes(t, t, d, q.dtype, bq, bk)),
        interpret=interpret, name="dvc_flash_fwd",
    )(q, k, v)


def _sliced_backward(causal, bq, bk, interpret, residuals, do, window, heads):
    q, k, v, out, lse = residuals
    b, t, hd = q.shape
    h, d, per = heads[0], hd // heads[0], pa.LANES // (hd // heads[0])
    n_q, n_k = t // bq, t // bk
    delta = pa._delta_merged(do, out, h, bq, interpret)
    block = lambda rows, where: pl.BlockSpec((None, rows, per * d), where)  # noqa: E731
    whole, part = block(t, lambda i, j, ik: (i, 0, j)), block(bk, lambda i, j, ik: (i, ik, j))
    rows = pl.BlockSpec((None, per, n_q, 1, bq), lambda i, j, ik: (i, j, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_sliced_bwd_kernel, scale=d ** -0.5, bq=bq, bk=bk, n_q=n_q, n_k=n_k, d=d),
        grid=(b, h // per, n_k),
        in_specs=[whole, part, part, whole, rows, rows],
        out_specs=[whole, part, part],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((t, per * d), jnp.float32), pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pa._compiler_params(
            interpret, ("parallel", "parallel", "arbitrary"), pa.vmem_bytes(t, t, d, q.dtype, bq, bk)),
        interpret=interpret, name="dvc_flash_bwd",
    )(q, k, v, do, lse, delta)


def timed(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="T = 256, two sequences, four heads: for the CPU")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    interpret = jax.default_backend() != "tpu"
    os.makedirs("chiprun_out", exist_ok=True)
    lines = []
    for name, b, h, hkv, t, d, causal in SHAPES:
        if args.tiny:
            b, t, h, hkv = 2, 256 if t % 128 == 0 else 197, 4, max(1, 4 * hkv // h)
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, cot = (jax.random.normal(k, (b, t, h * d), jnp.bfloat16) for k in keys[:2])
        k, v = (jax.random.normal(kk, (b, t, hkv * d), jnp.bfloat16) for kk in keys[2:])
        # the by-head call its cell runs: grouped where the model's heads are
        heads_args = (A.split_heads(q, h), A.split_heads(k, hkv), A.split_heads(v, hkv), A.split_heads(cot, h))
        fwd, both = (jax.jit(f) for f in by_head(h, hkv, interpret, causal))
        want = [A.merge_heads(x) for x in both(*heads_args)]
        if hkv != h:  # a group's dk and dv, summed as the merged side's repeated heads are below
            want[2:] = [x.reshape(b, t, hkv, 1, d) for x in want[2:]]
        base = {"shape": name, "B": b, "H": h, "Hkv": hkv, "T": t, "D": d, "causal": causal,
                "device": jax.devices()[0].device_kind, "iters": args.iters}
        line = dict(base, form="by_head", fwd_ms=timed(fwd, heads_args, args.iters),
                    fwd_bwd_ms=timed(both, heads_args, args.iters))
        print(json.dumps(line), flush=True)
        lines.append(line)
        group = h // hkv
        rep = [q, *(jnp.repeat(x.reshape(b, t, hkv, d), group, axis=2).reshape(b, t, h * d) for x in (k, v)), cot]
        for halves in ("zeroed", "sliced") if causal else ("zeroed",):
            line = dict(base, form=f"merged.{halves}", kv_heads_repeated=group > 1)
            try:
                fwd, both = (jax.jit(f) for f in merged(h, halves, interpret, causal))
                got = list(both(*rep))
                if group > 1:
                    got[2:] = [x.astype(jnp.float32).reshape(b, t, hkv, group, d).sum(3, keepdims=True) for x in got[2:]]
                line["max_abs_diff"] = {
                    n: float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
                    for n, x, y in zip(("o", "dq", "dk", "dv"), got, want)}
                line.update(fwd_ms=timed(fwd, rep, args.iters), fwd_bwd_ms=timed(both, rep, args.iters))
            except Exception as e:  # noqa: BLE001 — what the chip's compiler refuses is a finding
                line["error"] = f"{type(e).__name__}: {str(e)[:400]}"
            print(json.dumps(line), flush=True)
            lines.append(line)
    with open("chiprun_out/attention_head64_sweep.jsonl", "w") as f:
        f.writelines(json.dumps(ln) + "\n" for ln in lines)


if __name__ == "__main__":
    main()
