"""The merged-layout attention entry against the path it replaces, on the chip.

``ops/attention.attention_merged`` (q, k, v as the projections leave them,
``[B, T, H * D]``; the rotary pairs turned by a lane roll) against
``merge_heads(attention_core(rope(split_heads(q)), rope(split_heads(k)),
split_heads(v)))``, both compiled for the chip, at the shapes of the three cells
that take the entry: values and the gradients of q, k and v (the largest
absolute difference, beside the same against the XLA core in float32 on a short
sequence, which says which side is nearer the mathematics), and the time of one
forward + backward of each. One JSON line a shape, all in
``chiprun_out/merged_attention_check.jsonl``:

    chiprun -- python experiments/merged_attention_check.py

On the CPU the kernels are interpreted (paths, not speeds): give ``--tiny``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.ops import attention as A
from distributedvolunteercomputing_tpu.utils import traced

# (name, B, T, H, Hkv, D, window, rotary): one layer kind of each cell
SHAPES = [
    ("laguna.sliding", 4, 8192, 64, 8, 128, 512, A.Rotary(layout="half")),
    ("laguna.full", 4, 8192, 48, 8, 128, None, A.Rotary(
        layout="half", rotary_dim=64, scale=1.4158883,
        inv_freq=A.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0))),
    ("smallthinker.sliding", 2, 16384, 28, 4, 128, 4096, A.Rotary(base=1.5e6, layout="half")),
    ("smallthinker.global", 2, 16384, 28, 4, 128, None, None),
    ("olmoe", 4, 4096, 16, 16, 128, None, A.Rotary(layout="half")),
]


def sides(h, hkv, window, rotary):
    def merged(q, k, v):
        return A.attention_merged(q, k, v, h, hkv, causal=True, window=window, rotary=rotary)

    def by_head(q, k, v):
        qh, kh, vh = A.split_heads(q, h), A.split_heads(k, hkv), A.split_heads(v, hkv)
        if rotary is not None:
            qh, kh = A.rope(qh, **rotary._asdict()), A.rope(kh, **rotary._asdict())
        return A.merge_heads(A.attention_core(qh, kh, vh, causal=True, window=window))

    return merged, by_head


def fwd_bwd(fn):
    def run(q, k, v, cot):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out, *vjp(cot))

    return jax.jit(run)


def timed(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="T = 256, two sequences: for the CPU")
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()
    os.makedirs("chiprun_out", exist_ok=True)
    seen = []
    listening = traced.subscribe(
        lambda kind, said: kind == "attention_core" and seen.append("{impl}:{layout}/{rotary}".format(**said)))
    if args.tiny:
        A.set_attention_impl("flash")
    with open("chiprun_out/merged_attention_check.jsonl", "w") as out_file:
        for name, b, t, h, hkv, d, window, rotary in SHAPES:
            if args.tiny:
                b, t, window = 2, 256, None if window is None else 96
            keys = jax.random.split(jax.random.PRNGKey(len(name)), 4)
            shapes = [(b, t, h * d), (b, t, hkv * d), (b, t, hkv * d), (b, t, h * d)]
            q, k, v, cot = (jax.random.normal(key, s, jnp.bfloat16) for key, s in zip(keys, shapes))
            merged, by_head = sides(h, hkv, window, rotary)
            del seen[:]
            got, want = fwd_bwd(merged)(q, k, v, cot), fwd_bwd(by_head)(q, k, v, cot)
            line = {"shape": name, "dims": [b, t, h, hkv, d, window], "traced": list(seen)}
            for part, x, y in zip(("out", "dq", "dk", "dv"), got, want):
                x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
                line[part] = {"max_abs_diff": float(jnp.max(jnp.abs(x32 - y32))),
                              "max_abs": float(jnp.max(jnp.abs(y32)))}
            # both sides against float32 mathematics on the first 512 positions of one sequence
            n = min(t, 512)
            w = None if window is None else min(window, n)
            short = [a[:1, :n] for a in (q, k, v, cot)]
            m_short, h_short = sides(h, hkv, w, rotary)
            A.set_attention_impl("xla")
            exact = fwd_bwd(h_short)(*(a.astype(jnp.float32) for a in short))
            A.set_attention_impl("flash" if args.tiny else "auto")
            for side, fn in (("merged", m_short), ("by_head", h_short)):
                res = fwd_bwd(fn)(*short)
                line[f"{side}_against_float32"] = [
                    float(jnp.max(jnp.abs(x.astype(jnp.float32) - y))) for x, y in zip(res, exact)]
            if not args.tiny:
                line["ms_merged"] = timed(fwd_bwd(merged), (q, k, v, cot), args.iters)
                line["ms_by_head"] = timed(fwd_bwd(by_head), (q, k, v, cot), args.iters)
            print(json.dumps(line), flush=True)
            out_file.write(json.dumps(line) + "\n")
    listening.close()


if __name__ == "__main__":
    main()
