"""The block-diffusion attention kernels alone, at sdar-solo-4k's layer: 2 x 8,192
rows ([x_0 ; x_t] of 4,096 data tokens), 32 query heads over 4, head 128,
blocks of 4, positions 0..4,095 twice.

    chiprun -- python experiments/sdar_attention_check.py [--blocks 256,512,1024] [--tiny]

- the merged entry on the kernels against the XLA core under the same mask as an
  explicit array, both in bfloat16 and both against the XLA core in float32 at
  the highest precision, at ONE batch row and one key/value head's first two
  query heads (the core's float32 scores of all 32 heads are 17 GB): output and
  dq, dk, dv, relative to the float32 values' norm;
- one forward and one forward + backward of the kernels at the whole layer's
  shape, over ``--blocks`` (``pallas_attention.BD_BLOCK`` is what ships), with
  the tiles each visits (``bd_tiles``) and the share of the bf16 peak the kept
  pairs come to; the XLA core's time at the small shape beside them.

One JSON line each, all in ``chiprun_out/sdar_attention_check.jsonl``. ``--tiny``
interprets the kernels on the CPU at a small size (paths, not speeds)."""

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
from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

PEAK_BF16 = 197e12  # benchmark/flops.py, TPU v5 lite


def timed(fn, *args, iters: int = 5) -> float:
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / iters * 1e3


def rel(got, want) -> float:
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--blocks", default="256,512,1024")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="chiprun_out/sdar_attention_check.jsonl")
    args = ap.parse_args()
    b, t, h, hkv, d, bd = (1, 256, 4, 2, 128, 4) if args.tiny else (2, 8192, 32, 4, 128, 4)
    dev = jax.devices()[0]
    rows = []

    def say(rec):
        rec["device"] = dev.device_kind
        rows.append(rec)
        print(json.dumps(rec), flush=True)

    positions = jnp.tile(jnp.arange(t // 2), 2)
    rotary = A.Rotary(base=1e6, layout="half", positions=positions)

    def entry(heads, kv_heads, impl):
        def f(q, k, v, cot):
            A.set_attention_impl(impl)
            try:
                out, vjp = jax.vjp(lambda q, k, v: A.attention_merged(
                    q, k, v, heads, kv_heads, rotary=rotary, block_diffusion=bd), q, k, v)
                return (out, *vjp(cot))
            finally:
                A.set_attention_impl("auto")
        return jax.jit(f)

    def inputs(batch, heads, kv_heads, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        shapes = [(batch, t, heads * d), (batch, t, kv_heads * d), (batch, t, kv_heads * d), (batch, t, heads * d)]
        return [jax.random.normal(k, s, jnp.float32).astype(dtype) for k, s in zip(ks, shapes)]

    # -- against the XLA core, one batch row, two query heads over one key/value head --
    small16 = inputs(1, 2, 1, jnp.bfloat16)
    small32 = [x.astype(jnp.float32) for x in small16]
    with jax.default_matmul_precision("highest"):
        want = entry(2, 1, "xla")(*small32)
    kernel = entry(2, 1, "flash")(*small16)
    core = entry(2, 1, "xla")(*small16)
    names = ("out", "dq", "dk", "dv")
    say({"what": "kernel_against_float32", "shape": [1, t, 2, 1, d],
         **{n: rel(x, y) for n, x, y in zip(names, kernel, want)}})
    say({"what": "xla_core_bf16_against_float32", "shape": [1, t, 2, 1, d],
         **{n: rel(x, y) for n, x, y in zip(names, core, want)}})
    say({"what": "kernel_against_xla_core_bf16", "shape": [1, t, 2, 1, d],
         **{n: rel(x, y) for n, x, y in zip(names, kernel, core)}})
    say({"what": "xla_core_ms", "shape": [1, t, 2, 1, d], "fwd_bwd_ms": timed(entry(2, 1, "xla"), *small16),
         "kernel_fwd_bwd_ms_same_shape": timed(entry(2, 1, "flash"), *small16)})
    del want, kernel, core, small32

    # -- the whole layer's call, over block sizes --
    q, k, v, cot = inputs(b, h, hkv, jnp.bfloat16)
    pairs = (t // 2) ** 2 + (t // 2) * bd
    for block in [int(x) for x in args.blocks.split(",")]:
        if args.tiny and block > t // 2:
            continue
        pa.BD_BLOCK = block
        jax.clear_caches()  # the jitted halves resolved their blocks when they were traced
        fwd = jax.jit(lambda q, k, v: A.attention_merged(q, k, v, h, hkv, rotary=rotary, block_diffusion=bd))
        A.set_attention_impl("flash")
        try:
            fwd_ms = timed(fwd, q, k, v)
            both_ms = timed(entry(h, hkv, "flash"), q, k, v, cot)
        finally:
            A.set_attention_impl("auto")
        tiles = pa.bd_tiles(t, bd, *pa.choose_blocks(t, t, d, jnp.bfloat16, None, True, bd))
        say({"what": "kernels_whole_layer", "shape": [b, t, h, hkv, d], "block": block,
             "blocks_chosen": list(pa.choose_blocks(t, t, d, jnp.bfloat16, None, True, bd)),
             "fwd_ms": fwd_ms, "fwd_bwd_ms": both_ms, "tiles": tiles,
             "tiles_share": (tiles["fwd"] + tiles["bwd"]) / (tiles["causal_fwd"] + tiles["causal_bwd"]),
             # 4 D forward and 10 D backward a kept pair a head: the passes beside the kernels are in the time
             "kept_pairs_share_of_peak": 14.0 * d * b * h * pairs / (both_ms / 1e3) / PEAK_BF16})
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        fh.writelines(json.dumps(r) + "\n" for r in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
