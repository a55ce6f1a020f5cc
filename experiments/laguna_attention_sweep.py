"""The windowed and the grouped-query attention kernel on the chip, at
laguna-solo-8k's shapes: forward + backward of ``flash_attention`` over 8
key/value heads, full (48 query heads) and windowed (64, window 512), at a
list of block geometries, with the share of the roofline each reaches
(benchmark/flops_laguna.py's counts), and the kernel against the XLA core at a
shape the XLA core can hold. A windowed geometry whose edges fall corner to
corner through its tiles (``pallas_attention.strip_form``) is timed once a
``--strips`` entry: 0 is the masked whole tiles of PR 33, 128, 256 and 512 the
rows of a strip (``WINDOW_STRIP``), forward and backward apart.

    chiprun -- python experiments/laguna_attention_sweep.py
    chiprun -- python experiments/laguna_attention_sweep.py --window 4096 --seq 16384 --batch 2 --heads 28 --kv-heads 4 \
        --full-heads 0 --blocks 1024x1024 --check-seq 4096 --check-window 1024 --check-blocks 256x256   # SmallThinker's windowed layer
    python experiments/laguna_attention_sweep.py --batch 1 --seq 256 --check-seq 128 --iters 1   # paths, on the CPU

One JSON line per measurement, all of them in ``chiprun_out/laguna_attention_sweep.json``.
That ``choose_blocks`` keeps a windowed call's blocks as wide as the window
comes from this script's readings (PERF.md, Findings of PR 33), and so does
``WINDOW_STRIP`` (PERF.md, Findings of PR 73)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmark import flops_laguna
from distributedvolunteercomputing_tpu.ops import attention, pallas_attention
from distributedvolunteercomputing_tpu.ops.pallas_attention import flash_attention


def _time(fn, args, iters: int) -> float:
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def fwd_bwd(core):
    def f(q, k, v, cot):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(core(q, k, v).astype(jnp.float32) * cot), argnums=(0, 1, 2))(q, k, v)

    return jax.jit(f)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--check-seq", type=int, default=2048)
    ap.add_argument("--window", type=int, default=512)
    ap.add_argument("--blocks", default="128x128;256x256;512x256;256x512;512x512;1024x1024")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--heads", type=int, default=64, help="query heads of the windowed call")
    ap.add_argument("--full-heads", type=int, default=48, help="query heads of the full call; 0 leaves it out")
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--strips", default="0;128;256;512", help="rows of a strip where the strips engage; 0: whole tiles")
    ap.add_argument("--check-window", type=int, default=None, help="the check's window (the timed one)")
    ap.add_argument("--check-blocks", default=None, help="the check's blocks, e.g. 256x256 (the chosen ones)")
    ap.add_argument("--out", default="chiprun_out/laguna_attention_sweep.json")
    args = ap.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind}
    on_tpu = dev.platform == "tpu"
    peak = 197e12
    rows = []

    def emit(**rec):
        rec.update(device)
        rows.append(rec)
        print(json.dumps(rec), flush=True)

    d, kv = 128, args.kv_heads
    shipped = pallas_attention.WINDOW_STRIP
    whole_tiles = lambda *a: None  # noqa: E731  ``strip_form`` of a call that keeps PR 33's masked whole tiles

    def with_strips(rows: int, fn):
        """``fn()`` traced with strips of ``rows`` rows (0: whole tiles); the kernels read both at trace time."""
        form = pallas_attention.strip_form
        try:
            pallas_attention.WINDOW_STRIP = rows or shipped
            if not rows:
                pallas_attention.strip_form = whole_tiles
            return fn()
        finally:
            pallas_attention.WINDOW_STRIP, pallas_attention.strip_form = shipped, form

    calls = [(args.heads, args.window)] + ([(args.full_heads, None)] if args.full_heads else [])
    for heads, window in calls:
        # the kernel against the XLA core, where the XLA core's scores fit
        t = args.check_seq
        win_c = window if args.check_window is None or window is None else args.check_window
        blk_c = (None, None) if args.check_blocks is None else tuple(int(x) for x in args.check_blocks.split("x"))
        keys = jax.random.split(jax.random.PRNGKey(heads), 4)
        q = jax.random.normal(keys[0], (1, heads, t, d), jnp.bfloat16)
        k, v = (jax.random.normal(kk, (1, kv, t, d), jnp.bfloat16) for kk in keys[1:3])
        cot = jax.random.normal(keys[3], q.shape, jnp.bfloat16)
        attention.set_attention_impl("xla")
        want = fwd_bwd(lambda q, k, v: attention.attention_core_local(q, k, v, True, None, win_c))(q, k, v, cot)
        got = fwd_bwd(lambda q, k, v: flash_attention(q, k, v, True, *blk_c, None, win_c))(q, k, v, cot)
        errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))]
        bq_c, bk_c = (blk_c if blk_c[0] else pallas_attention.choose_blocks(t, t, d, q.dtype, win_c))
        emit(check="kernel against the XLA core", heads=heads, kv_heads=kv, T=t, window=win_c,
             form=pallas_attention.strip_form(t, win_c, bq_c, bk_c),
             max_abs_err={"loss": errs[0], "dq": errs[1], "dk": errs[2], "dv": errs[3]})

        t, b = args.seq, args.batch
        q = jax.random.normal(keys[0], (b, heads, t, d), jnp.bfloat16)
        k, v = (jax.random.normal(kk, (b, kv, t, d), jnp.bfloat16) for kk in keys[1:3])
        cot = jax.random.normal(keys[3], q.shape, jnp.bfloat16)
        pairs = flops_laguna.causal_pairs(t, window or 0)
        flops = {"fwd": 4.0 * d * b * heads * pairs, "bwd": 10.0 * d * b * heads * pairs}
        share = lambda which, ms: 100 * flops[which] / peak / (ms / 1e3) if on_tpu else None  # noqa: E731
        for blk in args.blocks.split(";"):
            bq, bk = (int(x) for x in blk.split("x"))
            if bq > t or bk > t:
                continue
            strips = [None]  # a geometry whose tiles no strip can cut, and the full call: as they are
            if window is not None:
                strips = [r for r in (int(x) for x in args.strips.split(";"))
                          if not r or with_strips(r, lambda: pallas_attention.strip_form(t, window, bq, bk))] or [None]
            for strip_rows in strips:
                # a function of its own a reading: ``jax.jit`` keeps one trace a function, and the strips are read at trace time
                core = lambda q, k, v: flash_attention(q, k, v, True, bq, bk, None, window)  # noqa: E731
                try:
                    ms_f, ms_fb, tiles = with_strips(strip_rows or 0, lambda: (
                        _time(jax.jit(core), (q, k, v), args.iters), _time(fwd_bwd(core), (q, k, v, cot), args.iters),
                        None if window is None else pallas_attention.window_tiles(t, window, bq, bk)))
                    emit(heads=heads, kv_heads=kv, T=t, batch=b, window=window, blocks=blk, strip_rows=strip_rows,
                         form=tiles and tiles["form"],
                         computed_over_band=tiles and round(tiles["fwd"] / tiles["band"], 4),
                         fwd_ms=ms_f, bwd_ms=ms_fb - ms_f, fwd_bwd_ms=ms_fb,
                         fwd_roofline=share("fwd", ms_f), bwd_roofline=share("bwd", ms_fb - ms_f),
                         fwd_bwd_roofline=100 * (flops["fwd"] + flops["bwd"]) / peak / (ms_fb / 1e3) if on_tpu else None)
                except Exception as e:  # noqa: BLE001 — a geometry the compiler refuses is a reading
                    emit(heads=heads, window=window, blocks=blk, strip_rows=strip_rows, error=repr(e)[:300])
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
