"""Both attention cores on the chip: the XLA core against the Pallas kernel at
a list of block geometries, alone (forward + backward) and inside a
rematerialised layer (qkv projection, heads, core, output projection, scanned).

    chiprun -- python experiments/attention_sweep.py            # the PR 27 sweep
    python experiments/attention_sweep.py --shapes 2,2,128,64 --blocks 64x64 --iters 1

One JSON line per measurement on stdout, all of them in
``chiprun_out/attention_sweep.json``. The routing constants in
ops/attention.py and ``PREFERRED_BLOCK`` in ops/pallas_attention.py come from
this script's readings (PERF.md, Findings of PR 27). Every line names the
device; a CPU run (kernel interpreted) checks the paths, not the speeds.
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

from distributedvolunteercomputing_tpu.models import common
from distributedvolunteercomputing_tpu.ops import attention
from distributedvolunteercomputing_tpu.ops.pallas_attention import flash_attention

DEFAULT_SHAPES = "16,16,1024,64;16,10,1024,64;64,16,256,64;32,16,512,64;8,16,2048,64"
DEFAULT_BLOCKS = "128x128;256x256;512x256;512x512;1024x512;1024x1024"


def _time(fn, args, iters: int) -> float:
    """Milliseconds a call, after two warm-up calls, synced at the end."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _core(impl, causal):
    if impl == "xla":
        attention.set_attention_impl("xla")
        return lambda q, k, v: attention.attention_core_local(q, k, v, causal=causal)
    bq, bk = impl
    return lambda q, k, v: flash_attention(q, k, v, causal, bq, bk)


def core_fwd_bwd(impl, causal):
    core = _core(impl, causal)

    def f(q, k, v, cot):
        def loss(q, k, v):
            return jnp.sum(core(q, k, v).astype(jnp.float32) * cot)

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    return jax.jit(f)


def core_fwd(impl, causal):
    return jax.jit(_core(impl, causal))


def remat_layers(impl, causal, n_heads, n_layers):
    """value_and_grad through ``n_layers`` scanned, rematerialised attention
    sublayers: what the step does around the core (projections, head
    layouts, the recomputed forward)."""
    core = _core(impl, causal)

    def layer(x, w):
        qkv = x @ w["qkv"].astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        heads = [attention.split_heads(t, n_heads) for t in (q, k, v)]
        out = attention.merge_heads(core(*heads))
        return x + out @ w["out"].astype(x.dtype)

    def f(x, ws):
        def loss(x, ws):
            body = common.remat_layer(lambda h, w: (layer(h, w), None), n_layers)  # as scan_blocks
            h, _ = jax.lax.scan(body, x, ws)
            return jnp.sum(h.astype(jnp.float32))

        return jax.value_and_grad(loss, argnums=(0, 1))(x, ws)

    return jax.jit(f)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=DEFAULT_SHAPES, help="B,H,T,D;...")
    ap.add_argument("--blocks", default=DEFAULT_BLOCKS, help="BQxBK;...")
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--noncausal", action="store_true", help="also time causal=False (core only)")
    ap.add_argument("--out", default="chiprun_out/attention_sweep.json")
    args = ap.parse_args()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind}
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[args.dtype]
    rows = []

    def emit(**rec):
        rec.update(device)
        rows.append(rec)
        print(json.dumps(rec), flush=True)

    for spec in args.shapes.split(";"):
        b, h, t, d = (int(x) for x in spec.split(","))
        keys = jax.random.split(jax.random.PRNGKey(t + h), 6)
        q, k, v, cot = (jax.random.normal(kk, (b, h, t, d), dtype) for kk in keys[:4])
        x = jax.random.normal(keys[4], (b, t, h * d), dtype)
        ws = {
            "qkv": jax.random.normal(keys[5], (args.layers, h * d, 3 * h * d), jnp.float32) * 0.02,
            "out": jax.random.normal(keys[5], (args.layers, h * d, h * d), jnp.float32) * 0.02,
        }
        impls = ["xla"] + [
            tuple(int(n) for n in blk.split("x")) for blk in args.blocks.split(";")
            if int(blk.split("x")[0]) <= t and int(blk.split("x")[1]) <= t
        ]
        ref = None
        for causal in ([True, False] if args.noncausal else [True]):
            for impl in impls:
                name = impl if impl == "xla" else f"flash{impl[0]}x{impl[1]}"
                base = dict(shape=[b, h, t, d], dtype=args.dtype, causal=causal, impl=name)
                try:
                    fb = core_fwd_bwd(impl, causal)
                    _, grads = jax.block_until_ready(fb(q, k, v, cot))
                    if causal:
                        if impl == "xla":
                            ref = grads
                        else:
                            base["grad_max_err"] = max(
                                float(jnp.max(jnp.abs(a.astype(jnp.float32) - r.astype(jnp.float32))))
                                for a, r in zip(grads, ref)
                            )
                    emit(what="core_fwd_bwd_ms", ms=_time(fb, (q, k, v, cot), args.iters), **base)
                    emit(what="core_fwd_ms", ms=_time(core_fwd(impl, causal), (q, k, v), args.iters), **base)
                    if causal:
                        fn = remat_layers(impl, causal, h, args.layers)
                        emit(what="remat_layer_ms", layers=args.layers,
                             ms=_time(fn, (x, ws), args.iters) / args.layers, **base)
                except Exception as e:  # noqa: BLE001 — a geometry the compiler refuses is a finding
                    emit(what="error", error=f"{type(e).__name__}: {str(e)[-400:]}", **base)
    attention.set_attention_impl("auto")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
