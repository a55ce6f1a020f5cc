#!/usr/bin/env python
"""Ways to write one chip's share of the by-head qkv projection, timed.

    chiprun -- python experiments/qkv_projection_sweep.py

Until PR 71 ``models/common.qkv_heads`` divided the fused projection by head
over ``tp``: each chip ran ``[B, T, d] x [d, 3, H/tp, hd]`` feeding the
attention kernel as three ``[B, H/tp, T, hd]`` (the shipped form since is
three merged ``[B, T, d] x [d, d/tp]`` products and the pair kernels; what
this sweep read of by head, 47% of peak against the merged product's 90%, is
why: PERF.md, Findings of PR 66 and 71). This times the forms the by-head
product can take, on ONE chip at a shard's shape (``large-solo-4chip``:
B 16, T 1,024, d 1,280, 10 of 20 heads, hd 64), inside a rematerialised,
scanned layer (projection, kernel, merge, a row-parallel product back to d),
forward and backward: milliseconds a layer, one JSON line a form, all in
``chiprun_out/qkv_projection_sweep.json``. On the CPU it checks that the
forms agree, not how fast they are.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.ops.attention import attention_core, merge_heads


def stacked(x, w, b):
    """One einsum to [3, B, H, T, hd]; q, k, v are its leading slices."""
    qkv = jnp.einsum("btd,dshe->sbhte", x, w) + b[:, None, :, None, :]
    return qkv[0], qkv[1], qkv[2]


def three(x, w, b):
    """An einsum each for q, k and v, straight to [B, H, T, hd]."""
    return tuple(
        jnp.einsum("btd,dhe->bhte", x, w[:, s]) + b[s][None, :, None, :] for s in range(3)
    )


def token_major(x, w, b):
    """The product in its natural order [B, T, 3, H, hd], bias, then a slice
    and the head transpose each: the fused form's operations on a shard."""
    qkv = jnp.einsum("btd,dshe->btshe", x, w) + b
    return tuple(qkv[:, :, s].transpose(0, 2, 1, 3) for s in range(3))


FORMS = {"stacked": stacked, "three": three, "token_major": token_major}


def make_step(form):
    def layer(x, p):
        w, b, wo = p
        q, k, v = form(x, w.astype(x.dtype), b.astype(x.dtype))
        attn = merge_heads(attention_core(q, k, v, causal=True))
        return x + jnp.dot(attn, wo.astype(x.dtype)), None

    def loss(params, x):
        y, _ = jax.lax.scan(jax.checkpoint(layer), x, params)
        return jnp.mean(y.astype(jnp.float32) ** 2)

    return jax.jit(jax.value_and_grad(loss))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="16,1024,1280,10,64", help="B,T,d,heads on the chip,hd")
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()
    b_, t, d, h, hd = (int(v) for v in args.shape.split(","))
    on_tpu = jax.devices()[0].platform == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    params = (
        jax.random.normal(ks[0], (args.layers, d, 3, h, hd), jnp.float32) * 0.02,
        jax.random.normal(ks[1], (args.layers, 3, h, hd), jnp.float32) * 0.02,
        jax.random.normal(ks[2], (args.layers, h * hd, d), jnp.float32) * 0.02,
    )
    x = jax.random.normal(ks[3], (b_, t, d), dtype)
    dev = jax.devices()[0]
    lines, first_grads = [], None
    for name, form in FORMS.items():
        step = make_step(form)
        loss, grads = jax.block_until_ready(step(params, x))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = step(params, x)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) * 1e3 / args.reps / args.layers
        if first_grads is None:
            first_grads = grads
        err = max(
            float(jnp.max(jnp.abs(a - b))) for a, b in zip(
                jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(first_grads))
        )
        lines.append({
            "form": name, "shape": [b_, t, d, h, hd], "dtype": jnp.dtype(dtype).name,
            "layers": args.layers, "ms_per_layer": ms if on_tpu else None,
            "loss": float(loss), "max_abs_grad_diff_to_first": err,
            "device": {"platform": dev.platform, "kind": dev.device_kind},
        })
        print(json.dumps(lines[-1]), flush=True)
    out_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "qkv_projection_sweep.json"), "w") as fh:
        fh.write("\n".join(json.dumps(ln) for ln in lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
