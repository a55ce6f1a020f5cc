"""The scalar-decay delta rule as Qwen3-Next's mixer calls it, on the chip:
``ops/gdn.gdn_with_sums`` from the mixer's raw streams (ONE [B, T, 8192] array
of q, k at 16 heads and v at 32, the log decay and beta a value head) to ``o`` at
qwen3-next-solo-8k's shape, forward alone and forward with all its gradients;
beside it the same function through the PER-CHANNEL form, ``ops/kda.kda`` fed the
decay broadcast over a head's 128 channels and q, k repeated to the 32 value
heads (what the model would run without a form of its own: the oracle of
``tests/test_gdn.py``), so that what the second form buys is read on the chip.

    chiprun -- python experiments/gdn_sweep.py [--scalar-only]
    python experiments/gdn_sweep.py --shape 2,40,2,4,16,16 --iters 1

``--scalar-only`` leaves the per-channel form and the float32 comparison out
(PR 68 timed three forms of this module's loops with it, a minute a form).

A shape is ``batch,T,key_heads,value_heads,head_dim,chunk``. Timed in bf16, at
decays the model is initialised with (``exp(A_log)`` in (0, 16), ``dt``
log-uniform in [1e-3, 1e-1]). The two forms' ``o`` and gradients are held to
each other in float32 over the first ``--exact-tokens`` tokens (largest
difference over the largest magnitude). One JSON line a measurement on stdout,
all appended to ``--out`` (``chiprun_out/gdn_sweep.json``). A CPU run checks the
path, not the speed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.ops import gdn, kda
from experiments.gmm_sweep import _time

NAMES = ("o", "d_qkv", "d_g", "d_beta")


def streams(z, t, hk, hv, d, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    qkv = jax.random.normal(k[0], (z, t, (2 * hk + hv) * d), dtype)
    a = jax.random.uniform(k[1], (hv,), jnp.float32, 1e-6, 16.0)
    dt = jnp.exp(jax.random.uniform(k[2], (z, t, hv), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    beta = jax.random.uniform(k[3], (z, t, hv), jnp.float32, 0.05, 0.95)
    return (qkv, -a * dt, beta), jax.random.normal(k[4], (z, t, hv * d), dtype)


def forms(hk, hv, d, chunk):
    r, kd = hv // hk, hk * d

    def scalar(qkv, g, beta):
        return gdn.gdn_with_sums(qkv, g, beta, hk, hv, d, chunk)[0]

    def per_channel(qkv, g, beta):
        z, t, _ = qkv.shape
        q = jnp.repeat(qkv[..., :kd].reshape(z, t, hk, d), r, axis=2)
        k = jnp.repeat(qkv[..., kd:2 * kd].reshape(z, t, hk, d), r, axis=2)
        v = qkv[..., 2 * kd:].reshape(z, t, hv, d)
        return kda.kda(q, k, v, jnp.broadcast_to(g[..., None], (z, t, hv, d)), beta, chunk)[0].reshape(z, t, hv * d)

    return {"scalar_decay": scalar, "per_channel_broadcast": per_channel}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shape", default="2,8192,16,32,128,64")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--exact-tokens", type=int, default=1024)
    ap.add_argument("--out", default="chiprun_out/gdn_sweep.json")
    ap.add_argument("--scalar-only", action="store_true")
    args = ap.parse_args()
    z, t, hk, hv, d, chunk = (int(n) for n in args.shape.split(","))
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    dtype = jnp.bfloat16 if dev.platform == "tpu" else jnp.float32
    lines = []

    def say(**rec):
        rec = {"shape": args.shape, "device": device, **rec}
        lines.append(rec)
        print(json.dumps(rec), flush=True)

    args_, probe = streams(z, t, hk, hv, d, dtype)
    for name, fn in forms(hk, hv, d, chunk).items():
        if args.scalar_only and name != "scalar_decay":
            continue
        fwd = jax.jit(fn)
        both = jax.jit(lambda *a, fn=fn: jax.vjp(fn, *a)[1](probe))
        say(what=name, forward_ms=_time(fwd, args_, args.iters), forward_and_gradients_ms=_time(
            lambda *a, both=both, fwd=fwd: (fwd(*a), both(*a)), args_, args.iters))
    if not args.scalar_only:
        # the two forms against each other, float32, over the first tokens
        n = min(args.exact_tokens, t)
        exact, probe32 = streams(z, n, hk, hv, d, jnp.float32)
        with jax.default_matmul_precision("highest"):
            got = {name: (fn(*exact), *jax.vjp(fn, *exact)[1](probe32)) for name, fn in forms(hk, hv, d, chunk).items()}
        far = lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))  # noqa: E731
        say(what="scalar_decay against per_channel_broadcast, float32", tokens=n,
            **{name: far(a, b) for name, a, b in zip(NAMES, got["scalar_decay"], got["per_channel_broadcast"])})
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as fh:
        fh.writelines(json.dumps(rec) + "\n" for rec in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
