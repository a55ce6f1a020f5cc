"""The state-space scan as the mixer calls it, on the chip: ``ops/ssd.ssd`` from
the convolution's ``xbc`` to ``y`` at nemotron3-nano-solo-8k's shape, forward
alone and forward with all its gradients, the kernels beside the plain form.

    chiprun -- python experiments/ssd_sweep.py                     # the PR 49 table
    python experiments/ssd_sweep.py --shape 2,40,4,8,2,16,16 --iters 1

A shape is ``batch,T,heads,head_dim,groups,state,chunk``. Timed in bf16; the
kernels' ``y`` and gradients are held to the plain form's in bf16 and both to the
plain form's in float32 (largest difference over the float32 values' largest
magnitude). The same file runs against a tree whose ``ssd`` still takes ``x``,
``B`` and ``C`` apart (PR 48's: copy it there), so that one call times both.
One JSON line a measurement on stdout, all appended to ``--out`` (``chiprun_out/ssd_sweep.json``). A
CPU run interprets the kernels: it checks the paths, not the speeds.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.ops import ssd
from experiments.gmm_sweep import _time

NAMES = ("y", "d_xbc", "d_dt", "d_a_log", "d_D")


def inputs(seed: int, z, t, h, p, g, n, dtype):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    xbc = jax.nn.silu(jax.random.normal(k[0], (z, t, h * p + 2 * g * n))).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (z, t, h)) - 3.0)
    a_log = jnp.log(jnp.linspace(1.0, 16.0, h))
    d = 1.0 + 0.1 * jax.random.normal(k[2], (h,))
    probe = jax.random.normal(k[3], (z, t, h * p)).astype(dtype)
    return (xbc, dt, a_log, d), probe


def scan(form: str, g: int, n: int, chunk: int):
    """``(xbc, dt, a_log, d) -> y [Z, T, H P]`` through this tree's ``ssd``."""
    if "groups" in inspect.signature(ssd.ssd).parameters:
        return lambda xbc, dt, a_log, d: ssd.ssd(xbc, dt, a_log, d, g, n, chunk, form)[0]

    def apart(xbc, dt, a_log, d):  # PR 48's entry: the streams split and by head
        z, t, h = dt.shape
        x, b, c = jnp.split(xbc, [xbc.shape[-1] - 2 * g * n, xbc.shape[-1] - g * n], axis=-1)
        y, _ = ssd.ssd(x.reshape(z, t, h, -1), dt, a_log, b.reshape(z, t, g, n), c.reshape(z, t, g, n), d, chunk, form)
        return y.reshape(z, t, -1)

    return apart


def with_gradients(fn):
    """``(probe, *args) -> (y, every gradient of sum(y probe))``; the probe an argument, not a constant of the program."""
    def run(probe, *args):
        y, vjp = jax.vjp(fn, *args)
        return (y, *vjp(probe))

    return run


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="2,8192,64,64,8,128,128")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join("chiprun_out", "ssd_sweep.json"))
    a = ap.parse_args()
    z, t, h, p, g, n, chunk = (int(v) for v in a.shape.split(","))
    on_chip = jax.default_backend() == "tpu"
    kernel = ssd.KERNEL if on_chip else ssd.INTERPRET
    entry = "xbc" if "groups" in inspect.signature(ssd.ssd).parameters else "x, B, C apart (PR 48)"
    lines = []

    def say(**line):
        line = {"device": jax.devices()[0].device_kind, "shape": a.shape, "entry": entry, **line}
        lines.append(line)
        print(json.dumps(line), flush=True)

    args, probe = inputs(a.seed, z, t, h, p, g, n, jnp.bfloat16)
    got = {}
    for form in (kernel, ssd.PLAIN):
        fwd = jax.jit(scan(form, g, n, chunk))
        both = jax.jit(with_gradients(scan(form, g, n, chunk)))
        say(form=form, fwd_ms=_time(fwd, args, a.iters), fwd_bwd_ms=_time(both, (probe, *args), a.iters))
        got[form] = [v.astype(jnp.float32) for v in both(probe, *args)]
    exact_args = (args[0].astype(jnp.float32), *args[1:])
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(with_gradients(scan(ssd.PLAIN, g, n, chunk)))(probe.astype(jnp.float32), *exact_args)
    for name, k, pl_, ex in zip(NAMES, got[kernel], got[ssd.PLAIN], exact):
        scale = float(jnp.max(jnp.abs(ex)))
        say(value=name, largest=scale,
            kernel_to_plain=float(jnp.max(jnp.abs(k - pl_))) / scale,
            kernel_to_float32=float(jnp.max(jnp.abs(k - ex))) / scale,
            plain_to_float32=float(jnp.max(jnp.abs(pl_ - ex))) / scale,
            kernel_rms_to_float32=float(jnp.sqrt(jnp.mean((k - ex) ** 2))) / scale,
            plain_rms_to_float32=float(jnp.sqrt(jnp.mean((pl_ - ex) ** 2))) / scale)
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
