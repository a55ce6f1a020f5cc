"""The delta-rule scan as the mixer calls it, on the chip: ``ops/kda.kda`` from
the streams q, k, v, the log decay and beta to ``o`` at kimi-linear-solo-8k's
shape, forward alone and forward with all its gradients.

    chiprun -- python experiments/kda_sweep.py
    python experiments/kda_sweep.py --shape 2,40,2,16,16 --iters 1

A shape is ``batch,T,heads,head_dim,chunk``. Timed in bf16; ``o`` and the
gradients are held to the TOKEN-BY-TOKEN recurrence in float32 (largest
difference over the float32 values' largest magnitude) over the sequence's
first ``--exact-tokens`` tokens (8192: all of the cell's), at decays the model is
initialised with (``exp(A_log)`` in [1, 16], ``dt`` log-uniform in [1e-3, 1e-1]:
a chunk's summed log decay reaches past -88). One JSON line a measurement on
stdout, all appended to ``--out`` (``chiprun_out/kda_sweep.json``). A CPU run
checks the path, not the speed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.ops import kda
from experiments.gmm_sweep import _time

NAMES = ("o", "d_q", "d_k", "d_v", "d_g", "d_beta")


def inputs(seed: int, z, t, h, d, dtype):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = (unit(jax.random.normal(k[0], (z, t, h, d))) * d ** -0.5).astype(dtype)
    key = unit(jax.random.normal(k[1], (z, t, h, d))).astype(dtype)
    v = jax.nn.silu(jax.random.normal(k[2], (z, t, h, d))).astype(dtype)
    a = jax.random.uniform(k[3], (h, 1), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(k[4], (h, d), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    g = -a * jax.nn.softplus(jnp.log(jnp.expm1(dt)) + 0.1 * jax.random.normal(k[5], (z, t, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(k[6], (z, t, h)))
    probe = jax.random.normal(k[7], (z, t, h, d)).astype(dtype)
    return (q, key, v, g, beta), probe


BLOCK = 64  # tokens whose states the recurrence's backward recomputes from the state that entered them


def recurrence(q, k, v, g, beta):
    """The three steps a token, float32: decay by channel, the delta, the rank-one update. Blocks of ``BLOCK`` tokens
    under ``jax.checkpoint``, so that the gradient over 8,192 tokens keeps the blocks' boundary states only."""
    def token(s, now):
        q_t, k_t, v_t, g_t, b_t = now
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("zhkv,zhk->zhv", s, k_t))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("zhkv,zhk->zhv", s, q_t)

    z, t, h, d = q.shape
    pad = (-t) % BLOCK  # tokens that neither decay nor write
    by_block = lambda a: jnp.moveaxis(jnp.pad(a.astype(jnp.float32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)), 1, 0
                                      ).reshape((t + pad) // BLOCK, BLOCK, *a.shape[:1], *a.shape[2:])
    block = jax.checkpoint(lambda s, xs: jax.lax.scan(token, s, xs))
    o = jax.lax.scan(block, jnp.zeros((z, h, d, v.shape[-1]), jnp.float32), tuple(by_block(a) for a in (q, k, v, g, beta)))[1]
    return jnp.moveaxis(o.reshape(t + pad, z, h, v.shape[-1])[:t], 0, 1)


def with_gradients(fn):
    """``(probe, *args) -> (o, every gradient of sum(o probe))``; the probe an argument, not a constant of the program."""
    def run(probe, *args):
        o, vjp = jax.vjp(fn, *args)
        return (o, *vjp(probe))

    return run


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="2,8192,32,128,64")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--exact-tokens", type=int, default=8192,
                    help="the recurrence's float32 comparison runs over the first so many tokens")
    ap.add_argument("--out", default=os.path.join("chiprun_out", "kda_sweep.json"))
    a = ap.parse_args()
    z, t, h, d, chunk = (int(v) for v in a.shape.split(","))
    lines = []

    def say(**line):
        line = {"device": jax.devices()[0].device_kind, "shape": a.shape, **line}
        lines.append(line)
        print(json.dumps(line), flush=True)

    args, probe = inputs(a.seed, z, t, h, d, jnp.bfloat16)
    sums = kda.kda_with_sums(*args, chunk)[1]
    say(lowest_chunk_sum=float(jnp.min(sums)), carry_share=float(kda.carry_share(sums)))
    scan = lambda *xs: kda.kda(*xs, chunk)[0]
    both = jax.jit(with_gradients(scan))
    say(fwd_ms=_time(jax.jit(scan), args, a.iters), fwd_bwd_ms=_time(both, (probe, *args), a.iters),
        finite_over_the_whole_sequence=all(bool(jnp.all(jnp.isfinite(v))) for v in both(probe, *args)))
    # against the recurrence token by token, in float32, over the sequence's first tokens
    n = min(t, a.exact_tokens)
    short = tuple(x[:, :n] for x in args)
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(with_gradients(recurrence))(probe[:, :n].astype(jnp.float32), *(x.astype(jnp.float32) for x in short))
    for name, got, ex in zip(NAMES, both(probe[:, :n], *short), exact):
        got, scale = got.astype(jnp.float32), float(jnp.max(jnp.abs(ex)))
        say(value=name, tokens=n, largest=scale, finite=bool(jnp.all(jnp.isfinite(got))),
            to_float32=float(jnp.max(jnp.abs(got - ex))) / scale, rms_to_float32=float(jnp.sqrt(jnp.mean((got - ex) ** 2))) / scale)
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
