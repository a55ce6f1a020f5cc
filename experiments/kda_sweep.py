"""The delta-rule scan as the mixer calls it, on the chip: ``ops/kda.kda`` from
the mixer's raw streams (q and k un-normed, v, the log decay and beta, the heads
side by side as the convolution leaves them) to ``o`` at kimi-linear-solo-8k's
shape, forward alone and forward with all its gradients; beside it the same two
for the formulation it replaced (``parent_kda``: PR 52's, the streams normed,
folded, summed and laid out by chunk as whole passes around a scan over stacked
chunks), so that what the loops grew by and what the passes cost are read apart.

    chiprun -- python experiments/kda_sweep.py
    python experiments/kda_sweep.py --shape 2,40,2,16,16 --iters 1

A shape is ``batch,T,heads,head_dim,chunk``. Timed in bf16; ``o`` and the
gradients of both are held to the TOKEN-BY-TOKEN recurrence in float32 (largest
difference over the float32 values' largest magnitude) over the sequence's
first ``--exact-tokens`` tokens (8192: all of the cell's), at decays the model is
initialised with (``exp(A_log)`` in [1, 16], ``dt`` log-uniform in [1e-3, 1e-1]:
a chunk's summed log decay reaches past -88). One JSON line a measurement on
stdout, all appended to ``--out`` (``chiprun_out/kda_sweep.json``). A CPU run
checks the path, not the speed.
"""

from __future__ import annotations

import argparse
import functools
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
    """The mixer's streams with the heads side by side ([z, t, h d]; beta [z, t, h]) and a probe for ``o``."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    q, key, v = (jax.nn.silu(jax.random.normal(k[i], (z, t, h * d))).astype(dtype) for i in range(3))
    a = jnp.repeat(jax.random.uniform(k[3], (h,), jnp.float32, 1.0, 16.0), d)
    dt = jnp.exp(jax.random.uniform(k[4], (h * d,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    g = -a * jax.nn.softplus(jnp.log(jnp.expm1(dt)) + 0.1 * jax.random.normal(k[5], (z, t, h * d)))
    beta = jax.nn.sigmoid(jax.random.normal(k[6], (z, t, h)))
    probe = jax.random.normal(k[7], (z, t, h * d)).astype(dtype)
    return (q, key, v, g, beta), probe


def by_head(fn, h: int):
    """``fn`` of streams [z, t, h, d] as a function of the streams with the heads side by side, as ``models/kimi_linear._kda`` calls it."""
    def run(q, k, v, g, beta):
        z, t, _ = q.shape
        return fn(*(a.reshape(z, t, h, -1) for a in (q, k, v, g)), beta).reshape(z, t, -1)

    return run


# ---------------------------------------------------------------------------
# the formulation ``ops/kda.py`` had until PR 55, from its chunk functions (which stayed)
# ---------------------------------------------------------------------------


def _stacked(a, chunk: int):
    """[Z, T, H, D] -> [nc, Z, C, H, D]: a copy, two sequences lead the chunk axis."""
    z, t, h, d = a.shape
    return jnp.moveaxis(a.reshape(z, t // chunk, chunk, h, d), 1, 0)


def _unstacked(a):
    """[nc, Z, H, C, D] -> [Z, T, H, D]."""
    nc, z, h, c, d = a.shape
    return jnp.moveaxis(a, (0, 3), (1, 2)).reshape(z, nc * c, h, d)


def _mapped(fn, n_states: int, n_streams: int):
    axes = lambda stream_axis: (0,) * n_states + (stream_axis,) * n_streams
    return jax.vmap(jax.vmap(fn, in_axes=axes(1)), in_axes=axes(0))


def _parent_fwd(q, k, kb, vb, gc, chunk):
    def step(st, xs):
        o, st_new = _mapped(kda._chunk_fwd, 1, 5)(st, *xs)
        return st_new, (o, st)

    z, _, h, dk = q.shape
    xs = tuple(_stacked(a, chunk) for a in (q, k, kb, vb, gc))
    _, (o, states) = jax.lax.scan(step, jnp.zeros((z, h, vb.shape[-1], dk), jnp.float32), xs)
    return _unstacked(o), (q, k, kb, vb, gc, states)


def _parent_bwd(chunk, res, do):
    q, k, kb, vb, gc, states = res

    def step(dst, xs):
        *out, dg, dst_prev = _mapped(kda._chunk_bwd, 2, 6)(dst, *xs)
        return dst_prev, (*(d.astype(a.dtype) for d, a in zip(out, (q, k, kb, vb))), dg)

    xs = (states, *(_stacked(a, chunk) for a in (q, k, kb, vb, gc, do)))
    _, outs = jax.lax.scan(step, jnp.zeros(states.shape[1:], jnp.float32), xs, reverse=True)
    return tuple(_unstacked(a) for a in outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _parent_core(q, k, kb, vb, gc, chunk):
    return _parent_fwd(q, k, kb, vb, gc, chunk)[0]


_parent_core.defvjp(_parent_fwd, _parent_bwd)


def parent_kda(q, k, v, g, beta, chunk: int):
    """``o`` [Z, T, H, V] from the same raw streams [Z, T, H, D] as ``ops/kda.kda`` takes (T a whole number of chunks)
    as PR 52 computed it: the l2 norms, beta's fold and the decay's running sum by chunk as float32 passes over whole
    streams that JAX differentiates, then a scan whose steps are handed their chunks stacked ``[nc, Z, C, H, D]``."""
    z, t, h, dk = q.shape
    f32 = jnp.float32
    q, k = kda._l2norm(q, dk ** -0.5), kda._l2norm(k)
    b = beta.astype(f32)[..., None]
    kb, vb = (k.astype(f32) * b).astype(k.dtype), (v.astype(f32) * b).astype(v.dtype)
    gc = jnp.cumsum(g.astype(f32).reshape(z, t // chunk, chunk, h, dk), axis=2).reshape(z, t, h, dk)
    return _parent_core(q, k, kb, vb, gc, chunk)


BLOCK = 64  # tokens whose states the recurrence's backward recomputes from the state that entered them


def recurrence(q, k, v, g, beta):
    """Each head's q at length 1 / sqrt(d) and k at length 1, then the three steps a token, float32: decay by channel, the
    delta, the rank-one update. Blocks of ``BLOCK`` tokens under ``jax.checkpoint``, so that the gradient over 8,192 tokens
    keeps the blocks' boundary states only."""
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + kda.L2_EPS)
    q, k = unit(q) * q.shape[-1] ** -0.5, unit(k)

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
    sums = kda.kda_with_sums(*(x.reshape(z, t, h, -1) for x in args[:4]), args[4], chunk)[1]
    say(lowest_chunk_sum=float(jnp.min(sums)), carry_share=float(kda.carry_share(sums)))
    forms = {"kda": by_head(lambda *xs: kda.kda(*xs, chunk)[0], h)}
    if t % chunk == 0:
        forms["parent_kda"] = by_head(lambda *xs: parent_kda(*xs, chunk), h)
    # against the recurrence token by token, in float32, over the sequence's first tokens
    n = min(t, a.exact_tokens)
    short = tuple(x[:, :n] for x in args)
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(with_gradients(by_head(recurrence, h)))(
            probe[:, :n].astype(jnp.float32), *(x.astype(jnp.float32) for x in short))
    for form, scan in forms.items():
        both = jax.jit(with_gradients(scan))
        say(form=form, fwd_ms=_time(jax.jit(scan), args, a.iters), fwd_bwd_ms=_time(both, (probe, *args), a.iters),
            finite_over_the_whole_sequence=all(bool(jnp.all(jnp.isfinite(v))) for v in both(probe, *args)))
        for name, got, ex in zip(NAMES, both(probe[:, :n], *short), exact):
            got, scale = got.astype(jnp.float32), float(jnp.max(jnp.abs(ex)))
            say(form=form, value=name, tokens=n, largest=scale, finite=bool(jnp.all(jnp.isfinite(got))),
                to_float32=float(jnp.max(jnp.abs(got - ex))) / scale,
                rms_to_float32=float(jnp.sqrt(jnp.mean((got - ex) ** 2))) / scale)
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
