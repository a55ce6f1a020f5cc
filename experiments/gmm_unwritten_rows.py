#!/usr/bin/env python
"""Grouped products whose sizes end before the last row, on the chip (PR 46).

    chiprun -- python experiments/gmm_unwritten_rows.py

Three things the CPU tests can only interpret, one JSON line each, all in
``chiprun_out/gmm_unwritten_rows.jsonl``:

* ``grouped_matmul`` (compiled megablox) with sizes that sum to nothing, to
  part of a row tile and to every row, the rows of ``lhs`` and of the result's
  cotangent past the sum set to NaN: the rows inside the groups, their
  cotangent and the stack's cotangent against the float32 dense product, and
  what the rows past the sum of the result hold (nobody wrote them);
* ``share_glu_experts`` at a cell's widths on megablox against the same call
  on ``ragged_dot`` (zeros past the sum), for routes as drawn and for routes
  that send this share nothing (a grid of no row tiles in all seven products);
* one product's time over SmallThinker's chunk with sizes that fill it and
  with the ledger's held rows (19,840 of 104,448): the kernel's grid follows
  the sizes, 211 row tiles against about 46.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributedvolunteercomputing_tpu.ops import moe_dispatch

OUT = os.path.join("chiprun_out", "gmm_unwritten_rows.jsonl")


def say(**line):
    line["device"] = jax.devices()[0].device_kind
    print(json.dumps(line), flush=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(line) + "\n")


def f32(a):
    return np.asarray(a, np.float32)


def rel(a, b):
    a, b = f32(a), f32(b)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def products(m=4096, k=2560, n=1536, e=8):
    ks = jax.random.split(jax.random.PRNGKey(46), 3)
    lhs = jax.random.normal(ks[0], (m, k), jnp.bfloat16)
    rhs = (jax.random.normal(ks[1], (e, k, n)) * 0.02).astype(jnp.bfloat16)
    cot = jax.random.normal(ks[2], (m, n), jnp.bfloat16)
    for name, sizes in (("nothing", [0] * e), ("inside_the_second_tile", [300, 0, 200, 100, 0, 0, 17, 0]),
                        ("every_row", [m // e] * e)):
        sizes = jnp.asarray(sizes, jnp.int32)
        total = int(sizes.sum())
        inside = (jnp.arange(m) < total)[:, None]
        out, pull = jax.vjp(lambda a, b: moe_dispatch.grouped_matmul(a, b, sizes),
                            jnp.where(inside, lhs, jnp.nan), rhs)
        d_lhs, d_rhs = pull(jnp.where(inside, cot, jnp.nan))
        ends = np.concatenate([[0], np.cumsum(np.asarray(sizes))])
        want = np.concatenate([f32(lhs[a:b]) @ f32(rhs[g]) for g, (a, b) in enumerate(zip(ends[:-1], ends[1:]))])
        want_lhs = np.concatenate([f32(cot[a:b]) @ f32(rhs[g]).T for g, (a, b) in enumerate(zip(ends[:-1], ends[1:]))])
        want_rhs = np.stack([f32(lhs[a:b]).T @ f32(cot[a:b]) for a, b in zip(ends[:-1], ends[1:])])
        past = f32(out)[total:]
        say(check="grouped_matmul", impl=moe_dispatch.grouped_matmul_impl(m, k, n), sizes=name, rows_in_groups=total,
            out_rel_err=rel(f32(out)[:total], want) if total else 0.0,
            d_lhs_rel_err=rel(f32(d_lhs)[:total], want_lhs) if total else 0.0,
            d_rhs_rel_err=rel(d_rhs, want_rhs) if total else float(np.abs(f32(d_rhs)).max()),
            d_rhs_finite=bool(np.isfinite(f32(d_rhs)).all()),
            past_the_sum={"rows": int(past.shape[0]), "not_finite": int((~np.isfinite(past)).any(axis=1).sum()),
                          "not_zero": int((past != 0).any(axis=1).sum())})


def share(s=8192, k=6, d=2560, f=768, held=8, e=64, slack=4.25):
    ks = jax.random.split(jax.random.PRNGKey(47), 6)
    x = jax.random.normal(ks[0], (s, d), jnp.bfloat16)
    stacks = [(jax.random.normal(kk, shape) * 0.02).astype(jnp.bfloat16)
              for kk, shape in zip(ks[1:4], ((held, d, f), (held, d, f), (held, f, d)))]
    drawn = jnp.argsort(jax.random.uniform(ks[4], (s, e)), axis=1)[:, :k].astype(jnp.int32)
    gates = jax.nn.softmax(jax.random.normal(ks[5], (s, k)), axis=1)
    probe = jax.random.normal(jax.random.PRNGKey(48), (s, d), jnp.bfloat16)
    choose = moe_dispatch.grouped_matmul_impl
    for name, idx in (("as_drawn", drawn), ("nothing_held", jnp.where(drawn < held, drawn + held, drawn))):
        got = {}
        for impl in ("chosen", "ragged_dot"):
            moe_dispatch.grouped_matmul_impl = choose if impl == "chosen" else (lambda *a: "ragged_dot")

            def loss(x, gates, *w):
                y, _, dropped, moved, _ = moe_dispatch.share_glu_experts(x, idx, gates, *w, 0, e, act="relu", slack=slack)
                return jnp.sum((y * probe).astype(jnp.float32)), (y, dropped, moved)

            got[impl] = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, gates, *stacks)
        moe_dispatch.grouped_matmul_impl = choose
        (_, (y, dropped, moved)), grads = got["chosen"]
        (_, (y_ref, _, _)), grads_ref = got["ragged_dot"]
        held_rows = int(jnp.sum(idx < held))
        names = ("x", "gates", "w_gate", "w_up", "w_down")
        say(check="share_glu_experts", impl=choose(int(moved), d, f), routes=name, held_rows=held_rows,
            rows_moved=int(moved), dropped=int(dropped),
            finite=bool(all(np.isfinite(f32(g)).all() for g in (y, *grads))),
            y_rel_err=rel(y, y_ref) if held_rows else float(jnp.abs(y.astype(jnp.float32)).max()),
            grad_rel_err={n: rel(a, b) if held_rows else float(jnp.abs(a.astype(jnp.float32)).max())
                          for n, a, b in zip(names, grads, grads_ref)},
            gates_cotangent_not_zero_off_the_share=int(jnp.sum((grads[1] != 0) & (idx >= held))))


def tiles(m=104448, k=2560, n=1536, e=8, iters=20):
    ks = jax.random.split(jax.random.PRNGKey(49), 2)
    lhs = jax.random.normal(ks[0], (m, k), jnp.bfloat16)
    rhs = (jax.random.normal(ks[1], (e, k, n)) * 0.02).astype(jnp.bfloat16)
    fn = jax.jit(moe_dispatch.grouped_matmul)
    for name, total in (("the_chunk", m), ("the_held_rows", 19840), ("nothing", 0)):
        sizes = jnp.full((e,), total // e, jnp.int32).at[0].add(total - total // e * e)
        fn(lhs, rhs, sizes).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(lhs, rhs, sizes)
        out.block_until_ready()
        say(check="one_product_ms", sizes=name, rows_in_groups=total, rows=m,
            ms=(time.perf_counter() - t0) / iters * 1e3)


if __name__ == "__main__":
    os.makedirs("chiprun_out", exist_ok=True)
    products()
    share()
    tiles()
