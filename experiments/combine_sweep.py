"""How a token's run is summed on the chip: ``ops/moe_dispatch._combine`` at the
two share cells' shapes, the product's tile swept, beside the three shifted
adds it replaced (PR 36's formulation, kept here only to be timed).

    chiprun -- python experiments/combine_sweep.py                 # the PR 38 table
    python experiments/combine_sweep.py --shapes 256,16,48,4 --tiles 8,128 --iters 1

A shape is ``rows,d,tokens,k``: 104448,2560,32768,6 is one chunk of
smallthinker-solo-16k, 49152,2048,32768,8 one of laguna-solo-8k. A third of the
rows are held assignments of random tokens (at most k a token) in random order,
the rest are not held, as a chunk's are. Timed in bf16, a call of ``_combine``
whole (the gather into token order, the run's sum, the gather of each token's
last row) and the two gathers alone, so that the sum's own part is their
difference; every variant is held to a float32 per-token sum. One JSON line per
measurement on stdout, all in ``chiprun_out/combine_sweep.json``; ``--trace 1``
also lists the device's operations of one call of each variant. ``_RUN_TILE``
in ops/moe_dispatch.py takes its value from this table (PERF.md, Findings of
PR 38). A CPU run checks the paths, not the speeds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributedvolunteercomputing_tpu.ops import moe_dispatch
from experiments.gmm_sweep import _time, op_names

DEFAULT_SHAPES = "104448,2560,32768,6;49152,2048,32768,8"


def chunk(seed: int, rows: int, tokens: int, k: int):
    """(tok [rows], valid [rows]): a third of the rows are distinct (token,
    choice) assignments in random order, the others not held."""
    rng = np.random.default_rng(seed)
    n = min(rows // 3, tokens * k)
    held = rng.choice(tokens * k, size=n, replace=False) // k
    tok = np.concatenate([held, rng.integers(0, tokens, size=rows - n)])
    valid = np.arange(rows) < n
    order = rng.permutation(rows)
    return jnp.asarray(tok[order], jnp.int32), jnp.asarray(valid[order])


def shifted_adds(rows, where, k):
    """PR 36's ``_combine``: rounds at distances 1, 2, 4, each a shifted slice
    and an add over the whole buffer."""
    perm, tok_sorted, last_pos = where[2:]
    total = rows[perm]
    shift = 1
    while shift < min(k, total.shape[0]):
        same = tok_sorted[shift:] == tok_sorted[:-shift]
        earlier = jnp.where(same[:, None], total[:-shift], jnp.zeros((), total.dtype))
        total = total + jnp.pad(earlier, ((shift, 0), (0, 0)))
        shift *= 2
    r = total.shape[0]
    last = total[jnp.minimum(last_pos, r - 1)]
    return jnp.where((last_pos < r)[:, None], last, jnp.zeros((), total.dtype))


def gathers_alone(rows, where, k):
    """The part every variant shares: rows into token order, a token's last row."""
    perm, _, last_pos = where[2:]
    r = rows.shape[0]
    return rows[perm][jnp.minimum(last_pos, r - 1)]


def per_token_sum(rows, tok, valid, tokens: int):
    held = jnp.where(valid[:, None], rows.astype(jnp.float32), 0.0)
    return jax.ops.segment_sum(held, jnp.where(valid, tok, tokens), num_segments=tokens + 1)[:tokens]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shapes", default=DEFAULT_SHAPES)
    ap.add_argument("--tiles", default="128,256,512")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=38)
    ap.add_argument("--trace", type=int, default=1)
    ap.add_argument("--out", default="chiprun_out/combine_sweep.json")
    args = ap.parse_args()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    results = []

    def emit(rec):
        rec["device"] = device
        results.append(rec)
        print(json.dumps(rec), flush=True)

    for shape in args.shapes.split(";"):
        r, d, s, k = (int(v) for v in shape.split(","))
        tok, valid = chunk(args.seed, r, s, k)
        rows = jax.random.normal(jax.random.PRNGKey(args.seed), (r, d), jnp.bfloat16)
        want = np.asarray(jax.jit(per_token_sum, static_argnums=3)(rows, tok, valid, s))
        scale = float(np.abs(want).max())

        def call(fn):
            def f(rows, tok, valid):
                return fn(rows, (tok, valid, *moe_dispatch._token_runs(tok, valid, s)), k)

            return jax.jit(f)

        variants = [("gathers_alone", gathers_alone, None), ("shifted_adds", shifted_adds, None)]
        variants += [("product", moe_dispatch._combine, int(t)) for t in args.tiles.split(",") if r % int(t) == 0]
        for name, fn, tile in variants:
            rec = {"what": name, "tile": tile, "rows": r, "d": d, "tokens": s, "k": k}
            standing = moe_dispatch._RUN_TILE
            try:
                if tile is not None:
                    moe_dispatch._RUN_TILE = tile  # read when the call is traced
                jitted = call(fn)
                rec["ms"] = _time(jitted, (rows, tok, valid), args.iters)
                if name != "gathers_alone":
                    got = np.asarray(jitted(rows, tok, valid).astype(jnp.float32))
                    rec["max_err_over_max"] = float(np.abs(got - want).max()) / scale
                if args.trace:
                    with tempfile.TemporaryDirectory() as tmp:
                        with jax.profiler.trace(tmp):
                            jax.block_until_ready(jitted(rows, tok, valid))
                        rec["ops"] = op_names(tmp, top=8)
            except Exception as e:  # noqa: BLE001 - a variant the compiler refuses is a reading
                rec["error"] = repr(e)[:300]
            finally:
                moe_dispatch._RUN_TILE = standing
            emit(rec)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(results, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
