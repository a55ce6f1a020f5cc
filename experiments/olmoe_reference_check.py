"""How close the program comes to the plain reference at OLMoE-1B-7B's published
widths, and how much of the distance is routing: the reading that sets
``reference_check`` in ``benchmark/configs/olmoe-1b-7b.json``.

    chiprun -- python experiments/olmoe_reference_check.py --seeds 3
    python experiments/olmoe_reference_check.py --config tiny-rehearsal-olmoe --seeds 1

Per seed and sequence (the benchmark's own seeded sequences and seeded initial
parameters): the program's loss and gradients (bf16 compute on a TPU) against
``benchmark/references/olmoe.py`` (float32, highest precision)

- as the harness calls it, without routes: the error ``correct`` sees, and the
  share of the S x k assignments on which the two picked another expert;
- with the program's routes handed over: the arithmetic's error alone;
- the reference on parameters rounded to an 8-bit float (e4m3), with the same
  routes, against itself: what the nearest precision below bf16 reads, which
  the limit must refuse.

One JSON line per (seed, sequence) and a summary; all in
``chiprun_out/olmoe_reference_check.json``. A CPU run compares float32 with
float32 and checks the paths only.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import datagen, references
from benchmark.manifest import Manifest
from distributedvolunteercomputing_tpu.models import get_model, olmoe


def _sq(tree):
    return sum(float(x) for x in jax.tree_util.tree_leaves(tree))


@jax.jit
def _diff2(a, b):
    return jax.tree_util.tree_map(lambda x, y: jnp.sum((x.astype(jnp.float32) - y) ** 2), a, b)


@jax.jit
def _norm2(b):
    return jax.tree_util.tree_map(lambda y: jnp.sum(y.astype(jnp.float32) ** 2), b)


def rel_err(got, want) -> float:
    return math.sqrt(_sq(_diff2(got, want)) / _sq(_norm2(want)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="olmoe-1b-7b")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2200002801)
    ap.add_argument("--compute", choices=("as_is", "f32", "f32_highest"), default="as_is",
                    help="diagnosis: the program's activations in float32, with the "
                         "device's default matmul precision or the highest")
    ap.add_argument("--sequences", type=int, default=None)
    ap.add_argument("--per-leaf", type=int, default=0,
                    help="also print each leaf's error with the routes handed over")
    ap.add_argument("--out", default="chiprun_out/olmoe_reference_check.json")
    args = ap.parse_args()

    cfg = Manifest().load_config(args.config)
    rc = dict(cfg["reference_check"])
    if args.sequences:
        rc["sequences"] = args.sequences
    if args.compute != "as_is":
        from distributedvolunteercomputing_tpu.models import common

        common.compute_dtype = lambda: jnp.float32
    ref = references.load(cfg["family"])
    sizes = ref.sizes(cfg)
    bundle = get_model(cfg["registry_model"], **cfg["model_overrides"])
    ref.check_config(bundle.config, cfg)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}

    @jax.jit
    def program(params, tokens, targets):
        """Loss, gradients and the routes of that very computation."""
        def f(p):
            loss, _, routes = olmoe.loss_and_routes(
                p, {"tokens": tokens, "targets": targets}, bundle.config)
            return loss, routes

        if args.compute == "f32_highest":
            with jax.default_matmul_precision("highest"):
                (loss, routes), grads = jax.value_and_grad(f, has_aux=True)(params)
        else:
            (loss, routes), grads = jax.value_and_grad(f, has_aux=True)(params)
        return loss, grads, routes

    reference = jax.jit(ref.make_loss_and_grad(cfg))
    ref_routes = jax.jit(lambda p, t, y: ref.loss(p, t, y, ref.hyper(cfg), with_routes=True)[1])
    # e4m3's 4 exponent and 3 mantissa bits (a convert there and back is
    # folded away by the TPU compiler: it read exactly 0)
    to_fp8 = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda a: jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3), p))

    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = jax.jit(bundle.init)(jax.random.PRNGKey(seed))
        arrays = datagen.lm_arrays(seed + 0x5EED, rc["sequences"], rc["seq_len"], sizes["vocab"])
        for s in range(rc["sequences"]):
            tok, tgt = arrays["tokens"][s:s + 1], arrays["targets"][s:s + 1]
            lp, gp, mine = program(params, tok, tgt)
            mine = np.asarray(mine)                                # [L, S, k]
            theirs = np.asarray(ref_routes(params, tok, tgt))
            n_experts = cfg["num_experts"]
            oh = lambda r: np.eye(n_experts, dtype=bool)[r].any(axis=-2)  # noqa: E731
            flipped = float(1.0 - (oh(mine) & oh(theirs)).sum() / mine.size)
            lr, gr = reference(params, tok, tgt)
            rec = {"seed": seed, "sequence": s, "loss_program": float(lp),
                   "loss_reference": float(lr), "flipped_share": flipped,
                   "grad_rel_err_no_routes": rel_err(gp, gr),
                   "loss_abs_err_no_routes": abs(float(lp) - float(lr))}
            del gr
            lr2, gr2 = reference(params, tok, tgt, jnp.asarray(mine))
            rec["grad_rel_err_with_routes"] = rel_err(gp, gr2)
            rec["loss_abs_err_with_routes"] = abs(float(lp) - float(lr2))
            if args.per_leaf:
                num, den = _diff2(gp, gr2), _norm2(gr2)
                rec["per_leaf_with_routes"] = {
                    jax.tree_util.keystr(path): [math.sqrt(float(n) / float(d)), math.sqrt(float(d))]
                    for (path, n), d in zip(jax.tree_util.tree_leaves_with_path(num),
                                            jax.tree_util.tree_leaves(den))}
            del gp
            l8, g8 = reference(to_fp8(params), tok, tgt, jnp.asarray(mine))
            rec["fp8_params_grad_rel_err_with_routes"] = rel_err(g8, gr2)
            rec["fp8_params_loss_abs_err"] = abs(float(l8) - float(lr2))
            del g8, gr2
            rec["device"], rec["compute"] = device, args.compute
            rows.append(rec)
            print(json.dumps(rec), flush=True)
        del params
    keys = [k for k, v in rows[0].items() if isinstance(v, float)]
    summary = {"what": "summary", "device": device,
               **{f"max_{k}": max(r[k] for r in rows) for k in keys},
               **{f"min_{k}": min(r[k] for r in rows) for k in keys}}
    mem = dev.memory_stats() or {}
    summary["peak_bytes_in_use"] = mem.get("peak_bytes_in_use")
    print(json.dumps(summary), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(rows + [summary], fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
