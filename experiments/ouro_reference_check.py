"""How close the program comes to the looped model's plain reference at the
published widths (``ouro-2.6b``: six layers run four times, the whole
vocabulary, one sequence of 4,096 tokens): the readings that set
``reference_check`` in ``benchmark/configs/ouro-2.6b.json``.

    chiprun -- python experiments/ouro_reference_check.py --seeds 3 --left-out
    python experiments/ouro_reference_check.py --config tiny-rehearsal-ouro --seeds 1 --left-out

Per seed (the benchmark's own seeded sequence and seeded initial parameters):
the program's loss and gradients (bf16 compute on a TPU) against
``benchmark/references/ouro.py`` (float32, highest precision) as the harness
calls it; the reference on parameters rounded to bfloat16 and to an 8-bit float
(e4m3) against itself: what lower precisions read, which the limits must
refuse; ``--left-out`` (first seed): the reference with one term computed
wrongly (``VARIANTS``, or ``--variants`` of them) against itself: each must
land outside a limit. ``--gate-scale`` makes the parameters of every reading
seeded non-initial ones: the exit gate's vector times this and its bias
``normal(0, 1)`` (at the initial parameters every gate reads 0.5 whatever it is
handed), the norms' weights ``normal(1, 0.3)``.

One JSON line per seed and a summary; all in
``chiprun_out/ouro_reference_check.json``. A CPU run compares float32 with
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

from benchmark import datagen, references
from benchmark.manifest import Manifest
from distributedvolunteercomputing_tpu.models import get_model
from experiments.olmoe_reference_check import _diff2, _norm2, rel_err


def seeded_state(params, seed: int, gate_scale: float):
    """``params`` a few thousand steps in (module docstring)."""
    def leaf(path, a):
        keys = [getattr(k, "key", None) for k in path]
        if "exit_gate" in keys:
            return a * gate_scale if keys[-1] == "w" else jax.random.normal(jax.random.PRNGKey(seed), a.shape)
        if keys[-1] == "g":
            return a + 0.3 * jax.random.normal(jax.random.PRNGKey(seed + len(keys) + a.size % 7), a.shape)
        return a

    return jax.tree_util.tree_map_with_path(leaf, params)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="ouro-2.6b")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2700000401)
    ap.add_argument("--left-out", action="store_true")
    ap.add_argument("--variants", default="", help="--left-out: these of the reference's VARIANTS (comma-separated)")
    ap.add_argument("--gate-scale", type=float, default=1.0, help="the exit gate's vector times this (1: as initialised)")
    ap.add_argument("--out", default="chiprun_out/ouro_reference_check.json")
    args = ap.parse_args()

    cfg = Manifest().load_config(args.config)
    ref = references.load(cfg["family"])
    rc = cfg["reference_check"]
    sizes = ref.sizes(cfg)
    bundle = get_model(cfg["registry_model"], **cfg["model_overrides"])
    ref.check_config(bundle.config, cfg)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    hp = ref.hyper(cfg)
    rng = jax.random.PRNGKey(0)

    @jax.jit
    def program(params, tokens, targets):
        return jax.value_and_grad(lambda p: bundle.loss_fn(p, {"tokens": tokens, "targets": targets}, rng)[0])(params)

    def reference_with(variant=None):
        return jax.jit(lambda p, t, y: jax.value_and_grad(ref.loss)(p, t, y, hp, variant))

    reference = reference_with()
    rounded = {
        # bfloat16's 8 exponent and 7 mantissa bits, e4m3's 4 and 3 (a convert
        # there and back is folded away by the TPU compiler: it reads exactly 0)
        name: jax.jit(lambda p, e=e, m=m: jax.tree_util.tree_map(
            lambda a: jax.lax.reduce_precision(a, exponent_bits=e, mantissa_bits=m), p))
        for name, (e, m) in {"bf16": (8, 7), "e4m3": (4, 3)}.items()
    }

    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = bundle.init(jax.random.PRNGKey(seed))
        if args.gate_scale != 1.0:
            params = seeded_state(params, seed, args.gate_scale)
        arrays = datagen.lm_arrays(seed + 0x5EED, 1, rc["seq_len"], sizes["vocab"])
        tok, tgt = arrays["tokens"][:1], arrays["targets"][:1]
        lp, gp = program(params, tok, tgt)
        lr, gr = reference(params, tok, tgt)
        rec = {"seed": seed, "seq_len": rc["seq_len"], "gate_scale": args.gate_scale,
               "loss_program": float(lp), "loss_reference": float(lr),
               "grad_rel_err": rel_err(gp, gr), "loss_abs_err": abs(float(lp) - float(lr))}
        per_leaf = sorted(
            ((math.sqrt(float(n) / float(d)), jax.tree_util.keystr(path))
             for (path, n), d in zip(jax.tree_util.tree_leaves_with_path(_diff2(gp, gr)),
                                     jax.tree_util.tree_leaves(_norm2(gr))) if float(d) > 0),
            reverse=True)
        rec["worst_leaves"] = [[name, err] for err, name in per_leaf[:3]]
        del gp
        for name, to in rounded.items():
            lq, gq = reference(to(params), tok, tgt)
            rec[f"{name}_params_grad_rel_err"] = rel_err(gq, gr)
            rec[f"{name}_params_loss_abs_err"] = abs(float(lq) - float(lr))
            del gq
        if args.left_out and seed == args.first_seed:
            for variant in (args.variants.split(",") if args.variants else ref.VARIANTS):
                lo, go = reference_with(variant)(params, tok, tgt)
                rec[f"{variant}_grad_rel_err"] = rel_err(go, gr)
                rec[f"{variant}_loss_abs_err"] = abs(float(lo) - float(lr))
                del go
        del gr, params
        rec["device"] = device
        rows.append(rec)
        print(json.dumps(rec), flush=True)
    keys = [k for k, v in rows[-1].items() if isinstance(v, float)]
    summary = {"what": "summary", "device": device,
               **{f"max_{k}": max(r[k] for r in rows if k in r) for k in keys},
               **{f"min_{k}": min(r[k] for r in rows if k in r) for k in keys}}
    summary["peak_bytes_in_use"] = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(json.dumps(summary), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(rows + [summary], fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
