"""How close the program comes to its family's plain reference at the published
widths of a share configuration (SmallThinker-21BA3B-Instruct by default: one
period of four layers, eight of 64 experts, an eighth of the vocabulary, one
sequence of 16,384 tokens; ``--config lfm2-24b-a2b``: published layers 0 and
2-5, eight of 64 experts, an eighth of the vocabulary, 8,192 tokens;
``--config glm-4.7-flash``: published layers 0-4, likewise;
``--config nemotron-3-nano-30b-a3b``: published blocks 0-6, eight of 128 experts;
``--config kimi-linear-48b-a3b``: published layers 1-5, eight of 256 experts, 4,096 tokens;
``--config sdar-30b-a3b-chat``: published layers 0-4, sixteen of 128 experts, 4,096 data tokens as 8,192 rows;
``--config qwen3-next-80b-a3b``: published layers 0-3, sixteen of 512 experts, 8,192 tokens;
``--config xing4.0-29b-a4b``: published layers 1-5, eight of 64 experts, four residual streams, 4,096 tokens): the
readings that set ``reference_check`` in ``benchmark/configs/<config>.json``.

    chiprun -- python experiments/smallthinker_reference_check.py --seeds 3 --left-out
    chiprun -- python experiments/smallthinker_reference_check.py --config lfm2-24b-a2b --seeds 3 --left-out
    chiprun -- python experiments/smallthinker_reference_check.py --config lfm2-24b-a2b --seeds 2 --left-out \
        --bias 0.5 --norm-scale 0.3 --qk-scale 4 --variants bias_in_weights,no_qk_norm
    python experiments/smallthinker_reference_check.py --config tiny-rehearsal-lfm2 --seeds 1 --left-out

Per seed (the benchmark's own seeded sequence and seeded initial parameters):
the program's loss and gradients (bf16 compute on a TPU) against
``benchmark/references/<family>.py`` (float32, highest precision)

- as the harness calls it, without routes: the error ``correct`` sees, and the
  share of the L x S x k assignments on which the two picked another expert;
- with the program's routes handed over: the arithmetic's error alone;
- the reference on parameters rounded to bfloat16 and to an 8-bit float (e4m3),
  same routes, against itself: what lower precisions read, which the limits
  must refuse;
- ``--left-out`` (first seed): the reference with one term of the layer
  equations computed wrongly (the reference's ``VARIANTS``, or ``--variants``
  of them), against itself with the same routes (a variant that scores the
  experts otherwise picks its own): each must land outside a limit.

``--bias``, ``--norm-scale``, ``--qk-scale``, ``--maps`` make the parameters of EVERY
reading seeded non-initial ones (a state a few thousand steps in, not a trained
one): each router's selection bias ``normal(0, --bias)``, each per-head q / k
norm's scales ``normal(1, --norm-scale)``, the q and k projections times
``--qk-scale``; the three scalars ``a`` of every residual map (``models/xing4.py``) set to ``--maps``, so that the
token's own state moves its maps (0.01 at initialisation: ``static_maps`` cannot show there). At the initial parameters the bias is zero (adding it to the
weights is then no mistake) and q and k have RMS 0.9 under scales of 1 (the
norm is nearly the identity), so neither ``bias_in_weights`` nor ``no_qk_norm``
can show there. With any of the three, every reading also comes BY GROUP of
leaves (``by_group``: attention, experts, router, ...): the whole-gradient
norm is the dense layer's and the head's, and a mistake in one mixer or in the
experts' weights moves its own group's leaves.

One JSON line per seed and a summary; all in
``chiprun_out/<family>_reference_check.json``. A CPU run compares float32
with float32 and checks the paths only.
"""

from __future__ import annotations

import argparse
import functools
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
from distributedvolunteercomputing_tpu.models import get_model
from experiments.olmoe_reference_check import _diff2, _norm2, rel_err

# variants that score the experts otherwise, so they pick their own routes
OWN_ROUTES = ("router_after_attention", "softmax_for_sigmoid")
# a leaf's group, by the first of these keys its path holds
GROUPS = {"hc_mixer": "residual", "hc_ffn": "residual", "router": "router", "experts": "experts", "shared": "shared", "conv": "conv", "mlp": "mlp",
          **dict.fromkeys(("wq", "wk", "wv", "wo", "q_norm", "k_norm",
                           "wq_a", "wq_b", "wkv_a", "wkv_b", "q_a_norm", "kv_a_norm"), "attention"),
          **dict.fromkeys(("w_qkv", "w_fa", "w_fb", "w_beta", "w_ga", "w_gb", "gate_b", "o_norm"), "kda"),
          **dict.fromkeys(("w_qkvz", "w_ba"), "gdn"),
          **dict.fromkeys(("w_in", "w_out", "conv_w", "conv_b", "a_log", "dt_bias", "d_skip", "norm"), "mamba")}


def by_group(got, want):
    """Relative error of ``got`` against ``want`` over each group of leaves."""
    num, den = {}, {}
    for (path, n), d in zip(jax.tree_util.tree_leaves_with_path(_diff2(got, want)),
                            jax.tree_util.tree_leaves(_norm2(want))):
        keys = [getattr(k, "key", None) for k in path]
        group = next((name for key, name in GROUPS.items() if key in keys), "other")
        num[group] = num.get(group, 0.0) + float(n)
        den[group] = den.get(group, 0.0) + float(d)
    return {g: math.sqrt(num[g] / den[g]) for g in sorted(num) if den[g] > 0}


def seeded_state(params, seed, bias, norm_scale, qk_scale, maps=0.0):
    """``params`` a few thousand steps in, by the four options (module docstring)."""
    def leaf(path, a):
        key = getattr(path[-1], "key", None)
        inside = [getattr(k, "key", None) for k in path]
        if key == "a" and maps and ("hc_mixer" in inside or "hc_ffn" in inside):
            return jnp.full_like(a, maps)
        if key == "bias" and bias:
            return bias * jax.random.normal(jax.random.PRNGKey(seed), a.shape)
        if any(n in inside for n in ("q_norm", "k_norm", "q_a_norm", "kv_a_norm")) and norm_scale:
            return a + norm_scale * jax.random.normal(jax.random.PRNGKey(seed + 1), a.shape)
        if key in ("wq", "wk") and qk_scale != 1.0:
            return a * qk_scale
        if key == "wq_b" and qk_scale != 1.0:  # latent attention: the keys come through a norm; the scores' scale on the query
            return a * qk_scale ** 2
        return a

    return jax.tree_util.tree_map_with_path(leaf, params)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="smallthinker-21b-a3b")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3500003301)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--left-out", action="store_true")
    ap.add_argument("--variants", default="", help="--left-out: these of the reference's VARIANTS (comma-separated)")
    ap.add_argument("--bias", type=float, default=0.0, help="scale of a seeded selection bias (0: as initialised)")
    ap.add_argument("--norm-scale", type=float, default=0.0,
                    help="spread of the q and k norms' seeded scales about 1 (0: as initialised)")
    ap.add_argument("--qk-scale", type=float, default=1.0, help="the q and k projections times this")
    ap.add_argument("--maps", type=float, default=0.0,
                    help="the residual maps' three scalars a sublayer set to this (0: as initialised)")
    ap.add_argument("--both-states", action="store_true",
                    help="with a seeded state: read every seed at the initial parameters as well, in the same process")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cfg = Manifest().load_config(args.config)
    ref = references.load(cfg["family"])
    args.out = args.out or f"chiprun_out/{cfg['family']}_reference_check.json"
    seeded = bool(args.bias or args.norm_scale or args.qk_scale != 1.0 or args.maps)
    rc = dict(cfg["reference_check"])
    if args.seq_len:
        rc["seq_len"] = args.seq_len
    sizes = ref.sizes(cfg)
    bundle = get_model(cfg["registry_model"], **cfg["model_overrides"])
    ref.check_config(bundle.config, cfg)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    hp = ref.hyper(cfg)
    n_routed = bundle.config.n_experts
    model = sys.modules[type(bundle.config).__module__]  # the program's module of this family

    @jax.jit
    def program(params, tokens, targets):
        def f(p):
            batch = {"tokens": tokens, "targets": targets}
            if hasattr(model, "loss_fn"):  # a loss that draws its noise: keyed as the harness keys it
                loss, _, routes = model.loss_and_routes(p, batch, jax.random.PRNGKey(0), bundle.config)
            else:
                loss, _, routes = model.loss_and_routes(p, batch, bundle.config)
            return loss, routes

        (loss, routes), grads = jax.value_and_grad(f, has_aux=True)(params)
        return loss, grads, routes

    @functools.lru_cache(maxsize=None)  # a variant's program is compiled once, whatever the states it reads
    def reference_with(variant=None):
        return jax.jit(lambda p, t, y, r=None: jax.value_and_grad(ref.loss)(p, t, y, hp, r, False, variant))

    reference = reference_with()
    # as the harness calls it, and the routes it chose (one compilation for both)
    reference_alone = jax.jit(lambda p, t, y: jax.value_and_grad(
        lambda p: ref.loss(p, t, y, hp, None, True), has_aux=True)(p))
    rounded = {
        # bfloat16's 8 exponent and 7 mantissa bits, e4m3's 4 and 3 (a convert
        # there and back is folded away by the TPU compiler: it reads exactly 0)
        "bf16": jax.jit(lambda p: jax.tree_util.tree_map(
            lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7), p)),
        "e4m3": jax.jit(lambda p: jax.tree_util.tree_map(
            lambda a: jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3), p)),
    }

    rows = []
    # ``--both-states``: every seed at the initial parameters AND at the seeded ones, in one process (a variant's
    # program compiled once: a compile a variant is what a state costs)
    states = [False, True] if args.both_states and seeded else [seeded]
    for seed, seeded in ((seed, state) for seed in range(args.first_seed, args.first_seed + args.seeds)
                         for state in states):
        params = jax.jit(bundle.init)(jax.random.PRNGKey(seed))
        if seeded:
            params = seeded_state(params, seed, args.bias, args.norm_scale, args.qk_scale, args.maps)
        arrays = datagen.lm_arrays(seed + 0x5EED, 1, rc["seq_len"], sizes["vocab"])
        tok, tgt = arrays["tokens"][:1], arrays["targets"][:1]
        lp, gp, mine = program(params, tok, tgt)
        mine = np.asarray(mine)                                # [L, S, k]
        (lr, theirs), gr = reference_alone(params, tok, tgt)
        oh = lambda r: np.eye(n_routed, dtype=bool)[r].any(axis=-2)  # noqa: E731
        rec = {"seed": seed, "state": "seeded" if seeded else "initial", "seq_len": rc["seq_len"],
               "loss_program": float(lp),
               "flipped_share": float(1.0 - (oh(mine) & oh(np.asarray(theirs))).sum() / mine.size)}
        rec["loss_reference"] = float(lr)
        if seeded:
            rec["seeded"] = {"bias": args.bias, "norm_scale": args.norm_scale, "qk_scale": args.qk_scale,
                             "maps": args.maps}
            rec["by_group_no_routes"] = by_group(gp, gr)
        rec["grad_rel_err_no_routes"] = rel_err(gp, gr)
        rec["loss_abs_err_no_routes"] = abs(float(lp) - float(lr))
        per_leaf = sorted(
            ((math.sqrt(float(n) / float(d)), jax.tree_util.keystr(path))
             for (path, n), d in zip(jax.tree_util.tree_leaves_with_path(_diff2(gp, gr)),
                                     jax.tree_util.tree_leaves(_norm2(gr))) if float(d) > 0),
            reverse=True)
        rec["worst_leaves_no_routes"] = [[name, err] for err, name in per_leaf[:3]]
        del gr
        routes = jnp.asarray(mine)
        lr2, gr2 = reference(params, tok, tgt, routes)
        rec["grad_rel_err_with_routes"] = rel_err(gp, gr2)
        rec["loss_abs_err_with_routes"] = abs(float(lp) - float(lr2))
        if seeded:
            rec["by_group_with_routes"] = by_group(gp, gr2)
        del gp
        for name, to in rounded.items():
            lq, gq = reference(to(params), tok, tgt, routes)
            rec[f"{name}_params_grad_rel_err"] = rel_err(gq, gr2)
            rec[f"{name}_params_loss_abs_err"] = abs(float(lq) - float(lr2))
            del gq
        if args.left_out and seed == args.first_seed:
            for variant in (args.variants.split(",") if args.variants else ref.VARIANTS):
                lo, go = reference_with(variant)(params, tok, tgt, None if variant in OWN_ROUTES else routes)
                rec[f"{variant}_grad_rel_err"] = rel_err(go, gr2)
                rec[f"{variant}_loss_abs_err"] = abs(float(lo) - float(lr2))
                if seeded:
                    rec[f"{variant}_by_group"] = by_group(go, gr2)
                del go
        del gr2
        rec["device"] = device
        rows.append(rec)
        print(json.dumps(rec), flush=True)
        del params
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
