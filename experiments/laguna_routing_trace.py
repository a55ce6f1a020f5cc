"""What the router sends one chip's share while the model trains: per step of
a cell's first steps (laguna-solo-8k's by default; ``--config
smallthinker-21b-a3b`` for smallthinker-solo-16k's), the assignments on held
experts (all expert layers together), the rows the dispatch gathered for them,
and the fullest held expert: the reading ``SHARE_ROWS_SLACK`` in
ops/moe_dispatch.py rests on. For a model whose step counts its experts'
assignments by layer (``--config lfm2-24b-a2b``: a levelled router) also every
LAYER's held assignments at every step against the chunk's rows in force, and
the chunks each step ran beyond one a layer: the reading
``SHARE_ROWS_SLACK_LEVELLED`` rests on.

    chiprun -- python experiments/laguna_routing_trace.py --seeds 2
    chiprun -- python experiments/laguna_routing_trace.py --config smallthinker-21b-a3b --seeds 3 --steps 60
    chiprun -- python experiments/laguna_routing_trace.py --config lfm2-24b-a2b --seeds 4 --steps 110
    python experiments/laguna_routing_trace.py --config tiny-rehearsal-laguna --steps 12 --seeds 1

One JSON line per seed in ``chiprun_out/laguna_routing_trace.json``; with
``--steps`` past ``--settled-from`` also the settled state's mean and most. It syncs
every step (``log_every=1``), so its step times are not the cell's."""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import dataclasses

import jax
import jax.numpy as jnp

from benchmark import datagen, references
from benchmark.manifest import Manifest
from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.ops import moe_dispatch
from distributedvolunteercomputing_tpu.training.data import npz_batch_iter
from distributedvolunteercomputing_tpu.training.trainer import Trainer


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="laguna-xs2")
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2300003301)
    ap.add_argument("--steps", type=int, default=45)
    ap.add_argument("--settled-from", type=int, default=100,
                    help="summarise the steps from this one on (a run of a few hundred steps)")
    ap.add_argument("--window-from", type=int, default=5,
                    help="hold the layers' held assignments against the bound from this step on (the cell's "
                         "window opens after its warm-up steps)")
    ap.add_argument("--warmup-steps", type=int, default=None,
                    help="LR warm-up other than the configuration's (volunteer.warmup_steps, else 0)")
    ap.add_argument("--out", default="chiprun_out/laguna_routing_trace.json")
    args = ap.parse_args()
    cfg = Manifest().load_config(args.config)
    sizes = references.load(cfg["family"]).sizes(cfg)
    dev = jax.devices()[0]
    rows = []
    with tempfile.TemporaryDirectory(prefix="laguna_routing_") as workdir:  # under TMPDIR: the run's own ground
        for seed in range(args.first_seed, args.first_seed + args.seeds):
            rows.append(one_seed(args, cfg, sizes, dev, workdir, seed))
            print(json.dumps(rows[-1]), flush=True)
    by_layer_all = [step for r in rows for step in r.get("held_by_layer", [])[args.window_from:]]
    if by_layer_all:  # every seed's layer-steps together: the reading the levelled bound's margin rests on
        print(json.dumps({"all_seeds": against_the_bound(by_layer_all, rows[0]["chunk_rows"]),
                          "chunks_extra": sum(sum(r["chunks_extra"]) for r in rows)}), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(rows, fh, indent=1)
    return 0


KEPT = ("loss", "moe_rows_held", "moe_rows_moved", "moe_load_max", "moe_chunks_extra",
        "moe_bias_max", "moe_bias_min", "moe_bias_moved")
HELD_IN_LAYER = "held_in_layer_"


def by_layer(bundle):
    """``bundle``, its loss function also giving every expert layer's held
    assignments as a scalar of its own, where the step's metrics count the
    experts' assignments by layer (the signal a stepped rule reads, ``[L, E]``:
    the step takes it out before the loop sees it); else as it came."""
    if bundle.stepped is None:
        return bundle
    c, key = bundle.config, bundle.stepped.signal

    def loss_fn(params, batch, rng):
        loss, m = bundle.loss_fn(params, batch, rng)
        held = jnp.sum(m[key][:, c.expert_offset:c.expert_offset + c.experts_held], axis=1)
        return loss, {**m, **{f"{HELD_IN_LAYER}{i}": held[i] for i in range(held.shape[0])}}

    return dataclasses.replace(bundle, loss_fn=loss_fn)


def against_the_bound(held_by_layer, bound):
    """Where the layer-steps' held assignments lie against one chunk's rows."""
    flat = sorted(h for step in held_by_layer for h in step)
    if not flat:
        return {}
    at = lambda q: flat[min(len(flat) - 1, int(q * len(flat)))]
    return {"layer_steps": len(flat), "min": flat[0], "p01": at(0.01), "p50": at(0.5), "p99": at(0.99),
            "max": flat[-1], "over_the_bound": sum(h > bound for h in flat), "chunk_rows": bound}


def one_seed(args, cfg, sizes, dev, workdir, seed):
    """Train ``--steps`` steps on one seed; what the router sent the share at each."""
    vol = cfg["volunteer"]
    path = datagen.write_token_file(
        os.path.join(workdir, f"tokens_{seed}.npz"), seed, 256, sizes["seq_len"], sizes["vocab"])
    bundle = by_layer(get_model(cfg["registry_model"], **cfg["model_overrides"]))
    warm = vol.get("warmup_steps", 0) if args.warmup_steps is None else args.warmup_steps
    tr = Trainer(bundle, batch_size=vol["batch_size"], optimizer=vol["optimizer"], lr=vol["lr"],
                 total_steps=vol["steps"], warmup_steps=warm, data=npz_batch_iter(path, vol["batch_size"], seed=seed),
                 seed=seed, init_seed=seed)
    steps = []
    inner = tr.metrics.record
    tr.metrics.record = lambda step, m, n_samples=0: (
        steps.append({k: float(m[k]) for k in m if k in KEPT or k.startswith(HELD_IN_LAYER)}),
        inner(step, m, n_samples=n_samples))
    tr.run(steps=args.steps, log_every=1)
    c = bundle.config
    # the expert layers: as the step counted them by layer, else all but the leading dense ones
    n_sparse = sum(k.startswith(HELD_IN_LAYER) for k in steps[0]) or c.n_layers - getattr(c, "dense_layers", 0)
    slack = getattr(sys.modules[type(c).__module__], "SHARE_ROWS_SLACK", None)  # the model's own, if it brings one
    bound = moe_dispatch.share_rows_bound(
        vol["batch_size"] * c.max_len, c.top_k, c.experts_held, c.n_experts, slack)
    rec = {"seed": seed, "warmup_steps": warm, "device": {"platform": dev.platform, "kind": dev.device_kind},
           "chunk_rows": bound, "expert_layers": n_sparse,
           "even_share_a_layer": vol["batch_size"] * c.max_len * c.top_k * c.experts_held / c.n_experts,
           "held_a_layer": [round(s["moe_rows_held"] / n_sparse) for s in steps],
           "chunks": [round(s["moe_rows_moved"] / bound) for s in steps],
           "load_max": [s["moe_load_max"] for s in steps],
           "loss": [round(s["loss"], 3) for s in steps]}
    if "moe_bias_max" in steps[0]:  # a router with a selection bias: what the step chose with, and how many it moved
        rec["bias_max"] = [round(s["moe_bias_max"], 4) for s in steps]
        rec["bias_min"] = [round(s["moe_bias_min"], 4) for s in steps]
        rec["bias_moved"] = [round(s["moe_bias_moved"]) for s in steps]
    if "moe_chunks_extra" in steps[0]:  # a chunk sized for a levelled router: how often it was not enough
        rec["chunks_extra"] = [round(s["moe_chunks_extra"]) for s in steps]
    if f"{HELD_IN_LAYER}0" in steps[0]:
        rec["held_by_layer"] = [[round(s[f"{HELD_IN_LAYER}{i}"]) for i in range(n_sparse)] for s in steps]
        rec["against_the_bound"] = {"from_step": args.window_from,
                                    **against_the_bound(rec["held_by_layer"][args.window_from:], bound)}
    settled = rec["held_a_layer"][args.settled_from:]
    if settled:  # the state a donor lives in, past the router's first steps
        rec["settled"] = {"from_step": args.settled_from, "steps": len(settled),
                          "held_a_layer_mean": sum(settled) / len(settled),
                          "held_a_layer_max": max(settled), "chunks_max": max(rec["chunks"][args.settled_from:])}
    del tr
    return rec


if __name__ == "__main__":
    sys.exit(main())
