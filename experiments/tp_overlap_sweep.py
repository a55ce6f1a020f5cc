#!/usr/bin/env python
"""The dp x tp train step under candidate compiler options, timed on the chips.

    chiprun --chips 4 -- python experiments/tp_overlap_sweep.py

What ``parallel/train_step._TP_COMPILER_OPTIONS`` rests on. One process holds
the four chips, builds ``--model`` on ``--mesh`` with the optimizer the
four-chip cell runs (adam, lr 1e-3, clip 1.0), and for each variant jits the
sharded step anew with that variant's options, runs ``--steps`` steps on one
seeded batch and prints the median seconds a step between device-synced
points, the compile's seconds, and how many of a row stream's all-reduces the
compiled text holds as asynchronous pairs and how many on the instruction
stream. Variants: ``one-stream`` (``ops/attention.tp_streams`` held to 1, no
option: the step as it was before PR 57), ``one-stream-shipped`` (one stream
under the options in the code: what a model over ``tp`` that ``scan_blocks``
does not split pays or gains), ``two-streams`` (the split, no option),
``shipped`` (the options in the code), then every JSON object of
``--options`` laid over the shipped ones (``null`` takes a key out). One JSON
line a variant, all in ``chiprun_out/tp_overlap_sweep.jsonl``.

On the CPU (``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4``
with ``--override n_layers=2 --override d_model=64 --override n_heads=4
--override d_ff=128 --override max_len=32 --override vocab=128 --batch 8``) it
rehearses the paths: that backend's compiler is given no option.
"""

import argparse
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.ops import attention
from distributedvolunteercomputing_tpu.parallel import make_mesh, make_param_shardings, train_step
from distributedvolunteercomputing_tpu.parallel.mesh import parse_mesh_spec
from distributedvolunteercomputing_tpu.training.optim import make_optimizer
from distributedvolunteercomputing_tpu.training.steps import TrainState


def stream_all_reduces(text: str, rows: int):
    """(asynchronous pairs, on the instruction stream) among the all-reduces
    whose result is one ``[rows, T, d]`` activation."""
    shape = rf"bf16\[{rows},\d+,\d+\]"
    pairs = len(re.findall(rf"%async-collective-start[\w.]* = \({shape}", text))
    sync = [ln for ln in text.splitlines()
            if re.search(rf"= {shape}\S* all-reduce\(", ln) and "chain_id" not in ln]
    return pairs, len(sync)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2_large")
    ap.add_argument("--mesh", default="dp=2,tp=2")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--override", action="append", default=[], metavar="KEY=INT")
    ap.add_argument("--options", action="append", default=[], metavar="JSON",
                    help="a variant: compiler options laid over the shipped ones (null removes a key)")
    ap.add_argument("--only", default=None, help="comma-separated variant names to run")
    args = ap.parse_args()

    overrides = {k: int(v) for k, v in (o.split("=") for o in args.override)}
    mesh = make_mesh(**parse_mesh_spec(args.mesh))
    dp = mesh.shape["dp"]
    tx = make_optimizer("adam", lr=1e-3, total_steps=1_000_000)
    shipped = dict(train_step._TP_COMPILER_OPTIONS)
    variants = [("one-stream", {}, 1), ("one-stream-shipped", shipped, 1), ("two-streams", {}, None),
                ("shipped", shipped, None)]
    for i, text in enumerate(args.options):
        laid = {**shipped, **json.loads(text)}
        variants.append((f"options-{i}", {k: v for k, v in laid.items() if v is not None}, None))
    if args.only:
        variants = [v for v in variants if v[0] in args.only.split(",")]

    out_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    tp_streams = attention.tp_streams
    bundle = get_model(args.model, **overrides)
    # born sharded: the whole float32 tree never sits on one chip; every variant steps the same state on
    shardings = make_param_shardings(mesh, jax.eval_shape(bundle.init, jax.random.PRNGKey(args.seed)))
    params = jax.jit(bundle.init, out_shardings=shardings)(jax.random.PRNGKey(args.seed))
    state, _ = train_step.shard_train_state(TrainState.create(params, tx, jax.random.PRNGKey(1)), mesh, tx)
    batch = train_step.put_batch(bundle.make_batch(jax.random.PRNGKey(args.seed + 1), args.batch), mesh)
    with open(os.path.join(out_dir, "tp_overlap_sweep.jsonl"), "w") as fh:
        for name, options, held in variants:
            bundle = get_model(args.model, **overrides)  # a traced loss is cached by its function
            train_step._TP_COMPILER_OPTIONS = options if on_tpu else {}
            if held is not None:
                attention.tp_streams = lambda rows, held=held: held
            try:
                step = train_step.make_sharded_train_step(bundle.loss_fn, tx, mesh, stepped=bundle.stepped)
                t0 = time.perf_counter()
                compiled = step.lower(state, batch).compile()
                compile_s = time.perf_counter() - t0
            except Exception as e:  # noqa: BLE001 — an option this compiler refuses: say so, go on
                line = {"variant": name, "options": options, "error": str(e)[:300]}
                print(json.dumps(line), flush=True)
                fh.write(json.dumps(line) + "\n")
                continue
            finally:
                attention.tp_streams = tp_streams
                train_step._TP_COMPILER_OPTIONS = shipped
            text = compiled.as_text()
            rows = args.batch // dp // (1 if held == 1 or args.batch % (2 * dp) else 2)
            pairs, sync = stream_all_reduces(text, rows)
            state, metrics = compiled(state, batch)  # warm: the first run of a loaded program
            jax.block_until_ready(metrics["loss"])
            times = []
            for _ in range(args.steps):
                t0 = time.perf_counter()
                state, metrics = compiled(state, batch)
                jax.block_until_ready(metrics["loss"])
                times.append(time.perf_counter() - t0)
            line = {
                "variant": name, "options": options, "model": args.model, "mesh": args.mesh, "batch": args.batch,
                "device": {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()},
                "step_s_median": statistics.median(times), "step_s_min": min(times), "compile_s": compile_s,
                "stream_rows": rows, "stream_all_reduces": {"asynchronous_pairs": pairs, "on_the_stream": sync},
                "loss": float(metrics["loss"]),
                "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            }
            print(json.dumps(line), flush=True)
            fh.write(json.dumps(line) + "\n")
            del compiled, step
    return 0


if __name__ == "__main__":
    sys.exit(main())
