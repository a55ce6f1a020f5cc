"""Benchmark: samples/sec/volunteer-chip on one train-step configuration.

One process, one configuration, one JSON line:
    {"metric": ..., "value": N, "unit": ..., "platform": "tpu",
     "device_kind": ..., "device_count": N, ...extras}

Metric per BASELINE.json:2 (samples/sec/volunteer-chip). The configuration
comes from the command line, with the DVC_BENCH_* variables as defaults:
    python bench.py --model gpt2_small --batch 8 --iters 20

A measurement path that finds no chip fails: the script exits non-zero,
printing no result, when the device is not a TPU, and when the chip's
``device_kind`` is missing from the peak table below.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

# Peak dense bf16 FLOP/s per chip, keyed by the EXACT ``device_kind`` jax
# reports. A kind that is not here is an error, never a default.
_PEAK_BF16 = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip.
    "TPU v5 lite": 197e12,
}


def _parse_model_kw(spec: str) -> dict:
    """"k=v,k=v" model-config overrides (values JSON-parsed, the same k=v
    semantics as run_volunteer.py --model-override)."""
    kw: dict = {}
    for part in filter(None, spec.split(",")):
        k, _, v = part.partition("=")
        try:
            kw[k.strip()] = json.loads(v.strip())
        except ValueError:
            kw[k.strip()] = v.strip()
    return kw


def main() -> int:
    env = os.environ.get
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default=env("DVC_BENCH_MODEL", "gpt2_small"))
    ap.add_argument("--model-kw", default=env("DVC_BENCH_MODEL_KW", ""),
                    help="k=v,k=v config overrides; named in the metric, "
                         "since a changed config is a different metric")
    ap.add_argument("--batch", type=int, default=int(env("DVC_BENCH_BATCH", "8")),
                    help="micro-batch per compiled step")
    ap.add_argument("--accum", type=int, default=int(env("DVC_BENCH_ACCUM", "1")),
                    help="gradient-accumulation micro-batches per step")
    ap.add_argument("--param-dtype", default=env("DVC_BENCH_PARAM_DTYPE", ""))
    ap.add_argument("--steps-per-call", type=int,
                    default=int(env("DVC_BENCH_STEPS_PER_CALL", "1")),
                    help="scan N steps per dispatch (training/steps.py "
                         "make_multi_step — the same traced body)")
    ap.add_argument("--warmup", type=int, default=int(env("DVC_BENCH_WARMUP", "3")))
    ap.add_argument("--iters", type=int, default=int(env("DVC_BENCH_ITERS", "20")))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributedvolunteercomputing_tpu.utils.jaxenv import (
        device_record,
        enable_compile_cache,
    )

    device = device_record()
    if device["platform"] != "tpu":
        print(f"bench: device platform is {device['platform']!r}, not 'tpu'; "
              "nothing measured", file=sys.stderr)
        return 1
    if device["device_kind"] not in _PEAK_BF16:
        print(f"bench: device_kind {device['device_kind']!r} is not in the "
              f"peak table {sorted(_PEAK_BF16)}; add it with its source",
              file=sys.stderr)
        return 1

    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.training.optim import make_optimizer
    from distributedvolunteercomputing_tpu.training.steps import (
        TrainState,
        make_multi_step,
        make_train_step,
    )
    from distributedvolunteercomputing_tpu.utils.pytree import cast_floating

    cache_dir = enable_compile_cache()

    eff_batch = args.batch * args.accum
    bundle = get_model(args.model, **_parse_model_kw(args.model_kw))
    tx = make_optimizer("adamw", lr=1e-4)
    params = bundle.init(jax.random.PRNGKey(1))
    if args.param_dtype:
        params = cast_floating(params, args.param_dtype)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    state = TrainState.create(params, tx, jax.random.PRNGKey(2))
    del params  # donated into state's first step
    batch = bundle.make_batch(jax.random.PRNGKey(0), eff_batch)

    spc = max(args.steps_per_call, 1)
    if spc > 1:
        step = make_multi_step(bundle.loss_fn, tx, accum_steps=args.accum)
        batch = jax.tree_util.tree_map(lambda x: jnp.stack([x] * spc), batch)
    else:
        step = make_train_step(bundle.loss_fn, tx, accum_steps=args.accum)
    calls = max(args.iters // spc, 1)

    def last_loss(out):
        """The multi step returns per-step losses, the single a metrics dict."""
        return out[-1] if spc > 1 else out["loss"]

    t0 = time.perf_counter()
    state, out = step(state, batch)
    jax.block_until_ready(out)
    first_call_s = time.perf_counter() - t0
    for _ in range(max(args.warmup, 1) - 1):
        state, out = step(state, batch)
    jax.block_until_ready(out)

    t0 = time.perf_counter()
    for _ in range(calls):
        state, out = step(state, batch)
    jax.block_until_ready(out)
    dt_s = time.perf_counter() - t0
    final_loss = float(last_loss(out))
    if not math.isfinite(final_loss):
        print(f"bench: non-finite loss {final_loss}", file=sys.stderr)
        return 1

    # The single-volunteer step runs on the default device only; divide by the
    # devices the computation actually uses, not everything visible.
    n_chips = len(last_loss(out).sharding.device_set)
    samples_per_sec_chip = eff_batch * calls * spc / dt_s / n_chips
    kw_tag = f", {args.model_kw}" if args.model_kw else ""
    payload = {
        "metric": f"samples/sec/volunteer-chip ({args.model}{kw_tag}, bs={eff_batch})",
        "value": round(samples_per_sec_chip, 3),
        "unit": "samples/sec/chip",
        **device,
        "n_chips": n_chips,
        "batch_size": eff_batch,
        "accum_steps": args.accum,
        "steps_per_call": spc,
        "iters": calls * spc,
        "step_ms": round(dt_s / (calls * spc) * 1e3, 3),
        "first_call_s": round(first_call_s, 2),  # trace + compile + one step
        "compile_cache_dir": cache_dir,
        "loss": round(final_loss, 4),
        "n_params": n_params,
        "param_dtype": args.param_dtype or "float32",
        "attn_impl": os.environ.get("DVC_ATTN_IMPL", "auto"),
        "peak_bytes_in_use": (
            jax.local_devices()[0].memory_stats() or {}
        ).get("peak_bytes_in_use"),
    }
    seq_len = getattr(bundle.config, "max_len", None)
    if seq_len:
        tokens_per_sec = samples_per_sec_chip * seq_len
        payload["tokens_per_sec_chip"] = round(tokens_per_sec, 1)
        # 6ND convention (fwd 2ND + bwd 4ND); remat recompute not counted.
        payload["est_mfu"] = round(
            6.0 * n_params * tokens_per_sec / _PEAK_BF16[device["device_kind"]], 4
        )
    print(json.dumps(payload), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
