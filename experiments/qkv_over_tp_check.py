#!/usr/bin/env python
"""The step's qkv projection over ``tp`` against the no-mesh one, on the chips.

    chiprun --chips 4 -- python experiments/qkv_over_tp_check.py

``benchmark/probe.py:reference_check`` jits the loss outside any step builder,
so on four chips it traces with no step mesh and holds ``models/common.qkv_heads``'
plain branch (three products off the leaf's column ranges, the XLA core) to the
float32 reference; the branch a ``dp=2,tp=2`` volunteer's step takes (the three
products column-parallel off the leaf's head-aligned view, the pair kernels per
shard of ``tp``, merged since PR 71; by head before) it never sees. This script
closes that: loss and gradients of ``--model`` on its initial parameters and
one seeded batch, traced under the step's mesh, against the same trace with no
step mesh, by the reference check's own measures (loss difference, relative
error of the whole gradient and of its worst leaf). With ``--reference
benchmark/configs/gpt2-large.json`` (and ``--batch 2 --seq-len 512``, the
reference check's size: the float32 reference keeps every T x T tensor) both
are also held to the benchmark's plain float32 reference, which says how much
of their distance from each other is bf16 rounding met in another order. The
step's trace also keeps ``attn_out``'s result after its sum over ``tp``
(``ops/attention.keep_tp_reduced``, PR 43): a third trace under a bare
``jax.checkpoint`` a layer (nothing kept) says under ``kept_against_bare`` what
the keep alone moves. And it runs each replica's rows as two independent
streams where ``tp`` divides the layer (``models/common.scan_blocks``,
``ops/attention.tp_streams``, PR 57) under the sharded step's own compiler
options (``parallel/train_step.step_compiler_options``): a fourth trace with
``tp_streams`` held to 1 says under ``two_streams_against_one`` what the split
moves (the order in which a weight gradient sums its rows). One JSON line, also
in ``chiprun_out/qkv_over_tp_check.json``; the script reads whichever form the
tree it runs in traces (``traced.step``), so a parent's tree gives the line to
compare with. What each form read: PERF.md section 6.

On the CPU (``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4``
with ``--override n_layers=2 --override d_model=64 --override n_heads=4
--override d_ff=128 --override max_len=32 --override vocab=128 --batch 8``)
it rehearses the paths, not the numbers.
"""

import argparse
import contextlib
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common, get_model
from distributedvolunteercomputing_tpu.ops import attention
from distributedvolunteercomputing_tpu.parallel import make_mesh, make_param_shardings
from distributedvolunteercomputing_tpu.parallel.mesh import parse_mesh_spec
from distributedvolunteercomputing_tpu.parallel.sharding import batch_sharding
from distributedvolunteercomputing_tpu.parallel.train_step import step_compiler_options
from distributedvolunteercomputing_tpu.utils import traced


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2_large")
    ap.add_argument("--mesh", default="dp=2,tp=2")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--override", action="append", default=[], metavar="KEY=INT")
    ap.add_argument("--seq-len", type=int, default=None, help="cut the batch's sequences to this")
    ap.add_argument("--reference", default=None, metavar="CONFIG.json",
                    help="a benchmark configuration whose family's float32 reference to hold both to")
    args = ap.parse_args()

    overrides = {k: int(v) for k, v in (o.split("=") for o in args.override)}
    bundle = get_model(args.model, **overrides)
    mesh = make_mesh(**parse_mesh_spec(args.mesh))
    shardings = make_param_shardings(
        mesh, jax.eval_shape(bundle.init, jax.random.PRNGKey(args.seed))
    )
    # born sharded: the whole float32 tree never sits on one chip
    params = jax.jit(bundle.init, out_shardings=shardings)(jax.random.PRNGKey(args.seed))
    batch = bundle.make_batch(jax.random.PRNGKey(args.seed + 1), args.batch)
    batch = jax.device_put(
        jax.tree_util.tree_map(lambda a: a[:, :args.seq_len], batch), batch_sharding(mesh)
    )
    # what the traces note (utils/traced.py), one entry a trace: the ``tp`` the qkv projection's heads were divided over; a chip's
    # bytes kept a step, where a layer kept something; the row streams a layer scan ran
    layouts, kept, streams = [], [], []
    gathered = {"qkv_projection": ("tp", layouts), "remat_kept": ("bytes", kept), "tp_streams": ("streams", streams)}

    def gather(kind, said):
        if kind in gathered:
            label, values = gathered[kind]
            values.append(said[label])

    listening = traced.subscribe(gather)

    def make_loss_and_grads(bundle=bundle, in_step=True):  # a new function each time: jit caches traces by function
        def loss_and_grads(params, batch):
            # what parallel/train_step.py announces; without it, what the benchmark's reference check traces
            with attention.step_mesh(mesh) if in_step else contextlib.nullcontext():
                return jax.value_and_grad(
                    lambda p: bundle.loss_fn(p, batch, jax.random.PRNGKey(0))[0]
                )(params)

        return jax.jit(loss_and_grads, compiler_options=step_compiler_options(mesh) if in_step else None)

    @jax.jit
    def compare(got, want):
        num = jax.tree_util.tree_map(
            lambda a, b: jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2), got, want
        )
        return num, jax.tree_util.tree_map(lambda b: jnp.sum(b.astype(jnp.float32) ** 2), want)

    loss_step, grads_step = make_loss_and_grads()(params, batch)
    traced_step = list(layouts)
    loss_plain, grads_plain = make_loss_and_grads(get_model(args.model, **overrides), in_step=False)(params, batch)
    traced_plain = layouts[len(traced_step):]

    def rel_errs(got, want):
        num, den = compare(got, want)
        num = [float(x) for x in jax.tree_util.tree_leaves(num)]
        den = [float(x) for x in jax.tree_util.tree_leaves(den)]
        return (math.sqrt(sum(num) / sum(den)),
                max(math.sqrt(n / d) for n, d in zip(num, den) if d > 0))

    grad_rel_err, worst_leaf_rel_err = rel_errs(grads_step, grads_plain)
    if not args.reference:
        del grads_plain  # room for the third trace's gradients beside the step's temporaries
    # the step again as ONE row stream (a new bundle: a traced loss is cached)
    tp_streams, attention.tp_streams = attention.tp_streams, lambda rows: 1
    try:
        loss_one, grads_one = make_loss_and_grads(get_model(args.model, **overrides))(params, batch)
    finally:
        attention.tp_streams = tp_streams
    one_grad_rel_err, one_worst_leaf_rel_err = rel_errs(grads_step, grads_one)
    del grads_one
    # the step again with nothing kept
    remat_layer, common.remat_layer = common.remat_layer, lambda body, *layers_and_calls: jax.checkpoint(body)
    try:
        loss_bare, grads_bare = make_loss_and_grads(get_model(args.model, **overrides))(params, batch)
    finally:
        common.remat_layer = remat_layer
    kept_grad_rel_err, kept_worst_leaf_rel_err = rel_errs(grads_step, grads_bare)
    del grads_bare
    dev = jax.devices()[0]
    result = {
        "model": args.model, "mesh": args.mesh, "batch": args.batch, "seed": args.seed,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()},
        "traced": {"step": traced_step, "no_mesh": traced_plain, "kept_bytes": kept, "streams": streams},
        "compiler_options": step_compiler_options(mesh),
        "loss_step": float(loss_step), "loss_no_mesh": float(loss_plain),
        "loss_abs_err": abs(float(loss_step) - float(loss_plain)),
        "grad_rel_err": grad_rel_err, "worst_leaf_rel_err": worst_leaf_rel_err,
        "two_streams_against_one": {
            "loss_abs_err": abs(float(loss_step) - float(loss_one)),
            "grad_rel_err": one_grad_rel_err, "worst_leaf_rel_err": one_worst_leaf_rel_err,
        },
        "kept_against_bare": {
            "loss_abs_err": abs(float(loss_step) - float(loss_bare)),
            "grad_rel_err": kept_grad_rel_err, "worst_leaf_rel_err": kept_worst_leaf_rel_err,
        },
    }
    if args.reference:
        from benchmark import references

        with open(args.reference) as fh:
            file_cfg = json.load(fh)
        loss_ref, grads_ref = jax.jit(references.load(file_cfg["family"]).make_loss_and_grad(file_cfg))(
            params, batch["tokens"], batch["targets"]
        )
        result["against_float32_reference"] = {
            name: {"loss_abs_err": abs(float(loss) - float(loss_ref)),
                   "grad_rel_err": rel_errs(grads, grads_ref)[0]}
            for name, loss, grads in (("step", loss_step, grads_step),
                                      ("no_mesh", loss_plain, grads_plain))
        }
    line = json.dumps(result)
    out_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "qkv_over_tp_check.json"), "w") as f:
        f.write(line + "\n")
    print(line)
    ok = (
        set(traced_step) == {mesh.shape["tp"]} and set(traced_plain) == {1}  # over tp inside the step, plain without
        and result["loss_abs_err"] <= 0.005 and result["grad_rel_err"] <= 0.04
        and result["two_streams_against_one"]["loss_abs_err"] <= 0.005
        and result["two_streams_against_one"]["grad_rel_err"] <= 0.04
        and result["kept_against_bare"]["loss_abs_err"] <= 0.005
        and result["kept_against_bare"]["grad_rel_err"] <= 0.04
    )
    listening.close()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
