#!/usr/bin/env python
"""The loss head that makes its gradients in its loss's loop against the
checkpointed loop under autodiff that it replaced (PR 61), on the chips.

    chiprun --chips 4 -- python experiments/loss_head_check.py

``experiments/qkv_over_tp_check.py``'s form: loss and gradients of ``--model``
on its initial parameters and one seeded batch, traced under the step's mesh
and compiled with the sharded step's own options, once as the tree has it
(``models/common.lm_xent_chunked``: a ``custom_vjp``, one scan, three
vocabulary-sized products a chunk) and once with the head held to what it was
until PR 60 (``checkpointed_head`` below: the scan under ``jax.checkpoint`` and
plain autodiff, four products and two loops), by the reference check's own
measures (loss difference, relative error of the whole gradient and of its
worst leaf). Two compilations of this step differ by 0.0096-0.0103 in the
gradient (PERF.md section 6, PR 43 and PR 57): the two heads should stand no
further apart. Each program's all-reduces of the head's gradient (float32, the
head's shape) are counted from its optimized text: two for gpt2_large over
``dp=2,tp=2`` before, and the loop's carried sum must not have made it one a
chunk. One JSON line, also in ``chiprun_out/loss_head_check.json``.

On the CPU (``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4``
with ``--override n_layers=2 --override d_model=64 --override n_heads=4
--override d_ff=128 --override max_len=32 --override vocab=128 --override
xent_chunk=8 --batch 8``) it rehearses the paths, not the numbers.
"""

import argparse
import json
import math
import os
import re
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


@jax.named_scope("loss_head")
def checkpointed_head(x, head, labels, mask=None, chunk=128, head_layout="vd", denominator=None):
    """``lm_xent_chunked`` as it stood until PR 60: the chunks scanned under
    ``jax.checkpoint`` and differentiated by autodiff (a chunk's logits made
    again in a second, backward loop)."""
    b, t, _ = x.shape
    if t % chunk != 0:
        chunk = t
    n = t // chunk
    xs = jnp.moveaxis(x.reshape(b, n, chunk, x.shape[-1]), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, n, chunk), 1, 0)
    ms = jnp.moveaxis((jnp.ones((b, t)) if mask is None else mask).astype(jnp.float32).reshape(b, n, chunk), 1, 0)

    def body(carry, xc_lc_mc):
        nll_sum, denom = carry
        xc, lc, mc = xc_lc_mc
        logits = common._project_vocab(xc, head, head_layout)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return (nll_sum + jnp.sum(nll * mc), denom + jnp.sum(mc)), None

    zero = jnp.zeros((), jnp.float32)
    (nll_sum, denom), _ = jax.lax.scan(jax.checkpoint(body), (zero, zero), (xs, ls, ms))
    return nll_sum / (jnp.maximum(denom, 1.0) if denominator is None else denominator)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2_large")
    ap.add_argument("--mesh", default="dp=2,tp=2")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--override", action="append", default=[], metavar="KEY=INT")
    args = ap.parse_args()

    overrides = {k: int(v) for k, v in (o.split("=") for o in args.override)}
    mesh = make_mesh(**parse_mesh_spec(args.mesh))
    init = get_model(args.model, **overrides).init
    shardings = make_param_shardings(mesh, jax.eval_shape(init, jax.random.PRNGKey(args.seed)))
    # born sharded: the whole float32 tree never sits on one chip
    params = jax.jit(init, out_shardings=shardings)(jax.random.PRNGKey(args.seed))
    batch = jax.device_put(
        get_model(args.model, **overrides).make_batch(jax.random.PRNGKey(args.seed + 1), args.batch), batch_sharding(mesh)
    )
    head_shape = max((leaf.shape for leaf in jax.tree_util.tree_leaves(params) if leaf.ndim == 2), key=math.prod)
    head_all_reduce = re.compile(r" = f32\[%d,%d\]\S* all-reduce(-start)?\(" % head_shape)

    def loss_and_grads_and_text():
        bundle = get_model(args.model, **overrides)  # a new bundle each time: jit caches traces by function

        def loss_and_grads(params, batch):
            with attention.step_mesh(mesh):  # what parallel/train_step.py announces
                return jax.value_and_grad(lambda p: bundle.loss_fn(p, batch, jax.random.PRNGKey(0))[0])(params)

        compiled = jax.jit(loss_and_grads, compiler_options=step_compiler_options(mesh)).lower(params, batch).compile()
        text = compiled.as_text()
        described = {
            "head_all_reduces": len(head_all_reduce.findall(text)),
            "head_products": len(re.findall(r' (?:convolution|dot)\([^\n]*op_name="[^"]*loss_head', text)),
            "head_recomputed": len(re.findall(r'op_name="[^";]*loss_head[^";]*rematted_computation', text)),
        }
        return compiled(params, batch), described

    (loss_new, grads_new), described_new = loss_and_grads_and_text()
    shipped, common.lm_xent_chunked = common.lm_xent_chunked, checkpointed_head
    try:
        (loss_old, grads_old), described_old = loss_and_grads_and_text()
    finally:
        common.lm_xent_chunked = shipped

    @jax.jit
    def compare(got, want):
        num = jax.tree_util.tree_map(
            lambda a, b: jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2), got, want
        )
        return num, jax.tree_util.tree_map(lambda b: jnp.sum(b.astype(jnp.float32) ** 2), want)

    num, den = compare(grads_new, grads_old)
    num = [float(x) for x in jax.tree_util.tree_leaves(num)]
    den = [float(x) for x in jax.tree_util.tree_leaves(den)]
    dev = jax.devices()[0]
    result = {
        "model": args.model, "mesh": args.mesh, "batch": args.batch, "seed": args.seed,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()},
        "compiler_options": step_compiler_options(mesh),
        "head_shape": list(head_shape), "in_loop": described_new, "checkpointed": described_old,
        "loss_in_loop": float(loss_new), "loss_checkpointed": float(loss_old),
        "loss_abs_err": abs(float(loss_new) - float(loss_old)),
        "grad_rel_err": math.sqrt(sum(num) / sum(den)),
        "worst_leaf_rel_err": max(math.sqrt(n / d) for n, d in zip(num, den) if d > 0),
    }
    line = json.dumps(result)
    out_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "loss_head_check.json"), "w") as f:
        f.write(line + "\n")
    print(line)
    ok = (
        result["loss_abs_err"] <= 0.005 and result["grad_rel_err"] <= 0.04
        and described_new["head_all_reduces"] == described_old["head_all_reduces"]
        and described_new["head_products"] == 3 and described_new["head_recomputed"] == 0
        and described_old["head_products"] == 4 and described_old["head_recomputed"] > 0
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
