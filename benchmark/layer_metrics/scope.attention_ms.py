"""scope.attention_ms (ms): device time a step spends under the program's
scope ``attention`` (group attention of ``utils/step_scopes.VOCABULARY``):
the attention kernels AND the projections, norms, rotary and layout passes
the models put in the same ``jax.named_scope``, forward, recomputed forward
and backward. Each ``XLA Ops`` event's own time inside the whole executions
of the step's program on chip 0 goes to the group of its instruction's
``op_name`` in the step's compiled module (``benchmark/scope_trace.py``);
summed and divided by the executions. Layer: compiled step. Moves tok_s_chip.

A program that does not offer its scope map (a parent commit) gives nothing."""

from benchmark import scope_trace


def compute(run):
    return scope_trace.group_ms(run, "attention")
