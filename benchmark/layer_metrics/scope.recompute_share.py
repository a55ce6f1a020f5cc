"""scope.recompute_share (%): of the own op time inside the whole executions
of the step's program on chip 0, the share whose instruction's ``op_name``
holds ``rematted_computation`` (pass ``refwd`` of the program's scope map):
the forward a checkpointed layer runs again in the backward pass, what the
checkpoints cost (``benchmark/scope_trace.py``). Layer: compiled step. Moves
tok_s_chip.

A program that does not offer its scope map gives nothing."""

from benchmark import scope_trace


def compute(run):
    return scope_trace.share(run, "refwd")
