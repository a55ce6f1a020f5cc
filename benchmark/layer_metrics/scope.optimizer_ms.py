"""scope.optimizer_ms (ms): device time a step spends under the scope
``optimizer`` (``training/steps.apply_half``, ``parallel/train_step.py``: the
update of every leaf and the re-constraint of sharded moments); reduced as
``scope.attention_ms`` is (``benchmark/scope_trace.py``). Layer: compiled
step. Moves tok_s_chip.

A program that does not offer its scope map gives nothing."""

from benchmark import scope_trace


def compute(run):
    return scope_trace.group_ms(run, "optimizer")
