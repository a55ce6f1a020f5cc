"""scope.loss_head_ms (ms): device time a step spends under the scope
``loss_head`` (``models/common.py``: the chunked output projection and the
cross-entropy, forward, recomputed and backward); reduced as
``scope.attention_ms`` is (``benchmark/scope_trace.py``). Layer: compiled
step. Moves tok_s_chip.

A program that does not offer its scope map gives nothing."""

from benchmark import scope_trace


def compute(run):
    return scope_trace.group_ms(run, "loss_head")
