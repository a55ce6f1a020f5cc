"""scope.mlp_ms (ms): device time a step spends under the scope ``mlp`` (a
dense block's feed-forward network with its norm and residual), all passes;
reduced as ``scope.attention_ms`` is (``benchmark/scope_trace.py``). Layer:
compiled step. Moves tok_s_chip.

A program that does not offer its scope map gives nothing."""

from benchmark import scope_trace


def compute(run):
    return scope_trace.group_ms(run, "mlp")
