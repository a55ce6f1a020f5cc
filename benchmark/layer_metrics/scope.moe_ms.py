"""scope.moe_ms (ms): device time a step spends under the scopes ``moe`` and
``moe_route`` (group moe of ``utils/step_scopes.VOCABULARY``): the router,
the dispatch, the grouped products, the shared expert and the block's norm and
residual, all passes; reduced as ``scope.attention_ms`` is
(``benchmark/scope_trace.py``). By the program's scope, not by the shape of a
result as ``moe.device_ms`` and ``moe.share_device_ms`` tell it. Layer:
compiled step. Moves tok_s_chip.

A program that does not offer its scope map gives nothing."""

from benchmark import scope_trace


def compute(run):
    return scope_trace.group_ms(run, "moe")
