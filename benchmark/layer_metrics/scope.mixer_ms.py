"""scope.mixer_ms (ms): device time a step spends under the scopes of the
token mixers that are not attention, ``kda``, ``conv_mixer`` and ``mamba``
(group mixer of ``utils/step_scopes.VOCABULARY``): the scan's loops or
kernels with the projections, convolutions, gates and norms of the same
block, all passes; reduced as ``scope.attention_ms`` is
(``benchmark/scope_trace.py``). Layer: compiled step. Moves tok_s_chip.

A program that does not offer its scope map gives nothing."""

from benchmark import scope_trace


def compute(run):
    return scope_trace.group_ms(run, "mixer")
