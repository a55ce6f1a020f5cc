"""scope.other_ms (ms): device time a step spends in instructions the scope
map resolves but that are under no word of the vocabulary: the embedding, the
final norm, the layer scan's own slices and stack updates, the gradient's
norm, casts hoisted out of a block; reduced as ``scope.attention_ms`` is
(``benchmark/scope_trace.py``). With the six named groups it sums to the
steps' busy time less what ``scope.unresolved_share`` holds. Layer: compiled
step. Moves tok_s_chip.

A program that does not offer its scope map gives nothing."""

from benchmark import scope_trace


def compute(run):
    return scope_trace.group_ms(run, "other")
