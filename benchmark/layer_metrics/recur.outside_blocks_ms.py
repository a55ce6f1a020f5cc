"""recur.outside_blocks_ms (ms): device time a step spends inside the loop over
the passes and inside no block and not in the head: instructions whose
innermost scope word is ``recur`` (``benchmark/recur_trace.py``; reduced as
``scope.attention_ms`` is, over the whole executions of the step's program on
chip 0, each event's own time). Layer: compiled step. Moves tok_s_chip: what
the loop costs beyond its layers: the carry's copies, the stack of the passes'
states, the layer scan's own slices of the shared weights, the shared weights'
gradient sums, and the passes' final norm. It is part of ``scope.other_ms``.

A program without the scope map or without the word (every model that is not
looped, the parent of PR 64) gives nothing."""

from benchmark import recur_trace


def compute(run):
    return recur_trace.outside_blocks_ms(run)
