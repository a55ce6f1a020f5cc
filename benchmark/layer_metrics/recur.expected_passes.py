"""recur.expected_passes (passes): the mean over the step's tokens of ``sum_r r
p_r``, the pass a token is expected to leave at under its exit distribution
(``expected_passes`` on the window's ``recur.exit`` spans: their median).
Layer: compiled step. Moves tok_s_chip: training runs every pass whatever the
gate says, so this moves no training step; it is what a decoder that exits
early would run, between 1 and R (1.875 at the initial parameters' 1/2, 1/4,
1/8, 1/8).

A program that records no such span (every model that is not looped, the
parent of PR 64) gives nothing."""

from benchmark import recur_trace


def compute(run):
    return recur_trace.exit_span_median(run, "expected_passes")
