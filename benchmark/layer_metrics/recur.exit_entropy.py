"""recur.exit_entropy (nats): the mean entropy of a token's exit distribution
over the passes, as the step computes it for its loss (``exit_entropy`` on the
window's ``recur.exit`` spans: their median). Layer: compiled step. Moves
tok_s_chip: between 0 (every token leaves at one pass: the gate has collapsed,
and a decoder could run that many passes alone) and ln R (1.386 at four passes:
the gate says nothing); at the initial parameters the gates read 0.5 and the
distribution is (1/2, 1/4, 1/8, 1/8), 1.213 nats.

A program that records no such span (every model that is not looped, the
parent of PR 64) gives nothing."""

from benchmark import recur_trace


def compute(run):
    return recur_trace.exit_span_median(run, "exit_entropy")
