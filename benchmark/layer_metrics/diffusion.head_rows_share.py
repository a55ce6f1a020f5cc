"""diffusion.head_rows_share (ratio): rows the loss head's products ran over
the rows the layers ran, as the step counts them where it slices the noised
half off (``diffusion_head_rows_share`` on the window's ``moe.route`` spans:
their median). Layer: compiled step. Moves tok_s_chip: a block-diffusion step
runs a sequence's clean and noised copy through every layer and needs logits
for the noised copy alone, so 0.5 is the head doing no row's work twice; 1.0
would be a head over all 2L rows, half of them for nothing.

A program that records no such attribute (every model without this objective,
the parent of PR 60) gives nothing."""

import statistics

from benchmark import sdar_trace


def compute(run):
    shares = sdar_trace.route_span_attribute(run, "diffusion_head_rows_share")
    return statistics.median(shares) if shares else None
