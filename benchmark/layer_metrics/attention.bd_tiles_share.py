"""attention.bd_tiles_share (ratio): (query, key) tiles the two block-diffusion
attention kernels' loops visit, forward and backward together, over the tiles a
causal mask over the same 2L rows would make them visit; the program's own
count from its kernels' loop bounds at the blocks it chose
(``ops/pallas_attention.bd_tiles``), carried as ``attention_bd_tiles_share`` on
the window's ``moe.route`` spans: their median. Layer: compiled step. Moves
tok_s_chip.

With n tiles a half the loops visit n^2 + 2n where a causal mask visits
2n^2 + n: 0.588 at tiles of 512 over 2 x 4,096 rows, 0.5 as tiles shrink; 1.0
would be loops that skip nothing a causal mask keeps, and a step whose
attention ran the XLA core (every pair of the 2L x 2L) reads about 2.

A program that records no such attribute (every model without this mask, the
parent of PR 60) gives nothing."""

import statistics

from benchmark import sdar_trace


def compute(run):
    shares = sdar_trace.route_span_attribute(run, "attention_bd_tiles_share")
    return statistics.median(shares) if shares else None
