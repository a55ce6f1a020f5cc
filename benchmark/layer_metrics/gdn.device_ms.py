"""gdn.device_ms (ms): device time a step spends in the scalar-decay delta
rule's loops (``ops/gdn.core``: the chunked Gated DeltaNet recurrence of every
delta mixer, forward, recomputed forward and backward, each a ``while`` over the
chunks that ``benchmark/gdn_trace.py`` finds by the value heads' states it
carries; NOT its projections, its convolution, or its gated norm), summed over
the whole executions of the train step's program on chip 0 and divided by their
number. Layer: compiled step. Moves tok_s_chip. A program with no such loop
gives nothing."""

from benchmark import gdn_trace


def compute(run):
    found = gdn_trace.loop_events(run)
    if found is None:
        return None
    return sum(dur for _, dur in found[1]) / found[0] / 1e6
