"""kda.device_ms (ms): device time a step spends in the delta-rule scan's
loops (``ops/kda.core``: the chunked Kimi Delta Attention recurrence of every
KDA mixer, forward, recomputed forward and backward, each a ``while`` over the
chunks that ``benchmark/kda_trace.py`` finds by the heads' states it carries;
NOT its projections, its convolutions, the passes that fold beta, sum the decay
and lay the streams out by chunk, or its gated norm), summed over the whole
executions of the train step's program on chip 0 and divided by their number.
Layer: compiled step. Moves tok_s_chip. A program with no such loop gives
nothing."""

from benchmark import kda_trace


def compute(run):
    found = kda_trace.loop_events(run)
    if found is None:
        return None
    return sum(dur for _, dur in found[1]) / found[0] / 1e6
