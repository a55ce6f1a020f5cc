"""conv.device_ms (ms): device time a step spends in the gated short
convolution's kernels (``dvc_short_conv_fwd`` / ``dvc_short_conv_bwd``,
ops/short_conv.py: the two gates and the three taps of every ``conv`` token
mixer, forward, recomputed forward and backward; NOT its two projections, which
are matrix products like the rest of the step), summed over the whole
executions of the train step's program on chip 0 and divided by their number.
Layer: compiled step. Moves tok_s_chip. A program with no such kernel gives
nothing."""

from benchmark import lfm2_trace


def compute(run):
    found = lfm2_trace.kernel_events(run)
    if found is None:
        return None
    return sum(dur for _, dur in found[1]) / found[0] / 1e6
