"""ssm.device_ms (ms): device time a step spends in the state-space scan's
kernels (``dvc_ssd_fwd`` / ``dvc_ssd_bwd``, ops/ssd.py: the chunked Mamba-2
recurrence of every state-space block, forward, recomputed forward and backward;
NOT its projections, its convolution or its gated norm), summed over the whole
executions of the train step's program on chip 0 and divided by their number.
Layer: compiled step. Moves tok_s_chip. A program with no such kernel gives
nothing."""

from benchmark import ssd_trace


def compute(run):
    found = ssd_trace.kernel_events(run)
    if found is None:
        return None
    return sum(dur for _, dur in found[1]) / found[0] / 1e6
