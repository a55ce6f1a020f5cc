"""device.collective_all_exposed (%): the time a chip spends in a collective with
no other operation beside it, over the traced window, mean over chips: the
synchronous collectives (what ``device.collective_exposed`` reads) and the
asynchronous pairs' own start and done fusions, the wait on the instruction
stream that the scheduler found nothing to hide behind. Nothing where the trace
holds no such fusion. Layer: device. Moves tok_s_chip."""

from benchmark import collective_pairs


def compute(run):
    return collective_pairs.share(run, "exposed_s")
