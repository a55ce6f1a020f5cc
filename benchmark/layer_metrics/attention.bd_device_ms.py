"""attention.bd_device_ms (ms): device time a step spends in the
block-diffusion attention kernels (``dvc_flash_bd_fwd`` / ``dvc_flash_bd_bwd``:
the calls under the three-part mask over a sequence's clean and noised copy),
summed over the whole executions of the train step's program on chip 0 and
divided by their number, as attention.device_ms sums its own. Layer: compiled
step. Moves tok_s_chip. A program with no such kernel gives nothing."""

from benchmark import sdar_trace


def compute(run):
    found = sdar_trace.kernel_events(run)
    if found is None:
        return None
    return sum(dur for _, dur in found[1]) / found[0] / 1e6
