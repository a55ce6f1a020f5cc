"""attention.roofline (%): the least time the chip could take for every
attention kernel call the trace shows, windowed (``dvc_flash_win_fwd`` /
``dvc_flash_win_bwd``) and full-causal (``dvc_flash_fwd`` / ``dvc_flash_bwd``)
together, over the device time they took; calls inside the whole executions of
the train step's program on chip 0. Layer: compiled step. Moves tok_s_chip.

A call's least time is ``kernel_least_seconds`` of the configuration's own
arithmetic (``benchmark.flops_<family>``): the larger of its FLOPs (4 D
forward, 10 D backward a kept pair a head) over the bf16 peak and its bytes
over the HBM bandwidth. A kernel a later PR writes under a ``dvc_flash_``
name is read here with the others. A configuration whose family has no such
module, or a program that runs no such kernel, gives nothing."""

import re

from benchmark import family_flops, flops_moe, moe_trace, references, trace

KERNEL_RE = re.compile(r"^dvc_flash_(win_)?(fwd|bwd)")


def compute(run):
    found = moe_trace.events_in_whole_steps(run)
    if found is None or run.get("peak") is None:
        return None
    cfg = run["config"]
    least_of = getattr(family_flops.load(cfg), "kernel_least_seconds", None)
    bandwidth = flops_moe.hbm_bytes_per_s(run["peak"])
    if least_of is None or bandwidth is None:
        return None
    seq_len = references.load(cfg["family"]).sizes(cfg)["seq_len"]
    batch = run["tokens_per_step"] // seq_len
    least = took_ns = 0.0
    for e in found[1]:
        m = KERNEL_RE.match(trace.op_name(e.name))
        if m:
            least += least_of(cfg, seq_len, batch, bool(m.group(1)), m.group(2) == "bwd",
                              run["peak"]["bf16_flops"], bandwidth)
            took_ns += e.dur_ns
    return 100.0 * least / (took_ns / 1e9) if took_ns else None
