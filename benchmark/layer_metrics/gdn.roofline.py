"""gdn.roofline (%): the least time the chip could take for every pass of the
scalar-decay delta rule's scan the trace shows (``ops/gdn.core``'s loops over
the chunks, forward and backward: ``benchmark/gdn_trace.py``), over the device
time they took; loops inside the whole executions of the train step's program on
chip 0. Layer: compiled step. Moves tok_s_chip.

A pass's least time is ``gdn_least_seconds`` of the configuration's own
arithmetic (``benchmark.flops_<family>``): the larger of the chunked form's
required FLOPs (the in-chunk matrices once a KEY head, the rest a value head)
over the bf16 peak and its streams' and states' bytes over the HBM bandwidth,
for one mixer's pass over the step's sequences. It counts the work the equations
need, not the code that does it: a faster scan raises the share, and one over
100% says the count is wrong. A configuration whose family has no such function,
or a program that runs no such loop, gives nothing."""

from benchmark import family_flops, flops_moe, gdn_trace, references


def compute(run):
    found = gdn_trace.loop_events(run)
    if found is None or run.get("peak") is None:
        return None
    cfg = run["config"]
    least_of = getattr(family_flops.load(cfg), "gdn_least_seconds", None)
    bandwidth = flops_moe.hbm_bytes_per_s(run["peak"])
    if least_of is None or bandwidth is None:
        return None
    seq_len = references.load(cfg["family"]).sizes(cfg)["seq_len"]
    batch = run["tokens_per_step"] // seq_len
    least = sum(least_of(cfg, batch, seq_len, bwd, run["peak"]["bf16_flops"], bandwidth)
                for bwd, _ in found[1])
    took_s = sum(dur for _, dur in found[1]) / 1e9
    return 100.0 * least / took_s if took_s else None
