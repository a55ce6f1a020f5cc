"""conv.roofline (%): the least time the chip could take for every call of
the gated short convolution's kernels the trace shows
(``dvc_short_conv_fwd`` / ``dvc_short_conv_bwd``), over the device time they
took; calls inside the whole executions of the train step's program on chip 0.
Layer: compiled step. Moves tok_s_chip.

A call's least time is its bytes over the chip's HBM bandwidth: the
convolution is five elementwise operations a channel and position, so the
bytes are its roofline. ``short_conv_bytes`` of the configuration's own
arithmetic (``benchmark.flops_<family>``) counts the streams read and written
once at the compute dtype. A configuration whose family has no such function,
or a program that runs no such kernel, gives nothing."""

from benchmark import family_flops, flops_moe, lfm2_trace, references


def compute(run):
    found = lfm2_trace.kernel_events(run)
    if found is None or run.get("peak") is None:
        return None
    cfg = run["config"]
    bytes_of = getattr(family_flops.load(cfg), "short_conv_bytes", None)
    bandwidth = flops_moe.hbm_bytes_per_s(run["peak"])
    if bytes_of is None or bandwidth is None:
        return None
    seq_len = references.load(cfg["family"]).sizes(cfg)["seq_len"]
    batch = run["tokens_per_step"] // seq_len
    least = sum(bytes_of(cfg, batch, seq_len, bwd) / bandwidth for bwd, _ in found[1])
    took_s = sum(dur for _, dur in found[1]) / 1e9
    return 100.0 * least / took_s if took_s else None
