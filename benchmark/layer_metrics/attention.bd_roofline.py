"""attention.bd_roofline (%): the least time the chip could take for the
block-diffusion attention calls the trace shows over the device time they
took. Layer: compiled step. Moves tok_s_chip.

A call's work is the query-key pairs the three-part mask KEEPS (``L^2 + L bd``
a head a sequence of L data tokens, 2L rows) at 4 x head_dim FLOPs a pair
forward and 10 x head_dim backward (the fused backward's five products), over
the bf16 peak, or its bytes over the HBM bandwidth if that is longer
(``benchmark/flops_sdar_moe.kernel_least_seconds``). Every call that ran is
counted. Pairs a tile visits and masks are not work: a noised query tile's
diagonal tile holds ``block x bd`` kept pairs of its ``block x block``, so the
tiles' size caps this below 100%. A reading over 100% means the count is wrong.

A program with no such kernel, or a configuration whose family brings no such
arithmetic, gives nothing."""

from benchmark import family_flops, flops_moe, references, sdar_trace


def compute(run):
    found = sdar_trace.kernel_events(run)
    if found is None or run.get("peak") is None:
        return None
    cfg = run["config"]
    flops = family_flops.load(cfg)
    bandwidth = flops_moe.hbm_bytes_per_s(run["peak"])
    if bandwidth is None or not hasattr(flops, "kept_pairs") or not hasattr(flops, "kernel_least_seconds"):
        return None
    seq_len = references.load(cfg["family"]).sizes(cfg)["seq_len"]
    batch = run["tokens_per_step"] // seq_len
    least = {bwd: flops.kernel_least_seconds(cfg, seq_len, batch, bwd, run["peak"]["bf16_flops"], bandwidth)
             for bwd in (False, True)}
    took_s = sum(dur for _, dur in found[1]) / 1e9
    return 100.0 * sum(least[bwd] for bwd, _ in found[1]) / took_s if took_s else None
