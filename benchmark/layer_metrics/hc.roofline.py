"""hc.roofline (%): the least time the chip could take for the residual path
of a step, over the device time the step spent under the scope ``hc``
(``scope.residual_ms``: the maps, the sum into each sublayer's input and the
mix of the streams with its result, forward, recomputed and backward).
Layer: compiled step. Moves tok_s_chip.

The least time is ``hc_least_seconds`` of the configuration's own arithmetic
(``benchmark.flops_<family>``): the bytes the path REQUIRES over the HBM
bandwidth, a sublayer a token forward ``(nC + C) + (nC + C + nC)`` elements
of the compute dtype (the streams read once for the maps and the input; the
streams and the result read and the new streams written), the recomputed
forward the same, the backward twice that. It counts what the equations move,
not the code that moves it: a fused pass raises the share, and one over 100%
says the count is wrong. A configuration whose family has no such function, or
a program with nothing under that scope, gives nothing."""

from benchmark import family_flops, flops_moe, scope_trace


def compute(run):
    got = scope_trace.by_scope(run)
    if got is None or not got["table"].get("residual") or run.get("peak") is None:
        return None
    cfg = run["config"]
    least_of = getattr(family_flops.load(cfg), "hc_least_seconds", None)
    bandwidth = flops_moe.hbm_bytes_per_s(run["peak"])
    if least_of is None or bandwidth is None:
        return None
    took_s = scope_trace.group_ms(run, "residual") / 1e3
    return 100.0 * least_of(cfg, run["tokens_per_step"], bandwidth) / took_s if took_s else None
