"""moe.gmm_roofline (%): the least time the chip could take for the grouped
matmul calls the trace shows (each 2 x rows x d x f FLOPs over the bf16 peak,
or its bytes over the HBM bandwidth where that is larger: benchmark/
flops_moe.py) over the device time they took. Layer: compiled step. Moves
tok_s_chip.

Every call that ran is counted, the recomputed forward's too; tile padding is
not. A reading over 100% means the count is wrong. Rows are the step's tokens
times the experts per token, d and f the configuration file's widths."""

from benchmark import flops_moe, moe_trace, trace


def compute(run):
    found = moe_trace.events_in_whole_steps(run)
    if found is None or run.get("peak") is None:
        return None
    durs = [e.dur_ns for e in found[1] if moe_trace.GMM_RE.search(trace.op_name(e.name))]
    bandwidth = flops_moe.hbm_bytes_per_s(run["peak"])
    if not durs or bandwidth is None:
        return None
    least_s = flops_moe.gmm_least_seconds(
        run["config"], run["tokens_per_step"], run["peak"]["bf16_flops"], bandwidth)
    return 100.0 * len(durs) * least_s / (sum(durs) / 1e9)
