"""step.mfu_active (%): the FLOPs one step's tokens require of the parameters
that work on a token, over the step program's device time at the chips'
published peak. Layer: compiled step. Moves tok_s_chip.

FLOPs a token: 6 N_active + 12 L T d (benchmark/flops_moe.py: q/k/v/o, the
experts per token, the router and the head; the embedding lookup and
recomputation not counted). step.mfu counts 6 N over every parameter, which
for a sparse-expert model is several times the work; this metric is for the
cells whose configuration file has ``num_experts``."""

import statistics

from benchmark import flops, flops_moe, trace


def compute(run):
    if run.get("trace") is None or run.get("peak") is None:
        return None
    cfg = run["config"]
    if "num_experts" not in cfg:
        return None
    runs = trace.program_runs(run["trace"], run["step_program"])
    if not runs:
        return None
    step_s = statistics.median(e.dur_ns for e in runs) / 1e9
    per_token = flops_moe.train_flops_per_token_active(cfg, cfg["max_position_embeddings"])
    return flops.mfu_percent(
        run["tokens_per_step"], per_token, step_s, run["chips"], run["peak"]["bf16_flops"])
