"""step.mfu_model (%): the FLOPs one step's tokens require of what THIS CHIP
holds of the model, over the step program's device time at the chip's
published peak. Layer: compiled step. Moves tok_s_chip.

FLOPs a token come from the configuration's own arithmetic: the module
``benchmark.flops_<family>`` (``family`` as the configuration file names it)
and its ``train_flops_per_token(cfg, seq_len)``: 6 N_active + 12 x head_dim x
(pairs the masks keep) / T for ``flops_smallthinker``; recomputation not
counted. A configuration whose family has no such module or function (the
gpt2 cells read ``step.mfu``, OLMoE ``step.mfu_active``, Laguna
``step.mfu_held``) gives nothing: the next configuration brings its module and
needs no further utilisation metric."""

import statistics

from benchmark import family_flops, flops, references, trace


def compute(run):
    if run.get("trace") is None or run.get("peak") is None:
        return None
    cfg = run["config"]
    per_token = getattr(family_flops.load(cfg), "train_flops_per_token", None)
    if per_token is None:
        return None
    runs = trace.program_runs(run["trace"], run["step_program"])
    if not runs:
        return None
    step_s = statistics.median(e.dur_ns for e in runs) / 1e9
    seq_len = references.load(cfg["family"]).sizes(cfg)["seq_len"]
    return flops.mfu_percent(
        run["tokens_per_step"], per_token(cfg, seq_len), step_s, run["chips"],
        run["peak"]["bf16_flops"])
