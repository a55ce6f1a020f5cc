"""moe.act_zero_share (ratio): of the hidden activations of the assignments on
held experts (``d_expert`` each), the share whose gate the ReLU set to exactly
zero: the median over the window's ``moe.route`` spans of the attribute
``moe_act_zero_share`` (the step's own count, taken where the activation is
computed: ``ops/moe_dispatch.py: _gated``). Layer: compiled step. Moves
tok_s_chip: it is the share of the down-projection's input rows' entries that
a kernel aware of the zeros could skip, and the experts' products do not yet.

A program whose experts are SiLU-gated (OLMoE, Laguna), a dense model and the
parent of PR 35 record no such attribute and give nothing."""

import statistics


def compute(run):
    shares = [float((s.get("attrs") or {})["moe_act_zero_share"]) for s in run["spans"]
              if s["name"] == "moe.route" and "moe_act_zero_share" in (s.get("attrs") or {})]
    return statistics.median(shares) if shares else None
