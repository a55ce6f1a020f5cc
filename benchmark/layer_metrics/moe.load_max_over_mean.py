"""moe.load_max_over_mean (ratio): the fullest expert's rows over the even
share, median over the window's ``moe.route`` spans (one per log point of the
train loop; attributes ``moe_load_max`` and ``moe_load_mean``, tokens per
expert over the step). Layer: compiled step. Moves tok_s_chip: the grouped
matmuls pad each expert's rows to a tile, and an uneven load pads more.

A program that records no such span (a dense model) gives nothing."""

import statistics


def compute(run):
    ratios = []
    for s in run["spans"]:
        attrs = s.get("attrs") or {}
        if s["name"] == "moe.route" and attrs.get("moe_load_mean"):
            ratios.append(float(attrs["moe_load_max"]) / float(attrs["moe_load_mean"]))
    return statistics.median(ratios) if ratios else None
