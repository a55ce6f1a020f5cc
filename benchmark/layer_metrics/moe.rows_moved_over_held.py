"""moe.rows_moved_over_held (ratio): rows the expert dispatch gathered to its
grouped matmuls over the assignments that fell on held experts, from the
window's ``moe.route`` spans (attributes ``moe_rows_moved`` and
``moe_rows_held``, each summed over the step's expert layers): the sums' ratio.
Layer: compiled step. Moves tok_s_chip.

1.0 is a dispatch that moves only what it computes; E / held (16 in
laguna-solo-8k) one that moves every one of the S x k assignments. The
program's bounded chunk is the model's slack times the even share
(ops/moe_dispatch.py: ``share_rows_bound``), so an even load reads that slack:
3.0 in Laguna, GLM-4.7-Flash and Kimi-Linear (the default), 4.25 in
SmallThinker (its own), 1.25 in LFM2 and Nemotron (the levelled routers'). A
share the router has trained away from reads more, one it crowds reads less.

A program that records no such attributes (a dense model, one that holds every
expert, the parent of PR 33) gives nothing."""


def compute(run):
    moved = held = 0.0
    for s in run["spans"]:
        attrs = s.get("attrs") or {}
        if s["name"] == "moe.route" and attrs.get("moe_rows_held"):
            moved += float(attrs.get("moe_rows_moved", 0.0))
            held += float(attrs["moe_rows_held"])
    return moved / held if held else None
