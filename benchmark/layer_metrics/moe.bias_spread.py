"""moe.bias_spread (bias): the largest selection bias minus the smallest, over
every expert layer's router, from the window's LAST ``moe.route`` span
(attributes ``moe_bias_max`` and ``moe_bias_min``: the biases that step chose
with). Layer: compiled step. Moves tok_s_chip: the bias is what levels the
experts' loads without an auxiliary loss, a step moves each by ``gamma`` towards
the even share, and a spread that still grows at the window's end says the
router's scores are still running away from it (the grouped matmuls pad each
expert's rows to a tile, and a chunk of the share's dispatch is sized for a
load near even).

A program whose spans carry no such attributes (every model without a
selection bias, the parent of PR 39) gives nothing."""


def compute(run):
    last = None
    for s in run["spans"]:
        attrs = s.get("attrs") or {}
        if s["name"] == "moe.route" and "moe_bias_max" in attrs and "moe_bias_min" in attrs:
            if last is None or s["t0"] >= last[0]:
                last = (s["t0"], float(attrs["moe_bias_max"]) - float(attrs["moe_bias_min"]))
    return None if last is None else last[1]
