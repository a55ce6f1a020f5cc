"""gdn.carry_share (ratio): of the (delta layer, sequence, value head, chunk
boundary) quadruples of a step, the share across which the carried state still
counts: the whole decay of the chunk behind the boundary (one number a head) is
over 1e-3 (``ops/kda.CARRY_FLOOR``). The median over the window's ``gdn.scan``
spans of the attribute ``gdn_carry_share`` (the step's own count, taken where
the decay is summed by chunk). Layer: compiled step. Moves tok_s_chip: 0 says
every head forgets inside a chunk, the scan between chunks does no work a local
model would not, and the cell measures a convolution.

A program whose loop records no such span (every model without a Gated
DeltaNet, the parent of PR 67) gives nothing."""

import statistics


def compute(run):
    shares = [float((s.get("attrs") or {})["gdn_carry_share"]) for s in run["spans"]
              if s["name"] == "gdn.scan" and "gdn_carry_share" in (s.get("attrs") or {})]
    return statistics.median(shares) if shares else None
