"""ssm.carry_share (ratio): of the (state-space block, sequence, head, chunk
boundary) quadruples of a step, the share across which the carried state still
counts: the whole decay ``exp(sum dt A)`` of the chunk behind the boundary is
over 1e-3 (``ops/ssd.CARRY_FLOOR``). The median over the window's ``ssm.scan``
spans of the attribute ``ssm_carry_share`` (the step's own count, taken where
the running sums are made). Layer: compiled step. Moves tok_s_chip: 0 says
every head forgets inside a chunk, the scan between chunks does no work a local
model would not, and the cell measures a convolution.

A program whose loop records no such span (every model without a state-space
mixer, the parent of PR 48) gives nothing."""

import statistics


def compute(run):
    shares = [float((s.get("attrs") or {})["ssm_carry_share"]) for s in run["spans"]
              if s["name"] == "ssm.scan" and "ssm_carry_share" in (s.get("attrs") or {})]
    return statistics.median(shares) if shares else None
