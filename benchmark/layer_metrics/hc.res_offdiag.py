"""hc.res_offdiag (ratio): how much the residual streams mix: the mean over a
step's tokens and sublayers of ``1 - trace(Hres) / n`` (0: every stream keeps
to itself, the one-stream block four times; ``1 - 1/n``: every stream becomes
the mean of all). The median over the window's ``hc.mix`` spans of the
attribute ``hc_res_offdiag`` (the step's own count, taken where the maps are
made). Layer: compiled step. Moves tok_s_chip: a cell whose maps have gone to
the identity measures a one-stream model carried four times.

A program whose loop records no such span (every model with one residual
stream, the parent of PR 70) gives nothing."""

import statistics


def compute(run):
    shares = [float((s.get("attrs") or {})["hc_res_offdiag"]) for s in run["spans"]
              if s["name"] == "hc.mix" and "hc_res_offdiag" in (s.get("attrs") or {})]
    return statistics.median(shares) if shares else None
