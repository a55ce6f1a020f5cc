"""moe.dropped (count): routed (token, expert) rows that no grouped matmul
computed with the expert the router chose, summed over the window's
``moe.route`` spans (attribute ``moe_dropped``). The step counts them from what
its kernel is handed: the sorted rows whose group, by the running sum of the
group sizes, is another expert than their route's, or none
(``ops/moe_dispatch.py: rows_not_computed``). It does not look inside the
kernel: that the products are right is what ``correct`` checks, against a
reference that runs every expert. Layer: compiled step. Moves tok_s_chip:
dropping rows is how a capacity-limited dispatch buys speed, and OLMoE drops
none, so anything but 0 is a different model.

A program that records no such span (a dense model) gives nothing."""


def compute(run):
    dropped = [float((s.get("attrs") or {})["moe_dropped"]) for s in run["spans"]
               if s["name"] == "moe.route" and "moe_dropped" in (s.get("attrs") or {})]
    return sum(dropped) if dropped else None
