"""scope.unresolved_share (%): of the own op time inside the whole executions
of the step's program on chip 0, the share of events whose instruction the
program's scope map does not hold, or holds with another result type (the map
of another executable); meant to read 0 (``benchmark/scope_trace.py``, which
logs the largest such events). Layer: compiled step. Moves tok_s_chip.

A program that does not offer its scope map gives nothing."""

from benchmark import scope_trace


def compute(run):
    return scope_trace.share(run, "unresolved")
