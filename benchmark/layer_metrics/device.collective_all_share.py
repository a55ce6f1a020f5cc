"""device.collective_all_share (%): time a chip has a collective operation under
way over the traced window, mean over chips, where the step's asynchronous
collectives are pairs of ``async-collective-start`` / ``-done`` fusions that
``device.collective_share`` cannot name: a pair counts from its start's
beginning to its done's end (an upper bound of its transfer), the synchronous
ones as there. Nothing where the trace holds no such fusion. Layer: device.
Moves tok_s_chip."""

from benchmark import collective_pairs


def compute(run):
    return collective_pairs.share(run, "collective_s")
