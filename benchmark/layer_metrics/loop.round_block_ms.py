"""loop.round_block_ms (ms): device idle time inside the traced round's
interval, for that one round. Layer: train loop. Moves round_tok_s_chip.

The trace runs from the step before a round's launch to two steps after its
merge, so the idle time in it is what the round cost the chip: the train
thread's host snapshot at launch (trainer.py:673, 898), the merge
(trainer.py:721-727), and whatever of the codec's transfers held the queue."""

from benchmark import trace


def compute(run):
    if run.get("trace") is None:
        return None
    bi = trace.busy_idle(run["trace"])
    if bi is None:
        return None
    return (bi["window_s"] - bi["busy_s_per_chip"][0]) * 1e3
