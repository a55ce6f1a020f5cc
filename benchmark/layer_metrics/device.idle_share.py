"""device.idle_share (%): 1 - (union of the op intervals) / traced window, the
mean over the volunteer's chips. Layer: device. Moves tok_s_chip."""

from benchmark import trace


def compute(run):
    if run.get("trace") is None:
        return None
    bi = trace.busy_idle(run["trace"])
    return None if bi is None else 100.0 * bi["idle_share"]
