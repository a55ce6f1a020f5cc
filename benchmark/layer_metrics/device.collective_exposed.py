"""device.collective_exposed (%): the part of the collective operations' time
during which no other operation ran on that chip, over the traced window, mean
over chips. Layer: device. Moves tok_s_chip."""

from benchmark import trace


def compute(run):
    if run.get("trace") is None:
        return None
    c = trace.collective_time(run["trace"])
    if c is None or c["window_s"] <= 0:
        return None
    return 100.0 * c["exposed_s"] / c["window_s"]
