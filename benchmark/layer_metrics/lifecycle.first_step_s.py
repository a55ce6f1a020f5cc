"""lifecycle.first_step_s (s): from the first step's dispatch to its metrics
ready on the device: the span ``lifecycle.first_step``, ended by a waiter
thread (the train thread dispatches on). Layer: entry / lifecycle. Moves
setup_s.

One step's device time plus whatever the chip still had queued of the init.
A program that records no such span gives nothing."""

from benchmark import lifecycle


def compute(run):
    return lifecycle.span_seconds(run, "lifecycle.first_step")
