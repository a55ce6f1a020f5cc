"""lifecycle.init_s (s): building the training state: the span
``lifecycle.init``, the whole of ``Trainer.__init__`` (the model's init
programs and ``TrainState.create``, sharding on a mesh, the step functions'
construction, and the blocking first snapshot of the parameters, which also
waits for the device's part of the init). Layer: entry / lifecycle. Moves
setup_s.

A program that records no such span gives nothing."""

from benchmark import lifecycle


def compute(run):
    return lifecycle.span_seconds(run, "lifecycle.init")
