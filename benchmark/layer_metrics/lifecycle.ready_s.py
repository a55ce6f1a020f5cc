"""lifecycle.ready_s (s): the program's own time from ``Volunteer()`` to its
first finished train step: the root span ``lifecycle``
(``swarm/volunteer.py`` opens it, the waiter of ``Trainer._call`` ends it
when the first step's metrics are ready). Layer: entry / lifecycle. Moves
setup_s.

What ``setup_s`` holds beside it: the interpreter's start and the imports
before the volunteer exists, the data file, the wait for the stub peer, and
the warm-up steps and rounds after the first step. A program that records no
such span gives nothing."""

from benchmark import lifecycle


def compute(run):
    return lifecycle.span_seconds(run, "lifecycle")
