"""lifecycle.step_build_s (s): the train thread's first call of the step
function until it returns: Python trace, lowering, backend compile or the
persistent cache's load, dispatch. The span ``lifecycle.step_build``
(``Trainer._call``). Layer: entry / lifecycle. Moves setup_s.

Its attributes say how it divides (``trace_s``, ``lower_s``, ``backend_s``,
``cache_load_s``, ``cache`` hit or miss). A program that records no such
span gives nothing."""

from benchmark import lifecycle


def compute(run):
    return lifecycle.span_seconds(run, "lifecycle.step_build")
