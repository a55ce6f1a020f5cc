"""lifecycle.trace_lower_s (s): seconds jax spent tracing Python functions to
jaxprs and lowering jaxprs to MLIR modules, all programs, from process start
to the window: ``compile_log().summary(until=window)``'s ``trace_seconds`` +
``lower_seconds``. Layer: entry / lifecycle. Moves setup_s.

What ``lifecycle.compile_s`` (the backend's seconds) leaves out of a first
call: a model whose layers are unrolled pays it per layer, with a warm cache
too. A program whose compile log counts the backend alone gives nothing."""

from benchmark import lifecycle


def compute(run):
    compiled = lifecycle.compiled_before_window(run)
    if compiled is None:
        return None
    return compiled["trace_seconds"] + compiled["lower_seconds"]
