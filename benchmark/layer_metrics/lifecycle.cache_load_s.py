"""lifecycle.cache_load_s (s): seconds spent fetching executables from the
persistent compilation cache (read, decompress, load onto the device), all
programs, from process start to the window: ``compile_log().summary(until=
window)``'s ``cache_load_seconds``. Layer: entry / lifecycle. Moves setup_s.

The part of ``lifecycle.compile_s`` that a cache hit costs: with it, eleven
seconds and no miss can be told from eleven seconds of compiling. A program
whose compile log cannot tell gives nothing."""

from benchmark import lifecycle


def compute(run):
    compiled = lifecycle.compiled_before_window(run)
    return None if compiled is None else compiled["cache_load_seconds"]
