"""The yardstick: benchmark runner, its data and its arithmetic.

Everything a later PR must not be able to change while claiming a gain lives
here: traffic and data generation, the reduction from traces and spans to
metrics, the peak table, the FLOP arithmetic, the plain references and the
comparison that decides ``correct``. From the program it takes only the
system under test (``Volunteer``) and its spans, counters and program names.
"""
