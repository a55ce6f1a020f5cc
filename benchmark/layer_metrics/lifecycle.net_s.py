"""lifecycle.net_s (s): joining the swarm: the span ``lifecycle.net``
(``Volunteer.start`` from ``transport.start`` through ``membership.join``,
and the first clock estimate where there is one). Layer: entry / lifecycle.
Moves setup_s.

Milliseconds for a solo volunteer; with a coordinator it is the DHT's
bootstrap and the join exchange. A program that records no such span gives
nothing."""

from benchmark import lifecycle


def compute(run):
    return lifecycle.span_seconds(run, "lifecycle.net")
