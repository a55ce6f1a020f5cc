"""What a trace chose, told to whoever listens.

Code that picks a program while a step is TRACED (which attention core, how a
projection is divided over ``tp``, which grouped matmul, which form of a scan)
says so where it chooses: ``note(kind, **labels)``, the labels formatted there.
Whoever wants to know subscribes (a volunteer's telemetry counts every note as
``swarm.<kind>``; the train loop puts a label a model's span declaration names
on that span; a test gathers them) and any number may at once. Trace time only:
a compiled step never comes back here, so a note counts traces, not steps.
"""

from __future__ import annotations

import weakref
from typing import Any, Callable, Dict

_subscriptions: "weakref.WeakSet[subscribe]" = weakref.WeakSet()


class subscribe:
    """``fn(kind, labels)`` is called with every note from now until ``close``,
    the end of a ``with`` block, or the day nobody holds this object any more:
    the subscription lasts as long as its handle, so keep it."""

    def __init__(self, fn: Callable[[str, Dict[str, Any]], None]):
        self.fn = fn
        _subscriptions.add(self)

    def close(self) -> None:
        _subscriptions.discard(self)

    def __enter__(self) -> "subscribe":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


def note(kind: str, **labels: Any) -> None:
    for subscription in tuple(_subscriptions):
        subscription.fn(kind, labels)
