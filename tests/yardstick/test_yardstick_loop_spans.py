"""The readers of the spans the program records on the train thread and in
the codec, on a hand-made run: two whole periods, one failed launch."""

import pytest

from benchmark import readers
from benchmark.manifest import REPO_ROOT, Manifest

M = Manifest(REPO_ROOT)


def span(trace, name, dur, parent=None):
    d = {"trace": trace, "name": name, "peer": "vol-chip", "t0": 0.0, "dur_s": dur}
    if parent:
        d["parent"] = parent
    return d


def period(trace, launch, merge, host, snap_in_merge, run_s, h2d, d2h):
    return [
        span(trace, "loop.launch", launch),
        span(trace, "loop.launch.d2h", launch - 0.01, "loop.launch"),
        span(trace, "loop.merge", merge),
        span(trace, "loop.merge.d2h", 0.5, "loop.merge"),
        span(trace, "loop.merge.host", host, "loop.merge"),
        span(trace, "loop.merge.h2d", 0.25, "loop.merge"),
        span(trace, "loop.snapshot", snap_in_merge, "loop.merge"),
        # two codec ops in the round: their parts add up per round
        span(trace, "codec.op", run_s + h2d + d2h, "encode"),
        span(trace, "codec.h2d", h2d, "codec.op"),
        span(trace, "codec.run", run_s / 2, "codec.op"),
        span(trace, "codec.run", run_s / 2, "codec.op"),
        span(trace, "codec.d2h", d2h, "codec.op"),
        span(trace, "encode", 6.0), span(trace, "round", 19.0),
    ]


SPANS = (
    period("r1", 0.40, 4.0, 2.0, 0.5, 6.0, 0.25, 0.5)
    + period("r2", 0.30, 5.0, 3.0, 0.5, 8.0, 0.25, 1.0)
    # boundaries between launches, and a launch that formed no group
    + [span("loop", "loop.snapshot", 0.375) for _ in range(8)]
    + [span("loop", "loop.launch", 9.0), span("loop", "loop.log_sync", 0.7),
       span("loop", "loop.snapshot", None)]
)
RUN = {"spans": SPANS, "stats": {"rounds.in_window": 2}, "trace": None}


@pytest.mark.parametrize("name,want", [
    ("loop.launch_ms", 400.0),        # median of 400, 300 and the keyless 9,000
    ("loop.merge_ms", 4500.0),
    ("loop.merge_host_ms", 2500.0),
    ("loop.snapshot_ms", 2000.0),     # (8 x 0.375 + 2 x 0.5) s over two periods
    ("codec.queue_s", 7.0),
    ("codec.transfer_s", 1.0),        # median of 0.75 and 1.25
])
def test_reader_on_a_hand_made_run(name, want):
    path = M.layer_metric_path(name)
    assert readers.compute(path, RUN) == pytest.approx(want)
    # the parent's program records none of these spans: nothing, and no error
    old = [s for s in SPANS if s["name"] in ("encode", "round")]
    assert readers.compute(path, dict(RUN, spans=old)) is None


def test_snapshot_time_needs_a_whole_period():
    path = M.layer_metric_path("loop.snapshot_ms")
    assert readers.compute(path, dict(RUN, stats={"rounds.in_window": 0})) is None
    assert readers.compute(path, dict(RUN, stats={})) is None
