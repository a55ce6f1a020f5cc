"""Span and counter reductions on hand-built inputs."""

import pytest

from benchmark import readers
from benchmark.manifest import REPO_ROOT, Manifest

M = Manifest(REPO_ROOT)


def span(trace, name, dur, t0=0.0):
    return {"trace": trace, "name": name, "peer": "vol-chip", "t0": t0, "dur_s": dur}


SPANS = [
    span("r1", "join", 0.9), span("r1", "encode", 0.5), span("r1", "wire", 1.0),
    span("r1", "fetch", 2.0), span("r1", "round", 5.0),
    span("r2", "join", 0.7), span("r2", "encode", 0.6), span("r2", "wire", 1.5),
    span("r2", "fetch", 2.5), span("r2", "round", 6.0),
    span("r3", "join", 0.8), span("r3", "wire", 1.1), span("r3", "fetch", None),
]


def test_spans_are_summed_per_round():
    assert readers.spans_per_round(SPANS, {"wire", "fetch"}) == {
        "r1": 3.0, "r2": 4.0, "r3": 1.1}


@pytest.mark.parametrize("name,want", [
    ("round.wire_s", 3.0), ("round.join_s", 0.8), ("round.encode_s", 0.55), ("round.wall_s", 5.5),
])
def test_span_metrics_take_the_median_over_rounds(name, want):
    run = {"spans": SPANS, "stats": {}, "trace": None}
    assert readers.compute(M.layer_metric_path(name), run) == pytest.approx(want)
    assert readers.compute(M.layer_metric_path(name), dict(run, spans=[])) is None


@pytest.mark.parametrize("name,stats,want", [
    ("codec.degraded", {"codec.degraded": 1, "codec.ring_vmem_fallbacks": 2}, 3.0),
    ("codec.degraded", {"codec.degraded": 0, "codec.ring_vmem_fallbacks": 0}, 0.0),
    ("codec.degraded", {}, None),
    ("device.peak_hbm_GB", {"memory.peak_bytes": 11_500_000_000}, 11.5),
    ("device.peak_hbm_GB", {"memory.peak_bytes": None}, None),
    ("device.peak_hbm_GB.round", {"memory.peak_bytes": 8_571_149_312}, 8.571149312),
    ("lifecycle.backend_init_s", {"setup.backend_init_s": 9.701}, 9.701),
    ("lifecycle.compile_s", {"compile.setup_seconds": 2.5}, 2.5),
    ("lifecycle.cache_misses", {"compile.setup_cache_misses": 0}, 0.0),
    ("round.in_window", {"rounds.in_window": 4}, 4.0),
])
def test_counter_metrics(name, stats, want):
    got = readers.compute(M.layer_metric_path(name), {"stats": stats, "spans": [], "trace": None})
    assert got == (pytest.approx(want) if want is not None else None)
