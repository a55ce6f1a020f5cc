"""``device.collective_all_share`` / ``collective_all_exposed`` on a hand-built
trace whose asynchronous collectives are pairs of start / done fusions on the
instruction stream (times in ns; worked by hand below), and on the fixture
beside this file, which holds none."""

import importlib
import json
import os

import pytest

from benchmark import collective_pairs, readers, trace
from benchmark.manifest import REPO_ROOT, Manifest

HERE = os.path.dirname(os.path.abspath(__file__))
M = Manifest(REPO_ROOT)

START = "%async-collective-start{n} = (bf16[8,1024,1280]{{2,1,0:T(8,128)(2,1)}}, s32[2]{{0:S(4)}}) fusion(%x), kind=kCustom, calls=%fc.1"
DONE = "%async-collective-done{n} = bf16[8,1024,1280]{{2,1,0:T(8,128)(2,1)}} fusion(%a, %b), kind=kCustom, calls=%fc.2"
SYNC = "%all-reduce.{n} = bf16[8,1024,1280]{{2,1,0}} all-reduce(bf16[8,1024,1280]{{2,1,0}} %x), replica_groups={{{{0,1}},{{2,3}}}}"
FUSION = "%fusion.{n} = bf16[8,1024,5120]{{2,1,0}} fusion(bf16[8,1024,1280]{{2,1,0}} %p), kind=kOutput"


def _trace():
    chip0 = [
        ["%while.1 = (s32[], bf16[8,1024,1280]{2,1,0}) while((s32[], bf16[8,1024,1280]) %t), body=%b", 1000, 8000],
        [FUSION.format(n=1), 1000, 1000],
        [START.format(n=".2"), 2000, 300],   # the pair's own time: 300 + 200, nothing beside it
        [FUSION.format(n=2), 2300, 1000],    # beside the transfer: hidden
        [DONE.format(n=".2"), 3300, 200],
        [SYNC.format(n=7), 3500, 500],       # what the accepted reader sees
        [START.format(n=".2"), 4000, 100],   # the same pair, the scan's next iteration
        [SYNC.format(n=8), 4100, 300],       # a collective inside a pair hides nothing
        [FUSION.format(n=3), 4400, 500],     # and leaves [4900, 5000) to no operation: a gap, not a wait
        [DONE.format(n=".2"), 5000, 100],
        [DONE.format(n=""), 6000, 100],      # its start fell outside: its own time only
        [FUSION.format(n=4), 7000, 2000],
    ]
    return trace.Trace.from_json({"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": chip0}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [[FUSION.format(n=1), 1000, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "train", "events": [
            ["bench:trace_begin", 0, 100], ["bench:trace_end", 10100, 50]]}]},
    ]})


def _run(tr):
    return {"trace": tr, "step_program": r"^jit_step(\(|$)", "stats": {}, "spans": []}


def test_a_pair_is_told_by_its_fusions_names():
    assert collective_pairs.pair_part(START.format(n=".2")) == ("start", ".2")
    assert collective_pairs.pair_part(DONE.format(n="")) == ("done", "")
    assert collective_pairs.pair_part(SYNC.format(n=7)) is None
    assert collective_pairs.pair_part("%fusion.3 = f32[8]{0} fusion(%async-collective-start.2)") is None
    assert not trace.is_collective(START.format(n=".2"))  # why the accepted readers pass them by


def test_pairs_close_the_latest_start_of_their_number():
    ops = _trace().device_planes()[0].line("XLA Ops").events
    got = [(s.start_ns, d.end_ns) for s, d in collective_pairs.pairs(ops)]
    assert got == [(2000, 3500), (4000, 5100)]


def test_collective_time_with_the_pairs():
    c = collective_pairs.collective_time(_trace())
    # chip 0: [2000, 5100) and the lone done's 100; chip 1: none. Mean of two chips.
    assert c["collective_s"] == pytest.approx((3100 + 100) / 2 * 1e-9)
    # the named two and the five fusions of the pairs: 500 + 300 + 300 + 200 + 100 + 100 + 100; the gap
    # inside the second pair is the instruction stream's own
    assert c["exposed_s"] == pytest.approx(1600 / 2 * 1e-9)
    assert c["window_s"] == pytest.approx(10000e-9)


@pytest.mark.parametrize("name,want", [
    ("device.collective_all_share", 100 * 1600 / 10000),
    ("device.collective_all_exposed", 100 * 800 / 10000),
    ("device.collective_share", 100 * 400 / 10000),     # the synchronous two, as before
    ("device.collective_exposed", 100 * 400 / 10000),
])
def test_readers_on_a_trace_with_pairs(name, want):
    assert readers.compute(M.layer_metric_path(name), _run(_trace())) == pytest.approx(want)


@pytest.mark.parametrize("name", ["device.collective_all_share", "device.collective_all_exposed"])
def test_a_program_without_pairs_gives_nothing(name):
    assert readers.compute(M.layer_metric_path(name), _run(None)) is None
    with open(os.path.join(HERE, "trace_fixture.json")) as fh:
        without = trace.Trace.from_json(json.load(fh))
    assert trace.collective_time(without)["collective_s"] > 0
    assert readers.compute(M.layer_metric_path(name), _run(without)) is None


@pytest.mark.parametrize("name", ["device.collective_all_share", "device.collective_all_exposed"])
def test_the_manifest_lists_them_for_the_four_chip_cell_alone(name):
    entry = next(m for m in M.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] == ["large-solo-4chip"] and entry["moves"] == "tok_s_chip"
    assert entry["layer"] == next(m for m in M.doc["per_layer"] if m["name"] == "device.collective_share")["layer"]


NEW = ["device.collective_all_share", "device.collective_all_exposed"]


@pytest.mark.parametrize("test,args", [
    ("test_manifest_holds_the_nine_scope_metrics_at_its_end", ()),
    ("test_manifest_as_the_kimi_tests_asserted_it_nine_places_up",
     ("test_manifest_holds_the_new_configuration_cell_and_metrics",)),
    ("test_manifest_as_the_kimi_tests_asserted_it_nine_places_up",
     ("test_manifest_tail_as_the_nemotron_tests_asserted_it_three_metrics_and_a_cell_up",)),
])
def test_manifest_as_the_scope_tests_asserted_it_two_places_up(test, args, monkeypatch):
    """``test_yardstick_scopes.py``'s three manifest tests (tests/conftest.py
    marks them: they assert that the nine ``scope.*`` metrics END ``per_layer``,
    66 entries), run as they stand against the manifest less this PR's two
    entries; against the manifest as it is each fails on the tail alone."""
    scopes = importlib.import_module("test_yardstick_scopes")
    assert [m["name"] for m in M.doc["per_layer"][-2:]] == NEW and len(M.doc["per_layer"]) == 68

    def less_two(root=REPO_ROOT):
        view = Manifest(root)
        view.doc = dict(view.doc, per_layer=view.doc["per_layer"][:-2])
        return view

    takes = [monkeypatch] if args else []
    monkeypatch.setattr(scopes, "M", less_two())
    monkeypatch.setattr(scopes, "Manifest", less_two)
    getattr(scopes, test)(*args, *takes)
    monkeypatch.setattr(scopes, "M", M)
    monkeypatch.setattr(scopes, "Manifest", Manifest)
    with pytest.raises(AssertionError):
        getattr(scopes, test)(*args, *takes)
