"""What PR 72 added to the yardstick: the six readers of the program's own
timeline of the chip's queue on hand-made runs (two whole periods of a round
cell, a solo window, a traced round whose spans are put on the trace's clock;
a program without the spans or without ``telemetry.chip_waits`` gives
nothing), the manifest with the six entries asserted BY NAME, and what the
seven yardstick tests that the appended entries moved asserted, run as they
stand against the manifest less this PR's entries (see tests/conftest.py)."""

import importlib
import json

import pytest

from benchmark import chip_timeline, readers
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.trace import Trace
from distributedvolunteercomputing_tpu.swarm import telemetry

M = Manifest(REPO_ROOT)
ROUND, SOLO = ["medium-round"], [w["name"] for w in M.doc["workloads"] if w["name"] != "medium-round"]
NEW_METRICS = {
    "loop.late_ms": ("ms", "lower", "round_tok_s_chip", ROUND),
    "loop.held_ms": ("ms", "lower", "round_tok_s_chip", ROUND),
    "loop.wait_unnamed_ms": ("ms", "lower", "round_tok_s_chip", ROUND),
    "loop.wait_share": ("%", "lower", "tok_s_chip", SOLO),
    "loop.step_wall_max_over_median": ("x", "lower", "tok_s_chip", SOLO),
    "loop.wait_over_trace_idle": ("ratio", "higher", "round_tok_s_chip", ROUND),
}


def span(name, t0, dur, **attrs):
    return {"trace": "loop", "name": name, "peer": "vol-chip", "t0": t0, "dur_s": dur,
            **({"attrs": attrs} if attrs else {})}


def steps(t0, dur, late=0.0, held=0.0, p50=0.368, longest=0.370, n=10):
    return span("loop.steps", t0, dur, step=0, steps=n, late_s=late, held_s=held, step_s_p50=p50, step_s_max=longest)


def read(name, run):
    return readers.compute(M.layer_metric_path(name), run)


# two launch-to-launch periods of a round cell: 0.3 s of waits, 0.02 s of them with nothing of the program in them
SPANS = [
    steps(1000.0, 3.70, late=0.010), steps(1003.7, 3.90, held=0.180, longest=0.520),
    steps(1007.6, 5.68, late=2.0, longest=2.368),                       # the probe's hook stopped the chip for 2 s in it
    span("loop.chip_wait", 1008.0, 2.0, step=22, kind="late", during="on_step", during_s=1.99),
    steps(1011.3, 3.80, late=0.004, held=0.100, longest=0.470), steps(1015.1, 3.68, held=0.006, longest=0.374),
    span("loop.steps", 1018.8, None),                                   # one that never ended is nobody's
    span("loop.chip_wait", 1001.0, 0.010, step=3, kind="late", during="loop.launch", during_s=0.008),
    span("loop.chip_wait", 1005.0, 0.110, step=14, kind="held", own_s=0.478),
    span("loop.chip_wait", 1006.0, 0.070, step=17, kind="held", own_s=0.438),
    span("loop.chip_wait", 1012.0, 0.100, step=33, kind="held", own_s=0.468),
    span("loop.chip_wait", 1013.0, 0.004, step=36, kind="late", during="loop", during_s=0.0),
    span("loop.chip_wait", 1016.0, 0.016, step=44, kind="held", own_s=0.384),   # nothing of the program near it
    span("loop.merge", 1004.9, 0.2), span("loop.merge.h2d", 1004.95, 0.12),
    span("codec.run", 1006.1, 0.3), span("loop.snapshot.land", 1011.9, 1.2),
    span("loop.log_sync", 1016.0, 0.4), span("round", 1000.5, 18.0),
]
RUN = {"spans": SPANS, "stats": {"rounds.in_window": 2}, "trace": None, "rounds": [], "window": {"wall0": 1000.0}}


@pytest.mark.parametrize("name,want", [
    ("loop.late_ms", 7.0),                   # (0.010 + 0.004) s over two periods
    ("loop.held_ms", 143.0),                 # (0.180 + 0.100 + 0.006) s over two periods
    ("loop.wait_unnamed_ms", 10.0),          # the held wait at step 44 and the late one in no phase: 0.020 s
    ("loop.wait_share", 100.0 * 0.300 / 18.76),         # the hook's 2 s off the waits and off the seconds
    ("loop.step_wall_max_over_median", 0.520 / 0.368),  # the stretch the hook stopped: its longest step less the hook's 2 s
])
def test_reader_on_a_hand_made_run(name, want):
    assert read(name, RUN) == pytest.approx(want)
    # the parent's program records none of these spans: nothing, and no error
    old = [s for s in SPANS if not s["name"].startswith(("loop.steps", "loop.chip_wait"))]
    assert read(name, dict(RUN, spans=old)) is None
    # spans of another shape under the name (no totals in them) are not read as zeros
    assert read(name, dict(RUN, spans=[span("loop.steps", 1.0, 2.0, step=3)])) is None


def test_a_window_the_hook_stopped_in_every_stretch_still_reads_its_longest_step():
    """Four chips: the profiler's stop takes 110 s, the window holds 15 steps and every stretch a hook wait."""
    spans = [steps(1000.0, 6.5, late=0.5, longest=1.1, p50=0.596),
             span("loop.chip_wait", 1001.0, 0.5, step=11, kind="late", during="on_step", during_s=0.5),
             steps(1006.5, 116.4, late=110.4, longest=111.0, p50=0.596, n=5),
             span("loop.chip_wait", 1008.0, 110.4, step=20, kind="late", during="on_step", during_s=110.4)]
    run = dict(RUN, spans=spans)
    assert read("loop.step_wall_max_over_median", run) == pytest.approx(0.6 / 0.596)
    assert read("loop.wait_share", run) == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("name", ["loop.late_ms", "loop.held_ms", "loop.wait_unnamed_ms"])
def test_a_period_metric_needs_a_whole_period(name):
    assert read(name, dict(RUN, stats={"rounds.in_window": 0})) is None
    assert read(name, dict(RUN, stats={})) is None


def test_the_held_waits_are_resolved_by_the_programs_own_function(monkeypatch):
    got = {w["step"]: w["during"] for w in chip_timeline.waits(RUN)}
    assert got == {3: "loop.launch", 14: "loop.merge", 17: "codec.run", 22: "on_step", 33: "loop.snapshot.land",
                   36: "loop", 44: "none"}
    # a program that has the spans and not the function (nobody's parent, but no reader may raise)
    monkeypatch.delattr(telemetry, "chip_waits")
    assert chip_timeline.waits(RUN) is None
    assert read("loop.wait_unnamed_ms", RUN) is None and read("loop.wait_over_trace_idle", traced_run()) is None
    assert read("loop.late_ms", RUN) == pytest.approx(7.0)   # the totals need no resolving


# -- the check on the instrument: the program's waits over the trace's idle time -------------

MS = 1_000_000


def traced_run(call_at_ns=100 * MS, spans=None):
    """A traced round on chip 0: a window of 2.0 s from the begin mark's end,
    busy but for three gaps (50, 110 and 90 ms, 250 ms in all); the averager
    is called 100 ms into the trace, which the probe noted as wall 1000.6."""
    busy = [["%fusion.1 = bf16[8]{0} fusion(%x)", a * MS, (b - a) * MS]
            for a, b in ((1, 400), (450, 900), (1010, 1500), (1590, 2001))]
    trace = Trace.from_json({"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": busy}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench:trace_begin", 0, 1 * MS], ["bench:trace_end", 2001 * MS, 10],
            ["bench:averager_call", call_at_ns, 1500 * MS],
        ]}]},
    ]})
    at = lambda ms: 1000.6 + (ms * MS - call_at_ns) / 1e9   # noqa: E731 - trace ms -> the spans' clock
    waits = [
        span("loop.chip_wait", at(400), 0.050, step=2, kind="late", during="loop.launch", during_s=0.04),
        span("loop.chip_wait", at(900), 0.110, step=12, kind="held", own_s=0.478),
        span("loop.chip_wait", at(1500), 0.090, step=13, kind="held", own_s=0.458),
        span("loop.chip_wait", at(-500), 0.6, step=1, kind="late", during="on_step", during_s=0.6),  # 0.099 s of it inside
        span("loop.chip_wait", at(5000), 0.2, step=30, kind="held", own_s=0.6),                       # after the trace
        steps(at(0), 3.7, late=0.05, held=0.2),
    ]
    return {"spans": waits if spans is None else spans, "stats": {"rounds.in_window": 2}, "trace": trace,
            "rounds": [{"wall0": 990.0, "index": 3}, {"wall0": 1000.6, "index": 4}, {"wall0": 1020.0, "index": 5}],
            "window": {"wall0": 1000.0}}


def test_the_programs_waits_over_the_traces_idle_time():
    run = traced_run()
    assert chip_timeline.trace_idle_s(run) == pytest.approx(0.250)
    t0, t1 = chip_timeline.traced_interval_on_the_spans_clock(run)
    assert (t0, t1) == (pytest.approx(1000.6 - 0.099), pytest.approx(1000.6 + 1.901))
    # 0.050 + 0.110 + 0.090 inside, and the 0.099 s of the wait the window's edge cuts
    assert read("loop.wait_over_trace_idle", run) == pytest.approx(0.349 / 0.250)
    # wherever the mark lies in the trace, the pair puts the spans where they were
    assert read("loop.wait_over_trace_idle", traced_run(call_at_ns=700 * MS)) == pytest.approx(0.349 / 0.250)
    # stamps that account for the gaps alone read 1
    exact = [s for s in run["spans"] if s["attrs"]["step"] not in (1, 30)]
    assert read("loop.wait_over_trace_idle", dict(run, spans=exact)) == pytest.approx(1.0)


@pytest.mark.parametrize("without", ["trace", "mark", "round", "waits", "idle"])
def test_nothing_where_it_cannot_align(without):
    run = traced_run()
    if without == "trace":
        run["trace"] = None
    elif without == "mark":
        host = next(p for p in run["trace"].planes if p.name == "/host:CPU")
        host.lines[0].events = [e for e in host.lines[0].events if e.name != "bench:averager_call"]
    elif without == "round":
        run["rounds"] = [r for r in run["rounds"] if r["wall0"] < 1000.0]   # none launched inside the window
    elif without == "waits":
        run["spans"] = [span("round", 1000.0, 18.0)]                        # the parent's program
    else:
        run["trace"].planes[0].lines[0].events = []                          # no device op: no idle time to speak of
    assert read("loop.wait_over_trace_idle", run) is None


# -- the manifest, by name -------------------------------------------------------------------


def test_manifest_holds_the_six_metrics_by_name():
    M.check()
    per_layer = {m["name"]: m for m in M.doc["per_layer"]}
    e2e = {m["name"]: m for m in M.doc["end_to_end"]}
    for name, (unit, better, moves, cells) in NEW_METRICS.items():
        m = per_layer[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["moves"], m["workloads"]) == (unit, better, moves, cells)
        assert m["source"] == "program_span" and m["layer"] == "train loop"
        assert set(cells) <= set(e2e[moves]["workloads"])
        assert M.layer_metric_path(name).endswith(name + ".py")
    assert len(SOLO) == 13 and SOLO == e2e["tok_s_chip"]["workloads"]
    # what times the same layer from outside stays (a `benchmark` issue retires it)
    for name in ("loop.round_block_ms", "loop.step_gap_ms", "device.idle_share", "loop.launch_ms", "loop.merge_ms",
                 "loop.merge_host_ms", "loop.snapshot_ms"):
        assert name in per_layer
    # appended: no entry the parent had moved (the six, in the issue's order, follow them)
    names = [m["name"] for m in M.doc["per_layer"]]
    assert sorted(names.index(n) for n in NEW_METRICS) == list(range(len(names) - 6, len(names))) or all(
        names.index(n) > names.index("hc.res_offdiag") for n in NEW_METRICS)


def less_this_pr(root=REPO_ROOT):
    """The manifest without this PR's six metrics, taken off BY NAME (no cell,
    no configuration and no list was touched)."""
    view = Manifest(root)
    doc = json.loads(json.dumps(view.doc))
    doc["per_layer"] = [m for m in doc["per_layer"] if m["name"] not in NEW_METRICS]
    view.doc = doc
    return view


XING4_CASES = [
    ("test_manifest_holds_the_new_configuration_cell_and_metrics", None),
    ("test_manifest_as_the_sdar_tests_asserted_it_before_this_cell",
     ("test_manifest_holds_the_new_configuration_cell_and_metrics", None)),
    ("test_manifest_as_the_sdar_tests_asserted_it_before_this_cell",
     ("test_manifest_as_the_collective_pairs_tests_asserted_it_before_this_cell",
      ("test_manifest_holds_the_nine_scope_metrics_at_its_end", ()))),
    ("test_manifest_as_the_sdar_tests_asserted_it_before_this_cell",
     ("test_manifest_as_the_collective_pairs_tests_asserted_it_before_this_cell",
      ("test_manifest_as_the_kimi_tests_asserted_it_nine_places_up",
       ("test_manifest_holds_the_new_configuration_cell_and_metrics",)))),
    ("test_manifest_as_the_sdar_tests_asserted_it_before_this_cell",
     ("test_manifest_as_the_collective_pairs_tests_asserted_it_before_this_cell",
      ("test_manifest_as_the_kimi_tests_asserted_it_nine_places_up",
       ("test_manifest_tail_as_the_nemotron_tests_asserted_it_three_metrics_and_a_cell_up",)))),
]


@pytest.mark.parametrize("test,args", XING4_CASES)
def test_manifest_as_the_xing4_tests_asserted_it_before_these_metrics(test, args, monkeypatch):
    """``test_yardstick_xing4.py`` runs Ouro's manifest cases against the
    manifest less PR 67's and PR 70's entries, which it builds from
    ``test_yardstick_qwen3_next.less_this_pr`` (tests/conftest.py marks its five
    cases: this PR's six metrics end ``per_layer`` now). The same five, as they
    stand, with that builder started from the manifest less this PR's entries;
    started from the manifest as it is each fails on the tail alone."""
    xing4 = importlib.import_module("test_yardstick_xing4")
    qwen3_next = importlib.import_module("test_yardstick_qwen3_next")
    case = xing4.test_manifest_as_the_qwen3_next_tests_asserted_it_before_this_cell
    monkeypatch.setattr(qwen3_next, "Manifest", less_this_pr)
    case(test, args, monkeypatch)
    monkeypatch.setattr(qwen3_next, "Manifest", Manifest)
    with pytest.raises((AssertionError, ValueError)):
        case(test, args, monkeypatch)


@pytest.mark.parametrize("module", ["test_yardstick_xing4", "test_yardstick_qwen3_next"])
def test_the_two_newest_cells_list_what_they_listed_before_these_metrics(module, monkeypatch):
    """Both files assert, by name, the exact set of per-layer metrics their
    cell reports (tests/conftest.py marks both: ``loop.wait_share`` and
    ``loop.step_wall_max_over_median`` list every solo cell now); as they stand
    against the manifest less this PR's entries, and with the two names added
    the set is what the manifest as it is gives."""
    older = importlib.import_module(module)
    monkeypatch.setattr(older, "M", less_this_pr())
    older.test_manifest_holds_the_new_configuration_cell_and_metrics_by_name()
    monkeypatch.setattr(older, "M", M)
    with pytest.raises(AssertionError):
        older.test_manifest_holds_the_new_configuration_cell_and_metrics_by_name()
    listed = {m["name"] for m in M.metrics_for(older.CELL, "per_layer")}
    before = {m["name"] for m in less_this_pr().metrics_for(older.CELL, "per_layer")}
    assert listed - before == {"loop.wait_share", "loop.step_wall_max_over_median"} and before <= listed
