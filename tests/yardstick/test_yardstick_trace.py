"""The reduction from a trace to busy/idle, programs, collectives and gaps, on
the hand-built trace beside this file (times in ns; worked by hand below)."""

import json
import os

import pytest

from benchmark import readers, trace
from benchmark.manifest import REPO_ROOT, Manifest

HERE = os.path.dirname(os.path.abspath(__file__))
M = Manifest(REPO_ROOT)


@pytest.fixture(scope="module")
def tr():
    with open(os.path.join(HERE, "trace_fixture.json")) as fh:
        return trace.Trace.from_json(json.load(fh))


def test_window_is_between_the_benchmarks_marks(tr):
    assert tr.window() == (500.0, 12000.0)
    assert [p.name for p in tr.device_planes()] == ["/device:TPU:0", "/device:TPU:1"]
    assert len(tr.marks("bench:on_step")) == 3


def test_busy_and_idle(tr):
    bi = trace.busy_idle(tr)
    # chip 0: three steps of 3,000 and one 300 program; chip 1's instruction
    # stream: 900 + 1,500 + 3,000 + 2,900 (the asynchronous all-reduce is not on it)
    assert bi["busy_s_per_chip"] == pytest.approx([9300e-9, 8300e-9])
    assert bi["window_s"] == pytest.approx(11500e-9)
    assert bi["busy_s"] == pytest.approx(8800e-9)
    assert bi["idle_share"] == pytest.approx(1 - 8800 / 11500)


def test_programs_and_gaps(tr):
    runs = trace.program_runs(tr, r"^jit_step(\(|$)")
    assert [e.dur_ns for e in runs] == [3000, 3000, 3000]
    assert trace.gaps_between(runs) == [500, 700]
    assert trace.program_totals(tr) == {"jit_step": (3, pytest.approx(9000e-9)),
                                        "jit_body": (1, pytest.approx(300e-9))}


def test_collectives_and_their_exposed_part(tr):
    c = trace.collective_time(tr)
    # chip 0: 3 x 500, nothing beside them; chip 1: 1,000, of which [2600,3000) runs beside a fusion
    assert c["collective_s"] == pytest.approx((1500 + 1000) / 2 * 1e-9)
    assert c["exposed_s"] == pytest.approx((1500 + 600) / 2 * 1e-9)


def test_breakdown_lists(tr):
    ops = trace.top_ops(tr, n=4)
    # own time: the `while` that spans each step's operations has none left
    assert ops[0] == ("fusion.2 f32[16,1024]", pytest.approx(4500e-9))
    assert ops[1] == ("fusion.1 bf16[16,1024,1024]", pytest.approx(3000e-9))
    assert ops[2] == ("all-reduce.1 f32[1280,1280]", pytest.approx(1500e-9))
    assert ops[3] == ("convert.9 u16[354823168]", pytest.approx(300e-9))
    labels = [("launch", (4000.0, 4500.0)), ("round:wire", (4250.0, 7000.0)),
              ("round:other", (7000.0, 8300.0))]
    gaps = trace.idle_gaps(tr, labels, n=4)
    # idle on chip 0: [500,1000) [4000,4500) [7500,7600) [7900,8200) [11200,12000)
    assert gaps == [("unattributed", pytest.approx(800e-9)), ("unattributed", pytest.approx(500e-9)),
                    ("launch", pytest.approx(500e-9)), ("round:other", pytest.approx(300e-9))]


def test_op_names_are_cut_out_of_the_instruction_text():
    text = "%fusion.450 = bf16[16,16,1024,1024]{2,3,1,0:T(8,128)(2,1)} fusion(f32[16]{0} %p)"
    assert trace.op_name(text) == "fusion.450"
    assert trace.op_label(text) == "fusion.450 bf16[16,16,1024,1024]"
    assert trace.op_label("%while.17 = (s32[]{:T(128)}, bf16[4]{0}) while(%t)") == "while.17 s32[]"
    assert trace.op_label("plain-name") == "plain-name"
    assert trace.is_collective("%all-reduce-start.2 = f32[8]{0} all-reduce-start(%y)")
    assert trace.is_collective("%reduce-scatter.1 = f32[8]{0} reduce-scatter(%y)")
    assert not trace.is_collective("%fusion.3 = f32[8]{0} fusion(%all-reduce.1)")


def test_own_time_of_nested_operations():
    ev = [trace.Event("outer", 0, 100), trace.Event("a", 10, 30), trace.Event("b", 50, 40),
          trace.Event("b.inner", 60, 10), trace.Event("next", 100, 5)]
    got = {e.name: (own, leaf) for e, own, leaf in trace.self_times(ev)}
    assert got == {"outer": (30, False), "a": (30, True), "b": (30, False),
                   "b.inner": (10, True), "next": (5, True)}


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
])
def test_interval_subtraction(a, b, want):
    assert trace.subtract(a, b) == want
    assert trace.merge([(5, 7), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 7)]


def _run(tr):
    return {"trace": tr, "step_program": r"^jit_step(\(|$)", "tokens_per_step": 16384,
            "flops_per_token": 2.4e9, "chips": 2, "peak": {"bf16_flops": 197e12},
            "stats": {}, "spans": []}


@pytest.mark.parametrize("name,want", [
    ("step.device_ms", 3000e-6),
    ("loop.step_gap_ms", 600e-6),
    ("loop.round_block_ms", (11500 - 9300) * 1e-6),
    ("device.idle_share", 100 * (1 - 8800 / 11500)),
    ("device.collective_share", 100 * 1250 / 11500),
    ("device.collective_exposed", 100 * 1050 / 11500),
    ("step.mfu", 100 * 16384 * 2.4e9 / (3000e-9 * 2 * 197e12)),
])
def test_layer_metric_readers_on_the_fixture(tr, name, want):
    assert readers.compute(M.layer_metric_path(name), _run(tr)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["step.device_ms", "step.mfu", "device.idle_share", "loop.step_gap_ms"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    run = dict(_run(None), trace=None)
    assert readers.compute(M.layer_metric_path(name), run) is None
    empty = trace.Trace.from_json({"planes": [{"name": "/host:CPU", "lines": []}]})
    assert readers.compute(M.layer_metric_path(name), _run(empty)) is None
