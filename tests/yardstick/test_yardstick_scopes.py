"""What PR 56 added to the yardstick: the nine ``scope.*`` readers
(``benchmark/scope_trace.py``) on a trace and a scope map built by hand (a
``while``'s own time against its body's, an event whose result type differs
from the map's, the groups' sum against the steps' busy time, nothing and no
error from a program without the accessor, nothing asked of the program in an
untraced run), the manifest with the nine entries appended, and what
``test_yardstick_kimi_linear.py``'s two manifest tests asserted, nine places up
(see tests/conftest.py)."""

import importlib
import json
import sys

import pytest

from benchmark import readers, scope_trace, trace as tr
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.trace import Trace

M = Manifest(REPO_ROOT)
SOLO = ["medium-solo", "large-solo-4chip", "olmoe-solo", "laguna-solo-8k", "smallthinker-solo-16k",
        "lfm2-solo-8k", "glm47-flash-solo-8k", "nemotron3-nano-solo-8k", "kimi-linear-solo-8k"]
NEW = {
    "scope.attention_ms": ("ms", SOLO),
    "scope.mixer_ms": ("ms", ["lfm2-solo-8k", "nemotron3-nano-solo-8k", "kimi-linear-solo-8k"]),
    "scope.mlp_ms": ("ms", ["medium-solo", "large-solo-4chip", "laguna-solo-8k", "lfm2-solo-8k",
                            "glm47-flash-solo-8k", "kimi-linear-solo-8k"]),
    "scope.moe_ms": ("ms", ["olmoe-solo", "laguna-solo-8k", "smallthinker-solo-16k", "lfm2-solo-8k",
                            "glm47-flash-solo-8k", "nemotron3-nano-solo-8k", "kimi-linear-solo-8k"]),
    "scope.loss_head_ms": ("ms", SOLO),
    "scope.optimizer_ms": ("ms", SOLO),
    "scope.other_ms": ("ms", SOLO),
    "scope.recompute_share": ("%", SOLO),
    "scope.unresolved_share": ("%", SOLO),
}
VOCABULARY = {"attention": "attention", "kda": "mixer", "conv_mixer": "mixer", "mamba": "mixer", "mlp": "mlp",
              "moe": "moe", "moe_route": "moe", "loss_head": "loss_head", "optimizer": "optimizer"}

# -- a trace and a map by hand ---------------------------------------------------------

LOOP = ("%while.1 = (s32[]{:T(128)}, f32[2,8]{1,0:T(8,128)S(1)}) while((s32[]{:T(128)}, f32[2,8]{1,0:T(8,128)S(1)}) "
        "%tuple.1), condition=%cond, body=%body")
OPS = {  # name -> the instruction's text as the chip names its event
    "fusion.1": "%fusion.1 = bf16[2,8]{1,0:T(8,128)(2,1)} fusion(bf16[2,8]{1,0} %p.1), kind=kLoop, calls=%fc.1",
    "while.1": LOOP,
    "dot.2": "%dot.2 = f32[8,8]{1,0:T(8,128)} dot(f32[8,2]{1,0} %a, f32[2,8]{1,0} %b)",
    "fusion.3": "%fusion.3 = (f32[8]{0:T(1024)}, bf16[2,8]{1,0:T(8,128)(2,1)}) fusion(f32[2,8]{1,0} %c), kind=kOutput, calls=%fc.3",
    "kernel.4": "%kernel.4 = bf16[2,8]{1,0} custom-call(bf16[2,8]{1,0} %d), custom_call_target=\"tpu_custom_call\"",
    "fusion.9": "%fusion.9 = bf16[4]{0:T(1024)(128)(2,1)} fusion(bf16[4]{0} %e), kind=kLoop, calls=%fc.9",
    "copy.7": "%copy.7 = f32[2,8]{0,1:T(8,128)} copy(f32[2,8]{1,0:T(8,128)} %f)",
    "head.5": "%head.5 = f32[]{:T(128)} fusion(f32[2,8]{1,0} %g), kind=kLoop, calls=%fc.5",
    "adam.6": "%adam.6 = f32[2,8]{1,0:T(8,128)} fusion(f32[2,8]{1,0} %h), kind=kLoop, calls=%fc.6",
}
MAP = {  # layouts printed as a module's text prints them: not as the events do
    "fusion.1": {"scope": "attention", "pass": "fwd", "result": "bf16[2,8]{1,0}", "mixed": False},
    "while.1": {"scope": None, "pass": "fwd", "result": "(s32[], f32[2,8]{1,0})", "mixed": False},
    "dot.2": {"scope": "mlp", "pass": "refwd", "result": "f32[8,8]{1,0}", "mixed": False},
    "fusion.3": {"scope": "moe_route", "pass": "bwd", "result": "(f32[8]{0}, bf16[2,8]{1,0})", "mixed": True},
    "kernel.4": {"scope": "kda", "pass": "bwd", "result": "bf16[2,8]{1,0}", "mixed": False},
    "fusion.9": {"scope": "mlp", "pass": "fwd", "result": "bf16[8]{0}", "mixed": False},   # another executable's
    "head.5": {"scope": "loss_head", "pass": "fwd", "result": "f32[]", "mixed": False},
    "adam.6": {"scope": "optimizer", "pass": "fwd", "result": "f32[2,8]{1,0}", "mixed": False},
}
DOC = {"program": "jit(step)", "module": "jit_step", "vocabulary": VOCABULARY, "map": MAP,
       "seconds": {"lower": 0.0, "compile": 0.0, "parse": 0.0}}
# one step, from its start, in ns: (name, start, duration); the loop spans dot.2 and fusion.3
STEP = [("fusion.1", 0, 100), ("while.1", 150, 500), ("dot.2", 200, 120), ("fusion.3", 350, 250),
        ("kernel.4", 650, 50), ("fusion.9", 700, 40), ("copy.7", 740, 10), ("head.5", 800, 60), ("adam.6", 900, 100)]
STEP_NS, BUSY_NS = 1000, 100 + 500 + 50 + 40 + 10 + 60 + 100   # 860: idle inside the step is no group's
STARTS = (1000, 2100)  # two whole executions; a third is cut by the window's end


def hand_trace(planes=1):
    ops = [[OPS[name], s0 + s, d] for s0 in STARTS for name, s, d in STEP]
    ops += [[OPS["fusion.1"], 10, 50],           # before the first step: another program's
            [OPS["adam.6"], 3300, 100]]          # inside the execution the window cuts
    modules = [["jit_step(123)", s0, STEP_NS] for s0 in STARTS]
    modules += [["jit_step(123)", 3250, STEP_NS], ["jit_other(9)", 0, 80]]
    device = [{"name": "XLA Modules", "events": modules}, {"name": "XLA Ops", "events": ops}]
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["bench:trace_begin", 0, 1], ["bench:trace_end", 3500, 1]]}]}
    return Trace.from_json({"planes": [host] + [
        {"name": f"/device:TPU:{i}", "lines": device} for i in range(planes)]})


def hand_run(trace=True, planes=1):
    return {"trace": hand_trace(planes) if trace else None, "step_program": r"^jit_step(\(|$)",
            "cell": {"name": "no-such-cell"}, "config": {}, "stats": {}, "spans": []}


@pytest.fixture(autouse=True)
def _each_test_reads_anew():
    """The reader keeps one result a trace: no test sees another's."""
    scope_trace._found.clear()
    yield
    scope_trace._found.clear()


@pytest.fixture
def offered(monkeypatch):
    """A program whose accessor gives the hand-built document."""
    monkeypatch.setattr(scope_trace, "scopes_of", lambda run: DOC)


def read(name, run):
    return readers.compute(M.layer_metric_path(name), run)


# -- the reduction ------------------------------------------------------------------------


def test_shapes_compare_results_without_their_layouts():
    assert scope_trace.event_result(LOOP) == ("s32[]", "f32[2,8]") == scope_trace.shapes(MAP["while.1"]["result"])
    assert scope_trace.event_result(OPS["fusion.3"]) == ("f32[8]", "bf16[2,8]")
    assert scope_trace.event_result(OPS["head.5"]) == ("f32[]",)
    assert scope_trace.event_result("%copy-done.3 = f8e4m3fn[4,2]{1,0:T(8,128)(4,1)} copy-done(%x)") == ("f8e4m3fn[4,2]",)
    assert scope_trace.event_result("no instruction") == ()
    assert tr.op_name(LOOP) == "while.1"


def test_each_events_own_time_goes_to_its_instructions_group_and_pass():
    got = scope_trace.attribute(hand_run(), DOC)
    assert got["steps"] == 2 and got["total_ns"] == 2 * BUSY_NS
    per_step = {g: {p: ns / 2 for p, ns in by.items()} for g, by in got["table"].items()}
    assert per_step == {
        "attention": {"fwd": 100.0},
        "other": {"fwd": 500.0 - 120 - 250},     # the loop WITHOUT its body
        "mlp": {"refwd": 120.0},
        "moe": {"bwd": 250.0},                   # `moe_route` is of group moe
        "mixer": {"bwd": 50.0},                  # `kda` of group mixer
        "loss_head": {"fwd": 60.0},
        "optimizer": {"fwd": 100.0},
    }
    # an event whose result differs from the map's, and one the map does not hold, are nobody's
    assert got["unresolved_ns"] == 2 * (40 + 10)
    assert {k: v[0] for k, v in got["unresolved"].items()} == {
        "fusion.9": "another result", "copy.7": "no such instruction"}
    assert got["mixed_ns"] == 2 * 250
    assert scope_trace.attribute(hand_run(trace=False), DOC) is None


def test_the_seven_sums_and_the_unresolved_rest_are_the_steps_busy_time(offered):
    run = hand_run()
    seven = {name: read(name, run) for name in NEW if name.endswith("_ms")}
    assert seven == {
        "scope.attention_ms": 100 / 1e6, "scope.mixer_ms": 50 / 1e6, "scope.mlp_ms": 120 / 1e6,
        "scope.moe_ms": 250 / 1e6, "scope.loss_head_ms": 60 / 1e6, "scope.optimizer_ms": 100 / 1e6,
        "scope.other_ms": 130 / 1e6}
    unresolved = read("scope.unresolved_share", run)
    assert unresolved == pytest.approx(100.0 * 50 / BUSY_NS)
    busy_ms = BUSY_NS / 1e6   # the union of the op intervals inside a step
    assert sum(seven.values()) + unresolved / 100.0 * busy_ms == pytest.approx(busy_ms)
    # with every event resolved the seven alone are the busy time
    whole = dict(DOC, map={**MAP, "fusion.9": dict(MAP["fusion.9"], result="bf16[4]{0}"),
                           "copy.7": {"scope": None, "pass": "fwd", "result": "f32[2,8]{0,1}", "mixed": False}})
    got = scope_trace.attribute(run, whole)
    assert got["unresolved_ns"] == 0
    assert sum(ns for by in got["table"].values() for ns in by.values()) == 2 * BUSY_NS
    assert read("scope.recompute_share", run) == pytest.approx(100.0 * 120 / BUSY_NS)


def test_the_map_is_asked_for_once_a_run_and_chip_0_is_read(monkeypatch):
    asked = []
    monkeypatch.setattr(scope_trace, "scopes_of", lambda run: (asked.append(1), DOC)[1])
    run = hand_run(planes=4)
    values = [read(name, run) for name in NEW]
    assert len(asked) == 1 and all(v is not None for v in values)
    assert read("scope.attention_ms", run) == 100 / 1e6   # one chip's plane, not the four summed


def test_a_group_the_step_has_nothing_under_reads_zero(monkeypatch):
    bare = dict(DOC, map={k: v for k, v in MAP.items() if k != "kernel.4"})
    monkeypatch.setattr(scope_trace, "scopes_of", lambda run: bare)
    run = hand_run()
    assert read("scope.mixer_ms", run) == 0.0 and read("scope.attention_ms", run) == 100 / 1e6


def test_a_program_without_the_accessor_gives_nothing_and_no_error(monkeypatch):
    import distributedvolunteercomputing_tpu.utils as package

    # a parent commit: no such module, and no attribute an earlier import left on the package
    monkeypatch.setitem(sys.modules, "distributedvolunteercomputing_tpu.utils.step_scopes", None)
    monkeypatch.delattr(package, "step_scopes", raising=False)
    run = hand_run()
    assert scope_trace.scopes_of(run) is None
    assert [read(name, run) for name in NEW] == [None] * 9


def test_a_program_that_remembered_no_such_step_gives_nothing(monkeypatch):
    program = pytest.importorskip("distributedvolunteercomputing_tpu.utils.step_scopes")  # a parent commit has none
    monkeypatch.setattr(program, "remembered", lambda: ["jit(multi)"])
    monkeypatch.setattr(program, "step_scopes", lambda name: dict(DOC, program=name, module="jit_multi"))
    assert read("scope.other_ms", hand_run()) is None
    # and a map that cannot be built leaves the metrics out, not the line
    monkeypatch.setattr(program, "step_scopes", lambda name: 1 / 0)
    assert read("scope.other_ms", hand_run()) is None


def test_an_untraced_run_asks_the_program_for_nothing(monkeypatch):
    def never(run):
        raise AssertionError("step_scopes() reached in an untraced run")

    monkeypatch.setattr(scope_trace, "scopes_of", never)
    assert [read(name, hand_run(trace=False)) for name in NEW] == [None] * 9
    # run.py computes per-layer metrics in a traced run alone, after the window and the reference check
    with open(f"{REPO_ROOT}/benchmark/run.py") as fh:
        text = fh.read()
    assert text.index("probe.reference_check()") < text.index("elif args.trace:") < text.index("readers.compute(")
    # and the window's counters are read before the check releases the training state (PR 69): run.py goes on
    # only from a closed window (`_close_window` fills `probe.after`, then sets the phase), takes the window's peak
    # from `after`, and calls nothing of the probe between the volunteer's end and the check; the check itself takes
    # the shardings, releases (which refuses before the phase is `done`), and only then puts the parameters back
    order = ["summary = asyncio.run(vol.run())", 'if probe.phase != "done":', "probe.reference_check()",
             "probe.finish()", 'after["memory_peak_bytes"],  # read before the reference check',
             'device["memory_peak_bytes"] = probe.peak_bytes()']
    assert [text.index(x) for x in order] == sorted(text.index(x) for x in order)
    assert "probe." not in text[text.index(order[0]) + len(order[0]):text.index(order[1])]
    with open(f"{REPO_ROOT}/benchmark/probe.py") as fh:
        text = fh.read()
    close = text[text.index("def _close_window"):text.index("# -- profiler")]
    assert close.index("self.after = self.counters()") < close.index("self.phase = DONE")
    release = text[text.index("def release_training_state"):text.index("def reference_check")]
    assert release.index("if self.phase != DONE:") < release.index("x.delete()")
    check = text[text.index("def reference_check"):text.index("# -- the three seams")]
    order = ["tr.state.params)", "self.release_training_state()", "jax.device_put(self._initial_params, shardings)"]
    assert [check.index(x) for x in order] == sorted(check.index(x) for x in order)
    assert "tr.state" not in check[check.index(order[1]):] and ".trainer.state" not in text[text.index("def finish"):text.index("def on_step")]


def test_the_reader_leaves_the_map_beside_the_trace(offered, tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(scope_trace.manifest, "REPO_ROOT", str(tmp_path))
    work = tmp_path / ".bench_work" / "some-cell"
    work.mkdir(parents=True)
    run = dict(hand_run(), cell={"name": "some-cell"})
    assert read("scope.mlp_ms", run) == 120 / 1e6
    with open(work / "step_scopes.json") as fh:
        assert json.load(fh) == json.loads(json.dumps(DOC))
    line = next(ln for ln in capsys.readouterr().err.splitlines() if "scopes: {" in ln)
    said = json.loads(line.split("scopes: ", 1)[1])
    assert said["mixed_share"] == pytest.approx(250 / BUSY_NS, abs=1e-5) and said["steps"] == 2
    assert said["ms_a_step"]["other"] == {"fwd": 0.0}  # 130 ns, rounded to microseconds
    assert [u[:2] for u in said["unresolved_most"]] == [["fusion.9", "another result"], ["copy.7", "no such instruction"]]


# -- the manifest ---------------------------------------------------------------------------


def test_manifest_holds_the_nine_scope_metrics_at_its_end():
    M.check()
    every = {m["name"]: m for kind in ("end_to_end", "per_layer") for m in M.doc[kind]}
    assert [m["name"] for m in M.doc["per_layer"][-9:]] == list(NEW)
    for name, (unit, cells) in NEW.items():
        assert every[name] == {"name": name, "unit": unit, "better": "lower", "source": "device_trace",
                               "layer": "compiled step", "moves": "tok_s_chip", "workloads": cells}, name
        path = M.layer_metric_path(name)
        assert path.endswith(".py") and "def compute(run)" in open(path).read()
    # `medium-round` is traced by round, not by steps: in no list; no cell and no configuration came
    assert all("medium-round" not in cells for _, cells in NEW.values())
    assert len(M.doc["workloads"]) == 10 and len(M.doc["configs"]) == 9 and len(M.doc["per_layer"]) == 66
    assert SOLO == [w["name"] for w in M.doc["workloads"] if w["name"] != "medium-round"]
    assert every["tok_s_chip"]["workloads"] == SOLO and every["tok_s_chip"]["bound"] == 0.01
    assert len(open(f"{REPO_ROOT}/BENCHMARK.json").read()) < 64 * 1024


@pytest.mark.parametrize("test", [
    "test_manifest_holds_the_new_configuration_cell_and_metrics",
    "test_manifest_tail_as_the_nemotron_tests_asserted_it_three_metrics_and_a_cell_up",
])
def test_manifest_as_the_kimi_tests_asserted_it_nine_places_up(test, monkeypatch):
    """``test_yardstick_kimi_linear.py``'s two manifest tests (tests/conftest.py
    marks them: they assert that the three ``kda.*`` metrics END ``per_layer``),
    run as they stand against the manifest less this PR's nine entries."""
    kimi = importlib.import_module("test_yardstick_kimi_linear")
    view = Manifest(REPO_ROOT)
    assert [m["name"] for m in view.doc["per_layer"][-9:]] == list(NEW)
    view.doc = dict(view.doc, per_layer=view.doc["per_layer"][:-9])
    monkeypatch.setattr(kimi, "M", view)
    getattr(kimi, test)()
    # and against the manifest as it is, each fails on the tail alone
    monkeypatch.setattr(kimi, "M", M)
    with pytest.raises(AssertionError):
        getattr(kimi, test)()
