"""What PR 37 added to the yardstick: the seven ``lifecycle.*`` readers, on
fixture spans and a fixture compile log (what they read, the cut at the
window's opening, nothing and no error from a program that records neither),
the manifest with the seven entries appended, and what
``test_yardstick_smallthinker.py`` asserted of the manifest's tail, seven
places up (see tests/conftest.py)."""

import pytest

from benchmark import readers
from benchmark.manifest import REPO_ROOT, Manifest

M = Manifest(REPO_ROOT)
CELLS = ["medium-solo", "medium-round", "large-solo-4chip", "olmoe-solo", "laguna-solo-8k",
         "smallthinker-solo-16k"]
SPAN_READERS = {"lifecycle.ready_s": "lifecycle", "lifecycle.net_s": "lifecycle.net",
                "lifecycle.init_s": "lifecycle.init", "lifecycle.step_build_s": "lifecycle.step_build",
                "lifecycle.first_step_s": "lifecycle.first_step"}
COUNTER_READERS = ("lifecycle.trace_lower_s", "lifecycle.cache_load_s")
NEW = {**{name: "program_span" for name in SPAN_READERS},
       **{name: "program_counter" for name in COUNTER_READERS}}

WINDOW = {"wall0": 1000.0, "wall1": 1045.0}


def span(name, t0, dur, parent=None, trace="lifecycle", **attrs):
    d = {"trace": trace, "name": name, "peer": "vol-chip", "t0": t0, "dur_s": dur}
    if parent:
        d["parent"] = parent
    if attrs:
        d["attrs"] = attrs
    return d


# A start that took 8 s, 30 s before the window opened; and what the same
# process records later, which is not start-up as the window saw it.
TREE = [
    span("lifecycle.process", 960.0, 4.0),
    span("lifecycle", 965.0, 8.0, model="gpt2_medium", averaging="none", chips=1, cold=False),
    span("lifecycle.net", 965.25, 0.25, "lifecycle", peers=0),
    span("lifecycle.model", 965.5, 0.5, "lifecycle"),
    span("lifecycle.init", 966.0, 3.0, "lifecycle"),
    span("lifecycle.init.params", 966.5, 1.5, "lifecycle.init", programs=43),
    span("loop.snapshot", 968.0, 1.0, "lifecycle.init", step=0),
    span("lifecycle.first_batch", 969.0, 0.5, "lifecycle"),
    span("lifecycle.step_build", 969.5, 2.75, "lifecycle", program="jit(step)", cache="hit"),
    span("lifecycle.first_step", 972.25, 0.75, "lifecycle", step=1),
    span("lifecycle.step_build", 1050.0, 99.0, "lifecycle"),  # after the window opened
    span("lifecycle.first_step", 990.0, None, "lifecycle"),  # one that never ended
]
WANT = {"lifecycle.ready_s": 8.0, "lifecycle.net_s": 0.25, "lifecycle.init_s": 3.0,
        "lifecycle.step_build_s": 2.75, "lifecycle.first_step_s": 0.75}


class FakeCompileLog:
    """``summary(until=t)`` of a log that heard 1.5 s of tracing, 0.75 of
    lowering and 0.5 of cache loads before the window, and the reference
    check's programs after it."""

    def __init__(self):
        self.asked = []

    def summary(self, program="", until=None):
        self.asked.append(until)
        late = until is None or until > 1045.0
        return {"programs": 50 + 3 * late, "seconds": 2.0 + 30.0 * late, "cache_hits": 50, "cache_misses": 3 * late,
                "program": program, "trace_seconds": 1.5 + 4.0 * late, "lower_seconds": 0.75 + 2.0 * late,
                "cache_load_seconds": 0.5, "slowest": []}


class ParentCompileLog:
    """The compile log before PR 37: one positional argument, backend seconds."""

    def summary(self, program):
        return {"programs": 50, "seconds": 2.0, "cache_hits": 50, "cache_misses": 0, "program": program}


@pytest.fixture
def program(monkeypatch):
    """The program's two process-wide offers, with fixtures behind them."""
    from distributedvolunteercomputing_tpu.swarm import telemetry
    from distributedvolunteercomputing_tpu.utils import jaxenv

    log = FakeCompileLog()
    monkeypatch.setattr(telemetry, "lifecycle_spans", lambda: list(TREE))
    monkeypatch.setattr(jaxenv, "compile_log", lambda: log)
    return log


def read(name, run=None):
    return readers.compute(M.layer_metric_path(name), run or {"window": WINDOW, "spans": [], "stats": {}})


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_on_a_fixture_tree(name, program, monkeypatch):
    from distributedvolunteercomputing_tpu.swarm import telemetry

    assert read(name) == pytest.approx(WANT[name])
    # the spans `run.py` hands over started inside the window: not these readers' source
    assert read(name, {"window": WINDOW, "spans": [span(SPAN_READERS[name], 1001.0, 77.0)]}) == pytest.approx(
        WANT[name])
    # a window that opened before the span began does not see it
    early = {"window": {"wall0": 965.1}}
    assert read(name, early) == (8.0 if name == "lifecycle.ready_s" else None)
    # a tree without the span: nothing, and no error
    without = [s for s in TREE if s["name"] != SPAN_READERS[name]]
    monkeypatch.setattr(telemetry, "lifecycle_spans", lambda: without)
    assert read(name) is None
    # a program without the function (the parent commit): nothing, and no error
    monkeypatch.delattr(telemetry, "lifecycle_spans")
    assert read(name) is None


def test_a_later_start_in_the_same_process_is_the_one_read(program, monkeypatch):
    from distributedvolunteercomputing_tpu.swarm import telemetry

    again = TREE + [span("lifecycle", 980.0, 5.0), span("lifecycle.init", 980.5, 1.25, "lifecycle")]
    monkeypatch.setattr(telemetry, "lifecycle_spans", lambda: again)
    assert read("lifecycle.ready_s") == 5.0 and read("lifecycle.init_s") == 1.25


def test_counter_readers_cut_the_compile_log_at_the_window(program, monkeypatch):
    from distributedvolunteercomputing_tpu.utils import jaxenv

    assert read("lifecycle.trace_lower_s") == pytest.approx(2.25)
    assert read("lifecycle.cache_load_s") == pytest.approx(0.5)
    assert program.asked == [1000.0, 1000.0]
    # with the reference check's programs in it the sum would read 8.25
    assert program.summary()["trace_seconds"] + program.summary()["lower_seconds"] == pytest.approx(8.25)
    # the parent's log takes no `until` and counts no stage but the backend's: nothing, no error
    monkeypatch.setattr(jaxenv, "compile_log", lambda: ParentCompileLog())
    assert read("lifecycle.trace_lower_s") is None and read("lifecycle.cache_load_s") is None


def test_readers_on_the_programs_own_tracer_and_log():
    """No fixture between reader and program: a tracer's recorded tree and
    the process's compile log, through the functions the readers call."""
    import time

    import jax
    import jax.numpy as jnp

    from distributedvolunteercomputing_tpu.swarm import telemetry
    from distributedvolunteercomputing_tpu.utils import jaxenv

    jaxenv.compile_log()  # listening starts at the first call, as a Trainer's constructor makes it
    tracer = telemetry.Tracer(peer_id="yardstick-lifecycle")
    # begun now: later than any start another test of this process has left behind
    now = time.time()
    tracer.record("lifecycle", telemetry.LIFECYCLE, now, 6.0)
    tracer.record("lifecycle.step_build", telemetry.LIFECYCLE, now + 0.001, 2.5, parent="lifecycle")
    jax.jit(lambda x: x * 37)(jnp.ones(3)).block_until_ready()
    run = {"window": {"wall0": time.time() + 1.0}}
    assert read("lifecycle.ready_s", run) == 6.0 and read("lifecycle.step_build_s", run) == 2.5
    assert read("lifecycle.trace_lower_s", run) > 0.0
    assert read("lifecycle.cache_load_s", run) >= 0.0  # off the TPU the cache is off: no load
    before_the_process = {"window": {"wall0": now - 3600.0}}
    assert read("lifecycle.ready_s", before_the_process) is None
    # (what a long-lived process has folded out of its event list counts before any moment)
    assert read("lifecycle.trace_lower_s", before_the_process) < read("lifecycle.trace_lower_s", run)


# -- the manifest ----------------------------------------------------------------------

SMALLTHINKER_METRICS = ("step.mfu_model", "attention.roofline", "moe.act_zero_share")
LAGUNA_METRICS = ("attention.window_device_ms", "attention.full_device_ms", "attention.window_roofline",
                  "step.mfu_held", "moe.rows_moved_over_held", "moe.share_device_ms")
LAGUNA_ONLY = ("attention.window_roofline", "step.mfu_held")
OLMOE_METRICS = ("step.mfu_active", "moe.device_ms", "moe.gmm_roofline", "moe.load_max_over_mean", "moe.dropped")
SPAN_METRICS = ("moe.load_max_over_mean", "moe.dropped")
APPENDED = ("tok_s_chip", "loop.step_gap_ms", "step.device_ms", "device.idle_share", "device.peak_hbm_GB",
            "moe.load_max_over_mean", "moe.dropped", "moe.rows_moved_over_held", "moe.share_device_ms",
            "attention.window_device_ms", "attention.full_device_ms")


def test_manifest_holds_the_seven_start_up_metrics_at_its_end():
    M.check()
    every = {m["name"]: m for kind in ("end_to_end", "per_layer") for m in M.doc[kind]}
    named = lambda names: [every[n] for n in names]  # noqa: E731
    assert M.doc["per_layer"][-7:] == named(NEW)
    for name, source in NEW.items():
        m = every[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"]) == ("s", "lower", source)
        assert m["layer"] == "entry / lifecycle" and m["moves"] == "setup_s"
        assert m["workloads"] == CELLS
        path = M.layer_metric_path(name)
        assert path.endswith(".py") and "def compute(run)" in open(path).read()
    # the three that time the layer from outside stay, first in the list
    assert [m["name"] for m in M.doc["per_layer"][:3]] == [
        "lifecycle.compile_s", "lifecycle.cache_misses", "lifecycle.backend_init_s"]
    assert all("workloads" not in m for m in M.doc["per_layer"][:3])
    # no cell and no configuration came with them
    assert [w["name"] for w in M.doc["workloads"]] == CELLS and len(M.doc["configs"]) == 5
    assert every["setup_s"] == {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
                                "source": "host_clock"}
    for cell in CELLS:
        assert set(NEW) <= {m["name"] for m in M.metrics_for(cell, "per_layer")}


def test_manifest_tail_as_the_smallthinker_test_asserted_it_seven_places_up():
    cell_name = "smallthinker-solo-16k"
    cell = M.cell(cell_name)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("smallthinker-21b-a3b", "solo", 1)
    every = {m["name"]: m for kind in ("end_to_end", "per_layer") for m in M.doc[kind]}
    named = lambda names: [every[n] for n in names]  # noqa: E731
    assert M.doc["per_layer"][-10:-7] == named(SMALLTHINKER_METRICS)
    assert M.doc["per_layer"][-16:-10] == named(LAGUNA_METRICS)
    assert M.doc["per_layer"][-21:-16] == named(OLMOE_METRICS)
    for m in named(SMALLTHINKER_METRICS):
        assert m["layer"] == "compiled step" and m["moves"] == "tok_s_chip" and m["workloads"] == [cell_name]
    for name in APPENDED:
        assert every[name]["workloads"][-2:] == ["laguna-solo-8k", cell_name], name
    assert M.doc["workloads"][-1] is cell and M.doc["configs"][-1]["name"] == "smallthinker-21b-a3b"
    assert len(M.doc["workloads"]) == 6 and sum(w["chips"] == 4 for w in M.doc["workloads"]) == 1
    assert M.doc["workloads"][-2]["name"] == "laguna-solo-8k" and M.doc["configs"][-2]["name"] == "laguna-xs2"
    assert M.doc["workloads"][-3]["name"] == "olmoe-solo" and M.doc["configs"][-3]["name"] == "olmoe-1b-7b"
    for m in named(LAGUNA_METRICS):
        assert m["workloads"] == ["laguna-solo-8k"] + [cell_name] * (m["name"] not in LAGUNA_ONLY)
        assert m["layer"] == "compiled step" and m["moves"] == "tok_s_chip"
    for m in named(OLMOE_METRICS):
        assert m["workloads"] == ["olmoe-solo"] + ["laguna-solo-8k", cell_name] * (m["name"] in SPAN_METRICS)
    per_layer = {m["name"] for m in M.metrics_for(cell_name, "per_layer")}
    for other in ("attention.device_ms", "step.mfu", "step.mfu_active", "moe.device_ms", "moe.gmm_roofline",
                  *LAGUNA_ONLY):
        assert other not in per_layer and cell_name not in every[other]["workloads"], other
    assert {m["name"] for m in M.metrics_for(cell_name, "end_to_end")} == {"tok_s_chip", "setup_s"}
    laguna = {m["name"] for m in M.metrics_for("laguna-solo-8k", "per_layer")}
    assert not laguna & set(SMALLTHINKER_METRICS) and set(LAGUNA_METRICS) <= laguna
