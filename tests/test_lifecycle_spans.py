"""Start-up as a span tree (trace ``lifecycle``): what a ``Trainer`` and an
in-process ``Volunteer`` record from construction to the first finished step,
who ends what, what ``CompileLog`` counts beside the backend's seconds, and
that nothing of it exists with telemetry off."""

import asyncio
import contextlib
import gc
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.swarm import telemetry as T
from distributedvolunteercomputing_tpu.swarm.volunteer import Volunteer, VolunteerConfig
from distributedvolunteercomputing_tpu.training.trainer import Trainer
from distributedvolunteercomputing_tpu.utils import jaxenv

TINY_GPT2 = dict(vocab=128, max_len=32, d_model=64, n_heads=4, n_layers=2, d_ff=128, remat=False)
WAITER = "loop-step-done"   # training/trainer.WATCHER: the one thread that notes when steps are done


def by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


def wait_for_root(tracer, timeout=30.0):
    """The waiter ends the root off the train thread: give it a moment."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        spans = tracer.spans(trace=T.LIFECYCLE)
        if any(s["name"] == T.LIFECYCLE for s in spans):
            return spans
        time.sleep(0.01)
    raise AssertionError("the lifecycle root never ended")


def covered_share(spans):
    """Share of the root's seconds that its children cover."""
    own = dict(zip((s["name"] for s in spans), T.self_seconds(spans)))
    root = next(s for s in spans if s["name"] == T.LIFECYCLE)
    return 1.0 - own[T.LIFECYCLE] / root["dur_s"]


# -- the trainer's part of the tree ------------------------------------------------


class TestTrainerTree:
    def test_a_trainer_handed_a_tracer_leaves_the_tree(self):
        tracer = T.Tracer(registry=T.MetricsRegistry(), peer_id="t")
        ended_on = {}
        tracer.on_record = lambda sp: ended_on.setdefault(sp["name"], threading.current_thread().name)
        tr = Trainer(get_model("mnist_mlp"), batch_size=8, optimizer="sgd", lr=1e-2, tracer=tracer)
        # nothing of the tree is recorded before its phases end; the root is still open
        assert {s["name"] for s in tracer.spans()} == {
            "lifecycle.init", "lifecycle.init.params", "loop.snapshot"}
        tr.run(steps=3, log_every=0)
        spans = wait_for_root(tracer)
        got = by_name(spans)
        assert set(got) == {"lifecycle", "lifecycle.init", "lifecycle.init.params", "loop.snapshot",
                            "lifecycle.first_batch", "lifecycle.step_build", "lifecycle.first_step"}
        assert all(len(v) == 1 and v[0]["trace"] == "lifecycle" for v in got.values())
        (root,) = got["lifecycle"]
        assert "parent" not in root
        assert root["attrs"] == {"model": "mnist_mlp", "chips": 1, "cold": False}
        for child in ("lifecycle.init", "lifecycle.first_batch", "lifecycle.step_build",
                      "lifecycle.first_step"):
            assert got[child][0]["parent"] == "lifecycle", child
        # grandchildren find their parent through the context
        assert got["lifecycle.init.params"][0]["parent"] == "lifecycle.init"
        assert got["loop.snapshot"][0]["parent"] == "lifecycle.init"
        assert got["loop.snapshot"][0]["attrs"]["step"] == 0
        params = got["lifecycle.init.params"][0]["attrs"]
        assert set(params) == {"programs", "backend_s", "cache_hits", "cache_misses", "bytes"}
        assert params["bytes"] == sum(x.nbytes for x in jax.tree_util.tree_leaves(tr.state.params))
        build = got["lifecycle.step_build"][0]["attrs"]
        assert set(build) == {"program", "trace_s", "lower_s", "backend_s", "cache", "cache_load_s"}
        assert build["program"] == "jit(step)" and build["cache"] in ("hit", "miss", "off")
        assert got["lifecycle.first_step"][0]["attrs"] == {"step": 1}
        # the phases follow one another, and the root ends with the first step
        order = ["lifecycle.init", "lifecycle.first_batch", "lifecycle.step_build", "lifecycle.first_step"]
        starts = [got[n][0]["t0"] for n in order]
        assert starts == sorted(starts)
        first = got["lifecycle.first_step"][0]
        assert root["t0"] + root["dur_s"] == pytest.approx(first["t0"] + first["dur_s"], abs=0.05)
        # the waiter ends the first step and the root; the train thread everything else
        me = threading.current_thread().name
        assert ended_on["lifecycle.first_step"] == ended_on["lifecycle"] == WAITER
        assert ended_on["lifecycle.step_build"] == ended_on["lifecycle.init"] == me
        # the loop's phases are back under `loop`
        tr._take_snapshot(3)
        assert tracer.spans()[-1]["trace"] == "loop"
        assert tracer.registry.histogram("swarm.span_seconds").snapshot(span="lifecycle")["count"] == 1

    def test_the_loop_does_not_wait_for_the_first_steps_result(self, monkeypatch):
        """The waiter is held; the loop dispatches every later step and
        returns with ``lifecycle.first_step`` still open."""
        release = threading.Event()
        real = jax.block_until_ready

        def held(x):
            if threading.current_thread().name == WAITER:
                assert release.wait(60)
            return real(x)

        monkeypatch.setattr(jax, "block_until_ready", held)
        tracer = T.Tracer(peer_id="t")
        tr = Trainer(get_model("mnist_mlp"), batch_size=8, optimizer="sgd", lr=1e-2, tracer=tracer)
        try:
            summary = tr.run(steps=5, log_every=0)
            assert summary["steps"] == 5
            names = {s["name"] for s in tracer.spans()}
            assert "lifecycle.step_build" in names
            assert not names & {"lifecycle.first_step", "lifecycle"}
        finally:
            release.set()
        assert {"lifecycle.first_step", "lifecycle"} <= {s["name"] for s in wait_for_root(tracer)}

    def test_the_first_call_is_whichever_step_function_runs_first(self):
        tracer = T.Tracer(peer_id="t")
        tr = Trainer(get_model("mnist_mlp"), batch_size=8, optimizer="sgd", lr=1e-2,
                     steps_per_call=4, tracer=tracer)
        tr.run(steps=8, log_every=0)
        got = by_name(wait_for_root(tracer))
        assert got["lifecycle.step_build"][0]["attrs"]["program"] == "jit(multi)"
        assert got["lifecycle.first_step"][0]["attrs"] == {"step": 3}  # the chunk's scanned prefix
        assert len(got["lifecycle.step_build"]) == 1
        # a second run of the same trainer is no start-up
        tr.run(steps=2, log_every=0)
        assert len(by_name(tracer.spans(trace="lifecycle"))["lifecycle.step_build"]) == 1

    def test_a_mesh_run_shards_and_a_one_device_run_does_not(self, eight_devices):
        from distributedvolunteercomputing_tpu.parallel.mesh import make_mesh

        bundle = get_model("gpt2_small", **TINY_GPT2)
        tracer = T.Tracer(peer_id="mesh")
        tr = Trainer(bundle, batch_size=8, mesh=make_mesh(dp=2, tp=2), tracer=tracer)
        tr.run(steps=1, log_every=0)
        got = by_name(wait_for_root(tracer))
        (shard,) = got["lifecycle.init.shard"]
        assert shard["parent"] == "lifecycle.init" and shard["trace"] == "lifecycle"
        assert got["lifecycle.init.params"][0]["t0"] <= shard["t0"] <= got["loop.snapshot"][0]["t0"]
        assert got["lifecycle"][0]["attrs"]["chips"] == 4
        alone = T.Tracer(peer_id="one")
        Trainer(bundle, batch_size=8, tracer=alone)
        assert "lifecycle.init.shard" not in {s["name"] for s in alone.spans()}

    @pytest.mark.parametrize("tracer", [None, "disabled"])
    def test_nothing_without_a_live_tracer(self, tracer, monkeypatch):
        opened, threads = [], []
        monkeypatch.setattr(T, "annotation", lambda name: opened.append(name))
        real = threading.Thread

        def counted(*a, **kw):
            threads.append(kw.get("name"))
            return real(*a, **kw)

        monkeypatch.setattr(threading, "Thread", counted)
        if tracer == "disabled":
            tracer = T.Tracer(registry=T.MetricsRegistry(), peer_id="off", enabled=False)
        tr = Trainer(get_model("mnist_mlp"), batch_size=8, optimizer="sgd", lr=1e-2, tracer=tracer)
        assert tr._lifecycle is None
        tr.run(steps=3, log_every=0)
        assert opened == [] and WAITER not in threads
        if tracer is not None:
            assert tracer.spans() == []
            assert tracer not in T._LIVE_TRACERS


# -- the whole tree, from an in-process volunteer ----------------------------------


def run_volunteer(**kw):
    phases = kw.pop("process_phases", ())
    vol = Volunteer(VolunteerConfig(model="mnist_mlp", averaging="none", steps=3, **kw), phases)
    summary = asyncio.run(vol.run())
    return vol, summary


class TestVolunteerTree:
    def test_a_volunteer_leaves_the_whole_tree(self):
        now = time.time()
        phases = [("imports", now - 3.0, now - 1.0), ("backend", now - 1.0, now - 0.5)]
        vol, summary = run_volunteer(process_phases=phases)
        spans = wait_for_root(vol.telemetry.tracer)
        got = by_name(spans)
        (root,) = got["lifecycle"]
        assert root["attrs"] == {"model": "mnist_mlp", "averaging": "none", "chips": 1, "cold": False}
        children = {s["name"] for s in spans if s.get("parent") == "lifecycle"}
        assert children == {"lifecycle.net", "lifecycle.model", "lifecycle.init", "lifecycle.first_batch",
                            "lifecycle.step_build", "lifecycle.first_step"}
        assert got["lifecycle.net"][0]["attrs"] == {"peers": 0}
        assert got["lifecycle.init.params"][0]["parent"] == "lifecycle.init"
        # no checkpoint directory, no averaging: neither phase ran, neither is recorded
        assert not {"lifecycle.restore", "lifecycle.state_sync"} & set(got)
        # what the entry script timed before the volunteer existed
        (process,) = got["lifecycle.process"]
        assert "parent" not in process and process["dur_s"] == pytest.approx(2.5)
        assert process["t0"] + process["dur_s"] <= root["t0"]
        assert got["lifecycle.process.imports"][0]["dur_s"] == pytest.approx(2.0)
        assert got["lifecycle.process.backend"][0]["parent"] == "lifecycle.process"
        # the root's children cover it: every second has a name
        assert covered_share([s for s in spans if not s["name"].startswith("lifecycle.process")]) >= 0.9
        # the operator's view: the summary, the histograms behind coord.status
        life = summary["lifecycle"]
        assert life == vol.telemetry.lifecycle and life["cold"] is False
        assert life["ready_s"] == pytest.approx(root["dur_s"], abs=1e-3)
        assert set(life) == {"ready_s", "cold", "process", "net", "model", "init", "first_batch",
                             "step_build", "first_step"}
        assert life["init"] == pytest.approx(got["lifecycle.init"][0]["dur_s"], abs=1e-3)
        rolled = vol.telemetry.summary()["spans"]
        assert rolled["lifecycle"]["count"] == rolled["lifecycle.step_build"]["count"] == 1
        # and for a reader that holds no volunteer
        mine = [s for s in T.lifecycle_spans() if s["peer"] == vol.cfg.peer_id]
        assert [s["name"] for s in mine] == [s["name"] for s in sorted(spans, key=lambda s: s["t0"])]

    def test_a_restore_is_a_phase_of_its_own(self, tmp_path):
        run_volunteer(checkpoint_dir=str(tmp_path), checkpoint_every=2)
        vol, _ = run_volunteer(checkpoint_dir=str(tmp_path), checkpoint_every=2)
        (restore,) = by_name(wait_for_root(vol.telemetry.tracer))["lifecycle.restore"]
        assert restore["parent"] == "lifecycle"
        assert restore["attrs"]["restored"] is True and restore["attrs"]["step"] == 3
        assert restore["attrs"]["bytes"] > 0

    def test_telemetry_off_records_nothing_and_starts_no_thread(self, monkeypatch):
        opened, threads = [], []
        monkeypatch.setattr(T, "annotation", lambda name: opened.append(name))
        real = threading.Thread

        def counted(*a, **kw):
            threads.append(kw.get("name"))
            return real(*a, **kw)

        monkeypatch.setattr(threading, "Thread", counted)
        vol, summary = run_volunteer(telemetry=False, process_phases=[("imports", 1.0, 2.0)])
        assert vol._lifecycle is None and vol.trainer._lifecycle is None
        assert vol.telemetry.tracer.spans() == [] and opened == []
        assert WAITER not in threads
        assert summary["lifecycle"] == {}
        assert vol.telemetry.tracer not in T._LIVE_TRACERS


def test_lifecycle_spans_returns_nothing_once_the_tracer_is_gone():
    tracer = T.Tracer(peer_id="short-lived")
    tracer.record("lifecycle", T.LIFECYCLE, 10.0, 2.0)
    tracer.record("lifecycle.net", T.LIFECYCLE, 10.5, 0.5, parent="lifecycle")
    tracer.record("round", "r1", 11.0, 1.0)  # another trace: not start-up
    mine = [s for s in T.lifecycle_spans() if s["peer"] == "short-lived"]
    assert [(s["name"], s.get("parent")) for s in mine] == [("lifecycle", None), ("lifecycle.net", "lifecycle")]
    assert T.lifecycle_summary(mine) == {"ready_s": 2.0, "cold": False, "net": 0.5}
    assert T.lifecycle_summary(mine[1:]) == {}  # no root yet: nothing to say
    del tracer, mine
    gc.collect()
    assert not [s for s in T.lifecycle_spans() if s["peer"] == "short-lived"]


# -- the compile log ---------------------------------------------------------------


class TestCompileLog:
    def test_a_fresh_jit_is_counted_stage_by_stage_under_its_name(self):
        log = jaxenv.compile_log()

        @jax.jit
        def lifecycle_inner(x):
            return x * 2

        def lifecycle_outer(x):
            return lifecycle_inner(x) + jnp.sin(x)

        before = log.summary("jit(lifecycle_outer)")
        began = time.time()
        jax.jit(lifecycle_outer)(jnp.ones(7)).block_until_ready()
        after = log.summary("jit(lifecycle_outer)")
        assert after["program_compiles"] - before["program_compiles"] == 1
        assert after["programs"] > before["programs"] and after["seconds"] > before["seconds"]
        assert after["trace_seconds"] > before["trace_seconds"]
        assert after["lower_seconds"] > before["lower_seconds"]
        mine = log.summary("jit(lifecycle_outer)", since=began, thread=threading.get_ident())
        named = {p["program"]: p for p in mine["slowest"]}
        outer = named["jit(lifecycle_outer)"]
        assert outer["trace_s"] > 0 and outer["lower_s"] > 0 and outer["backend_s"] > 0
        assert outer["seconds"] == pytest.approx(
            outer["trace_s"] + outer["lower_s"] + outer["backend_s"], abs=2e-3)
        # the jit called while the outer one was traced is part of it, not a program
        assert "jit(lifecycle_inner)" not in named
        assert mine["program_seconds"] == pytest.approx(outer["backend_s"], abs=1e-3)
        assert len(mine["slowest"]) <= 5
        # on another thread nothing happened
        assert log.summary(since=began, thread=-1)["programs"] == 0

    def test_until_cuts_at_a_moment_and_the_old_keys_keep_their_values(self):
        log = jaxenv.compile_log()
        a, b = jnp.ones(3).block_until_ready(), jnp.ones(5).block_until_ready()
        jax.jit(lambda x: x + 11)(a).block_until_ready()
        cut = time.time()
        early = log.summary("jit(step)", until=cut)
        time.sleep(0.01)
        jax.jit(lambda x: x - 13)(b).block_until_ready()
        late = log.summary("jit(step)")
        assert late["programs"] == early["programs"] + 1
        assert log.summary("jit(step)", until=cut) == early
        assert log.summary(since=cut)["programs"] == 1
        # the keys the probe and three per-layer metrics read, as they were
        with log._lock:
            backend = [(name, s) for _, _, stage, name, s in log._events if stage == "backend"]
            folded = [(name, c, s) for (stage, name), (c, s) in log._folded.items() if stage == "backend"]
        assert late["programs"] == len(backend) + sum(c for _, c, _ in folded)
        assert late["seconds"] == pytest.approx(
            sum(s for _, s in backend) + sum(s for _, _, s in folded), abs=1e-3)
        assert late["program"] == "jit(step)" and late["cache_hits"] >= 0 and late["cache_misses"] >= 0
        assert {"program_compiles", "program_seconds", "trace_seconds", "lower_seconds",
                "cache_load_seconds", "slowest"} <= set(late)

    def test_events_past_the_bound_are_folded_and_still_counted(self, monkeypatch):
        log = jaxenv.CompileLog.__new__(jaxenv.CompileLog)  # no listeners: events by hand
        log._lock, log._events, log._folded = threading.Lock(), jaxenv.collections.deque(), {}
        log._here = threading.local()
        monkeypatch.setattr(jaxenv.CompileLog, "MAX_EVENTS", 4)
        for i in range(6):
            log._here.program = f"jit(p{i})"
            log._on_event("/jax/compilation_cache/cache_hits")
            log._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
            log._on_duration("/jax/core/compile/backend_compile_duration", 1.0, fun_name=f"jit(p{i})")
        assert len(log._events) == 4
        total = log.summary("jit(p0)")
        assert (total["programs"], total["seconds"], total["cache_hits"]) == (6, 6.0, 6)
        assert total["cache_load_seconds"] == 1.5 and total["program_compiles"] == 1
        assert log.summary(until=time.time() + 1)["programs"] == 6
        # a filter on thread or `since` sees the kept events alone
        assert log.summary(since=0.0)["programs"] == 2

    def test_the_cache_events_are_filed_under_the_program_being_compiled(self):
        log = jaxenv.CompileLog.__new__(jaxenv.CompileLog)
        log._lock, log._events, log._folded = threading.Lock(), jaxenv.collections.deque(), {}
        log._here = threading.local()
        log._on_begin("/jax/core/compile/backend_compile_duration", 0.0, fun_name="jit(step)")
        log._on_event("/jax/compilation_cache/cache_misses")
        log._on_duration("/jax/core/compile/backend_compile_duration", 30.0, fun_name="jit(step)")
        log._on_begin("/jax/core/compile/backend_compile_duration", 0.0, fun_name="jit(copy)")
        log._on_event("/jax/compilation_cache/cache_hits")
        log._on_duration("/jax/compilation_cache/compile_time_saved_sec", 4.0)
        log._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
        log._on_duration("/jax/core/compile/backend_compile_duration", 0.6, fun_name="jit(copy)")
        with log._lock:
            filed = {(stage, name) for _, _, stage, name, _ in log._events}
        assert {("miss", "jit(step)"), ("hit", "jit(copy)"), ("cache_load", "jit(copy)")} <= filed
        s = log.summary("jit(step)")
        assert (s["cache_hits"], s["cache_misses"], s["cache_load_seconds"]) == (1, 1, 0.5)
        assert s["seconds"] == 30.6 and s["program_seconds"] == 30.0 and s["cache_saved_seconds"] == 4.0


def test_child_names_a_parent_that_is_never_ambient():
    tracer = T.Tracer(peer_id="c")
    root = tracer.start("lifecycle", T.LIFECYCLE)
    with tracer.child(root, "lifecycle.model") as model:
        assert model.parent == "lifecycle" and model.trace == T.LIFECYCLE
        with tracer.span("lifecycle.model.inner", T.LIFECYCLE):
            pass
    with tracer.span("lifecycle.loose", T.LIFECYCLE):  # the root is not in the context
        pass
    with tracer.child(None, "lifecycle.off") as off:
        assert off is None
    got = {s["name"]: s.get("parent") for s in tracer.spans()}
    assert got == {"lifecycle.model.inner": "lifecycle.model", "lifecycle.model": "lifecycle",
                   "lifecycle.loose": None}
    with contextlib.suppress(Exception):
        root.end()
