"""The program's own timeline of the chip's queue: the arithmetic on two
stamps a step as a pure object fed synthetic ``(q, d, phases)``, what a reader
resolves a held wait to, and a tiny trainer whose hook holds the chip up.

Counts and structure only: nothing here asserts a time of a CPU run.
"""

import threading
import time

import pytest

from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.swarm import telemetry as T
from distributedvolunteercomputing_tpu.training import trainer as trainer_mod
from distributedvolunteercomputing_tpu.training.trainer import Trainer

STEP = 0.100  # a synthetic step's own seconds


def timeline(every=10):
    """A timeline on a tracer whose clock is the stamps' clock plus 1,000."""
    now = [0.0]
    tracer = T.Tracer(registry=T.MetricsRegistry(), peer_id="t", clock=lambda: 1000.0 + now[0])
    return T.ChipTimeline(tracer, every, monotonic=lambda: now[0]), tracer, now


def feed(tl, now, entries):
    """``entries``: (step, steps, q, d, phases), the stamps in seconds; the
    pair the timeline takes when it writes is taken "now", after ``d``. What
    the timeline still holds back (a stamp waits for the next few to correct
    it) is settled at the end, without ending the run."""
    for step, steps, q, d, phases in entries:
        now[0] = d + 0.001
        tl.step(step, steps, q, d, phases)
    while tl._pending:
        tl._settle()


def steady(n, first=0, t0=0.0, ahead=0.5):
    """``n`` undisturbed steps after the step ``first``, each enqueued
    ``ahead`` seconds before the one before it is done (the queue is full)."""
    return [(first + i + 1, 1, t0 + i * STEP - ahead, t0 + (i + 1) * STEP, ()) for i in range(n)]


def waits(tracer):
    return [s for s in tracer.spans() if s["name"] == "loop.chip_wait"]


def stretches(tracer):
    return [s for s in tracer.spans() if s["name"] == "loop.steps"]


class TestArithmetic:
    def test_an_undisturbed_run_records_no_wait_and_a_stretch_every_ten_steps(self):
        tl, tracer, now = timeline()
        feed(tl, now, steady(40))
        assert waits(tracer) == []
        # the first call gives no interval and the next eight are left out: 31 steps counted
        got = stretches(tracer)
        assert [s["attrs"]["steps"] for s in got] == [10, 10, 10]
        assert [s["attrs"]["step"] for s in got] == [19, 29, 39]
        for s in got:
            assert s["trace"] == "loop" and s["dur_s"] == pytest.approx(10 * STEP)
            assert s["attrs"]["late_s"] == s["attrs"]["held_s"] == 0.0
            assert s["attrs"]["step_s_p50"] == pytest.approx(STEP) == pytest.approx(s["attrs"]["step_s_max"])
        # end to end: a stretch runs from the end of the step before its first to the end of its last,
        # on the tracer's clock
        assert got[0]["t0"] == pytest.approx(1000.0 + 9 * STEP, abs=1e-5)
        assert got[1]["t0"] == pytest.approx(got[0]["t0"] + got[0]["dur_s"], abs=1e-5)
        assert tl.summary() == {"steps": 31, "late_s": 0.0, "held_s": 0.0, "wait_share": 0.0,
                                "step_s_p50": pytest.approx(STEP), "step_s_max": pytest.approx(STEP)}
        assert tracer.registry.histogram("swarm.step_seconds").snapshot()["count"] == 31

    @pytest.mark.parametrize("left_out", range(1, 10))
    def test_the_first_call_and_the_first_eight_intervals_are_left_out(self, left_out):
        """A wait of a second in any of the first nine entries (the call that
        compiled, then eight intervals) is counted nowhere."""
        tl, tracer, now = timeline()
        entries = steady(20)
        step, steps, q, d, _ = entries[left_out - 1]
        shift = lambda e: (e[0], e[1], e[2] + 1.0, e[3] + 1.0, e[4])  # noqa: E731
        late = (step, steps, d - STEP + 1.0, d + 1.0, ())  # enqueued a second after the step before was done
        feed(tl, now, entries[:left_out - 1] + [late] + [shift(e) for e in entries[left_out:]])
        assert waits(tracer) == [] and tl.summary()["late_s"] == 0.0 and tl.summary()["steps"] == 11

    def test_the_tenth_entry_is_counted(self):
        tl, tracer, now = timeline()
        entries = steady(20)
        step, steps, q, d, _ = entries[9]
        shift = lambda e: (e[0], e[1], e[2] + 1.0, e[3] + 1.0, e[4])  # noqa: E731
        feed(tl, now, entries[:9] + [(step, steps, d - STEP + 1.0, d + 1.0, ())] + [shift(e) for e in entries[10:]])
        (w,) = waits(tracer)
        assert w["attrs"]["kind"] == "late" and w["attrs"]["step"] == 10 and w["dur_s"] == pytest.approx(1.0)
        assert tl.summary()["late_s"] == pytest.approx(1.0)

    def test_late_is_exact_and_names_the_phase_that_fills_most_of_the_gap(self):
        tl, tracer, now = timeline()
        t = 12 * STEP  # when step 12 was done
        phases = [("loop.log_sync", t - 0.05, t + 0.010), ("metrics", t + 0.010, t + 0.012),
                  ("on_step", t + 0.012, t + 0.052), ("data", t + 0.052, t + 0.055),
                  ("dispatch", t + 0.055, t + 0.060)]
        late = (13, 1, t + 0.060, t + 0.060 + STEP, phases)
        after = [(s, n, q + 0.060, d + 0.060, p) for s, n, q, d, p in steady(8, first=13, t0=13 * STEP)]
        feed(tl, now, steady(12) + [late] + after)
        (w,) = waits(tracer)
        assert w["attrs"] == {"step": 13, "kind": "late", "during": "on_step", "during_s": pytest.approx(0.040)}
        assert w["dur_s"] == pytest.approx(0.060) and w["t0"] == pytest.approx(1000.0 + t, abs=1e-5)
        # the step itself took its own time once it was enqueued: nothing held
        assert tl.summary()["held_s"] == 0.0 and tl.summary()["late_s"] == pytest.approx(0.060)
        (s,) = [s for s in stretches(tracer) if s["attrs"]["step"] == 19]
        assert s["attrs"]["late_s"] == pytest.approx(0.060) and s["attrs"]["step_s_max"] == pytest.approx(STEP + 0.060)
        counter = tracer.registry.counter("swarm.chip_wait_seconds_total")
        assert counter.value(kind="late", during="on_step") == pytest.approx(0.060)

    def test_a_late_wait_in_no_phase_is_the_loops(self):
        tl, tracer, now = timeline()
        t = 12 * STEP
        feed(tl, now, steady(12) + [(13, 1, t + 0.030, t + 0.030 + STEP, [("data", t - 0.2, t - 0.1)])])
        (w,) = waits(tracer)
        assert (w["attrs"]["during"], w["attrs"]["during_s"]) == ("loop", 0.0)

    def test_a_late_wait_under_the_threshold_is_in_the_totals_and_is_no_span(self):
        tl, tracer, now = timeline()
        t = 12 * STEP
        short = T.LATE_SPAN_S / 2
        feed(tl, now, steady(12) + [(13, 1, t + short, t + short + STEP, [("dispatch", t, t + short)])])
        assert waits(tracer) == [] and tl.summary()["late_s"] == pytest.approx(short)
        assert tracer.registry.counter("swarm.chip_wait_seconds_total").value(
            kind="late", during="dispatch") == pytest.approx(short)

    def test_held_is_what_a_step_took_beyond_the_running_median_of_its_own_time(self):
        tl, tracer, now = timeline()
        t = 12 * STEP
        # enqueued long before, done 80 ms later than its own time: it sat behind something
        held = (13, 1, t - 0.5, t + STEP + 0.080, ())
        after = [(s, n, q + 0.080, d + 0.080, p) for s, n, q, d, p in steady(8, first=13, t0=13 * STEP)]
        feed(tl, now, steady(12) + [held] + after)
        (w,) = waits(tracer)
        assert w["attrs"] == {"step": 13, "kind": "held", "own_s": pytest.approx(STEP + 0.080)}
        assert w["dur_s"] == pytest.approx(0.080) and w["t0"] == pytest.approx(1000.0 + t, abs=1e-5)
        assert tl.summary()["held_s"] == pytest.approx(0.080) and tl.summary()["late_s"] == 0.0
        assert tracer.registry.counter("swarm.chip_wait_seconds_total").value(
            kind="held", during="queue") == pytest.approx(0.080)

    def test_an_excess_under_the_thresholds_is_not_held(self):
        tl, tracer, now = timeline()
        t = 12 * STEP
        excess = max(T.HELD_MIN_S, T.HELD_SHARE * STEP) * 0.9
        feed(tl, now, steady(12) + [(13, 1, t - 0.5, t + STEP + excess, ())])
        assert waits(tracer) == [] and tl.summary()["held_s"] == 0.0

    def test_the_median_runs_over_the_last_32_steps(self):
        """A step that becomes 50% slower for good is held until the median
        has followed it, 17 steps on, and never after."""
        tl, tracer, now = timeline()
        slow = [(20 + i + 1, 1, 20 * STEP + i * 1.5 * STEP - 0.5, 20 * STEP + (i + 1) * 1.5 * STEP, ())
                for i in range(40)]
        feed(tl, now, steady(20) + slow)
        held = [w["attrs"]["step"] for w in waits(tracer)]
        assert all(w["attrs"]["kind"] == "held" for w in waits(tracer))
        assert held == list(range(21, 21 + len(held))) and 10 <= len(held) <= 17
        assert waits(tracer)[0]["dur_s"] == pytest.approx(0.5 * STEP)

    @pytest.mark.parametrize("delays", [(0.3,), (0.08, 0.2), (0.09, 0.2, 0.43), (0.3, 0.3, 0.3, 0.3)],
                             ids=["one", "two", "three-past-a-step", "four-equal"])
    def test_a_stamp_that_came_late_is_corrected_by_what_the_next_ones_prove(self, delays):
        """``d`` is a host stamp of a device event: the watcher woke up late
        (a bulk transfer held the link, another thread the interpreter) and the
        next stamps were on time. The chip runs its queue in order, so the
        step was done no later than the one after it less that one's own time:
        nothing was held, and no step took longer than its own time."""
        tl, tracer, now = timeline()
        entries = steady(30)
        for i, delay in enumerate(delays):
            step, steps, q, d, phases = entries[14 + i]
            entries[14 + i] = (step, steps, q, d + delay, phases)
        feed(tl, now, entries)
        assert waits(tracer) == []
        tol = T.HELD_SHARE * STEP   # what a corrected stamp may still be off by
        assert tl.summary()["held_s"] == 0.0 and tl.summary()["step_s_max"] <= STEP + len(delays) * tol + 1e-9

    def test_a_stamp_late_for_longer_than_the_lookahead_is_read_as_held(self):
        tl, tracer, now = timeline()
        entries = steady(30)
        for i in range(T.ChipTimeline.LOOKAHEAD + 2):
            step, steps, q, d, phases = entries[14 + i]
            entries[14 + i] = (step, steps, q, d + 0.3, phases)
        feed(tl, now, entries)
        assert [w["attrs"]["kind"] for w in waits(tracer)] == ["held"] and waits(tracer)[0]["attrs"]["step"] == 15

    def test_a_correction_hides_neither_a_real_wait_nor_a_late_host(self):
        tl, tracer, now = timeline()
        t = 14 * STEP
        # step 15 sat 80 ms in the queue AND its stamp came 0.2 s late; the host was 50 ms late with step 18
        shift = lambda e, by: (e[0], e[1], e[2] + by, e[3] + by, e[4])  # noqa: E731
        entries = steady(30)
        entries[14] = (15, 1, t - 0.5, t + STEP + 0.080 + 0.2, ())
        entries[15:] = [shift(e, 0.080) for e in entries[15:]]
        step, _, _, d17, _ = entries[16]
        entries[17] = (18, 1, d17 + 0.050, d17 + 0.050 + STEP, [("on_step", d17, d17 + 0.050)])
        entries[18:] = [shift(e, 0.050) for e in entries[18:]]
        feed(tl, now, entries)
        got = {(w["attrs"]["kind"], w["attrs"]["step"]): w["dur_s"] for w in waits(tracer)}
        tol = T.HELD_SHARE * STEP
        assert set(got) == {("held", 15), ("late", 18)}
        assert 0.080 <= got[("held", 15)] <= 0.080 + tol + 1e-9 and got[("late", 18)] == pytest.approx(0.050)

    def test_a_call_of_several_steps_is_one_entry_with_its_times_a_step(self):
        tl, tracer, now = timeline(every=10)
        t = 12 * STEP
        chunk = (16, 4, t - 0.5, t + 4 * STEP, ())  # four steps in one call, undisturbed
        feed(tl, now, steady(12) + [chunk] + steady(6, first=16, t0=16 * STEP))
        assert waits(tracer) == []
        (s,) = stretches(tracer)
        # steps 10, 11, 12, the call of four and three more: ten steps, seven entries
        assert s["attrs"]["steps"] == 10 and s["attrs"]["step"] == 19
        assert s["attrs"]["step_s_p50"] == pytest.approx(STEP) == pytest.approx(s["attrs"]["step_s_max"])
        assert tl.summary()["steps"] == 13 and tl.summary()["step_s_max"] == pytest.approx(STEP)
        # and a call of four that took two steps' time too long is held by that, not by four medians
        tl, tracer, now = timeline()
        feed(tl, now, steady(12) + [(16, 4, t - 0.5, t + 6 * STEP, ())])
        (w,) = waits(tracer)
        assert w["attrs"]["kind"] == "held" and w["dur_s"] == pytest.approx(2 * STEP)

    def test_a_run_that_ends_writes_its_last_stretch_and_the_next_run_starts_afresh(self):
        tl, tracer, now = timeline()
        feed(tl, now, steady(15))
        assert stretches(tracer) == []
        tl.boundary()
        (s,) = stretches(tracer)
        assert s["attrs"]["steps"] == 6 and s["attrs"]["step"] == 15
        tl.boundary()  # nothing more to write
        assert len(stretches(tracer)) == 1
        # a minute later the loop runs again: its first step waits for no step before it
        feed(tl, now, steady(12, first=15, t0=60.0))
        assert waits(tracer) == [] and tl.summary()["late_s"] == 0.0
        assert tl.summary()["steps"] == 6 + 11

    def test_a_disabled_tracer_has_no_timeline(self):
        off = T.Tracer(registry=T.MetricsRegistry(), peer_id="off", enabled=False)
        assert off.chip_timeline(10) is None and off.chip is None
        on = T.Tracer(peer_id="on")
        assert on.chip_timeline(10) is on.chip_timeline(10) is on.chip
        assert T.Telemetry(peer_id="none").summary()["chip"] == {}


# -- what a reader resolves a wait to -------------------------------------------------


def span(name, t0, dur, peer="v", **attrs):
    return {"trace": "loop", "name": name, "peer": peer, "t0": t0, "dur_s": dur, **({"attrs": attrs} if attrs else {})}


HELD = span("loop.chip_wait", 100.0, 0.1, step=7, kind="held", own_s=0.5)  # could start at 100.0, done at 100.5


class TestChipWaits:
    @pytest.mark.parametrize("others,during,during_s", [
        ([], "none", 0.0),
        ([span("loop.log_sync", 100.0, 0.5), span("round", 99.0, 5.0), span("encode", 100.0, 0.4)], "none", 0.0),
        ([span("codec.run", 100.1, 0.2)], "codec.run", 0.2),
        # the largest overlap, not the longest span and not the first
        ([span("codec.h2d", 99.0, 1.05), span("codec.run", 100.2, 0.3), span("codec.d2h", 100.45, 3.0)], "codec.run", 0.3),
        # a landing copy that lies across the whole interval
        ([span("loop.snapshot.land", 99.5, 2.0), span("codec.run", 100.2, 0.1)], "loop.snapshot.land", 0.5),
        # equal overlaps: the shorter span is the more specific one
        ([span("loop.merge", 99.9, 0.9), span("loop.merge.h2d", 99.95, 0.6)], "loop.merge.h2d", 0.5),
        ([span("loop.launch", 100.4, 1.0)], "loop.launch", 0.1),
        # another peer's spans, and one that has not ended, explain nothing
        ([span("codec.run", 100.0, 0.5, peer="w"), span("loop.merge", 100.0, None)], "none", 0.0),
        # ended before the step could start
        ([span("codec.run", 99.0, 1.0)], "none", 0.0),
    ])
    def test_a_held_wait_is_resolved_by_the_largest_overlap(self, others, during, during_s):
        got = T.chip_waits(others + [HELD])
        assert got[:-1] == [None] * len(others)
        assert got[-1] == {"kind": "held", "step": 7, "wait_s": 0.1, "during": during,
                           "during_s": pytest.approx(during_s)}

    def test_a_late_wait_says_itself_what_it_fell_into(self):
        late = span("loop.chip_wait", 100.0, 0.06, step=13, kind="late", during="on_step", during_s=0.04)
        (got, _) = T.chip_waits([late, span("codec.run", 100.0, 0.06)])
        assert got == {"kind": "late", "step": 13, "wait_s": 0.06, "during": "on_step", "during_s": 0.04}

    def test_every_name_of_the_list_is_a_span_somebody_records(self):
        import pathlib

        source = "".join(p.read_text() for p in pathlib.Path(T.__file__).parents[1].rglob("*.py"))
        for name in T.CHIP_WORK_SPANS:
            assert f'"{name}"' in source, name


# -- the train loop ------------------------------------------------------------------


def settle(tracer, steps, timeout=30.0):
    """The watcher writes off the train thread: until the timeline has counted ``steps``."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if tracer.chip is not None and tracer.chip.summary().get("steps", 0) >= steps and not tracer.chip._stretch:
            return
        time.sleep(0.01)
    raise AssertionError(f"the timeline never counted {steps} steps: {tracer.chip and tracer.chip.summary()}")


class TestTrainLoop:
    def test_a_hook_that_sleeps_is_late_during_on_step(self):
        tele = T.Telemetry(peer_id="t")
        tr = Trainer(get_model("mnist_mlp"), batch_size=8, optimizer="sgd", lr=1e-2, tracer=tele.tracer,
                     on_step=lambda trainer, step: time.sleep(0.05))
        tr.run(steps=30, log_every=1)  # every step syncs: the chip is idle while the hook sleeps
        settle(tele.tracer, 21)
        spans = tele.tracer.spans(trace="loop")
        late = [w for w in T.chip_waits(spans) if w is not None and w["kind"] == "late"]
        by_step = {w["step"]: w for w in late}
        # step 1 compiled and eight intervals are left out: steps 10 to 30. No time is asserted: on a loaded
        # machine a stamp may come late enough to hide a wait, so most of the steps, not each of them
        assert set(by_step) <= set(range(10, 31)) and len(by_step) >= 11
        assert sum(w["during"] == "on_step" for w in late) > len(late) / 2
        assert all(0.0 <= w["during_s"] <= w["wait_s"] + 1e-6 for w in late)
        steps = [s for s in spans if s["name"] == "loop.steps"]
        assert [s["attrs"]["steps"] for s in steps] == [10, 10, 1] and steps[-1]["attrs"]["step"] == 30
        # the stretches hold every late wait, the ones too short for a span too
        assert sum(s["attrs"]["late_s"] for s in steps) >= sum(w["wait_s"] for w in late) - 1e-4
        chip = tele.summary()["chip"]
        assert chip["steps"] == 21 and chip["late_s"] == pytest.approx(sum(s["attrs"]["late_s"] for s in steps), abs=1e-4)
        assert chip["held_s"] == pytest.approx(sum(s["attrs"]["held_s"] for s in steps), abs=1e-4)
        assert 0.0 < chip["wait_share"] <= 1.0 and chip["step_s_max"] >= chip["step_s_p50"] > 0.0
        counter = tele.registry.counter("swarm.chip_wait_seconds_total")
        counted = sum(v["value"] for v in counter._scrape()["values"] if v["labels"]["kind"] == "late")
        assert counted == pytest.approx(chip["late_s"], abs=1e-4)
        assert tele.registry.histogram("swarm.step_seconds").snapshot()["count"] == 21
        # one watcher a trainer, and the start-up tree's waiter is that thread
        names = [t.name for t in threading.enumerate()]
        assert "lifecycle-first-step" not in names and trainer_mod.WATCHER in names

    def test_the_hand_over_carries_one_entry_a_call_with_its_steps(self, monkeypatch):
        handed = []
        real = T.ChipTimeline.step

        def seen(self, step, steps, q, d, phases):
            handed.append((step, steps, [name for name, _, _ in phases]))
            assert q <= d and all(t0 <= t1 for _, t0, t1 in phases)
            real(self, step, steps, q, d, phases)

        monkeypatch.setattr(T.ChipTimeline, "step", seen)
        tracer = T.Tracer(peer_id="t")
        tr = Trainer(get_model("mnist_mlp"), batch_size=8, optimizer="sgd", lr=1e-2, steps_per_call=4,
                     tracer=tracer, on_step=lambda trainer, step: None)
        tr.run(steps=8, log_every=4)
        deadline = time.monotonic() + 30
        while len(handed) < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        # a chunk is a scanned prefix of three steps and one step of its own
        assert [(step, steps) for step, steps, _ in handed] == [(3, 3), (4, 1), (7, 3), (8, 1)]
        assert handed[0][2] == ["loop.snapshot", "data", "dispatch"]  # the constructor's snapshot, then the first call
        assert handed[1][2] == ["data", "dispatch"]
        # after a log point: its sync, the sink's record and the hook, then the next chunk's data and call
        assert handed[2][2] == ["loop.log_sync", "metrics", "on_step", "data", "dispatch"]

    def test_the_watcher_ends_with_its_trainer(self):
        import gc

        tracer = T.Tracer(peer_id="t")
        before = set(threading.enumerate())
        tr = Trainer(get_model("mnist_mlp"), batch_size=8, optimizer="sgd", lr=1e-2, tracer=tracer)
        tr.run(steps=2, log_every=0)
        (watcher,) = [t for t in set(threading.enumerate()) - before if t.name == trainer_mod.WATCHER]
        assert watcher.daemon
        del tr
        gc.collect()
        watcher.join(30)
        assert not watcher.is_alive()
