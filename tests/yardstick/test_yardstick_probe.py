"""The observer's window logic and its check of a round, on a fake volunteer:
no device, no socket, no thread, a clock the test moves."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import datagen, probe as probe_mod
from benchmark.manifest import REPO_ROOT, Manifest

M = Manifest(REPO_ROOT)


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def time(self):
        return 1e9 + self.now

    def strftime(self, fmt):
        return "00:00:00"


def fake_volunteer(batch_size=8, average_every=4, with_averager=False):
    params = {"w": np.zeros((3,), np.float32)}
    trainer = types.SimpleNamespace(
        on_step=None, averager=None, mutation_counter=0, average_every=average_every,
        steps_since_merge=average_every, mesh=None,
        state=types.SimpleNamespace(step=np.int32(0), params=params),
        host_snapshot=lambda: (0, params),
        metrics=types.SimpleNamespace(record=lambda step, metrics, n_samples=0: None),
        compile_summary=lambda: {"programs": 7, "seconds": 1.5, "cache_hits": 7,
                                 "cache_misses": 0, "program": "jit(step)"},
    )
    spans = []
    vol = types.SimpleNamespace(
        trainer=trainer, averager=None,
        transport=types.SimpleNamespace(bytes_sent=0, bytes_received=0),
        telemetry=types.SimpleNamespace(tracer=types.SimpleNamespace(spans=lambda: spans)),
        cfg=types.SimpleNamespace(batch_size=batch_size, average_every=average_every),
    )
    if with_averager:
        trainer.averager = lambda payload, step: None  # replaced per test
    return vol, spans


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(probe_mod, "time", c)
    killed = []
    monkeypatch.setattr(probe_mod.os, "kill", lambda pid, sig: killed.append(sig))
    c.killed = killed
    return c


def drive(p, trainer, steps, clock, dt=1.0, start=1):
    for step_no in range(start, start + steps):
        clock.now += dt
        trainer.state.step = np.int32(step_no)
        trainer.on_step(trainer, step_no)
        if p.phase == probe_mod.DONE:
            return step_no
    return None


def test_solo_window_opens_after_warmup_and_closes_by_the_preemption_signal(clock):
    vol, _ = fake_volunteer()
    chained = []
    vol.trainer.on_step = lambda trainer, step_no: chained.append(step_no)
    p = probe_mod.Probe(vol, M.load_config("gpt2-medium"), M.load_traffic("solo"),
                        seconds=10.0, trace=False, workdir="/nonexistent", seed=1)
    p.install()
    assert p.n_params == 3
    end = drive(p, vol.trainer, 40, clock)
    assert chained[:3] == [1, 2, 3], "the volunteer's own hook still runs first"
    assert p.window["step0"] == 5 and end == 15 and p.window["step1"] == 15
    assert p.window["t1"] - p.window["t0"] == pytest.approx(10.0)
    assert clock.killed == [probe_mod.signal.SIGTERM]
    assert p.before["compile"]["programs"] == p.after["compile"]["programs"] == 7


def test_window_closes_on_device_time_when_the_host_runs_ahead(clock, monkeypatch):
    """Dispatch costs the host 0.01 s a step, the device takes 1 s: the window
    must close with the step that FINISHES after `seconds`, not when the host
    clock gets there (which would be hundreds of steps later)."""
    device_t0 = clock.now

    def block(x):  # a sync returns when the device has finished that step
        clock.now = max(clock.now, device_t0 + float(x) * 1.0)

    monkeypatch.setattr(probe_mod.jax, "block_until_ready", block)
    vol, _ = fake_volunteer()
    p = probe_mod.Probe(vol, M.load_config("gpt2-medium"), M.load_traffic("solo"),
                        seconds=30.0, trace=False, workdir="/nonexistent", seed=1)
    p.install()
    end = drive(p, vol.trainer, 400, clock, dt=0.01)
    assert p.window["step0"] == 5 and end == 35
    assert p.window["t1"] - p.window["t0"] == pytest.approx(30.0)


def test_loss_reads_are_taken_where_the_loop_hands_them_over(clock):
    vol, _ = fake_volunteer()
    seen = []
    vol.trainer.metrics.record = lambda step, metrics, n_samples=0: seen.append(step)
    p = probe_mod.Probe(vol, M.load_config("gpt2-medium"), M.load_traffic("solo"),
                        seconds=10.0, trace=False, workdir="/nonexistent", seed=1)
    p.install()
    vol.trainer.metrics.record(50, {"loss": np.float32(9.5)}, n_samples=16)
    assert seen == [50] and p.losses == [{"step": 50, "loss": 9.5, "from": "loop"}]


def round_setup(clock, got_of):
    """A K=4 volunteer whose averager returns ``got_of(own, peer_leaf)``."""
    traffic = M.load_traffic("round-2peer-bf16")
    traffic["warmup"] = dict(traffic["warmup"], rounds=1)  # the logic is the same for any count
    vol, spans = fake_volunteer(with_averager=True)
    own = {"w": np.linspace(-1, 1, 3).astype(np.float32)}
    peer = datagen.seeded_leaf((3,), datagen.peer_seed(1, 0), 0, 0.02)

    def averager(payload, step):
        clock.now += 2.5
        spans.append({"name": "join", "t0": clock.time() - 2.0, "dur_s": 0.6, "trace": "e1",
                      "attrs": {"role": "member", "size": 2}})
        vol.transport.bytes_received += 700
        return got_of(payload, peer)

    vol.trainer.averager = averager
    p = probe_mod.Probe(vol, M.load_config("gpt2-medium"), traffic, seconds=10.0,
                        trace=False, workdir="/nonexistent", seed=1)
    p.install()
    return p, vol, own


def test_round_window_waits_for_the_warmup_round_and_the_cadence(clock):
    p, vol, own = round_setup(clock, lambda o, peer: {"w": 0.5 * o["w"] + 0.5 * peer})
    tr = vol.trainer
    assert drive(p, tr, 4, clock) is None and p.phase == probe_mod.WARMUP
    assert tr.averager(own, 4) is not None            # the loop launches at step 4
    assert p.round_check == {}, "checked after the run, not while the round's merge waits"
    assert p.rounds[0]["t1"] - p.rounds[0]["t0"] == pytest.approx(2.5)
    assert p.rounds[0]["bytes1"] - p.rounds[0]["bytes0"] == 700
    drive(p, tr, 1, clock, start=5)
    assert p.phase == probe_mod.WARMUP, "the result is not merged yet"
    tr.mutation_counter += 1                          # the loop swaps the result in
    drive(p, tr, 1, clock, start=6)
    assert p.phase == probe_mod.WARMUP, "step 6 is not the eve of a boundary"
    drive(p, tr, 1, clock, start=7)
    assert p.phase == probe_mod.WINDOW and p.window["step0"] == 7
    assert p.merges == [{"step": 6, "t": pytest.approx(clock.now - 1.0)}]
    assert [name for name, _, _ in p.host_intervals] == ["launch", "merge"]
    p.finish()
    assert p.round_check["ok"] and p.round_check["role"] == "member"


def test_round_window_holds_whole_round_periods(clock):
    """K = 4, one step a second, --seconds 10, no round in flight on any
    boundary's eve (a launch follows each): two periods (8 s) fit, a third
    does not, so the window closes on the second boundary's eve."""
    p, vol, own = round_setup(clock, lambda o, peer: {"w": 0.5 * o["w"] + 0.5 * peer})
    launched = []
    p.on_launch = lambda: launched.append(clock.now)
    tr = vol.trainer
    drive(p, tr, 4, clock)
    tr.averager(own, 4)
    tr.mutation_counter += 1
    assert launched, "the stub peer is told of every launch"
    end = drive(p, tr, 40, clock, start=5)
    assert p.window["step0"] == 7 and end == 15 and p.window["step1"] == 15


def test_round_window_does_not_close_while_a_round_is_in_flight(clock):
    """A boundary's eve with a round still out launches nothing: the period
    goes on to the next eve on which a launch follows."""
    p, vol, own = round_setup(clock, lambda o, peer: {"w": 0.5 * o["w"] + 0.5 * peer})
    tr = vol.trainer
    drive(p, tr, 4, clock)
    tr.averager(own, 4)
    tr.mutation_counter += 1
    drive(p, tr, 3, clock, start=5)                  # the window opens at step 7
    p.rounds.append({"index": 1, "step": 8, "t0": clock.now, "bytes0": 0})  # launched, not back
    assert drive(p, tr, 4, clock, start=8) is None   # eve 11: in flight, no close
    p.rounds[-1]["t1"] = clock.now                   # the round returns
    assert drive(p, tr, 4, clock, start=12) == 15    # eve 15: one period of 8 s; two exceed 10 s


@pytest.mark.parametrize("how,got_of", [
    ("peer dropped", lambda o, peer: {"w": o["w"]}),
    ("peer counted twice", lambda o, peer: {"w": (o["w"] + 2 * peer) / 3}),
    ("eight-bit wire", lambda o, peer: {"w": np.round((0.5 * o["w"] + 0.5 * peer) * 16) / 16}),
    ("nothing came back", lambda o, peer: None),
])
def test_round_check_fails_when_the_guarantee_is_broken(clock, how, got_of):
    p, vol, own = round_setup(clock, got_of)
    vol.trainer.averager(own, 4)
    p.finish()
    assert p.round_check["ok"] is False, how


def test_a_round_that_never_lands_does_not_hold_the_run_for_ever(clock):
    p, vol, own = round_setup(clock, lambda o, peer: None)
    drive(p, vol.trainer, 20, clock)
    assert p.phase == probe_mod.WARMUP, "no round has been launched yet"
    for i in range(5):                               # five launched, none back
        p.rounds.append({"index": i, "step": 4 * i, "t0": clock.now, "bytes0": 0})
    drive(p, vol.trainer, 1, clock, start=21)
    assert p.phase == probe_mod.WINDOW and p.window["warmup_incomplete"] is True


# -- the training state leaves the chip before the reference check ---------------------------


def test_the_release_waits_for_the_windows_counters_and_frees_every_buffer_the_trainer_holds(clock):
    """``release_training_state()`` refuses while the window's counters are
    unread, then deletes every device array that hangs on the trainer: state,
    a round's payload, a device-side copy; host arrays are left alone."""
    import jax
    import jax.numpy as jnp

    vol, _ = fake_volunteer()
    tr = vol.trainer
    p = probe_mod.Probe(vol, M.load_config("gpt2-medium"), M.load_traffic("solo"),
                        seconds=10.0, trace=False, workdir="/nonexistent", seed=1)
    p.install()
    drive(p, tr, 7, clock)
    assert p.phase == probe_mod.WINDOW and p.after == {}
    with pytest.raises(RuntimeError, match="counters"):
        p.release_training_state()
    assert drive(p, tr, 40, clock, start=8) == 15 and p.after["compile"]["programs"] == 7
    state = {"params": {"w": jnp.ones((3,))}, "opt_state": ({"w": jnp.zeros((3,))}, {"w": jnp.zeros((3,))}),
             "step": jnp.int32(15), "rng": jax.random.PRNGKey(0)}
    payload, host = {"w": jnp.ones((3,))}, np.ones((3,), np.float32)
    tr.state, tr._inflight, tr._snapshot = state, (10, payload, object()), (10, {"w": host})
    already = jnp.ones((2,))
    already.delete()
    tr._copied_tree = [already]  # a buffer a step donated: nothing to free, nothing to trip over
    p.release_training_state()
    leaves = jax.tree_util.tree_leaves((state, payload))
    assert len(leaves) == 6 and all(x.is_deleted() for x in leaves)
    assert p.released["arrays"] == 6 and p.released["bytes"] == 4 * 3 * 4 + 4 + 8
    assert host.sum() == 3.0 and p._initial_params["w"].shape == (3,)


STATE_WATCH = """
import json, sys
sys.path.insert(0, {root!r})
import jax
from benchmark import probe, run

inner = probe.Probe.reference_check

def watched(self):
    state = self.vol.trainer.state
    leaves = [x for x in jax.tree_util.tree_leaves(state) if isinstance(x, jax.Array)]
    live = sum(not x.is_deleted() for x in leaves)
    inner(self)
    print("STATE_WATCH " + json.dumps({{
        "leaves": len(leaves), "live_before": live, "deleted_after": sum(x.is_deleted() for x in leaves),
        "shards": max(len(x.sharding.device_set) for x in leaves), "same_state": self.vol.trainer.state is state,
        "reference": self.reference, "released": self.released}}), file=sys.stderr, flush=True)

probe.Probe.reference_check = watched
sys.exit(run.main())
"""

# What the parent (841632a, the training state alive through the check) reads on the same seed,
# `--rehearse <cell> --seed 3000000019 --seconds 3 --trace 0` on the CPU: the release changes no number.
PARENT_READS = {"loss": 7.692730188369751, "loss_abs_err": 4.76837158203125e-07}
PARENT_GRADS = {
    "tiny-rehearsal:solo": (5.659304446100458e-07, 7.364589535834498e-07),
    "tiny-rehearsal:round-2peer-bf16": (5.659304446100458e-07, 7.364589535834498e-07),
    "tiny-rehearsal-mesh:solo": (5.200796183884446e-07, 6.345221191692989e-07),  # dp=2,tp=2: large-solo-4chip's stand-in
}


@pytest.mark.parametrize("cell", list(PARENT_GRADS))
def test_no_leaf_of_the_training_state_outlives_the_check_and_the_check_reads_what_the_parent_read(cell):
    """A volunteer built from a ``tiny-rehearsal`` configuration, through
    ``benchmark/run.py`` itself: after ``reference_check()`` every leaf of
    ``trainer.state`` (parameters, both moments, step, rng; every shard on
    the four-device mesh) is deleted, the verdict and its four numbers are
    the parent's, and ``finish()`` (the round check, on host arrays) and the
    reduce go on to a ``correct`` result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", STATE_WATCH.format(root=REPO_ROOT), "--rehearse", cell,
         "--seed", "3000000019", "--seconds", "3", "--trace", "0"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    saw = json.loads(next(ln for ln in out.stderr.splitlines() if ln.startswith("STATE_WATCH ")).split(" ", 1)[1])
    assert saw["leaves"] == saw["live_before"] == saw["deleted_after"] >= 50 and saw["same_state"]
    assert saw["released"]["arrays"] >= saw["leaves"] and saw["released"]["bytes"] > 8_000_000
    assert saw["shards"] == (4 if "mesh" in cell else 1)
    # To the digit on the machine that read the parent's; three float32 roundings' room for another CPU's threads.
    grad, worst = PARENT_GRADS[cell]
    read = saw["reference"]
    assert read["ok"] is True and read["loss"] == pytest.approx(PARENT_READS["loss"], rel=1e-6)
    assert read["loss_abs_err"] <= 4 * PARENT_READS["loss_abs_err"]
    assert read["grad_rel_err"] == pytest.approx(grad, rel=0.5) and read["worst_leaf_rel_err"] == pytest.approx(worst, rel=0.5)
    assert sorted(read) == ["grad_rel_err", "loss", "loss_abs_err", "ok", "worst_leaf_rel_err"]
    line = json.loads(out.stdout.strip().splitlines()[-1])  # the reduce ran to the result line
    checks = json.loads(next(ln for ln in out.stderr.splitlines() if "] checks: " in ln).split("checks: ", 1)[1])
    assert line["attempted"] > 10 and checks["reference"] and checks["losses_finite"] and checks["loss_band"], checks
    if "round" in cell:
        # whether every round of the window came back in time is the machine's load, not the release
        assert checks["round_mean"] is True, checks
    else:
        assert line["correct"] is True and line["failed"] == 0 and all(checks.values()), checks
    err = out.stderr
    # the side whose compiled program takes more temporaries runs first, beside one gradient tree less
    said = next(ln for ln in err.splitlines() if "reference check: temporaries " in ln).split("temporaries ", 1)[1]
    temps = json.loads(said.split(", runs ")[0].replace("'", '"'))
    assert set(temps) == {"program", "reference"} and said.split(", runs ")[1] == f"{max(temps, key=temps.get)} first"
    assert err.index("window closes") < err.index("released the training state") < err.index("reference check (")
    if "round" in cell:
        assert err.index("reference check (") < err.index("round check (")
