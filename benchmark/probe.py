"""The observer: what the runner hangs on a ``Volunteer`` to measure it.

It watches from outside, at three seams between the program's layers, and
edits nothing:

- ``Trainer.on_step(trainer, step_no)``, chained after whatever hook the
  volunteer installed: opens and closes the measured window (one device sync
  each) and drives the profiler in a traced run;
- ``Trainer.averager``, the callable ``(payload_tree, step) -> averaged | None``
  through which the train loop enters the round layer: timed, counted, and in
  the warm-up round checked against the expected mean;
- ``Trainer.metrics.record``, where the loop hands over the losses it reads
  itself (every 50 steps), so the benchmark reads losses without a sync of
  its own.

Counters (``compile_summary()``, the codec's ``stats()``, transport bytes,
``memory_stats()``) and telemetry spans are read at the window's ends. The
benchmark's own device work (the reference check) runs after the window has
closed and the counters are read, so the peak they hold is the program's, and
on a chip the training state has left (``release_training_state``), so what a
configuration may hold is set by the step and not by the check.
"""

from __future__ import annotations

import contextlib
import glob
import math
import os
import signal
import sys
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from benchmark import datagen, references

WARMUP, WINDOW, DONE = "warmup", "window", "done"
# Steps into the window at which the observer syncs once more, to learn the
# step time before the loop's own first sync (its log point every 50 steps).
PROBE_STEPS = 10


def _log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


class Probe:
    def __init__(self, vol: Any, cfg: Dict[str, Any], traffic: Dict[str, Any], *,
                 seconds: float, trace: bool, workdir: str, seed: int,
                 on_launch: Optional[Any] = None):
        self.vol = vol
        self.on_launch = on_launch  # called as the loop enters a round
        self.cfg = cfg
        self.traffic = traffic
        self.seconds = float(seconds)
        self.trace_on = bool(trace)
        self.trace_dir = os.path.join(workdir, "trace")
        self.seed = int(seed)
        self.phase = WARMUP
        self.parts: Dict[str, float] = {}  # set-up breakdown, seconds on the host clock
        self.window: Dict[str, Any] = {}
        self.before: Dict[str, Any] = {}
        self.after: Dict[str, Any] = {}
        self.rounds: List[Dict[str, Any]] = []  # one record per call into the averager
        self.merges: List[Dict[str, Any]] = []  # one per parameter swap the loop made
        self.losses: List[Dict[str, Any]] = []  # every loss the loop or the check read
        self.reference: Dict[str, Any] = {}
        self.released: Dict[str, int] = {}  # what release_training_state() freed and left
        self.round_check: Dict[str, Any] = {}
        self.trace_info: Dict[str, Any] = {}
        self._trace_state = "idle"  # idle -> on -> done
        self._first_round: Optional[tuple] = None
        self._iteration_began = 0.0  # host clock when the previous on_step returned
        self._calls_seen = 0
        self.host_intervals: List[tuple] = []  # (label, t_begin, t_end) on the host clock
        self._steps_in_window = 0
        # The last (step, host time) at which host and device were level.
        self._level: Optional[tuple] = None
        warm = traffic.get("warmup", {})
        self._warm_steps = int(warm.get("steps", 5))
        self._warm_rounds = int(warm.get("rounds", 0)) if traffic.get("peers") else 0
        win = traffic.get("window", {})
        self._align = win.get("start") == "last_step_before_cadence_boundary"
        self._whole_periods = win.get("end") == "whole_round_periods"
        self._periods = 0

    # -- installation -------------------------------------------------------

    def install(self) -> None:
        """After ``Volunteer.start()`` built the trainer, before it trains."""
        tr = self.vol.trainer
        self._chain = tr.on_step
        tr.on_step = self.on_step
        self._inner_averager = tr.averager
        if tr.averager is not None:
            tr.averager = self.averager_call
        self._inner_record = tr.metrics.record
        tr.metrics.record = self.metrics_record
        self._start_step = int(tr.state.step)
        self._mut_seen = tr.mutation_counter
        self._every = int(tr.average_every)
        # The trainer's own host copy of the parameters it starts from
        # (``Trainer.host_snapshot()``; later snapshots replace the tuple and
        # leave these arrays alone): what the reference check runs on.
        self._initial_params = tr.host_snapshot()[1]
        self._log_memory("installed")
        self.n_params = sum(
            int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tr.state.params)
        )

    # -- correctness, once the volunteer has stopped --------------------------

    def release_training_state(self) -> None:
        """Give the allocator back every device buffer the stopped volunteer
        still holds: parameters, both Adam moments, step counter and rng,
        whatever else hangs on the trainer (a round's payload, a device-side
        copy), every shard of each. Nothing reads them after the window: its
        counters were taken when it closed, the round check works on host
        arrays, and the reference check puts the INITIAL parameters back. The
        leaves are deleted, not dropped for a collector to find, so the next
        allocation has the room; whoever reads ``trainer.state`` after this
        is told that the array was deleted."""
        if self.phase != DONE:
            raise RuntimeError("the training state is released once the window's counters are read")
        held = [x for x in jax.tree_util.tree_leaves(vars(self.vol.trainer))
                if isinstance(x, jax.Array) and not x.is_deleted()]
        jax.block_until_ready(held)  # a step still in the chip's queue reads them
        self._log_memory("before the release")
        for x in held:
            x.delete()
        left = jax.live_arrays()  # what someone else than the trainer still holds: the log says so
        self.released = {"arrays": len(held), "bytes": sum(x.nbytes for x in held),
                         "left_arrays": len(left), "left_bytes": sum(x.nbytes for x in left)}
        _log(f"released the training state: {self.released}")
        self._log_memory("released")

    def reference_check(self) -> None:
        """The program's loss and gradients on the initial parameters against
        the plain float32 reference, on seeded sequences, one at a time.

        It runs after the window, on a chip the training state has left
        (``release_training_state``): the host copy of the initial parameters
        put back as the trainer sharded them (4 bytes a parameter) and, while
        ``compare`` reads them, the two sides' gradient trees (8). Both sides
        read the same parameters, in every sequence, so neither donates them.
        Of the two, the one whose program takes more temporaries runs first,
        beside one tree less: the peak is 12 bytes a parameter and the
        SMALLER of the two sides' temporaries."""
        t_begin = time.perf_counter()
        tr = self.vol.trainer
        rc = self.cfg["reference_check"]
        ref = references.load(self.cfg["family"])
        ref.check_config(tr.bundle.config, self.cfg)
        sizes = ref.sizes(self.cfg)
        arrays = datagen.lm_arrays(
            self.seed + 0x5EED, rc["sequences"], rc["seq_len"], sizes["vocab"]
        )
        loss_fn = tr.bundle.loss_fn
        shardings = jax.tree_util.tree_map(lambda x: x.sharding, tr.state.params)
        self.release_training_state()
        rng = jax.random.PRNGKey(0)

        def program(params, tokens, targets):
            return jax.value_and_grad(
                lambda p: loss_fn(p, {"tokens": tokens, "targets": targets}, rng)[0]
            )(params)

        @jax.jit
        def compare(gp, gr):
            num = jax.tree_util.tree_map(
                lambda a, b: jax.numpy.sum((a.astype("float32") - b) ** 2), gp, gr
            )
            den = jax.tree_util.tree_map(lambda b: jax.numpy.sum(b ** 2), gr)
            return num, den

        params = jax.device_put(self._initial_params, shardings)
        tok, tgt = arrays["tokens"][:1], arrays["targets"][:1]
        sides = {
            name: jax.jit(fn).lower(params, tok, tgt).compile()
            for name, fn in (("program", program), ("reference", ref.make_loss_and_grad(self.cfg)))
        }
        temps = {name: c.memory_analysis().temp_size_in_bytes for name, c in sides.items()}
        order = sorted(sides, key=temps.get, reverse=True)
        _log(f"reference check: temporaries {temps}, runs {order[0]} first")
        loss_err, num_t, den_t, worst_leaf, losses = 0.0, 0.0, 0.0, 0.0, []
        for s in range(rc["sequences"]):
            tok, tgt = arrays["tokens"][s:s + 1], arrays["targets"][s:s + 1]
            out = {name: sides[name](params, tok, tgt) for name in order}
            (lp, gp), (lr, gr) = out.pop("program"), out.pop("reference")  # popped: `del` below frees the trees
            num, den = compare(gp, gr)
            del gp, gr
            num = [float(x) for x in jax.tree_util.tree_leaves(num)]
            den = [float(x) for x in jax.tree_util.tree_leaves(den)]
            lp, lr = float(lp), float(lr)
            losses.append(lp)
            loss_err = max(loss_err, abs(lp - lr))
            num_t, den_t = num_t + sum(num), den_t + sum(den)
            worst_leaf = max(
                [worst_leaf] + [math.sqrt(n / d) for n, d in zip(num, den) if d > 0]
            )
        grad_rel = math.sqrt(num_t / den_t) if den_t > 0 else float("inf")
        ok = (
            all(math.isfinite(x) for x in losses)
            and loss_err <= rc["loss_atol"]
            and grad_rel <= rc["grad_rel_err"]
        )
        self.reference = {
            "ok": ok, "loss": sum(losses) / len(losses), "loss_abs_err": loss_err,
            "grad_rel_err": grad_rel, "worst_leaf_rel_err": worst_leaf,
        }
        self.losses.insert(0, {"step": self._start_step, "loss": self.reference["loss"],
                            "from": "reference_check"})
        _log(f"reference check ({time.perf_counter() - t_begin:.1f} s): {self.reference}")
        self._log_memory("checked")

    # -- the three seams ------------------------------------------------------

    def metrics_record(self, step: int, metrics: Dict[str, Any], n_samples: int = 0) -> None:
        self._inner_record(step, metrics, n_samples=n_samples)
        self._note_level(step, time.perf_counter())  # the loop has just read this step's loss
        if "loss" in metrics:
            self.losses.append({"step": int(step), "loss": float(metrics["loss"]),
                                "from": "loop"})

    def averager_call(self, payload: Any, step: int) -> Optional[Any]:
        tr, transport = self.vol.trainer, self.vol.transport
        rec: Dict[str, Any] = {
            "index": len(self.rounds), "step": int(step),
            "t0": time.perf_counter(), "wall0": time.time(),
            "bytes0": transport.bytes_sent + transport.bytes_received,
            "weight_steps": int(tr.steps_since_merge),
        }
        self.rounds.append(rec)
        self._note_level(step, rec["t0"])  # the payload's device-to-host copy has landed
        if self.on_launch is not None:
            self.on_launch()
        mark = (
            jax.profiler.TraceAnnotation("bench:averager_call")
            if self._trace_state == "on" else contextlib.nullcontext()
        )
        with mark:
            out = self._inner_averager(payload, step)
        rec["bytes1"] = transport.bytes_sent + transport.bytes_received
        rec["t1"], rec["wall1"] = time.perf_counter(), time.time()
        rec["ok"] = out is not None
        if rec["index"] == 0 and self._warm_rounds:
            self.parts["warmup_round_s"] = rec["t1"] - rec["t0"]
            # Checked after the run (finish()): passes over 355 M elements
            # here would hold the warm-up round's merge back by their length.
            # Neither tree is written to again: the loop reads both.
            self._first_round = (payload, out, rec)
            _log(f"warm-up round: {rec['t1'] - rec['t0']:.2f} s")
        return out

    def finish(self) -> None:
        """What is checked once the volunteer has stopped."""
        if self._first_round is not None:
            t = time.perf_counter()
            try:
                self._check_round(*self._first_round)
            except Exception as e:  # noqa: BLE001 - a broken check is a failed check
                self.round_check = {"ok": False, "error": repr(e)}
            self._first_round = None
            _log(f"round check ({time.perf_counter() - t:.1f} s): {self.round_check}")

    def on_step(self, trainer: Any, step_no: int) -> None:
        if self._chain is not None:
            self._chain(trainer, step_no)
        now = time.perf_counter()
        if trainer.mutation_counter != self._mut_seen:
            # This loop iteration swapped a round's result in (trainer.py:721-727).
            self._mut_seen = trainer.mutation_counter
            self.merges.append({"step": int(step_no), "t": now})
            self.host_intervals.append(("merge", self._iteration_began, now))
        if len(self.rounds) != self._calls_seen:
            # This iteration launched a round: host snapshot of the payload,
            # hand-over to the pool, snapshot for state sync (trainer.py:673, 898).
            self._calls_seen = len(self.rounds)
            self.host_intervals.append(("launch", self._iteration_began, now))
        try:
            self._on_step(trainer, step_no)
        finally:
            self._iteration_began = time.perf_counter()

    def _on_step(self, trainer: Any, step_no: int) -> None:
        if self.phase == WARMUP:
            if self._warmed_up(step_no):
                self._open_window(trainer, step_no)
        elif self.phase == WINDOW:
            self._steps_in_window += 1
            if (self._level is None and self._steps_in_window >= PROBE_STEPS
                    and not self._whole_periods):
                jax.block_until_ready(trainer.state.step)
                self._note_level(step_no, time.perf_counter())
            if self.trace_on:
                self._trace_tick(trainer, step_no)
            if self._window_is_over(trainer, step_no):
                self._close_window(trainer, step_no)

    # -- window ---------------------------------------------------------------

    def _note_level(self, step: int, t: float) -> None:
        if self.phase == WINDOW and step > self.window["step0"]:
            self._level = (int(step), t)

    def _window_is_over(self, trainer: Any, step_no: int) -> bool:
        """The loop dispatches far ahead of the device (it syncs only at its
        log points), so the host clock at dispatch says little about when a
        step will have run. From the last point at which host and device were
        level, and the mean step time since the window opened, estimate when
        the step just dispatched will finish: the window closes with the
        first step that finishes after ``seconds``. The host clock is the
        fallback and the upper limit.

        A window of whole round periods (a round cell) opens on the last step
        before a launch and closes on the last step before a later launch,
        holding as many launch-to-launch periods as fit: every run then holds
        whole rounds (launch, flight, merge, the steps until the next launch)
        and none is cut. A launch follows a cadence boundary's eve when no
        round is in flight. On such an eve the hook syncs, one step before
        the loop's own launch would, so the period's length is exact."""
        t0, step0 = self.window["t0"], self.window["step0"]
        if time.perf_counter() - t0 >= self.seconds:
            return True
        if self._whole_periods:
            in_flight = any("t1" not in r for r in self.rounds)
            if not self._at_boundary_eve(step_no) or step_no <= step0 or in_flight:
                return False
            jax.block_until_ready(trainer.state.step)
            elapsed = time.perf_counter() - t0
            self._note_level(step_no, t0 + elapsed)
            self._periods += 1
            return elapsed + elapsed / self._periods > self.seconds
        if self._level is None:
            return False
        level_step, level_t = self._level
        per_step = (level_t - t0) / (level_step - step0)
        return level_t + (step_no - level_step) * per_step - t0 >= self.seconds

    def _at_boundary_eve(self, step_no: int) -> bool:
        return step_no % self._every == self._every - 1

    def _warmed_up(self, step_no: int) -> bool:
        if step_no - self._start_step < self._warm_steps:
            return False
        if len(self.rounds) >= self._warm_rounds + 4 or step_no - self._start_step >= 300:
            # Rounds that never land on a launch's eve (or fail) must not
            # hold the run for ever: measure, and say so.
            self.window["warmup_incomplete"] = True
            return True
        if self._warm_rounds:
            done = [r for r in self.rounds if "t1" in r]
            if len(done) < self._warm_rounds or len(done) < len(self.rounds):
                return False
            if len(self.merges) < sum(1 for r in done if r["ok"]):
                return False
        if self._align and not self._at_boundary_eve(step_no):
            return False
        return True

    def peak_bytes(self) -> Optional[int]:
        """The allocator's high-water mark so far, on the fullest chip."""
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use") for d in self._devices()
        ]
        return max([p for p in peaks if p is not None], default=None)

    def _log_memory(self, when: str) -> None:
        stats = self._devices()[0].memory_stats()
        if stats:  # None off an accelerator
            _log(f"memory, {when}: " + ", ".join(
                f"{k} {stats[k]}" for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                if k in stats))

    def counters(self) -> Dict[str, Any]:
        vol = self.vol
        out: Dict[str, Any] = {"compile": vol.trainer.compile_summary()}
        out["memory_peak_bytes"] = self.peak_bytes()
        out["bytes"] = vol.transport.bytes_sent + vol.transport.bytes_received
        if vol.averager is not None:
            out["codec"] = vol.averager.mesh_codec.stats()
            out["rounds_ok"] = vol.averager.rounds_ok
            out["rounds_skipped"] = vol.averager.rounds_skipped
            out["rounds_degraded"] = vol.averager.rounds_degraded
        return out

    def _devices(self) -> List[Any]:
        mesh = self.vol.trainer.mesh
        if mesh is not None:
            return list(mesh.devices.flat)
        return jax.local_devices()[:1]

    def _open_window(self, trainer: Any, step_no: int) -> None:
        jax.block_until_ready(trainer.state.step)
        self.before = self.counters()
        self.window.update(t0=time.perf_counter(), wall0=time.time(), step0=int(step_no))
        self.phase = WINDOW
        _log(f"window opens at step {step_no}")
        self._log_memory("window open")
        if self.trace_on and self.traffic.get("trace", {}).get("kind") == "round":
            self._start_trace(trainer, step_no)  # the round launched by the next step

    def _close_window(self, trainer: Any, step_no: int) -> None:
        if self._trace_state == "on":
            self._stop_trace(trainer, "window_end")
        jax.block_until_ready(trainer.state.step)
        self.window.update(t1=time.perf_counter(), wall1=time.time(), step1=int(step_no))
        self.after = self.counters()
        self.phase = DONE
        _log(f"window closes at step {step_no}")
        self._log_memory("window closed")
        # The way a preempted volunteer is told to stop (volunteer.py:1331).
        os.kill(os.getpid(), signal.SIGTERM)

    # -- profiler -------------------------------------------------------------

    def _trace_tick(self, trainer: Any, step_no: int) -> None:
        spec = self.traffic.get("trace", {"kind": "steps", "skip_steps": 5, "steps": 10})
        if self._trace_state == "idle":
            # A round's trace starts with the window (_open_window), on the
            # eve of the launch; a trace of steady steps a few steps in.
            if spec["kind"] == "steps" and self._steps_in_window >= int(spec.get("skip_steps", 5)):
                self._start_trace(trainer, step_no)
        elif self._trace_state == "on":
            info = self.trace_info
            elapsed = time.perf_counter() - info["t0"]
            if spec["kind"] == "round":
                merged = [m for m in self.merges if m["t"] > info["t0"]]
                done = bool(merged) and step_no >= merged[0]["step"] + int(
                    spec.get("steps_after_merge", 2)
                )
                if done or elapsed >= float(spec.get("cap_s", 15.0)):
                    self._stop_trace(trainer, "round_merged" if done else "cap")
            elif step_no - info["step0"] >= int(spec.get("steps", 10)):
                self._stop_trace(trainer, "steps")

    def _start_trace(self, trainer: Any, step_no: int) -> None:
        jax.block_until_ready(trainer.state.step)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # Python frames slow the host and are not read
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._trace_state = "on"
        with jax.profiler.TraceAnnotation("bench:trace_begin"):
            self.trace_info = {"t0": time.perf_counter(), "wall0": time.time(),
                               "step0": int(step_no)}

    def _stop_trace(self, trainer: Any, why: str) -> None:
        jax.block_until_ready(trainer.state.step)
        with jax.profiler.TraceAnnotation("bench:trace_end"):
            self.trace_info.update(t1=time.perf_counter(), wall1=time.time(),
                                   step1=int(trainer.state.step), stopped_by=why)
        self._trace_state = "done"
        jax.profiler.stop_trace()
        files = sorted(glob.glob(
            os.path.join(self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        ), key=os.path.getmtime)
        self.trace_info["file"] = files[-1] if files else None
        _log(f"trace stopped ({why}): {self.trace_info}")

    # -- the warm-up round's result -------------------------------------------

    def _check_round(self, own: Any, got: Any, rec: Dict[str, Any]) -> None:
        """The round's guarantees: two members, both contributions in, the
        sample-weighted mean on every leaf within the bf16 wire's rounding."""
        spec = self.traffic["peers"][0]
        check = self.traffic["round_check"]
        if got is None:
            self.round_check = {"ok": False, "error": "the warm-up round returned nothing"}
            return
        vol_cfg = self.vol.cfg
        w_own = float(vol_cfg.batch_size * rec["weight_steps"])
        w_peer = float(vol_cfg.batch_size * vol_cfg.average_every)  # benchmark/peer.py
        a, b = w_own / (w_own + w_peer), w_peer / (w_own + w_peer)
        own_leaves = jax.tree_util.tree_leaves(own)
        got_leaves = jax.tree_util.tree_leaves(got)
        if len(own_leaves) != len(got_leaves):
            self.round_check = {"ok": False, "error": "the result has another tree"}
            return
        seed = datagen.peer_seed(self.seed, 0)
        scale = float(spec["first_contribution"]["scale"])
        tol = float(check["abs_tol_per_unit"])
        worst, bad_leaves = 0.0, 0
        for i, (x, y) in enumerate(zip(own_leaves, got_leaves)):
            # 355 M elements: float32 throughout and results written into the
            # two arrays this loop owns (peer, work), so that no pass pays for
            # a fresh allocation.
            x = np.asarray(x, np.float32)
            peer = datagen.seeded_leaf(x.shape, seed, i, scale)
            work = np.multiply(x, np.float32(a))
            work += np.float32(b) * peer                       # work = the expected mean
            err = np.abs(np.subtract(np.asarray(y, np.float32), work))
            np.abs(work, out=work)
            np.abs(peer, out=peer)
            np.maximum(work, peer, out=work)
            np.maximum(work, np.abs(x, out=peer), out=work)    # work = unit
            # error in units of the allowed rounding; the tiny absolute floor
            # keeps an exact zero from dividing by zero
            work *= np.float32(tol)
            work += np.float32(1e-12)
            np.divide(err, work, out=err)
            ratio = float(err.max()) if err.size else 0.0
            worst = max(worst, ratio)
            bad_leaves += ratio > 1.0
        joins = [
            s for s in self.vol.telemetry.tracer.spans()
            if s["name"] == "join" and rec["wall0"] - 1.0 <= s["t0"] <= rec["wall1"]
        ]
        size = joins[-1].get("attrs", {}).get("size") if joins else None
        role = joins[-1].get("attrs", {}).get("role") if joins else None
        self.round_check = {
            "ok": bad_leaves == 0 and size == 2,
            "worst_err_over_tol": worst, "bad_leaves": int(bad_leaves),
            "group_size": size, "role": role, "weights": [w_own, w_peer],
        }
