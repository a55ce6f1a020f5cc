"""The volunteer train loop: local SGD + periodic collaborative averaging.

Reference call stack C (SURVEY.md §3): data -> device -> fwd/bwd -> local
optimizer step -> every K steps, hand params to the averager and continue
from the averaged result. The averager is injected as a callback so the
trainer (L5) never imports the swarm (L3/L4) — config 1 (single volunteer,
no averaging, BASELINE.json:7) is just ``averager=None``.

Params mode can OVERLAP the WAN round with continued local compute
(``overlap=True``): at an averaging point the trainer snapshots the payload
to host, hands it to a background thread, and keeps stepping; when the round
completes it merges Moshpit-style with a delta correction,

    new = averaged + (current - snapshot),

so the local steps taken during the round are preserved on top of the
contracted average. Grads mode stays synchronous BY DESIGN: GradientAverager
semantics feed each step's averaged gradient to the optimizer before the
next step — applying it late would mean stale-gradient SGD, a different
algorithm, not an optimization.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import os
import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributedvolunteercomputing_tpu.models.registry import Batch, ModelBundle
from distributedvolunteercomputing_tpu.training.metrics import MetricsWriter
from distributedvolunteercomputing_tpu.training.optim import make_optimizer
from distributedvolunteercomputing_tpu.training.steps import (
    TrainState,
    make_apply_step,
    make_grad_step,
    make_train_step,
)
from distributedvolunteercomputing_tpu.utils.logging import errstr, get_logger

log = get_logger(__name__)

# Averager callback: takes the CURRENT host params pytree, returns the
# averaged pytree (or None to keep local params, e.g. when no group formed).
AveragerFn = Callable[[Any, int], Optional[Any]]


class Trainer:
    def __init__(
        self,
        bundle: ModelBundle,
        batch_size: int = 32,
        optimizer: str = "adamw",
        lr: float = 1e-3,
        seed: int = 0,
        init_seed: int = 0,
        # Cast floating params to this dtype after init ("bfloat16" for
        # bf16 training — the bench's DVC_BENCH_PARAM_DTYPE arm, now a
        # first-class trainer/CLI option); None keeps the model's dtype.
        param_dtype: Optional[str] = None,
        # Microbatch count per optimizer step (gradient accumulation inside
        # the compiled step); batch_size must divide evenly. Semantics match
        # one big batch — only peak activation memory changes.
        accum_steps: int = 1,
        # Host-loop amortization: scan up to N train steps inside ONE
        # compiled call (steps.make_multi_step), so per-step Python dispatch
        # leaves the hot path. Chunks end at every metrics/eval/averaging
        # boundary, so cadence semantics are unchanged; within a chunk,
        # per-step losses still come back (scan ys) for target detection.
        # 1 = off. Params mode, single-device/slice-internal trainers only.
        steps_per_call: int = 1,
        # Extra step cadences scan chunks must end at (beyond eval/log/
        # averaging, which are clipped automatically) — e.g. the volunteer
        # passes its checkpoint_every here, since that cadence lives inside
        # its on_step closure where _chunk_len can't see it.
        chunk_cadences: Tuple[int, ...] = (),
        average_every: int = 10,
        # Wall-clock averaging cadence for HETEROGENEOUS swarms (params mode
        # only; 0 = off, use the step cadence above). Rounds trigger when
        # wall time crosses a multiple of the interval — every volunteer
        # with an NTP-ish clock crosses the same boundary within ms, so a
        # v4-8 doing 40 steps per window rendezvouses cleanly with a v5e-4
        # doing 15, where a step-count cadence would leave the fast peer
        # parked in matchmaking every round (or never aligned at all).
        # Contribution weights carry samples-since-last-merge, so unequal
        # local progress is weighted correctly by construction.
        average_interval_s: float = 0.0,
        # Clock the wall-cadence boundaries are computed on. The volunteer
        # passes its ClockSync's corrected clock (swarm/clocksync.py) so
        # boundaries rendezvous even under multi-second clock skew;
        # defaults to time.time for library users.
        wall_clock: Optional[Callable[[], float]] = None,
        averager: Optional[AveragerFn] = None,
        # params: local-SGD, averaged every `average_every` steps.
        # grads: GradientAverager semantics, averaged EVERY step
        #        (average_every then only sets the host-snapshot cadence).
        average_what: str = "params",
        # Overlap the WAN round with continued local steps (params mode
        # only). ``max_staleness`` bounds how many steps a round's result may
        # lag before it is discarded instead of merged (0 = no bound).
        overlap: bool = False,
        max_staleness: int = 0,
        metrics_path: Optional[str] = None,
        volunteer_id: str = "local",
        total_steps: Optional[int] = None,
        # Called after each HOST-VISIBLE step. With steps_per_call > 1 the
        # scan prefix runs whole chunks on-device, so on_step fires only on
        # chunk-final steps: any per-step or modular cadence inside the
        # callback MUST be declared in chunk_cadences (chunks then end at
        # every multiple, making those steps host-visible) — an undeclared
        # cadence is silently skipped for scan-prefix steps.
        on_step: Optional[Callable[["Trainer", int], None]] = None,
        data: Optional[Iterable[Batch]] = None,  # overrides the synthetic stream
        # In-slice device mesh: when a volunteer owns a multi-chip TPU slice,
        # the step is sharded over it (parallel/train_step.py) — dp/sp/tp/...
        # inside the slice, while the WAN averager still sees one volunteer.
        # ``fsdp`` shards params+opt over the mesh's dp axis (ZeRO-3);
        # ``seq_sharded`` routes attention to the ring kernel over sp.
        mesh: Optional[Any] = None,
        fsdp: bool = False,
        seq_sharded: bool = False,
        sp_impl: str = "ring",  # "ring" | "ulysses" (all-to-all; H % sp == 0)
        # Periodic held-out evaluation: every ``eval_every`` steps, mean loss
        # over ``eval_batches`` batches WITHOUT updating params, recorded as
        # an "eval" metrics event. With synthetic data the eval stream is an
        # independent rng stream (true held-out). With a custom ``data``
        # iterable, pass ``eval_data`` (an independently shuffled stream over
        # the same dataset) for matching semantics; without it, eval falls
        # back to consuming ``data``'s next batches — loss-before-update,
        # but it perturbs the training order volunteers were promised.
        eval_every: int = 0,
        eval_batches: int = 4,
        eval_data: Optional[Iterable[Batch]] = None,
        # DiLoCo-style OUTER optimizer over params-mode averaging rounds
        # (Douillard et al., "DiLoCo: Distributed Low-Communication Training
        # of Language Models"): treat (anchor - averaged) — the swarm's
        # aggregate progress since the last round — as an outer gradient and
        # apply Nesterov momentum to it, instead of adopting the raw mean.
        # At a fixed round cadence this buys convergence-per-round, i.e.
        # time-to-target at the same WAN byte budget (the whole game in the
        # volunteer setting). "none" = plain averaging. Identity when
        # outer_lr=1, outer_momentum=0.
        outer_optimizer: str = "none",
        outer_lr: float = 0.7,
        outer_momentum: float = 0.9,
        # The volunteer's span tracer (swarm/telemetry.py ``Tracer``, handed
        # in so this module never imports the swarm): the phases in which
        # the train thread holds the chip up (launch, merge, snapshot, log
        # sync) become spans and profiler annotations. None: every site is a
        # no-op.
        tracer: Optional[Any] = None,
    ):
        if eval_every and eval_batches < 1:
            raise ValueError(f"eval_batches must be >= 1, got {eval_batches}")
        if average_what not in ("params", "grads"):
            raise ValueError(f"unknown average_what {average_what!r}")
        if average_interval_s < 0:
            raise ValueError(
                f"average_interval_s must be >= 0, got {average_interval_s}"
            )
        if average_interval_s > 0 and average_what == "grads":
            # GradientAverager semantics are per-step by definition — a
            # wall-clock cadence would let optimizer steps run on unmerged
            # gradients, which is params mode's job.
            raise ValueError("average_interval_s requires average_what='params'")
        if steps_per_call < 1:
            raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
        if steps_per_call > 1:
            if averager is not None and average_what == "grads":
                # Grads cross the WAN between bwd and the optimizer EVERY
                # step — there is no multi-step run to amortize.
                raise ValueError("steps_per_call > 1 requires average_what='params'")
        if accum_steps < 1 or batch_size % accum_steps != 0:
            raise ValueError(
                f"accum_steps={accum_steps} must be >=1 and divide batch_size={batch_size}"
            )
        # Persistent XLA compilation cache: volunteers churn (rejoin =
        # re-trace + re-compile); the cache turns every rejoin after the
        # first into a disk hit.
        from distributedvolunteercomputing_tpu.utils.jaxenv import (
            compile_log,
            device_record,
            enable_compile_cache,
        )

        self.compile_cache_dir = enable_compile_cache()
        self._compile_log = compile_log()  # listening before the step compiles
        self.device = device_record()
        self.bundle = bundle
        self.batch_size = batch_size
        self.accum_steps = accum_steps
        self.average_every = average_every
        self.average_interval_s = float(average_interval_s)
        self._wall_clock = wall_clock or time.time
        # Next wall-clock boundary (multiple of the interval) a round is due
        # at; None until run() arms it.
        self._next_avg_t: Optional[float] = None
        # Steps of local progress behind the NEXT params-mode contribution —
        # read by the volunteer's averager callback to weight it in samples.
        # Under the step cadence this is average_every except after failed
        # rounds (progress accumulates); under the interval cadence it is
        # whatever this volunteer managed in the window, which is exactly
        # what makes heterogeneous contributions weigh correctly.
        self.steps_since_merge: int = average_every
        self._last_merge_step: Optional[int] = None
        self.tracer = tracer
        # Trace id of the phases opened now: "loop" for those that belong to
        # no round, a round's key (or the tracer's PENDING) inside
        # _round_phase.
        self._phase_trace = "loop"
        # Written by the averager callback before it returns: the trace id
        # (round key) of the round it just ran, None when no group formed.
        # Read after the call, or after the future that carried it resolved.
        self.round_trace: Optional[str] = None
        self.averager = averager
        self.average_what = average_what
        # ``seed`` is PER-VOLUNTEER: it drives the data order and the step
        # rng, so volunteers see different batches. ``init_seed`` is
        # TASK-CONSTANT: every volunteer training the same task must build
        # the same initial params — for LoRA models this is load-bearing
        # (the frozen base is NEVER averaged, so adapters averaged across
        # volunteers are deltas against one shared base; with per-volunteer
        # bases the average would be semantically meaningless), and for full
        # models it makes round 1 start contracted instead of spending early
        # rounds averaging away init noise.
        rng = jax.random.PRNGKey(seed)
        _, data_rng, state_rng = jax.random.split(rng, 3)
        self.tx = make_optimizer(optimizer, lr=lr, total_steps=total_steps)
        params = bundle.init(jax.random.PRNGKey(init_seed))
        self.param_dtype = param_dtype
        if param_dtype:
            # bf16 training (params + optimizer moments + every matmul in
            # the dtype): halves param/optimizer HBM and runs the MXU at
            # native rate. Floating leaves only — integer tables and the
            # step counter keep their dtypes. The swarm tier is
            # dtype-agnostic by construction (flatten_to_buffer ships f32
            # and restores per-leaf dtypes), and init stays bit-identical
            # across volunteers BEFORE the cast, so the task-constant
            # init_seed contract above still holds.
            from distributedvolunteercomputing_tpu.utils.pytree import cast_floating

            params = cast_floating(params, param_dtype)
        self.state = TrainState.create(params, self.tx, state_rng)
        # Gradient-averaging mode splits the step so grads can cross the WAN
        # between bwd and the optimizer (reference GradientAverager
        # semantics); the fused donate-everything step covers the rest.
        self._grads_mode = averager is not None and average_what == "grads"
        self.overlap = bool(overlap) and averager is not None and not self._grads_mode
        self.max_staleness = max_staleness
        # One worker: rounds never overlap each other, only local compute.
        self._avg_pool = (
            concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="avg-round"
            )
            if self.overlap
            else None
        )
        self._inflight: Optional[tuple] = None  # (launch_step, payload0, future)
        # The in-flight launch's spans, which wait for their round's key.
        self._launch_spans: tuple = ()
        if mesh is None and (fsdp or seq_sharded):
            raise ValueError("fsdp/seq_sharded require a mesh (--mesh dp=...,tp=...)")
        if outer_optimizer not in ("none", "nesterov"):
            raise ValueError(f"unknown outer_optimizer {outer_optimizer!r}")
        if outer_optimizer != "none" and averager is not None and average_what != "params":
            # The outer step operates on PARAMETER deltas between rounds;
            # grads mode has no per-round parameter anchor to difference
            # against (each step's gradients are averaged individually).
            raise ValueError("outer_optimizer requires average_what='params'")
        self.outer_optimizer = outer_optimizer
        self.outer_lr = float(outer_lr)
        self.outer_momentum = float(outer_momentum)
        # Host-side outer state: the anchor is the global params the current
        # inner phase STARTED from (payload/avg_select space); the momentum
        # tree accumulates per-round aggregate deltas.
        self._outer_anchor: Any = None
        self._outer_m: Any = None
        if fsdp and average_what == "grads":
            # The split grad/apply steps have no in-step constraint keeping
            # params at 1/dp, so ZeRO-3 would silently re-replicate — and
            # per-step host grad averaging defeats its purpose anyway.
            # Independent of whether an averager is attached NOW: the config
            # asked for grads-mode semantics, and accepting it only when the
            # wiring happens to be absent would make the same flag set pass
            # or fail on an unrelated condition.
            raise ValueError("fsdp is a params-mode feature; use average_what='params'")
        self.mesh = mesh
        self.fsdp = fsdp
        self._param_shardings = None
        self._put_batch: Optional[Callable[[Batch], Batch]] = None
        if mesh is not None:
            from distributedvolunteercomputing_tpu.parallel.train_step import (
                put_batch,
                shard_train_state,
            )

            self.state, self._param_shardings = shard_train_state(
                self.state, mesh, self.tx, fsdp=fsdp
            )
            self._put_batch = lambda b: put_batch(b, mesh, seq_sharded=seq_sharded)
        if self._grads_mode:
            # The split steps are plain jits: with mesh-sharded inputs GSPMD
            # partitions them like the fused sharded step for replicated-dp
            # layouts (tp/pp rules propagate from the input shardings). The
            # fsdp layout needs the fused step's in-step constraints and is
            # rejected above.
            self._grad_fn = make_grad_step(bundle.loss_fn, accum_steps=accum_steps)
            self._apply_fn = make_apply_step(self.tx)
            self._step_fn = None
        elif mesh is not None:
            from distributedvolunteercomputing_tpu.parallel.train_step import (
                make_sharded_train_step,
            )

            self._step_fn = make_sharded_train_step(
                bundle.loss_fn, self.tx, mesh, accum_steps=accum_steps,
                seq_sharded_batch=seq_sharded, fsdp=fsdp, sp_impl=sp_impl,
            )
        else:
            self._step_fn = make_train_step(
                bundle.loss_fn, self.tx, accum_steps=accum_steps
            )
        self.steps_per_call = int(steps_per_call)
        self.chunk_cadences = tuple(int(c) for c in chunk_cadences if c)
        # EMA of seconds per step, measured at chunk granularity — only
        # maintained (and only needed) under the wall-clock averaging
        # cadence, where chunk sizing must anticipate the next boundary.
        self._ema_step_s: Optional[float] = None
        self._multi_fn = None
        if self.steps_per_call > 1 and self._step_fn is not None:
            if mesh is not None:
                # The mesh twin scans the SAME sharded body (incl. the
                # ZeRO in-step re-constraints) — r4 VERDICT missing #5.
                from distributedvolunteercomputing_tpu.parallel.train_step import (
                    make_sharded_multi_step,
                )

                self._multi_fn = make_sharded_multi_step(
                    bundle.loss_fn, self.tx, mesh, accum_steps=accum_steps,
                    seq_sharded_batch=seq_sharded, fsdp=fsdp, sp_impl=sp_impl,
                )
            else:
                from distributedvolunteercomputing_tpu.training.steps import make_multi_step

                self._multi_fn = make_multi_step(
                    bundle.loss_fn, self.tx, accum_steps=accum_steps
                )
        self._data_rng = data_rng
        self._data = data
        self.eval_every = eval_every
        self.eval_batches = eval_batches
        self._eval_fn = None
        self._it: Optional[Any] = None
        self._eval_data = eval_data
        self._eval_it: Optional[Any] = None
        # Held-out stream: a distinct fold of the volunteer seed, so eval
        # batches never collide with any training batch at any seed.
        self._eval_rng = jax.random.fold_in(data_rng, 0x5EED)
        self.metrics = MetricsWriter(metrics_path, volunteer_id)
        # Header: every later record in this stream names what it ran on.
        self.metrics.record_event(
            0, "header",
            {**self.device, "compile_cache_dir": self.compile_cache_dir},
        )
        self.on_step = on_step
        # Host-side (step, params) snapshot for concurrent readers (the
        # state-sync provider serves fetches from the asyncio thread while
        # the train step DONATES the live state's buffers — reading
        # self.state.params cross-thread would hit deleted arrays). Updated
        # at safe points only; tuple assignment keeps readers consistent.
        self._snapshot: Any = None
        # Bumped on every out-of-band params mutation (averaging merge,
        # peer-pull adoption). Lets the checkpoint layer tell whether state
        # at the SAME step number still matches its last snapshot — the step
        # counter alone can't (the end-of-run overlap drain merges without
        # advancing it).
        self.mutation_counter = 0
        self._take_snapshot(0)

    def compile_summary(self) -> dict:
        """What this process compiled so far (``CompileLog.summary``) with
        the train step as the named program, and where the persistent cache
        lives."""
        fn = self._step_fn if self._step_fn is not None else self._grad_fn
        return {
            "cache_dir": self.compile_cache_dir,
            **self._compile_log.summary(f"jit({fn.__name__})"),
        }

    def adopt_params(self, params: Any, step: Optional[int] = None) -> None:
        """Replace params (and optionally the step counter) in place — the
        peer-pull state sync path. The optimizer state is NOT reset: at
        adoption time it is either cold-init (fresh process) or the restored
        moments, and averaging rounds re-sync it functionally either way."""
        import jax.numpy as jnp

        self.state = TrainState(
            params=jax.device_put(params, self._param_shardings)
            if self._param_shardings is not None
            else jax.device_put(params),
            opt_state=self.state.opt_state,
            step=self.state.step if step is None else jnp.asarray(step, jnp.int32),
            rng=self.state.rng,
        )
        self.mutation_counter += 1
        # A state-sync adoption invalidates the outer momentum stream: the
        # new params did not come from this trainer's anchor, so the next
        # round re-seeds (first-round semantics in _outer_transform).
        self._outer_anchor = None
        self._outer_m = None
        self._take_snapshot(int(self.state.step))

    # -- spans and profiler annotations (no-ops without a tracer) ------------

    def _phase(self, name: str, **attrs: Any):
        """Span + ``dvc:<name>`` annotation around a phase of this thread."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.phase(name, self._phase_trace, **attrs)

    @contextlib.contextmanager
    def _round_phase(self, name: str, trace: Optional[str] = None):
        """A phase that belongs to a round: it and the phases opened inside
        it carry the round's ``trace``, so one round is one tree from launch
        to merge. None: the round has no key yet, and the spans wait for
        ``_adopt_launch``."""
        if trace is None:
            trace = getattr(self.tracer, "PENDING", "")
        prev, self._phase_trace = self._phase_trace, trace
        try:
            with self._phase(name) as sp:
                yield sp
        finally:
            self._phase_trace = prev

    def _mark(self, name: str):
        """Annotation alone: the per-step phases."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.annotate(name)

    @staticmethod
    def _host_tree(tree: Any) -> Any:
        """Gather a pytree to host with every leaf's device-to-host DMA
        ISSUED UP FRONT (``copy_to_host_async``) before any blocking
        ``np.asarray``: the transfers run in parallel with each other AND
        with still-dispatching device compute, so the trainer thread waits
        ~max(leaf DMA) instead of the sum of sequential synchronous pulls.
        This is what lets the averaging launch overlap the contribution's
        D2H with the train step's tail instead of stalling on it."""
        for leaf in jax.tree_util.tree_leaves(tree):
            fn = getattr(leaf, "copy_to_host_async", None)
            if fn is not None:
                try:
                    fn()
                except Exception:  # noqa: BLE001 — async copy is an optimization
                    break
        return jax.tree_util.tree_map(np.asarray, tree)

    def _take_snapshot(self, step_no: int) -> None:
        """D2H copy of params at a point where the buffers are live (between
        steps, on the trainer thread). One copy per averaging interval."""
        with self._phase("loop.snapshot", step=step_no) as sp:
            self._snapshot = (
                step_no,
                self._host_tree(self.state.params),
            )
            if sp is not None:
                sp.attrs["bytes"] = sum(
                    x.nbytes for x in jax.tree_util.tree_leaves(self._snapshot[1])
                )

    def host_snapshot(self):
        """(step, host params pytree) — safe to read from any thread."""
        return self._snapshot

    def data_iter(self) -> Iterable[Batch]:
        rng = self._data_rng
        while True:
            rng, k = jax.random.split(rng)
            yield self.bundle.make_batch(k, self.batch_size)

    def _swap_params(self, new_params: Any, step_no: int) -> None:
        """Replace params on device, keep opt_state/step/rng, refresh the
        cross-thread snapshot. The ONE place a merge becomes live state —
        the overlap and blocking paths must not diverge here."""
        with self._phase("loop.merge.h2d"):
            # What the host waits: device_put returns before the copy lands.
            params = (
                jax.device_put(new_params, self._param_shardings)
                if self._param_shardings is not None
                else jax.device_put(new_params)
            )
        self.state = TrainState(
            params=params,
            opt_state=self.state.opt_state,
            step=self.state.step,
            rng=self.state.rng,
        )
        self.mutation_counter += 1
        self._take_snapshot(step_no)

    def evaluate(self, n_batches: Optional[int] = None) -> float:
        """Mean held-out loss over ``n_batches`` without updating params.
        Safe between steps (the jitted step donates buffers DURING a step,
        but params are live again once it returns)."""
        if self._eval_fn is None:
            from distributedvolunteercomputing_tpu.training.steps import make_eval_step

            self._eval_fn = make_eval_step(self.bundle.loss_fn)
        n = self.eval_batches if n_batches is None else n_batches
        if n < 1:
            raise ValueError(f"evaluate() needs n_batches >= 1, got {n}")
        rng = self._eval_rng
        total = 0.0
        done = 0
        for _ in range(n):
            if self._eval_data is not None:
                # Dedicated held-out stream (independently shuffled over the
                # same dataset): training batch order is untouched by eval.
                if self._eval_it is None:
                    self._eval_it = iter(self._eval_data)
                try:
                    batch = next(self._eval_it)
                except StopIteration:
                    break  # finite eval set exhausted
            elif self._data is not None:
                if self._it is None:  # standalone use before run()
                    self._it = iter(self._data)
                try:
                    batch = next(self._it)
                except StopIteration:
                    # Finite dataset exhausted: evaluate on what we got
                    # rather than killing a training run that was sized
                    # without eval's extra draws in mind.
                    break
            else:
                rng, k = jax.random.split(rng)
                batch = self.bundle.make_batch(k, self.batch_size)
            if self._put_batch is not None:
                batch = self._put_batch(batch)
            rng, ek = jax.random.split(rng)
            # Honor accum_steps: training fits memory by microbatching
            # inside the compiled step, so eval must not allocate the
            # whole-batch activation footprint in one forward.
            if self.accum_steps > 1:
                micro = jax.tree_util.tree_map(
                    lambda x: x.reshape(
                        (self.accum_steps, x.shape[0] // self.accum_steps) + x.shape[1:]
                    ),
                    batch,
                )
                losses = []
                for i in range(self.accum_steps):
                    mb = jax.tree_util.tree_map(lambda x: x[i], micro)
                    rng, mk = jax.random.split(rng)
                    losses.append(float(self._eval_fn(self.state.params, mb, mk)["loss"]))
                total += sum(losses) / len(losses)
            else:
                total += float(self._eval_fn(self.state.params, batch, ek)["loss"])
            done += 1
        self._eval_rng = rng
        return total / done if done else float("nan")

    def _outer_transform(self, averaged: Any) -> Any:
        """Apply the outer optimizer to one round's aggregate (payload
        space, host numpy). Plain averaging when disabled.

        Nesterov over the round delta: with anchor a (the global params this
        inner phase started from) and the round's average v,
            g  = a - v                    (aggregate outer gradient)
            m  = mu * m + g
            a' = a - lr * (mu * m + g)    (lookahead step)
        a' becomes the next anchor. lr=1, mu=0 reduces exactly to a' = v.
        The first successful round (or the first after a state-sync
        adoption reset) has no anchor — it adopts the plain average and
        seeds the anchor there."""
        if self.outer_optimizer == "none":
            return averaged
        if self._outer_anchor is None or jax.tree_util.tree_structure(
            self._outer_anchor
        ) != jax.tree_util.tree_structure(averaged):
            self._outer_anchor = jax.tree_util.tree_map(
                lambda v: np.asarray(v, np.float32).copy(), averaged
            )
            self._outer_m = jax.tree_util.tree_map(np.zeros_like, self._outer_anchor)
            return averaged
        lr, mu = self.outer_lr, self.outer_momentum
        grad = jax.tree_util.tree_map(
            lambda a, v: a - np.asarray(v, np.float32), self._outer_anchor, averaged
        )
        self._outer_m = jax.tree_util.tree_map(
            lambda m, g: mu * m + g, self._outer_m, grad
        )
        self._outer_anchor = jax.tree_util.tree_map(
            lambda a, m, g: a - lr * (mu * m + g), self._outer_anchor, self._outer_m, grad
        )
        return self._outer_anchor

    def _avg_due(self, step_no: int) -> bool:
        """Is a params-mode averaging round due at this step?

        Step cadence (the default): every ``average_every`` steps. Wall-clock
        cadence (``average_interval_s > 0``): when wall time crosses a
        multiple of the interval — boundaries are ABSOLUTE (``n * T``) on
        the swarm-consensus clock (``wall_clock``; the volunteer supplies
        ClockSync's corrected clock, so skewed volunteers still fire within
        ms of their peers), which is what makes heterogeneous swarms
        rendezvous without parking the fast peer.
        Advances the armed boundary exactly once per crossing (a slow step
        that skips past several boundaries still yields one round)."""
        if self.average_interval_s > 0:
            now = self._wall_clock()
            if self._next_avg_t is None:
                # First call arms the NEXT boundary: a joining volunteer's
                # first round aligns with the swarm's next window instead of
                # firing solo mid-window.
                self._arm_next_boundary(now)
                return False
            if now >= self._next_avg_t:
                self._arm_next_boundary(now)
                return True
            return False
        return step_no % self.average_every == 0

    def _arm_next_boundary(self, now: float) -> None:
        self._next_avg_t = (
            int(now // self.average_interval_s) + 1
        ) * self.average_interval_s

    def _chunk_len(self, next_step: int, remaining: int, log_every: int) -> int:
        """Steps the scan prefix + final per-step iteration may cover from
        ``next_step`` without straddling a cadence boundary — every
        metrics/eval/averaging/snapshot action happens on the chunk's LAST
        step, so a chunk must END at the first boundary it meets."""
        n = min(self.steps_per_call, remaining)
        cadences = [
            self.eval_every,
            self.average_every if self.averager else 0,
            log_every,
            *self.chunk_cadences,
        ]
        for c in cadences:
            if c:
                n = min(n, c - ((next_step - 1) % c))
        if self.averager is not None and self.average_interval_s > 0:
            # Wall-clock boundaries can't be mapped to a step count without
            # a step-time estimate; size the chunk to END just past the next
            # boundary (EMA maintained by the fast path, which syncs once
            # per chunk in this mode). Until the EMA exists, tiny chunks
            # bootstrap it — due-poll latency is then ~one step once
            # settled, not steps_per_call steps.
            if self._ema_step_s is None:
                n = min(n, 2)
            elif self._next_avg_t is not None:
                until = max(self._next_avg_t - self._wall_clock(), 0.0)
                n = min(n, max(1, int(until / self._ema_step_s) + 1))
        return max(1, n)

    def _record_target_crossed(
        self, cross_step: int, target_loss: float, t_start: float,
        wall_override: Optional[float] = None,
    ) -> Tuple[int, float]:
        """Log + record the first target crossing; shared by the per-step
        path and the scan-prefix path so the two can't diverge.

        ``wall_override``: the scan-prefix path detects a crossing only
        after its whole chunk completes, so it interpolates the crossing
        time from the chunk's per-step rate instead of charging the metric
        with up to a chunk of post-crossing steps (r4 advisor) — keeping
        time-to-target comparable with the per-step path."""
        wall = wall_override if wall_override is not None else time.monotonic() - t_start
        log.info(
            "target loss %.4f reached at step %d (%.1fs)",
            target_loss, cross_step, wall,
        )
        self.metrics.record_event(
            cross_step, "target_crossed",
            {"target_loss": target_loss, "wall_s": round(wall, 3)},
        )
        return (cross_step, wall)

    def _note_window_progress(self, step_no: int) -> None:
        """Record the local steps behind the contribution about to launch —
        the single source the volunteer's weight callback reads, shared by
        the blocking and overlap paths so they can't diverge."""
        if self._last_merge_step is not None:
            self.steps_since_merge = max(1, step_no - self._last_merge_step)

    def _adopt_launch(self, held: tuple) -> str:
        """Record a launch's spans, which ended before their round had a
        key, under the key the averager callback left in ``round_trace``
        (``loop`` when no group formed). Returns that trace id."""
        trace = self.round_trace or "loop"
        if self.tracer is not None:
            for sp in held:
                self.tracer.adopt(sp, trace)
        return trace

    def _run_average_round(self, tree: Any, step_no: int, what: str) -> Optional[Any]:
        """One WAN round: select payload -> averager -> record -> merge.
        Returns the merged tree, or None when no group formed / round failed.
        A params round's merge is swapped in here, inside its merge phase.

        The payload crosses to HOST first — the AveragerFn contract is host
        numpy (the overlap path already guarantees it; for a mesh-sharded
        state this is also the gather from the slice's shards). D2H DMAs
        issue up front and drain in parallel (_host_tree)."""
        with self._round_phase("loop.launch") as launch:
            with self._phase("loop.launch.d2h") as d2h:
                payload = self._host_tree(self.bundle.avg_select(tree))
            if what == "params":
                self._note_window_progress(step_no)
        t_avg = time.monotonic()
        self.round_trace = None
        averaged = self.averager(payload, step_no)
        trace = self._adopt_launch((launch, d2h))
        self.metrics.record_event(
            step_no, "avg_round",
            {"avg_s": time.monotonic() - t_avg, "ok": averaged is not None, "what": what},
        )
        if averaged is None:
            return None
        with self._round_phase("loop.merge", trace):
            with self._phase("loop.merge.host"):
                if what == "params":
                    averaged = self._outer_transform(averaged)
                merged = self.bundle.avg_merge(
                    tree, jax.tree_util.tree_map(np.asarray, averaged)
                )
            if what == "params":
                self._swap_params(merged, step_no)
                self._last_merge_step = step_no
        return merged

    # -- overlapped averaging (params mode) --------------------------------

    def _launch_overlap_round(self, step_no: int) -> None:
        """Snapshot the payload to HOST and launch the round on the pool.

        The host copy is load-bearing: the jitted step donates the live
        params' buffers, so the pool thread must never touch device arrays
        the train thread is about to consume. It stays on THIS thread for
        the same reason, but its D2H DMAs issue up front (_host_tree): the
        copies overlap the boundary step's still-dispatching tail, and the
        round then streams on the pool while the next step runs — the
        device never idles for the contribution transfer."""
        with self._round_phase("loop.launch") as launch:
            with self._phase("loop.launch.d2h") as d2h:
                payload0 = self._host_tree(self.bundle.avg_select(self.state.params))
            self._note_window_progress(step_no)
            t0 = time.monotonic()
            self.round_trace = None
            fut = self._avg_pool.submit(
                lambda: (self.averager(payload0, step_no), time.monotonic() - t0)
            )
        self._inflight = (step_no, payload0, fut)
        self._launch_spans = (launch, d2h)

    def _finish_overlap_round(self, step_no: int, wait: bool = False) -> None:
        """Merge a completed round: new = averaged + (current - snapshot).

        The delta correction keeps the steps taken while the round was in
        flight; the contraction toward the group average still happens on
        the snapshot term (Moshpit-style delayed parameter averaging)."""
        if self._inflight is None:
            return
        launch_step, payload0, fut = self._inflight
        if not wait and not fut.done():
            return
        self._inflight = None
        try:
            # The averager callback carries its own network timeouts; the
            # margin here only guards against a wedged callback at exit.
            averaged, avg_s = fut.result(timeout=600.0 if wait else 0.0)
        except Exception as e:  # noqa: BLE001 — a failed round never kills training
            self._adopt_launch(self._launch_spans)
            log.warning("overlapped averaging launched at step %d failed: %s", launch_step, errstr(e))
            self.metrics.record_event(
                step_no, "avg_round", {"ok": False, "what": "params", "overlap": True}
            )
            return
        trace = self._adopt_launch(self._launch_spans)
        staleness = step_no - launch_step
        ok = averaged is not None
        if ok and self.max_staleness and staleness > self.max_staleness:
            log.warning(
                "dropping averaging result: staleness %d > bound %d", staleness, self.max_staleness
            )
            ok = False
        self.metrics.record_event(
            step_no, "avg_round",
            {"avg_s": avg_s, "ok": ok, "what": "params", "overlap": True,
             "staleness": staleness},
        )
        if not ok:
            return
        # Outer step first, local-progress delta on top: the contraction
        # toward (outer-updated) consensus happens on the snapshot term,
        # the steps taken while the round was in flight are preserved.
        with self._round_phase("loop.merge", trace):
            with self._phase("loop.merge.d2h"):
                current = self._host_tree(self.bundle.avg_select(self.state.params))
            with self._phase("loop.merge.host"):
                averaged = self._outer_transform(averaged)
                merged_payload = jax.tree_util.tree_map(
                    lambda avg, cur, p0: np.asarray(avg, np.float32) + (cur - p0),
                    averaged, current, payload0,
                )
                merged = self.bundle.avg_merge(self.state.params, merged_payload)
            self._swap_params(merged, step_no)
        # Progress up to the LAUNCH step entered the average (the delta term
        # above preserved the rest locally).
        self._last_merge_step = launch_step

    def run(
        self,
        steps: int,
        target_loss: Optional[float] = None,
        target_mode: str = "stop",
        log_every: int = 50,
        stop_flag: Optional[Callable[[], bool]] = None,
    ) -> Dict[str, float]:
        """Train for ``steps``; returns summary.

        ``target_loss`` with ``target_mode="stop"`` ends the run at the
        first crossing (config-1 semantics); with ``"record"`` the run keeps
        going for the full ``steps`` and the summary reports WHEN the target
        was first crossed (``target_crossed_step`` / ``target_crossed_s``) —
        the time-to-target-loss half of the driver metric (BASELINE.json:2)
        measured without giving up the fixed-steps throughput row."""
        if target_mode not in ("stop", "record"):
            raise ValueError(f"unknown target_mode {target_mode!r}")
        it = iter(self._data) if self._data is not None else iter(self.data_iter())
        self._it = it  # evaluate() draws from the same iterator for custom data
        # Tracing hook (SURVEY.md §5): DVC_PROFILE_DIR=<dir> captures a
        # jax.profiler trace of steps [DVC_PROFILE_START, +DVC_PROFILE_STEPS)
        # — past warmup/compile, so the trace shows steady-state step time
        # and the compute-vs-averaging split. View with tensorboard/xprof.
        profile_dir = os.environ.get("DVC_PROFILE_DIR")
        profile_start = int(os.environ.get("DVC_PROFILE_START", "10"))
        profile_steps = int(os.environ.get("DVC_PROFILE_STEPS", "10"))
        profiling = False
        # Grads mode averages every step; after a FAILED round (no group —
        # e.g. the only partner died) skip averaging for average_every steps
        # instead of paying a full matchmaking timeout per step.
        avg_skip_until = 0
        # Materialising metrics forces a host<->device sync that breaks JAX's
        # async dispatch pipelining — only pay for it when something consumes
        # the value (target check, JSONL record, or a log line).
        sync_every_step = target_loss is not None or self.metrics.has_sink
        m = None
        last_loss = float("nan")
        start_step = int(self.state.step)
        if self._last_merge_step is None:
            self._last_merge_step = start_step
        t_start = time.monotonic()
        ran_steps = 0
        target_crossed: Optional[Tuple[int, float]] = None  # (step, wall_s)
        for i in range(steps):
            if ran_steps >= steps:
                break  # scan prefixes below may consume several steps per iteration
            if stop_flag is not None and stop_flag():
                log.info("stop flag set; exiting train loop at step %d", int(self.state.step))
                break
            # Multi-step fast path (steps_per_call > 1): run the first n-1
            # steps of this chunk inside ONE compiled scan, then fall
            # through to the ordinary per-step path for the chunk's final
            # step — so metrics records, eval, averaging rounds, and
            # snapshots all keep their exact cadence semantics (chunks end
            # at every boundary, enforced by _chunk_len). Disabled while
            # profiling (the trace hooks are per-step).
            if self._multi_fn is not None and not profile_dir:
                n = self._chunk_len(start_step + ran_steps + 1, steps - ran_steps, log_every)
                if n > 1:
                    prefix = [next(it) for _ in range(n - 1)]
                    stacked = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs), *prefix
                    )
                    t_chunk = time.perf_counter()
                    with self._mark("dispatch"):
                        self.state, losses = self._multi_fn(self.state, stacked)
                    ran_steps += n - 1
                    if self.averager is not None and self.average_interval_s > 0:
                        # One sync per chunk: the real chunk duration feeds
                        # the EMA that sizes chunks around wall boundaries
                        # (_chunk_len). Negligible next to the n-1 steps.
                        float(losses[-1])
                        per_step = (time.perf_counter() - t_chunk) / (n - 1)
                        self._ema_step_s = (
                            per_step
                            if self._ema_step_s is None
                            else 0.5 * self._ema_step_s + 0.5 * per_step
                        )
                    if sync_every_step:
                        host_losses = np.asarray(losses)
                        for k, lv in enumerate(host_losses):
                            self.metrics.record(
                                start_step + ran_steps - (n - 1) + k + 1,
                                {"loss": float(lv)},
                                n_samples=self.batch_size,
                            )
                        last_loss = float(host_losses[-1])
                        if target_loss is not None and target_crossed is None:
                            hit = np.nonzero(host_losses <= target_loss)[0]
                            if hit.size:
                                cross_step = (
                                    start_step + ran_steps - (n - 1) + int(hit[0]) + 1
                                )
                                # Back out the steps that ran AFTER the
                                # crossing at this chunk's per-step rate.
                                per_step = (time.perf_counter() - t_chunk) / (n - 1)
                                wall_est = (
                                    time.monotonic() - t_start
                                    - (n - 2 - int(hit[0])) * per_step
                                )
                                target_crossed = self._record_target_crossed(
                                    cross_step, target_loss, t_start,
                                    wall_override=wall_est,
                                )
                                if target_mode == "stop":
                                    # The end-of-run sync reads m; point it
                                    # at THIS chunk's last loss, not the
                                    # previous chunk's stale metrics.
                                    m = {"loss": host_losses[-1]}
                                    break
                    else:
                        self.metrics.count_samples(self.batch_size * (n - 1))
            with self._mark("data"):
                batch = next(it)
                if self._put_batch is not None:
                    batch = self._put_batch(batch)
            step_no = start_step + ran_steps + 1
            if profile_dir and not profiling and i == profile_start:
                jax.profiler.start_trace(profile_dir)
                profiling = True
            if self._grads_mode:
                # GradientAverager semantics are PER-STEP: every local
                # gradient is averaged before any optimizer sees it (skipping
                # steps would let replica params drift with nothing ever
                # re-contracting them — that's what params mode is for).
                grads, m, next_rng = self._grad_fn(self.state, batch)
                if step_no >= avg_skip_until:
                    merged = self._run_average_round(grads, step_no, "grads")
                    if merged is not None:
                        grads = merged
                    else:
                        avg_skip_until = step_no + self.average_every
                self.state = self._apply_fn(self.state, grads, next_rng)
                if step_no % self.average_every == 0:
                    self._take_snapshot(step_no)
            else:
                with self._mark("dispatch"):
                    self.state, m = self._step_fn(self.state, batch)
            ran_steps += 1
            at_log_point = bool(log_every) and step_no % log_every == 0
            if sync_every_step or at_log_point:
                # A span for the log point's sync only: with a sink or a
                # target every step syncs, and a span a step floods the ring.
                with (
                    contextlib.nullcontext() if sync_every_step
                    else self._phase("loop.log_sync", step=step_no)
                ):
                    last_loss = float(m["loss"])
                self.metrics.record(step_no, m, n_samples=self.batch_size)
            else:
                self.metrics.count_samples(self.batch_size)

            if self.eval_every and step_no % self.eval_every == 0:
                ev = self.evaluate()
                if ev == ev:  # nan = finite dataset exhausted; nothing to record
                    self.metrics.record_event(
                        step_no, "eval",
                        {"eval_loss": ev, "n_batches": self.eval_batches},
                    )
                    log.info("step %d eval_loss %.4f", step_no, ev)

            if self.averager is not None and not self._grads_mode:
                if self.overlap:
                    # Merge any round that completed since the last step,
                    # then (at the cadence, with no round in flight) launch
                    # the next one — the device keeps stepping either way.
                    self._finish_overlap_round(step_no)
                    if self._avg_due(step_no):
                        if self._inflight is None:
                            self._launch_overlap_round(step_no)
                        # Refresh the cross-thread snapshot at the cadence
                        # even when no merge landed (failed/skipped rounds):
                        # state-sync must serve CURRENT weights, not the
                        # last merge — a rejoiner pulling a stale snapshot
                        # would bootstrap thousands of steps behind.
                        self._take_snapshot(step_no)
                elif self._avg_due(step_no):
                    if self._run_average_round(self.state.params, step_no, "params") is None:
                        # Snapshot at the cadence regardless of round outcome
                        # (see overlap branch).
                        self._take_snapshot(step_no)
                if self.average_interval_s > 0 and step_no % self.average_every == 0:
                    # Under the wall-clock cadence, rounds can be a full
                    # interval apart — far longer than average_every steps.
                    # Keep the state-sync snapshot fresh on the STEP cadence
                    # regardless, or a rejoiner pulls a window-old state
                    # (the hazard the comment above describes).
                    self._take_snapshot(step_no)

            if profiling and i + 1 >= profile_start + profile_steps:
                jax.block_until_ready(m["loss"])
                jax.profiler.stop_trace()
                profiling = False
                log.info("profiler trace written to %s", profile_dir)

            if self.on_step is not None:
                self.on_step(self, step_no)

            if at_log_point:
                log.info(
                    "step %d loss %.4f (%.1f samples/s)",
                    step_no,
                    last_loss,
                    self.metrics.samples_per_sec(),
                )
            if target_loss is not None and last_loss <= target_loss:
                if target_crossed is None:
                    target_crossed = self._record_target_crossed(
                        step_no, target_loss, t_start
                    )
                if target_mode == "stop":
                    break
        if profiling:  # loop ended inside the trace window
            jax.profiler.stop_trace()
        # Drain an in-flight round so the returned params are contracted and
        # a partner mid-round isn't abandoned by our exit.
        if self.overlap:
            self._finish_overlap_round(start_step + ran_steps, wait=True)
        if m is not None:
            last_loss = float(m["loss"])  # sync once at the end regardless
        wall = time.monotonic() - t_start
        summary = {
            "final_loss": last_loss,
            "steps": int(self.state.step),
            "wall_time_s": wall,
            "samples_per_sec": ran_steps * self.batch_size / wall if wall > 0 else 0.0,
        }
        if target_loss is not None:
            summary["target_loss"] = target_loss
            summary["target_crossed_step"] = target_crossed[0] if target_crossed else None
            summary["target_crossed_s"] = (
                round(target_crossed[1], 3) if target_crossed else None
            )
        return summary
