"""Volunteer lifecycle: join swarm -> collaborative train loop -> leave.

Reference call stack B (SURVEY.md §3): connect to coordinator, DHT join,
announce, build model+optimizer on device, train with periodic averaging,
and on SIGTERM/preemption leave cleanly and flush state.

Threading model: the asyncio loop (swarm services: DHT, heartbeat, averaging
RPC handlers) owns the MAIN thread; the blocking JAX train loop runs in a
worker thread and bridges into the loop per averaging round via
``run_coroutine_threadsafe``. On TPU-VMs the preemption notice arrives as
SIGTERM (BASELINE.json:5) — handled exactly like a user Ctrl-C: stop flag,
final checkpoint, tombstone, exit.
"""

from __future__ import annotations

import asyncio
import dataclasses
import os
import signal
import threading
import time
import uuid
from typing import Any, Dict, Optional, Sequence, Tuple

import jax

from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.swarm.averager import make_averager
from distributedvolunteercomputing_tpu.swarm.dht import DHTNode
from distributedvolunteercomputing_tpu.swarm.membership import SwarmMembership
from distributedvolunteercomputing_tpu.swarm.state_sync import StateSyncService
from distributedvolunteercomputing_tpu.swarm.transport import Transport, read_secret
from distributedvolunteercomputing_tpu.training.trainer import Trainer
from distributedvolunteercomputing_tpu.utils import traced
from distributedvolunteercomputing_tpu.utils.logging import errstr, get_logger
from distributedvolunteercomputing_tpu.utils.pytree import tree_size_bytes

log = get_logger(__name__)

# Wall-clock cadence the AUTO default resolves to for butterfly params-mode
# swarms (the value both committed A/Bs ran: BASELINE.md config 4b and the
# scale16 butterfly arm).
DEFAULT_BUTTERFLY_INTERVAL_S = 20.0


@dataclasses.dataclass
class VolunteerConfig:
    model: str = "mnist_mlp"
    model_overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # "host:port[,host:port...]" — several = several DHT bootstrap nodes
    # (join works while ANY is alive); None = run standalone.
    coordinator: Optional[str] = None
    host: str = "127.0.0.1"
    port: int = 0
    advertise_host: Optional[str] = None  # dialable address when binding 0.0.0.0
    peer_id: str = ""
    averaging: str = "none"  # none|sync|gossip|butterfly|byzantine
    average_every: int = 10
    # Wall-clock averaging cadence (params mode; 0 = step cadence above).
    # Rounds fire when wall time crosses a multiple of the interval, so
    # clock-synced heterogeneous volunteers rendezvous within ms regardless
    # of step speed; contributions weigh by actual window progress.
    # None = AUTO (the default): butterfly params-mode swarms — the
    # heterogeneous-volunteer config — get the wall-clock cadence at
    # DEFAULT_BUTTERFLY_INTERVAL_S (the step cadence is measured-
    # pathological there at n=4 and n=16: BASELINE.md config 4 vs 4b and
    # the scale16 step-cadence arm); every other mode keeps the step
    # cadence. Pass an explicit 0 to force step cadence anywhere.
    average_interval_s: Optional[float] = None
    average_what: str = "params"  # params (local-SGD) | grads (GradientAverager)
    # Overlap WAN rounds with local compute (params mode; see Trainer). On by
    # default: blocking the device for a whole WAN round is what sinks
    # samples/sec at payload scale (BASELINE.md north-star).
    overlap: bool = True
    max_staleness: int = 0  # steps; 0 = unbounded (rounds self-bound via timeouts)
    wire: str = "f32"  # f32|bf16|q8|topk|powersgd — WAN payload codec
    # wire="topk" fraction: ship only the top |value| fraction of gradient
    # entries per round (error feedback banks the rest). ~50x fewer DCN
    # bytes at 0.01. Grads mode + sync/byzantine only.
    topk_frac: float = 0.01
    # DGC-style sparsity warmup: ramp the kept fraction from dense to
    # topk_frac over the first N successful rounds (0 = off). Early rounds
    # contract init noise and need (nearly) full gradients.
    topk_warmup_rounds: int = 0
    # wire="powersgd" target rank: each >=2D gradient tensor ships as a
    # rank-r (P, Q) pair — (n+m)·r floats instead of n·m — with warm-started
    # power iteration + the same error feedback as topk. Unlike topk it
    # composes with the robust estimators (reconstructions are dense), so
    # byzantine mode keeps its guarantees. Grads mode + sync/byzantine only.
    powersgd_rank: int = 4
    min_group: int = 2
    max_group: int = 16
    # Multi-group round scheduling (Moshpit-style): partition the live
    # swarm into many groups of ~this size per round via a rotating seeded
    # hash grid over the DHT keyspace, instead of one group per epoch —
    # swarm-wide sync throughput stops being capped by one leader's NIC,
    # and group averages still mix globally in O(log N) rounds because the
    # grid re-seeds every rotation. 0 = off (classic single-group
    # rendezvous). Gather-style modes only (sync/byzantine/butterfly).
    group_size: int = 0
    # Rotation cadence of the group schedule, seconds. 0 = AUTO: the
    # wall-clock averaging interval when one is set (one fresh grid per
    # round boundary), else 15s. Every member of a prospective group must
    # land in the same rotation window to rendezvous, so wall-cadence
    # swarms (clock-synced) are the natural fit.
    group_rotation_s: float = 0.0
    # Locality zone this volunteer advertises in its membership record
    # (e.g. "dc-eu1", "home-us"): volunteers in the same zone share fast
    # links. "" = unzoned. Advertised regardless of scheduling mode; the
    # hierarchical schedule below consumes it.
    zone: str = ""
    # Hierarchical two-level scheduling cadence: with a group schedule and
    # >= 2 advertised zones live, every k-th rotation runs the zone-blind
    # CROSS-zone mixing grid and the rest stay INTRA-zone (groups never
    # span a zone boundary, so those rounds move zero cross-zone bytes).
    # 0 = flat single-level grid. Degrades to flat automatically while
    # fewer than two zones are advertised (mixed-version swarms).
    cross_zone_every_k: int = 0
    # Zone-sharded training (swarm/sharding.py): partition the averaged
    # parameter tree into K zone-local shards — this volunteer holds its
    # HRW-assigned shard(s), advertises its primary shard so cross-zone
    # rotations rendezvous same-shard holders (~1/K wire bytes/round),
    # and runs the fenced re-shard + hedged-recovery autopilot on zone
    # churn. 0 = unsharded (full replica).
    zone_shards: int = 0
    batch_size: int = 32  # samples per optimizer step (across accum microbatches)
    # Scan up to N steps inside one compiled call between cadence points
    # (host-loop amortization; params mode, no mesh). 1 = off.
    steps_per_call: int = 1
    accum_steps: int = 1  # gradient-accumulation microbatches inside the step
    data_path: Optional[str] = None  # .npz real-data file; None = synthetic
    optimizer: str = "adam"
    lr: float = 1e-3
    seed: int = 0  # per-volunteer: data order + step rng
    init_seed: int = 0  # TASK-constant: shared initial params (see Trainer)
    param_dtype: Optional[str] = None  # e.g. "bfloat16" for bf16 training
    steps: int = 1000
    warmup_steps: int = 0  # linear LR warm-up from 0, before the cosine decay over `steps`
    target_loss: Optional[float] = None
    # "stop" ends the run at the target; "record" trains the full --steps
    # and reports when the target was first crossed (time-to-target-loss).
    target_mode: str = "stop"
    eval_every: int = 0  # 0 = no held-out evaluation
    eval_batches: int = 4
    metrics_path: Optional[str] = None
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 200
    heartbeat_ttl: float = 15.0
    join_timeout: float = 10.0
    gather_timeout: float = 20.0
    method: str = "mean"  # robust aggregation estimator for byzantine mode
    # Estimator keyword overrides (krum/bulyan n_byzantine, trimmed_mean
    # trim, centered_clip clip_tau/iters, ...) — passed straight through to
    # ops/robust.aggregate. None = each estimator's defaults.
    method_kw: Optional[Dict[str, Any]] = None
    # Adaptive round deadlines (EWMA of successful rounds; see AveragerBase):
    # a dead peer costs seconds instead of the full gather budget.
    adaptive_timeout: bool = False
    # Resilience layer (swarm/resilience.py + swarm/failure_detector.py):
    # phi-accrual liveness feeding straggler pre-exclusion at group
    # formation, plus the adaptive policy engine (learned round deadlines,
    # failure backoff, runtime robust-estimator escalation). Opt-in — the
    # deadline-bounded COMMIT machinery itself is always on (rounds commit
    # with the contributions that arrived by the budget), this flag adds
    # the adaptive/learning layer on top.
    resilience: bool = False
    # phi at/above which a peer counts as suspected (8 ~ one-in-1e8 under
    # the fitted heartbeat model — the classic accrual-detector default).
    phi_threshold: float = 8.0
    # Closed-loop adaptive controller (swarm/controller.py): reads the
    # telemetry plane and retunes, live and epoch-fenced, the averaging
    # topology / dense wire / cross-zone cadence / per-level deadlines /
    # hedge regime. Rides the resilience layer (needs its policy and
    # evidence), so it engages only with --resilience; --no-adapt pins
    # today's static behavior end-to-end — no controller is constructed
    # and no controller bytes ride the report beat.
    adapt: bool = True
    # Static wall-clock budget per averaging round, seconds (0 = use the
    # gather timeout; the resilience policy, when on, supersedes both with
    # its learned deadline). The leader stamps clock()+budget into the
    # round begin; the whole group commits at that instant with whatever
    # contributions arrived, re-weighting the mean over the subset.
    round_deadline_s: float = 0.0
    # DiLoCo-style outer optimizer over params-mode rounds (see Trainer):
    # Nesterov momentum on the per-round aggregate delta instead of adopting
    # the raw mean — convergence-per-round at the same WAN byte budget.
    outer_optimizer: str = "none"  # none | nesterov
    outer_lr: float = 0.7
    outer_momentum: float = 0.9
    # In-slice mesh: "dp=2,tp=2"-style spec over THIS volunteer's local
    # devices (a TPU slice); empty = single-device step. The WAN tier still
    # sees one volunteer either way. ``fsdp`` shards params+optimizer over
    # the mesh's dp axis (ZeRO-3); ``seq_sharded`` turns on ring attention
    # over its sp axis.
    mesh: str = ""
    fsdp: bool = False
    seq_sharded: bool = False
    sp_impl: str = "ring"  # ring | ulysses (all-to-all seq<->heads)
    # Host a control-plane replica on this volunteer (swarm/control_plane.py):
    # the process serves coord.status / batched cp.exchange heartbeat
    # traffic and becomes an election candidate for the replicated,
    # key-range-sharded control plane — with a few of these in the swarm,
    # coordinator death is a non-event (volunteers fail their control
    # traffic over to a surviving replica within one heartbeat).
    host_replica: bool = False
    # Shared-secret frame authentication (transport-level HMAC): path to a
    # file holding the swarm secret. Every member (coordinator included)
    # must use the same secret; peers without it can't join, spoof
    # identities, or inject contributions. A file, not a flag value —
    # secrets in argv leak via process listings.
    secret_file: Optional[str] = None
    # Byzantine mode + the topk wire is a trap: topk forces method='mean'
    # (robust estimators over sparse supports collapse to zero), so the run
    # would carry the name "byzantine" with ZERO robustness. Refused unless
    # this flag says the caller understands that trade.
    allow_unrobust_topk: bool = False
    # Telemetry plane (swarm/telemetry.py): round tracing, unified metrics
    # registry, flight recorder, and the telemetry.* debug RPCs. On by
    # default (the record paths are ring-buffer appends; the overhead smoke
    # in tests/test_telemetry.py bounds the cost at <5% of commit latency);
    # --no-telemetry turns every record path into a no-op.
    telemetry: bool = True
    # Training-health layer (swarm/health.py): post-round parameter
    # sketches (live mixing error), gradient-mass accounting, per-peer
    # contribution quality, codec distortion. On by default (the health
    # overhead smoke in tests/test_health.py bounds the cost at <5% of
    # commit latency); --no-health-probe disables the sketch computation
    # and every health tally end-to-end — no sketch bytes ride the
    # heartbeat report — while the rest of the telemetry plane stays on.
    # --no-telemetry disables both.
    health_probe: bool = True
    # Swarm watchdog (swarm/watchdog.py): streaming anomaly detectors
    # (commit-rate collapse, per-level round-wall inflation, mass-fraction
    # drops, bandwidth collapse, control-plane beat failure streaks,
    # quality-flag alerts) with hysteresis + cooldown, riding the report
    # beat as a compact firing set. On by default; --no-watchdog disables
    # every detector end-to-end — no alert bytes ride the heartbeat —
    # while tracing/health stay on. --no-telemetry disables everything.
    watchdog: bool = True
    # Tail-optimal hedged recovery (ISSUE 14, docs/PERFORMANCE.md): when
    # this volunteer LEADS a streaming round, predicted-late peers'
    # missing tile ranges are re-requested over a second stream ahead of
    # the deadline (sync.refetch, idempotent per tile). On by default —
    # it spends idle gather wait, never the deadline; --no-hedge restores
    # pure deadline-drop.
    hedge: bool = True
    # Optional summand redundancy: each contribution's last-k% tiles ride
    # XOR-coded on the ring successor's sidecar, decodable by the leader
    # iff the original misses commit. 0.0 = off (costs one extra k%-sized
    # member-to-member transfer per round when on).
    tail_redundancy_frac: float = 0.0
    # Local Prometheus text endpoint (GET /metrics) for stock scrapers:
    # 0 = off (the telemetry.prom debug RPC always answers on the swarm
    # transport regardless).
    metrics_port: int = 0

    def __post_init__(self):
        if not self.peer_id:
            self.peer_id = f"vol-{uuid.uuid4().hex[:8]}"
        if self.average_interval_s is None:
            # AUTO cadence (VERDICT r5 #5): butterfly is the heterogeneous-
            # swarm config, and both committed cadence A/Bs (config 4 vs 4b
            # at n=4; scale16 butterfly arms at n=16) show the step cadence
            # parking fast peers / never aligning there. Wall-clock default
            # for butterfly params mode; step cadence everywhere else.
            self.average_interval_s = (
                DEFAULT_BUTTERFLY_INTERVAL_S
                if self.averaging == "butterfly" and self.average_what == "params"
                else 0.0
            )
        if self.average_interval_s < 0:
            raise ValueError(
                f"average_interval_s must be >= 0, got {self.average_interval_s}"
            )
        if self.round_deadline_s < 0:
            raise ValueError(
                f"round_deadline_s must be >= 0, got {self.round_deadline_s}"
            )
        if self.phi_threshold <= 0:
            raise ValueError(
                f"phi_threshold must be > 0, got {self.phi_threshold}"
            )
        if not (0 <= self.metrics_port <= 65535):
            raise ValueError(
                f"metrics_port must be in [0, 65535] (0 = off), got "
                f"{self.metrics_port}"
            )
        if self.group_rotation_s < 0:
            raise ValueError(
                f"group_rotation_s must be >= 0, got {self.group_rotation_s}"
            )
        if self.cross_zone_every_k < 0:
            raise ValueError(
                f"cross_zone_every_k must be >= 0 (0 = flat), got "
                f"{self.cross_zone_every_k}"
            )
        if self.cross_zone_every_k and not self.group_size:
            # Fail at config time (the method/wire validation policy): the
            # hierarchy is a property of the group schedule — without one
            # the flag would silently do nothing for the whole run.
            raise ValueError(
                "--cross-zone-every-k requires --group-size (the hierarchy "
                "schedules the multi-group grid; single-group swarms have "
                "no grid to layer)"
            )
        if self.zone_shards < 0:
            raise ValueError(
                f"zone_shards must be >= 0 (0 = unsharded), got "
                f"{self.zone_shards}"
            )
        if self.zone_shards:
            # Fail at config time (the method/wire validation policy): the
            # shard domain IS the zone, and the shard-scoped rendezvous
            # lives in the group schedule — without either the flag would
            # silently train a full replica.
            if not self.zone:
                raise ValueError(
                    "--zone-shards requires --zone (the zone is the shard "
                    "domain: shards are held within a zone and replicated "
                    "across zones)"
                )
            if self.averaging != "none" and not self.group_size:
                raise ValueError(
                    "--zone-shards with averaging requires --group-size "
                    "(same-shard holders rendezvous through the shard-"
                    "scoped group schedule)"
                )
        if self.group_size:
            # Fail at config time (the method/wire validation policy): the
            # schedule only makes sense for round-structured gather-style
            # modes — gossip has no rounds to group and "none" no averaging.
            if self.group_size < 2:
                raise ValueError(
                    f"group_size must be >= 2 (or 0 = off), got {self.group_size}"
                )
            if self.averaging not in ("sync", "byzantine", "butterfly"):
                raise ValueError(
                    "--group-size requires --averaging sync, byzantine, or "
                    "butterfly (gossip is pairwise — there is no round-"
                    "structured group to schedule)"
                )
            if self.group_size < self.min_group:
                raise ValueError(
                    f"group_size {self.group_size} < min_group "
                    f"{self.min_group}: every scheduled group would be "
                    "refused at formation"
                )
            if self.group_size > self.max_group:
                raise ValueError(
                    f"group_size {self.group_size} > max_group "
                    f"{self.max_group}: the leader freezes at max_group, so "
                    "the surplus members of every scheduled group would "
                    "join-retry until the deadline and skip the round"
                )
        if self.average_interval_s > 0:
            if self.average_what != "params":
                raise ValueError(
                    "--average-interval-s requires --average-what params "
                    "(gradient rounds are per-step by definition)"
                )
            if self.averaging == "none":
                raise ValueError("--average-interval-s requires an averaging mode")
        if self.param_dtype:
            import jax.numpy as jnp

            try:
                dt = jnp.dtype(self.param_dtype)
            except TypeError:
                raise ValueError(
                    f"unknown --param-dtype {self.param_dtype!r}"
                ) from None
            if not jnp.issubdtype(dt, jnp.floating):
                # int8 would truncate weights at the cast and TypeError in
                # jax.grad at step 1 — fail here, not after transport binds.
                raise ValueError(
                    f"--param-dtype must be a floating dtype, got {dt}"
                )
        # Fail at config time, not per round: an unknown method (or kwarg)
        # would raise inside every averaging round, be swallowed by the
        # round-failure containment, and leave the volunteer training solo
        # forever with only warnings in the log (r4 advisor: the kwarg
        # validation below used to silently no-op on a typo'd method name —
        # the exact failure it existed to prevent).
        from distributedvolunteercomputing_tpu.ops import robust

        if self.method not in robust.AGGREGATORS:
            raise ValueError(
                f"unknown --method {self.method!r}; "
                f"known: {sorted(robust.AGGREGATORS)}"
            )
        if self.method_kw:
            import inspect

            fn = robust.AGGREGATORS[self.method]
            allowed = set(inspect.signature(fn).parameters) - {"stack", "weights"}
            unknown = set(self.method_kw) - allowed
            if unknown:
                raise ValueError(
                    f"--method-kw keys {sorted(unknown)} are not accepted "
                    f"by method {self.method!r} (accepts: {sorted(allowed)})"
                )
        if self.outer_optimizer != "none":
            if self.average_what != "params":
                raise ValueError("--outer-optimizer requires --average-what params")
            if self.averaging not in ("sync", "byzantine"):
                # The outer step's math assumes every member adopts a COMMON
                # aggregate each round (anchor - average is the swarm's
                # consensus delta). Gossip averages are pairwise — per-round
                # momentum would push each volunteer 1.33x past a DIFFERENT
                # partner's midpoint (lr 0.7, mu 0.9), amplifying
                # disagreement; butterfly degrades to subset averages under
                # churn with the same issue. Only the gather-style modes,
                # where all members adopt one aggregate, are validated
                # (experiments/outer_opt.py).
                raise ValueError(
                    "--outer-optimizer requires --averaging sync or byzantine "
                    "(gossip/butterfly rounds are pairwise/subset averages, "
                    "not a common aggregate — momentum over them amplifies "
                    "disagreement)"
                )
        if self.wire == "powersgd":
            # Fail at config time (same policy as topk below). Low-rank of a
            # parameter tree would truncate the model itself, and pairwise
            # protocols compound truncation per hop — but robust estimators
            # are FINE: reconstructions are dense vectors.
            if self.average_what != "grads":
                raise ValueError("wire='powersgd' requires --average-what grads")
            if self.averaging not in ("sync", "byzantine"):
                raise ValueError(
                    "wire='powersgd' requires --averaging sync or byzantine"
                )
            if self.powersgd_rank < 1:
                raise ValueError(
                    f"powersgd_rank must be >= 1, got {self.powersgd_rank}"
                )
        if self.wire == "sign":
            # Same config-time policy as topk/powersgd: 1-bit EF-signSGD is
            # a gradient compressor for gather-style protocols. Robust
            # estimators ARE allowed (dense ±scale reconstructions).
            if self.average_what != "grads":
                raise ValueError("wire='sign' requires --average-what grads")
            if self.averaging not in ("sync", "byzantine"):
                raise ValueError(
                    "wire='sign' requires --averaging sync or byzantine"
                )
        if self.wire == "topk":
            # Fail at config time, before the transport binds or membership
            # announces anything. Top-k of a parameter tree would zero most
            # of the model; pairwise protocols compound truncation per hop;
            # robust estimators over sparse supports aggregate to zero.
            if self.average_what != "grads":
                raise ValueError("wire='topk' requires --average-what grads")
            if self.averaging not in ("sync", "byzantine"):
                raise ValueError(
                    "wire='topk' requires --averaging sync or byzantine"
                )
            if self.topk_warmup_rounds < 0:
                raise ValueError(
                    f"topk_warmup_rounds must be >= 0, got {self.topk_warmup_rounds}"
                )
            if self.averaging == "byzantine":
                if self.method != "mean":
                    raise ValueError("wire='topk' requires --method mean")
                if not self.allow_unrobust_topk:
                    raise ValueError(
                        "--averaging byzantine --wire topk runs a plain "
                        "weighted mean (topk forces method='mean'), i.e. NO "
                        "Byzantine tolerance; use --averaging sync with "
                        "topk, or pass --allow-unrobust-topk if you want "
                        "byzantine's full-mesh/first-write-wins transport "
                        "properties without a robust estimator"
                    )


def _parse_addrs(spec: Optional[str]) -> list:
    """``host:port[,host:port...]`` -> [(host, port), ...]. Several
    coordinators = several DHT bootstrap nodes: a volunteer can join (and a
    rejoiner can re-bootstrap) as long as ANY of them is alive."""
    if not spec:
        return []
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        host, _, port = part.rpartition(":")
        if not host or not port.isdigit():
            raise ValueError(
                f"bad coordinator address {part!r} in {spec!r}: expected host:port"
            )
        out.append((host, int(port)))
    return out


class Volunteer:
    def __init__(
        self, cfg: VolunteerConfig,
        process_phases: Sequence[Tuple[str, float, float]] = (),
    ):
        """``process_phases``: what the entry script timed before any
        telemetry existed, as ``(name, wall time begun, wall time ended)``
        (run_volunteer.py: ``imports``, ``backend``, ``native``)."""
        self.cfg = cfg
        # Telemetry plane: one bundle per volunteer process, shared by the
        # averager, membership, resilience policy, and mesh codec. Built
        # first so every later subsystem can register into it; adopts the
        # ClockSync-corrected clock once one exists (start()).
        from distributedvolunteercomputing_tpu.swarm.telemetry import LIFECYCLE, Telemetry

        self.telemetry = Telemetry(
            peer_id=cfg.peer_id, enabled=cfg.telemetry,
            health_enabled=cfg.telemetry and cfg.health_probe,
            watchdog_enabled=cfg.telemetry and cfg.watchdog,
        )
        # The start-up tree (docs/OBSERVABILITY.md, "Span vocabulary
        # (start-up)"): its root runs from here until the first train step's
        # outputs are ready, where the trainer's waiter ends it. Never the
        # ambient span: its phases name it by hand (``Tracer.child``). None
        # with telemetry off.
        tracer = self.telemetry.tracer
        self._lifecycle = tracer.start(
            LIFECYCLE, LIFECYCLE, model=cfg.model, averaging=cfg.averaging
        )
        if process_phases:
            process = f"{LIFECYCLE}.process"
            began = min(t0 for _, t0, _ in process_phases)
            tracer.record(
                process, LIFECYCLE, began, max(t1 for _, _, t1 in process_phases) - began
            )
            for name, t0, t1 in process_phases:
                tracer.record(f"{process}.{name}", LIFECYCLE, t0, t1 - t0, parent=process)
        # What the traced step chose (which attention core, how a projection
        # was divided over tp, what a rematerialised layer kept, which grouped
        # matmul): the telemetry counts every note (none with telemetry off),
        # for as long as this holds the subscription (``run`` ends it).
        self._traced = traced.subscribe(self.telemetry.count_traced)
        self._metrics_server = None
        # Structured-log identity: with DVC_LOG_JSON=1 every line this
        # process emits carries who/where, join-able against traces.
        # First volunteer wins — the fields are process-global, and in a
        # multi-volunteer test process a later construction must not
        # relabel earlier volunteers' lines (round-scoped lines always
        # carry the exact peer via the averager's ambient log_context).
        from distributedvolunteercomputing_tpu.utils.logging import (
            current_log_context,
            set_log_fields,
        )

        if "peer" not in current_log_context():
            set_log_fields(peer=cfg.peer_id, zone=cfg.zone or None)
        self.transport = Transport(
            cfg.host, cfg.port, advertise_host=cfg.advertise_host,
            secret=read_secret(cfg.secret_file),
        )
        self.dht = DHTNode(self.transport)
        self.membership: Optional[SwarmMembership] = None
        self.control_plane = None  # ControlPlaneClient (failover routing)
        self.replica = None        # ControlPlaneReplica when host_replica
        self.clocksync = None
        self.failure_detector = None
        self.resilience_policy = None
        self.controller = None
        self.averager = None
        self.shard_manager = None  # ShardManager when zone_shards
        self.state_sync: Optional[StateSyncService] = None
        self.trainer: Optional[Trainer] = None
        self._stop = threading.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self.summary: Dict[str, float] = {}

    # -- averager bridge (called from the trainer thread) ------------------

    def _averager_callback(self, params, step: int):
        if self.averager is None or self._stop.is_set():
            return None
        # Fault-injection hook (SURVEY.md §5): DVC_CHAOS_CONTRIB_SCALE=<x>
        # turns this volunteer BYZANTINE — it contributes its real tree
        # scaled by x (well-formed frames, garbage values; the case CRCs
        # can't catch and robust aggregation exists for). Test-only; unset
        # in production.
        chaos_scale = float(os.environ.get("DVC_CHAOS_CONTRIB_SCALE", "0") or 0.0)
        if chaos_scale:
            import numpy as np

            params = jax.tree_util.tree_map(
                lambda x: np.asarray(x, np.float32) * chaos_scale, params
            )
        # Weight = samples behind this contribution: one batch for a
        # gradient round; for a parameter round, the trainer's actual
        # steps-since-last-merge (== average_every on the happy step-cadence
        # path, more after failed rounds, and the per-volunteer window
        # progress under --average-interval-s — heterogeneous peers weigh
        # by what they really computed).
        if self.cfg.average_what == "grads":
            per_round = 1
        else:
            per_round = max(
                1,
                getattr(self.trainer, "steps_since_merge", self.cfg.average_every),
            )
        samples_since = self.cfg.batch_size * per_round
        fut = asyncio.run_coroutine_threadsafe(
            self.averager.average(params, round_no=step, weight=float(samples_since)),
            self._loop,
        )
        try:
            return fut.result(timeout=self.cfg.join_timeout + self.cfg.gather_timeout + 15.0)
        except Exception as e:
            log.warning("averaging at step %d failed: %s", step, errstr(e))
            return None
        finally:
            # The round's key beside the result: the train loop files its
            # launch and merge spans under it (one round, one trace).
            self.trainer.round_trace = self.averager.last_trace

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        from distributedvolunteercomputing_tpu.utils.asyncio_debug import maybe_enable_from_env

        # DVC_ASYNC_DEBUG=1: loop stall/race detectors (stopped at teardown)
        self._loop_monitor = maybe_enable_from_env()
        tracer = self.telemetry.tracer
        with tracer.child(self._lifecycle, "lifecycle.net") as net:
            await self.transport.start()
            # Debug/collection surface: telemetry.scrape / telemetry.trace /
            # telemetry.flight / telemetry.prom answer on this volunteer's
            # transport (operators and experiments/trace_report.py dial them
            # directly).
            self.telemetry.register_rpcs(self.transport)
            if self.cfg.metrics_port:
                # Local Prometheus endpoint: any stock scraper can watch this
                # volunteer without the coordinator (or the swarm transport).
                from distributedvolunteercomputing_tpu.swarm.telemetry import (
                    MetricsHTTPServer,
                )

                # Loopback ONLY: the swarm transport binds cfg.host (often
                # 0.0.0.0) with MAC-covered frames, but this endpoint is
                # plain unauthenticated HTTP serving the full registry — the
                # documented contract is a LOCAL scrape shim, so it must not
                # ride the volunteer's public bind address.
                self._metrics_server = MetricsHTTPServer(
                    self.telemetry, "127.0.0.1", self.cfg.metrics_port
                )
                await self._metrics_server.start()
            bootstrap = _parse_addrs(self.cfg.coordinator) or None
            await self.dht.start(bootstrap=bootstrap)
            from distributedvolunteercomputing_tpu.swarm.control_plane import (
                ControlPlaneClient,
                ControlPlaneReplica,
            )

            # Control-plane failover client: discovers the elected replica set
            # from DHT soft state and routes this volunteer's batched
            # heartbeat/report traffic to its key-range shard owner, failing
            # over on conn failure (fast-fail + bounded AIMD backoff). Always
            # constructed — it costs nothing until a replica answers, and the
            # direct DHT path remains the fallback every beat.
            self.control_plane = ControlPlaneClient(
                self.transport, self.dht, self.cfg.peer_id
            )
            if self.cfg.host_replica:
                # This volunteer is an election candidate for the replicated
                # control plane: it serves status/exchange traffic and owns a
                # key range when elected into the active set.
                self.replica = ControlPlaneReplica(
                    self.transport, self.dht, telemetry=self.telemetry
                )
                await self.replica.start()
            self._build_resilience_layer()
            extra_info = {
                "model": self.cfg.model,
                # Full averaging namespace (model/average_what): gossip picks
                # partners from membership records (no rendezvous key), so the
                # record must carry the same string the averagers namespace
                # their rounds by — a params-mode peer must never gossip with
                # a grads-mode peer on the same model.
                "avg_ns": f"{self.cfg.model}/{self.cfg.average_what}",
            }
            if self.cfg.zone:
                # Locality advertisement for the hierarchical schedule; absent
                # on unzoned volunteers so mixed-version swarms degrade to
                # flat scheduling instead of treating "" as a real zone name.
                extra_info["zone"] = self.cfg.zone
            self.membership = SwarmMembership(
                self.dht, self.cfg.peer_id, ttl=self.cfg.heartbeat_ttl,
                failure_detector=self.failure_detector,
                extra_info=extra_info,
                # Measured up/down bandwidth rides every heartbeat (refreshed
                # from the transport's bulk-transfer throughput EWMAs; stale
                # estimates age out to absent fields): the input to
                # bandwidth-weighted leader election.
                bandwidth_source=self.transport.bandwidth_advertisement,
                # Batched control plane: announce + metrics report + peers
                # snapshot coalesce into one cp.exchange per heartbeat interval
                # while any replica is reachable (direct DHT fallback per beat).
                control_plane=self.control_plane,
                report_source=self._build_report,
                telemetry=self.telemetry,
            )
            await self.membership.join()
            if self.cfg.average_interval_s > 0:
                # Wall-cadence rendezvous no longer assumes NTP: peer-to-peer
                # clock-offset estimation corrects this volunteer's boundary
                # clock onto swarm-consensus time (swarm/clocksync.py).
                # DVC_CLOCK_SKEW_S injects artificial skew so the e2e suite can
                # prove rendezvous under multi-second skew.
                from distributedvolunteercomputing_tpu.swarm.clocksync import ClockSync

                skew = float(os.environ.get("DVC_CLOCK_SKEW_S", "0") or "0")
                clock = (lambda: time.time() + skew) if skew else time.time
                self.clocksync = ClockSync(self.transport, self.membership, clock=clock)
                # First estimate immediately: the first boundary this volunteer
                # arms must already be on swarm time.
                await self.clocksync.estimate()
                self.clocksync.start(interval_s=max(self.cfg.heartbeat_ttl, 15.0))
                # Span timestamps align to swarm-consensus time: cross-volunteer
                # traces stitch even when volunteer clocks are skewed.
                self.telemetry.set_clock(self.clocksync.now)
            if net is not None:
                # Who was there at the join (the view a join exchange left, or
                # one DHT read). Advisory: a failed read must not fail a start.
                try:
                    peers = await self.membership.alive_peers(
                        include_self=False, max_age=self.cfg.heartbeat_ttl
                    )
                    net.attrs["peers"] = len(peers)
                except Exception as e:  # noqa: BLE001
                    log.debug("peers at join not read: %s", errstr(e))
        with tracer.child(self._lifecycle, "lifecycle.model"):
            if self.cfg.averaging != "none":
                kw = dict(
                    min_group=self.cfg.min_group,
                    max_group=self.cfg.max_group,
                    join_timeout=self.cfg.join_timeout,
                    gather_timeout=self.cfg.gather_timeout,
                    wire=self.cfg.wire,
                    topk_frac=self.cfg.topk_frac,
                    topk_warmup_rounds=self.cfg.topk_warmup_rounds,
                    powersgd_rank=self.cfg.powersgd_rank,
                    adaptive_timeout=self.cfg.adaptive_timeout,
                    # Deadline-bounded rounds: leaders stamp clock()+budget into
                    # the begin on the consensus clock when one exists (wall-
                    # cadence swarms), else local wall time — the same clock the
                    # whole group's members compare the deadline against.
                    clock=self.clocksync.now if self.clocksync is not None else None,
                    round_deadline_s=self.cfg.round_deadline_s or None,
                    resilience=self.resilience_policy,
                    failure_detector=self.failure_detector,
                    # Closed-loop controller (None under --no-adapt / without
                    # --resilience): the averager is both its evidence feed
                    # and its actuator.
                    controller=self.controller,
                    # Matchmaking rendezvous reads ride the replicated control
                    # plane's micro-cache when a replica answers (direct DHT
                    # fallback otherwise).
                    control_plane=self.control_plane,
                    # Shared telemetry bundle: round spans, the unified metrics
                    # registry, and the flight recorder all live here.
                    telemetry=self.telemetry,
                    # Tail-optimal hedged recovery (docs/PERFORMANCE.md):
                    # soft-deadline re-requests for predicted-late tile ranges
                    # when this node leads a streaming round, plus the optional
                    # last-k% summand redundancy ring.
                    hedge=self.cfg.hedge,
                    tail_redundancy_frac=self.cfg.tail_redundancy_frac,
                )
                if self.cfg.group_size:
                    from distributedvolunteercomputing_tpu.swarm.matchmaking import (
                        GroupSchedule,
                    )

                    # Rotation rides the consensus clock when one exists: every
                    # member of a prospective group must land in the same
                    # window or they rendezvous under different keys.
                    kw["group_schedule"] = GroupSchedule(
                        target_size=self.cfg.group_size,
                        rotation_s=self.cfg.group_rotation_s
                        or (self.cfg.average_interval_s or 15.0),
                        clock=self.clocksync.now
                        if self.clocksync is not None
                        else time.time,
                        min_size=self.cfg.min_group,
                        cross_zone_every_k=self.cfg.cross_zone_every_k,
                    )
                if self.cfg.averaging == "byzantine" and (
                    self.cfg.method != "mean" or self.cfg.wire == "topk"
                ):
                    # Passing "mean" explicitly matters for topk: without it the
                    # ByzantineAverager defaults to trimmed_mean, which the topk
                    # wire (validated in __post_init__) must not run under.
                    kw["method"] = self.cfg.method
                if self.cfg.method_kw:
                    kw["method_kw"] = dict(self.cfg.method_kw)
                # Namespace rounds by model AND by what is averaged: a grads-mode
                # peer must never rendezvous with a params-mode peer on the same
                # model — averaging a gradient tree against a parameter tree
                # would silently destroy both.
                kw["namespace"] = f"{self.cfg.model}/{self.cfg.average_what}"
                self.averager = make_averager(
                    self.cfg.averaging, self.transport, self.dht, self.membership, **kw
                )
            bundle = get_model(self.cfg.model, **self.cfg.model_overrides)
            on_step = None
            if self.cfg.checkpoint_dir and self.cfg.checkpoint_every > 0:
                from distributedvolunteercomputing_tpu.training.checkpoint import save_async

                ckpt_dir, every = self.cfg.checkpoint_dir, self.cfg.checkpoint_every

                def on_step(trainer, step_no):
                    # Periodic snapshot: a kill -9 between saves loses at most
                    # checkpoint_every steps, not the whole run. Async: the D2H
                    # copy happens here, the file write on a background thread —
                    # the device never idles on disk I/O.
                    if step_no % every == 0:
                        save_async(trainer, ckpt_dir)

            # Heterogeneity injection (test/experiment hook, like
            # DVC_CHAOS_CONTRIB_SCALE below): DVC_STEP_DELAY_MS=<x> slows THIS
            # volunteer's step rate by x ms/step — on a shared localhost core,
            # batch-size spreads don't produce real step-rate skew (per-step
            # overhead dominates), so heterogeneous-cadence experiments need an
            # explicit clock. Unset in production.
            delay_ms = float(os.environ.get("DVC_STEP_DELAY_MS", "0") or 0.0)
            if delay_ms > 0:
                prev_on_step = on_step

                def on_step(trainer, step_no, _prev=prev_on_step):  # noqa: F811
                    time.sleep(delay_ms / 1e3)
                    if _prev is not None:
                        _prev(trainer, step_no)

            data = None
            eval_data = None
            if self.cfg.data_path:
                import zlib

                from distributedvolunteercomputing_tpu.training.data import npz_batch_iter

                # Seeded per-peer so volunteers shard the shuffle order, not the
                # data: every volunteer sees the full file in a different order.
                # crc32, not hash(): PYTHONHASHSEED randomization would make the
                # per-peer order non-reproducible across restarts.
                data_seed = zlib.crc32(self.cfg.peer_id.encode()) & 0x7FFFFFFF
                data = npz_batch_iter(self.cfg.data_path, self.cfg.batch_size, seed=data_seed)
                if self.cfg.eval_every:
                    # Independent shuffled stream over the same file: eval draws
                    # never perturb the training order (matches the synthetic
                    # path's separate-rng held-out semantics).
                    eval_data = npz_batch_iter(
                        self.cfg.data_path, self.cfg.batch_size, seed=data_seed ^ 0x5EED
                    )
            mesh = None
            if self.cfg.mesh:
                from distributedvolunteercomputing_tpu.parallel.mesh import (
                    make_mesh,
                    parse_mesh_spec,
                )

                mesh = make_mesh(**parse_mesh_spec(self.cfg.mesh))
            # Build THIS volunteer's swarm data path (ops.mesh_codec: the bf16
            # wire codec, PowerSGD matmuls and the leader's tile folds on the
            # local device mesh where that is TPU silicon, host numpy elsewhere)
            # now that the local mesh exists. The averager resolves the process
            # default lazily, so configuring here covers the averager built
            # earlier. Surfaced in stats()["mesh_codec"]; degrades to host on
            # slice failure.
            from distributedvolunteercomputing_tpu.ops import mesh_codec as mesh_codec_mod

            codec = mesh_codec_mod.configure(mesh=mesh)
            # Slice-loss degrades land in this volunteer's flight recorder, the
            # codec's device ops in its span ring.
            codec.recorder = self.telemetry.recorder
            codec.tracer = self.telemetry.tracer
            chosen = codec.stats()
            log.info(
                "swarm data path: %s backend, pallas %s, collective %s (mesh=%s)",
                codec.backend, chosen["pallas"], chosen["collective"],
                self.cfg.mesh or "single-device",
            )
        self.trainer = Trainer(
            bundle,
            data=data,
            mesh=mesh,
            fsdp=self.cfg.fsdp,
            seq_sharded=self.cfg.seq_sharded,
            sp_impl=self.cfg.sp_impl,
            batch_size=self.cfg.batch_size,
            optimizer=self.cfg.optimizer,
            lr=self.cfg.lr,
            seed=self.cfg.seed,
            init_seed=self.cfg.init_seed,
            param_dtype=self.cfg.param_dtype,
            accum_steps=self.cfg.accum_steps,
            average_every=self.cfg.average_every,
            average_interval_s=self.cfg.average_interval_s,
            wall_clock=self.clocksync.now if self.clocksync is not None else None,
            steps_per_call=self.cfg.steps_per_call,
            # The checkpoint cadence lives inside on_step where chunk
            # sizing can't see it — declare it so scan chunks end there.
            # The step-delay injection hook also sleeps inside on_step, so
            # scan chunks would dilute it N-fold (and hide it from the
            # interval-cadence step-time EMA): a cadence of 1 forces
            # per-step chunks whenever the hook is active.
            chunk_cadences=(
                ((self.cfg.checkpoint_every,)
                 if self.cfg.checkpoint_dir and self.cfg.checkpoint_every > 0
                 else ())
                + ((1,) if delay_ms > 0 else ())
            ),
            averager=self._averager_callback if self.averager else None,
            average_what=self.cfg.average_what,
            overlap=self.cfg.overlap,
            max_staleness=self.cfg.max_staleness,
            metrics_path=self.cfg.metrics_path,
            volunteer_id=self.cfg.peer_id,
            total_steps=self.cfg.steps,
            warmup_steps=self.cfg.warmup_steps,
            on_step=on_step,
            eval_every=self.cfg.eval_every,
            eval_batches=self.cfg.eval_batches,
            eval_data=eval_data,
            outer_optimizer=self.cfg.outer_optimizer,
            outer_lr=self.cfg.outer_lr,
            outer_momentum=self.cfg.outer_momentum,
            tracer=tracer,
            lifecycle=self._lifecycle,
        )
        if self.averager is not None:
            # Checkpoint sidecars persist the averager's compressor state
            # (EF residual + PowerSGD warm Q) across preemption; the
            # checkpoint module reaches it through this handle.
            self.trainer._wire_averager = self.averager
        if self.cfg.checkpoint_dir:
            from distributedvolunteercomputing_tpu.training.checkpoint import maybe_restore

            with tracer.child(self._lifecycle, "lifecycle.restore") as sp:
                restored = maybe_restore(self.trainer, self.cfg.checkpoint_dir)
                if sp is not None:
                    sp.attrs.update(
                        restored=restored, step=int(self.trainer.state.step),
                        # of the host snapshot the restore has just published
                        bytes=tree_size_bytes(self.trainer.host_snapshot()[1]) if restored else 0,
                    )
        if self.cfg.averaging != "none":
            # Peer-pull state sync: catch up to the swarm BEFORE the first
            # step, so a (re)joining volunteer's first averaging round
            # contributes swarm-current weights, not a cold init (or a
            # checkpoint from before a long absence).
            self.state_sync = StateSyncService(
                self.transport, self.dht, self.cfg.peer_id, namespace=self.cfg.model,
                # Serve state over the averaging wire's codec (bf16 halves,
                # q8 quarters a rejoin transfer); topk is grads-only, so
                # such volunteers serve plain f32 snapshots.
                wire=self.cfg.wire if self.cfg.wire in ("bf16", "q8") else "f32",
            )

            # State sync ships the bundle's SYNC SUBTREE (avg_select):
            # identity for full models, adapters-only for LoRA — the frozen
            # base is reconstructed bit-identically from init_seed, so
            # shipping it (~1000x the adapters at llama2_7b scale) would be
            # pure waste. The provider reads the trainer's HOST snapshot,
            # never the live TrainState: the jitted step donates its input
            # buffers, so touching state.params from this (asyncio) thread
            # mid-training would hit deleted arrays.
            def provider():
                step, params = self.trainer.host_snapshot()
                tree = bundle.avg_select(params)
                # Fault-injection hook (SURVEY.md §5), the state-sync twin of
                # DVC_CHAOS_CONTRIB_SCALE: "lie,scale" makes this volunteer a
                # BYZANTINE state provider — it announces/serves step+lie
                # (pull targets the freshest provider, so a big lie attracts
                # every rejoiner) and serves its real tree scaled by `scale`:
                # IN-RANGE garbage the puller's sanity guard cannot catch
                # (finite, bounded), the exact case where the rejoiner's only
                # defense is its next robust averaging round (state_sync.py
                # trust model). Test-only; unset in production.
                poison = os.environ.get("DVC_CHAOS_STATE_POISON")
                if poison:
                    import jax
                    import numpy as np

                    lie, scale = (float(x) for x in poison.split(","))
                    tree = jax.tree_util.tree_map(
                        lambda a: np.asarray(a, np.float32) * scale, tree
                    )
                    step = int(step + lie)
                return step, tree

            self.state_sync.set_provider(provider)
            with tracer.child(self._lifecycle, "lifecycle.state_sync") as sp:
                pulled = await self.state_sync.pull(
                    bundle.avg_select(self.trainer.state.params),
                    int(self.trainer.state.step),
                )
                if pulled is not None:
                    step, subtree = pulled
                    self.trainer.adopt_params(
                        bundle.avg_merge(self.trainer.state.params, subtree), step=step
                    )
                if sp is not None:
                    sp.attrs.update(
                        adopted=pulled is not None, step=int(self.trainer.state.step),
                        bytes=tree_size_bytes(pulled[1]) if pulled is not None else 0,
                    )
            await self.state_sync.announce()
            if self.cfg.averaging == "gossip" and self.cfg.average_what == "params":
                # Publish the post-state-sync params so exchanges from
                # faster peers succeed BEFORE our first averaging point —
                # without this, startup skew (one peer compiling while the
                # other trains) can burn both peers' entire runs against
                # each other's unpublished window (GossipAverager.publish).
                _, snap = self.trainer.host_snapshot()
                self.averager.publish(bundle.avg_select(snap))
        if self.cfg.zone_shards:
            # Zone-sharded training autopilot: this volunteer holds its
            # HRW shard(s) of the averaged subtree, advertises its primary
            # shard (the shard-scoped rendezvous reads it like a zone),
            # seeds the held shards from the post-state-sync params, and
            # runs the maintenance beat — churn triggers a fenced re-shard
            # + hedged recovery with no operator in the loop.
            import numpy as np

            from distributedvolunteercomputing_tpu.swarm.sharding import (
                ShardManager,
                shard_slice,
            )

            _, snap = self.trainer.host_snapshot()
            leaves = jax.tree_util.tree_leaves(bundle.avg_select(snap))
            flat = np.concatenate(
                [np.asarray(a, np.float32).ravel() for a in leaves]
            ) if leaves else np.zeros(0, np.float32)
            self.shard_manager = ShardManager(
                self.transport, self.dht, self.membership, self.cfg.peer_id,
                n_elems=flat.size, k=self.cfg.zone_shards,
                namespace=f"{self.cfg.model}/{self.cfg.average_what}",
                zone=self.cfg.zone,
                telemetry=self.telemetry,
                resilience=self.resilience_policy,
                controller=self.controller,
            )
            sm = self.shard_manager
            await sm.reshard(recover=False)
            for s in sm.owned():
                sm.store.put(s, shard_slice(flat, sm.ranges, s).copy())
            await sm.announce()
            if self.averager is not None:
                self.averager.shard_manager = sm
                self.telemetry.registry.source("sharding", sm.summary)
            sm.start_maintenance(
                interval_s=max(self.cfg.heartbeat_ttl / 3.0, 2.0)
            )
            log.info(
                "zone-sharded: k=%d zone=%s own=%s (%d/%d elems, gen %d)",
                sm.k, sm.zone, sm.owned(),
                sum(hi - lo for lo, hi in
                    (sm.ranges[s] for s in sm.owned())),
                sm.n_elems, sm.map.gen,
            )
        if self.telemetry.watchdog.enabled:
            # Watchdog probes over the surfaces built above: commit-rate,
            # mass-fraction, per-peer bandwidth EWMAs, control-plane beat
            # outcomes, quality flags (per-level round walls feed via the
            # tracer hook). Ticked once per report beat (_build_report).
            transport = self.transport

            def _peer_bandwidths(max_age_s: float = 120.0) -> Dict[str, float]:
                cutoff = time.monotonic() - max_age_s
                return {
                    f"{host}:{port}": float(st.bw_down_ewma)
                    for (host, port), st in transport._peer_stats.items()
                    if st.bw_down_ewma is not None and st.bw_down_t >= cutoff
                }

            self.telemetry.watchdog.wire_volunteer(
                averager=self.averager,
                control_plane=self.control_plane,
                health=self.telemetry.health,
                bandwidths=_peer_bandwidths,
            )
        log.info(
            "volunteer %s up on %s:%d (model=%s averaging=%s)",
            self.cfg.peer_id, *self.transport.addr, self.cfg.model, self.cfg.averaging,
        )

    def _build_resilience_layer(self) -> None:
        """Construct the resilience layer (phi detector + adaptive policy)
        and, with ``adapt`` on, the closed-loop controller over it.
        Synchronous and side-effect-free beyond the three attributes, so
        the --no-adapt plumbing tests can exercise it without a full
        start(). No-op without --resilience. Called from start() BEFORE
        membership so the very first observed peer records start the
        heartbeat distributions."""
        if not self.cfg.resilience:
            return
        from distributedvolunteercomputing_tpu.swarm.failure_detector import (
            PhiAccrualDetector,
        )
        from distributedvolunteercomputing_tpu.swarm.resilience import (
            ResiliencePolicy,
        )

        self.failure_detector = PhiAccrualDetector(
            threshold=self.cfg.phi_threshold,
            # Heartbeats arrive at the announce cadence (ttl/3, see
            # SwarmMembership.join): seed the bootstrap gap there so a
            # peer heard from once accrues suspicion on the right scale.
            bootstrap_s=max(self.cfg.heartbeat_ttl / 3.0, 1.0),
        )
        self.resilience_policy = ResiliencePolicy(
            max_deadline_s=self.cfg.gather_timeout,
            # A tight-LAN --gather-timeout below the stock 2s deadline
            # floor must not trip the ctor's range check at startup.
            min_deadline_s=min(2.0, float(self.cfg.gather_timeout)),
            initial_deadline_s=self.cfg.round_deadline_s or None,
            failure_detector=self.failure_detector,
            # Escalation/backoff transitions land in the flight recorder.
            recorder=self.telemetry.recorder,
        )
        if self.cfg.adapt and self.cfg.averaging in ("sync", "byzantine"):
            # Closed-loop controller over the policy + telemetry: the
            # averager feeds it evidence and applies its epoch-fenced
            # decisions. Round-structured gather modes only — gossip has
            # no rounds to fence a decision against.
            from distributedvolunteercomputing_tpu.swarm.controller import (
                SwarmController,
            )

            self.controller = SwarmController(
                policy=self.resilience_policy,
                telemetry=self.telemetry,
            )

    def _build_report(self) -> dict:
        """This volunteer's metrics report (the coord.report payload).
        Piggybacked on every batched control-plane exchange by the
        membership heartbeat loop, and sent standalone by the legacy
        report loop while no replica is reachable. May raise when the
        trainer's buffers are donated mid-step — callers skip that report
        rather than die."""
        report = {
            "peer": self.cfg.peer_id,
            "step": int(self.trainer.state.step) if self.trainer else 0,
            "samples_per_sec": self.trainer.metrics.samples_per_sec()
            if self.trainer
            else 0.0,
            **{k: v for k, v in self.summary.items()},
        }
        if self.averager is not None and self.averager._agg_gauges:
            # Live leader-aggregation pipeline gauges (peak bytes
            # held, early/deadline tiles, busy fraction) — reported
            # mid-run so coord.status sees them before the final
            # summary lands.
            report["aggregation"] = dict(self.averager._agg_gauges)
        if self.averager is not None:
            # On-mesh data-path backend + degrade evidence: a slice
            # failure mid-run shows up in coord.status as
            # backend=host/configured=mesh while training continues.
            report["mesh_codec"] = self.averager.mesh_codec.stats()
        if self.telemetry.enabled:
            # Compact telemetry summary (schema version, per-span count/sum
            # pairs, flight-recorder high-water): rides the batched
            # cp.exchange beat via report_source and is rolled up by the
            # control-plane replicas into coord.status["telemetry"].
            report["telemetry"] = self.telemetry.summary()
        wd = self.telemetry.watchdog
        if wd.enabled:
            # One watchdog evaluation pass per report beat (the probes
            # sample commit counters, mass fractions, bandwidth EWMAs,
            # beat outcomes), then the compact firing set rides the same
            # batched cp.exchange the rest of the report does. Absent
            # entirely — no alert bytes on the heartbeat — under
            # --no-watchdog / --no-telemetry.
            wd.tick()
            summary = wd.summary()
            if summary is not None:
                report["watchdog"] = summary
        if self.controller is not None:
            # Closed-loop controller rollup (current policy per level /
            # zone-pair, last transition + reason, transitions/hour):
            # rides the batched beat; replicas roll it into
            # coord.status["controller"]. Absent entirely — no controller
            # bytes on the heartbeat — under --no-adapt (the
            # --no-health-probe pattern).
            report["controller"] = self.controller.summary()
        health = self.telemetry.health.summary()
        if health is not None:
            # Training-health summary (post-round parameter sketch, mass
            # accounting, per-peer quality, codec distortion): rides the
            # same batched beat; replicas roll it into
            # coord.status["health"]. None — and therefore absent, no
            # sketch bytes on the heartbeat — under --no-health-probe.
            report["health"] = health
        if (
            self.averager is not None
            and getattr(self.averager, "group_schedule", None) is not None
        ):
            # Multi-group schedule gauges (current rotation/group,
            # per-group round counters): coord.status rolls these
            # up per group swarm-wide instead of silently averaging
            # across groups.
            report["groups"] = self.averager.group_stats()
        sm = self.shard_manager or getattr(self.averager, "shard_manager", None)
        if sm is not None:
            # Zone-sharded training gauges (map generation, owned/missing
            # shards, recovery latency window): the watchdog's
            # shard_recovery_latency SLO reads this section off the
            # merged fleet view — absent entirely on unsharded swarms.
            report["sharding"] = sm.summary()
        failover_stats = getattr(self.averager, "failover_stats", None)
        if failover_stats is not None:
            fo = failover_stats()
            if (
                fo["leaders_deposed"]
                or fo["rounds_recovered"]
                or fo["recoveries_failed"]
            ):
                # Leader-failover gauges (depositions, recovered
                # rounds, recovery latency): reported mid-run —
                # recovery is exactly the event an operator wants
                # to see from coord.status while it happens.
                report["failover"] = fo
        return report

    async def _report_loop(self) -> None:
        caddrs = _parse_addrs(self.cfg.coordinator)
        caddr = caddrs[0] if caddrs else None
        while not self._stop.is_set():
            await asyncio.sleep(5.0)
            if self.state_sync is not None:
                try:
                    # Re-announce our step so rejoining peers can find the
                    # freshest provider (TTL'd, like heartbeats).
                    await self.state_sync.announce()
                except Exception:
                    pass
            if caddr is None:
                continue
            if self.membership is not None and self.membership.last_beat_batched:
                # The LAST heartbeat went through a replica carrying our
                # report — a standalone coord.report here would double the
                # message cost back up. Gated on the last beat, not the
                # lifetime counter: a volunteer that loses the batched path
                # (asymmetric reachability, replica churn) must resume
                # legacy reports or its metrics age out of coord.status.
                continue
            try:
                # Built INSIDE the try: reading trainer.state from this
                # thread can hit a donated (deleted) buffer mid-step on a
                # real accelerator — that must skip one report, not kill
                # the loop (which also carries the announce() refresh).
                report = self._build_report()
                # Fast-fail dial: a dead coordinator costs the connect
                # budget, never the generic call timeout (the heartbeat
                # loop has its own AIMD-backed fast path; this legacy loop
                # must not lag behind it).
                await self.transport.call(
                    caddr, "coord.report", report, timeout=5.0,
                    connect_timeout=1.5,
                )
            except Exception:
                # Coordinator reachability is not correctness-critical; with
                # several bootstrap coordinators, rotate to the next one so
                # metrics survive a coordinator death.
                if len(caddrs) > 1:
                    caddrs = caddrs[1:] + caddrs[:1]
                    caddr = caddrs[0]

    def _train_blocking(self) -> Dict[str, float]:
        assert self.trainer is not None
        result = self.trainer.run(
            steps=self.cfg.steps,
            target_loss=self.cfg.target_loss,
            target_mode=self.cfg.target_mode,
            stop_flag=self._stop.is_set,
        )
        if self.cfg.checkpoint_dir:
            from distributedvolunteercomputing_tpu.training.checkpoint import (
                latest_step,
                save,
                wait_pending_saves,
            )

            # Final save is SYNCHRONOUS (preemption-safe), after draining any
            # in-flight periodic write so it can't race an older write to the
            # same path. Skip it only when the drained async save covers the
            # current state EXACTLY — same step AND same mutation count; the
            # end-of-run overlap drain can merge averaged params at an
            # unchanged step number, and that merge must not be lost.
            drained = wait_pending_saves(self.trainer)
            # Evaluate AFTER the drain: latest_step only reflects the
            # in-flight write once it has landed.
            current_id = (
                int(self.trainer.state.step),
                getattr(self.trainer, "mutation_counter", 0),
            )
            already_saved = (
                getattr(self.trainer, "_ckpt_snapshot_id", None) == current_id
                and latest_step(self.cfg.checkpoint_dir) == current_id[0]
            )
            if drained and not already_saved:
                save(self.trainer, self.cfg.checkpoint_dir)
        return result

    async def run(self) -> Dict[str, float]:
        await self.start()
        report_task = asyncio.create_task(self._report_loop())
        try:
            self.summary = await asyncio.to_thread(self._train_blocking)
            # What this result ran on: the device as jax reports it, what
            # was compiled (and whether the persistent cache served it),
            # and the device allocator's high-water mark where reported.
            self.summary["device"] = self.trainer.device
            self.summary["compile"] = self.trainer.compile_summary()
            # How long this start took to be useful (``ready_s``: Volunteer()
            # to the first finished step) and where it went, phase by phase;
            # {} with telemetry off.
            self.summary["lifecycle"] = self.telemetry.lifecycle
            # What the traced step chose, by the telemetry's own keys
            # (``attention_core``, ``attention_layout``, ``qkv_projection``,
            # ``tp_streams``, ``remat_kept``; each empty with telemetry off).
            self.summary.update(self.telemetry.traced_summary())
            # What the chip waited, by the loop's own two stamps a step: for
            # the host (``late_s``), with the step already in its queue
            # (``held_s``), both as a share of the steps' wall time ({} with
            # telemetry off).
            self.summary["chip"] = self.telemetry.chip()
            moe = self.telemetry.moe()
            if moe:
                # a sparse-expert model: traced dispatches by grouped matmul,
                # fullest expert over the even share, rows dropped (0: dropless)
                self.summary["moe"] = moe
            self.summary["peak_bytes_in_use"] = (
                jax.local_devices()[0].memory_stats() or {}
            ).get("peak_bytes_in_use")
            if self.averager is not None:
                from distributedvolunteercomputing_tpu import native

                # Settled long before the end of a run: the first round
                # (or run_volunteer.py at startup) built or failed to.
                self.summary["native"] = (
                    "built" if native.ensure_built() else "numpy"
                )
                self.summary.update(self.averager.stats())
            # WAN accounting: every byte this volunteer moved over DCN
            # (averaging payloads dominate; DHT/heartbeat traffic is noise).
            # rpcs/connects expose the pooling win directly: pre-pool these
            # were equal (one dial per RPC); pooled, connects stays at
            # ~one-per-peer while rpcs keeps counting.
            if self.shard_manager is not None:
                # Zone-sharding outcome gauges on the done line: the e2e
                # kill matrix asserts recovery happened WITHOUT an epoch
                # restart from exactly these.
                sm = self.shard_manager
                self.summary["shard_gen"] = float(
                    sm.map.gen if sm.map is not None else -1
                )
                self.summary["shard_reshardings"] = float(sm.resharding_count)
                self.summary["shard_recoveries"] = float(sm.recoveries)
                self.summary["shard_recoveries_failed"] = float(
                    sm.recoveries_failed
                )
                self.summary["shard_missing"] = float(len(sm.missing()))
            self.summary["wan_bytes_sent"] = self.transport.bytes_sent
            self.summary["wan_bytes_received"] = self.transport.bytes_received
            self.summary["wan_rpcs"] = self.transport.rpcs_sent
            self.summary["wan_connects"] = self.transport.connects
            return self.summary
        finally:
            self._stop.set()
            report_task.cancel()
            self._traced.close()
            if self.clocksync is not None:
                self.clocksync.stop()
            if self.shard_manager is not None:
                try:
                    await self.shard_manager.stop()
                except Exception:
                    pass
            try:
                await self.membership.leave()
            except Exception:
                pass
            if self.replica is not None:
                try:
                    # Graceful exit of a replica-hosting volunteer: the
                    # retiring tombstone makes the rest of the swarm
                    # re-resolve the active set immediately.
                    await self.replica.retire(grace=0.0)
                except Exception:
                    pass
            await self.dht.stop()
            if self._metrics_server is not None:
                try:
                    await self._metrics_server.close()
                except Exception:
                    pass
            if getattr(self, "_loop_monitor", None) is not None:
                await self._loop_monitor.stop()
            await self.transport.close()

    def install_signal_handlers(self) -> None:
        """SIGTERM == TPU-VM preemption notice; SIGINT == operator stop."""

        def _on_signal(signum, frame):
            log.info("signal %d: stopping after current step (preemption-safe)", signum)
            self._stop.set()

        signal.signal(signal.SIGTERM, _on_signal)
        signal.signal(signal.SIGINT, _on_signal)


def run_volunteer(
    cfg: VolunteerConfig, process_phases: Sequence[Tuple[str, float, float]] = ()
) -> Dict[str, float]:
    vol = Volunteer(cfg, process_phases)
    vol.install_signal_handlers()
    return asyncio.run(vol.run())
