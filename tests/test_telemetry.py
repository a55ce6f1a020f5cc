"""Telemetry-plane tests: unified metrics registry, cross-volunteer round
tracing (span vocabulary + frame-meta trace propagation), flight recorder,
stats() snapshot semantics, the versioned coord.status telemetry schema,
and the telemetry overhead smoke.

In-process swarms over real localhost TCP (the test_failover.py harness
shape); the multi-process collection path is exercised by
experiments/trace_report.py.
"""

import asyncio
import json
import logging

import numpy as np
import pytest

from distributedvolunteercomputing_tpu.swarm import telemetry as T
from distributedvolunteercomputing_tpu.swarm.averager import SyncAverager
from distributedvolunteercomputing_tpu.swarm.control_plane import ControlPlaneReplica
from distributedvolunteercomputing_tpu.swarm.dht import DHTNode
from distributedvolunteercomputing_tpu.swarm.membership import SwarmMembership
from distributedvolunteercomputing_tpu.swarm.resilience import ResiliencePolicy
from distributedvolunteercomputing_tpu.swarm.transport import RPCError, Transport
from distributedvolunteercomputing_tpu.utils.logging import (
    JsonFormatter,
    current_log_context,
    log_context,
    set_log_fields,
)

pytestmark = pytest.mark.telemetry


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


def make_tree(value: float, elems: int = 4096):
    return {"w": np.full((elems,), value, np.float32)}


async def spawn(n, *, telemetry_enabled=True, **avg_kw):
    vols = []
    boot = None
    kw = {"join_timeout": 6.0, "gather_timeout": 8.0, "min_group": 2, **avg_kw}
    for i in range(n):
        t = Transport()
        dht = DHTNode(t)
        await dht.start(bootstrap=[boot] if boot else None)
        if boot is None:
            boot = t.addr
        mem = SwarmMembership(dht, f"vol{i}", ttl=10.0)
        await mem.join()
        tele = T.Telemetry(peer_id=f"vol{i}", enabled=telemetry_enabled)
        tele.register_rpcs(t)
        avg = SyncAverager(t, dht, mem, telemetry=tele, **kw)
        vols.append({"t": t, "dht": dht, "mem": mem, "avg": avg, "tele": tele})
    return vols


async def teardown(vols):
    for v in vols:
        try:
            await v["mem"].leave()
        except Exception:
            pass
        try:
            await v["t"].close()
        except Exception:
            pass


async def run_rounds(vols, n_rounds, elems=4096, start=0):
    committed = 0
    for r in range(start, start + n_rounds):
        res = await asyncio.gather(
            *(
                v["avg"].average(make_tree(float(i), elems), round_no=r)
                for i, v in enumerate(vols)
            ),
            return_exceptions=True,
        )
        if all(x is not None and not isinstance(x, BaseException) for x in res):
            committed += 1
    return committed


# -- registry ---------------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = T.MetricsRegistry()
        c = reg.counter("c")
        c.inc()
        c.inc(2.0, rpc="sync.fetch")
        assert c.value() == 1.0
        assert c.value(rpc="sync.fetch") == 2.0
        g = reg.gauge("g")
        g.set(3.5)
        g.set(1.0, zone="a")
        assert g.value() == 3.5
        h = reg.histogram("h")
        h.observe(0.0015)
        h.observe(0.01)
        h.observe(1e9)  # lands in the +inf bucket
        snap = h.snapshot()
        assert snap["count"] == 3
        assert snap["buckets"][-1] == 1  # overflow bucket
        assert sum(snap["buckets"]) == 3

    def test_metric_type_conflict_refused(self):
        reg = T.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")
        with pytest.raises(ValueError):
            reg.gauge_fn("x", lambda: 1.0)
        # A set()-style gauge pre-registered under the name adopts the
        # callback instead of silently never reporting it.
        reg.gauge("y").set(1.0)
        g = reg.gauge_fn("y", lambda: 42.0)
        assert g.value() == 42.0

    def test_scrape_shape_and_sources(self):
        reg = T.MetricsRegistry()
        reg.counter("swarm.c").inc(4)
        reg.gauge_fn("swarm.live", lambda: 7.0)
        reg.source("legacy", lambda: {"a": 1, "nested": {"b": 2.5, "skip": "str"}})
        out = reg.scrape()
        assert out["schema_version"] == T.TELEMETRY_SCHEMA_VERSION
        m = out["metrics"]
        assert m["swarm.c"]["type"] == "counter"
        assert m["swarm.live"]["values"][0]["value"] == 7.0
        # Source dicts flatten numeric leaves into dotted gauges; non-
        # numeric leaves are skipped, not stringified.
        assert m["legacy.a"]["values"][0]["value"] == 1.0
        assert m["legacy.nested.b"]["values"][0]["value"] == 2.5
        assert "legacy.nested.skip" not in m

    def test_broken_source_does_not_fail_scrape(self):
        reg = T.MetricsRegistry()
        reg.source("bad", lambda: 1 / 0)
        reg.counter("ok").inc()
        out = reg.scrape()
        assert "ok" in out["metrics"]

    def test_membership_beat_metrics(self):
        """The heartbeat loop's control-traffic accounting re-registers
        into the unified registry (beats by path + per-beat message cost)."""

        async def main():
            t = Transport()
            dht = DHTNode(t)
            await dht.start(bootstrap=None)
            tele = T.Telemetry(peer_id="m0")
            mem = SwarmMembership(dht, "m0", ttl=10.0, telemetry=tele)
            await mem.join()
            msgs_seen = 0
            await mem._beat_once()
            msgs_seen += mem.msgs_last_beat
            await mem._beat_once()
            msgs_seen += mem.msgs_last_beat
            await mem.leave()
            await dht.stop()
            await t.close()
            return tele, msgs_seen

        tele, msgs_seen = run(main())
        ctr = tele.registry.counter("swarm.beats_total")
        assert ctr.value(path="direct") == 2
        msgs = tele.registry.counter("swarm.beat_msgs_total")
        # Exact agreement with the beat accounting (a solo node's stores
        # are local, so the count may legitimately be 0 here).
        assert msgs.value(path="direct") == float(msgs_seen)

    def test_rollup_status(self):
        tele = T.Telemetry(peer_id="p1")
        tele.tracer.record("round", "tr1", 0.0, 0.5)
        tele.tracer.record("fold", "tr1", 0.1, 0.3)
        reports = [
            {"peer": "p1", "telemetry": tele.summary()},
            {"peer": "p2", "telemetry": {"schema_version": 999}},  # wrong version
            {"peer": "p3"},  # no telemetry
        ]
        roll = T.rollup_status(reports)
        assert roll["schema_version"] == T.TELEMETRY_SCHEMA_VERSION
        assert roll["reporting"] == 1
        assert roll["spans"]["round"]["count"] == 1
        assert roll["spans"]["round"]["mean_s"] == pytest.approx(0.5)
        assert T.rollup_status([{"peer": "x"}]) is None


# -- tracing ----------------------------------------------------------------


class TestTracing:
    def test_trace_propagates_in_frame_meta(self):
        """The ambient trace id crosses the wire in the frame meta and is
        restored around the remote handler — no new RPCs, no args changes."""

        async def main():
            server = Transport()
            seen = []

            async def handler(args, payload):
                seen.append(T.current_trace())
                return {"ok": True}, b""

            server.register("t.probe", handler)
            await server.start()
            client = Transport()
            tele = T.Telemetry(peer_id="c")
            with tele.tracer.trace_scope("trace-xyz"):
                await client.call(server.addr, "t.probe", {}, b"")
            await client.call(server.addr, "t.probe", {}, b"")  # no ambient trace
            await client.close()
            await server.close()
            return seen

        seen = run(main())
        assert seen == ["trace-xyz", None]

    def test_span_vocabulary_and_cross_volunteer_stitch(self):
        """One committed round: every phase span present, all volunteers'
        spans share the round's trace id (the matchmaking epoch), the
        leader's handler-side fold.push stitches in via the frame meta,
        and the leader's sequential phases sum to ~the round wall."""

        async def main():
            vols = await spawn(3)
            try:
                committed = await run_rounds(vols, 1)
            finally:
                await teardown(vols)
            return vols, committed

        vols, committed = run(main())
        assert committed == 1
        spans = [s for v in vols for s in v["tele"].tracer.spans()]
        traces = {s["trace"] for s in spans}
        assert len(traces) == 1, f"one round must be one trace, got {traces}"
        by_peer = {}
        for s in spans:
            by_peer.setdefault(s["peer"], set()).add(s["name"])
        assert by_peer["vol0"] >= {"join", "arm", "encode", "fold", "commit", "round"}
        # fold.push on the leader proves the members' trace ids crossed in
        # the transport frame meta (the handler runs under their trace).
        assert "fold.push" in by_peer["vol0"]
        for member in ("vol1", "vol2"):
            assert by_peer[member] >= {"join", "encode", "wire", "fetch", "round"}
        # Critical path: the leader's phases are sequential by construction.
        lead = [s for s in spans if s["peer"] == "vol0"]
        root = next(s for s in lead if s["name"] == "round")
        assert root["attrs"]["ok"] is True
        phase_sum = sum(
            s["dur_s"] for s in lead
            if s["name"] in ("join", "arm", "encode", "fold", "commit")
        )
        assert phase_sum <= root["dur_s"] * 1.05
        assert phase_sum >= root["dur_s"] * 0.5, (
            f"phases {phase_sum:.4f}s vs wall {root['dur_s']:.4f}s: "
            "the vocabulary no longer covers the round"
        )
        # Span histogram lands in the registry (scrapeable without traces).
        summary = vols[0]["tele"].summary()
        assert summary["spans"]["round"]["count"] == 1

    def test_disabled_telemetry_records_nothing(self):
        async def main():
            vols = await spawn(2, telemetry_enabled=False)
            try:
                committed = await run_rounds(vols, 1)
            finally:
                await teardown(vols)
            return vols, committed

        vols, committed = run(main())
        assert committed == 1
        for v in vols:
            assert v["tele"].tracer.spans() == []
            assert v["tele"].recorder.dump() == []

    def test_span_ring_bounded(self):
        tr = T.Tracer(T.MetricsRegistry(), "p")
        for i in range(T.Tracer.MAX_SPANS + 100):
            tr.record("x", "t", 0.0, 0.001)
        assert len(tr.spans()) == T.Tracer.MAX_SPANS


# -- flight recorder --------------------------------------------------------


class TestFlightRecorder:
    def test_ring_bounded_and_filterable(self):
        rec = T.FlightRecorder(peer_id="p")
        for i in range(T.FlightRecorder.MAX_EVENTS + 50):
            rec.record("a" if i % 2 else "b", i=i)
        evs = rec.dump()
        assert len(evs) == T.FlightRecorder.MAX_EVENTS
        assert all(e["peer"] == "p" for e in evs)
        only_a = rec.dump(kinds=["a"])
        assert {e["kind"] for e in only_a} == {"a"}
        # seq is monotone across the ring (post-mortems need ordering).
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs)

    def test_deposition_and_recovery_events(self):
        """Leader killed mid-round: the survivors' flight recorders hold
        the deposition and the recovery outcome — the post-mortem a chaos
        verdict attaches."""

        async def main():
            vols = await spawn(3)

            async def die():
                await vols[0]["t"].close()
                raise RuntimeError("chaos: leader killed")

            vols[0]["avg"]._phase_hooks["mid_stream"] = die
            try:
                await asyncio.gather(
                    *(
                        v["avg"].average(make_tree(float(i)), round_no=1)
                        for i, v in enumerate(vols)
                    ),
                    return_exceptions=True,
                )
            finally:
                await teardown(vols)
            return vols

        vols = run(main())
        surv_events = [e for v in vols[1:] for e in v["tele"].recorder.dump()]
        kinds = {e["kind"] for e in surv_events}
        assert "leader_deposed" in kinds
        dep = next(e for e in surv_events if e["kind"] == "leader_deposed")
        assert dep["leader"] == "vol0"
        assert "round_recovered" in kinds or "recovery_failed" in kinds

    def test_fence_rejection_recorded(self):
        """A stale-generation fetch against an armed round is refused AND
        leaves a fence_rejected event + counter behind."""

        async def main():
            vols = await spawn(2)
            try:
                await run_rounds(vols, 1)
                leader = vols[0]["avg"]
                epoch = next(iter(leader._rounds))
                with pytest.raises(RPCError, match="fencing mismatch"):
                    await vols[1]["t"].call(
                        vols[0]["t"].addr, "sync.fetch",
                        {"epoch": epoch, "fence": 7}, timeout=10.0,
                    )
            finally:
                await teardown(vols)
            return vols

        vols = run(main())
        evs = vols[0]["tele"].recorder.dump(kinds=["fence_rejected"])
        assert evs and evs[-1]["rpc"] == "sync.fetch"
        assert evs[-1]["got_gen"] == 7
        ctr = vols[0]["tele"].registry.counter("swarm.fences_rejected_total")
        assert ctr.value(rpc="sync.fetch") >= 1

    def test_resilience_escalation_event(self):
        rec = T.FlightRecorder(peer_id="p")
        pol = ResiliencePolicy(escalate_rejections=2.0, recorder=rec)
        for _ in range(5):
            pol.record_rejection("byz")
        kinds = [e["kind"] for e in rec.dump()]
        assert "method_escalated" in kinds


# -- stats snapshot (satellite: staleness footgun) --------------------------


class TestStatsSnapshot:
    def test_stats_reference_frozen_under_concurrent_rounds(self):
        """A held stats() reference must NOT change while background
        rounds keep mutating the live gauges underneath (the pre-telemetry
        sub-dicts were returned by reference and mutated in place)."""

        async def main():
            vols = await spawn(3)
            try:
                await run_rounds(vols, 1)
                snap = vols[0]["avg"].stats()
                frozen = json.dumps(snap, sort_keys=True, default=str)
                await run_rounds(vols, 2, start=10)
                after = vols[0]["avg"].stats()
            finally:
                await teardown(vols)
            return snap, frozen, after

        snap, frozen, after = run(main())
        assert json.dumps(snap, sort_keys=True, default=str) == frozen, (
            "stats() snapshot mutated under a concurrent round"
        )
        # ... while the live surface did move on.
        assert after["rounds_ok"] > snap["rounds_ok"]
        assert after["transport"]["rpcs"] > snap["transport"]["rpcs"]


# -- coord.status schema (satellite) ----------------------------------------


def _check_types(schema, obj, path=""):
    for key, typ in schema.items():
        assert key in obj, f"missing documented key {path}{key}"
        val = obj[key]
        assert isinstance(val, typ), (
            f"{path}{key}: expected {typ.__name__}, got {type(val).__name__}"
        )


class TestStatusSchema:
    def test_status_telemetry_schema(self):
        """coord.status['telemetry'] carries every documented key, typed
        per the versioned schema — rollup drift breaks HERE, not on a
        dashboard."""

        async def main():
            t = Transport()
            dht = DHTNode(t)
            await dht.start(bootstrap=None)
            rep = ControlPlaneReplica(t, dht, rid="cp0", interval=0.5)
            await rep.start()
            try:
                tele = T.Telemetry(peer_id="v0")
                tele.tracer.record("round", "tr", 0.0, 0.25)
                tele.tracer.record("fold", "tr", 0.0, 0.1)
                tele.recorder.record("round_degraded", key="k")
                await rep._rpc_report(
                    {
                        "peer": "v0",
                        "samples_per_sec": 1.0,
                        "telemetry": tele.summary(),
                    },
                    b"",
                )
                status, _ = await rep._rpc_status({}, b"")
            finally:
                await rep.stop()
                await dht.stop()
                await t.close()
            return status

        status = run(main())
        roll = status["telemetry"]
        assert roll is not None
        _check_types(T.STATUS_TELEMETRY_SCHEMA, roll)
        assert roll["schema_version"] == T.TELEMETRY_SCHEMA_VERSION
        assert roll["reporting"] == 1
        for name, rec in roll["spans"].items():
            _check_types(T.STATUS_SPAN_SCHEMA, rec, path=f"spans.{name}.")
        assert roll["spans"]["round"]["count"] == 1
        assert roll["events_recorded_total"] == 1
        # per_peer carries the verbatim volunteer summary.
        assert roll["per_peer"]["v0"]["schema_version"] == T.TELEMETRY_SCHEMA_VERSION

    def test_status_telemetry_none_without_reports(self):
        async def main():
            t = Transport()
            dht = DHTNode(t)
            await dht.start(bootstrap=None)
            rep = ControlPlaneReplica(t, dht, rid="cp0", interval=0.5)
            await rep.start()
            try:
                status, _ = await rep._rpc_status({}, b"")
            finally:
                await rep.stop()
                await dht.stop()
                await t.close()
            return status

        status = run(main())
        assert status["telemetry"] is None


# -- structured logging (satellite) -----------------------------------------


class TestJsonLogging:
    def test_json_formatter_carries_context(self):
        set_log_fields(peer="v7", zone="dc-a")
        try:
            rec = logging.LogRecord(
                "swarm.test", logging.INFO, __file__, 1, "round %s done", ("r1",), None
            )
            with log_context(round_key="avg/sync/r1.g0", level="intra"):
                line = JsonFormatter().format(rec)
                ctx = current_log_context()
            out = json.loads(line)
        finally:
            set_log_fields(peer=None, zone=None)
        assert out["msg"] == "round r1 done"
        # Core record fields win a name collision: severity stays "level",
        # the colliding context field lands prefixed.
        assert out["level"] == "INFO"
        assert out["ctx_level"] == "intra"
        assert out["peer"] == "v7"
        assert out["zone"] == "dc-a"
        assert out["round_key"] == "avg/sync/r1.g0"
        assert ctx["round_key"] == "avg/sync/r1.g0"
        assert ctx["level"] == "intra"

    def test_round_binds_log_context(self):
        """The averaging round binds round_key/trace/level into the ambient
        log context, and it unwinds after the round."""

        async def main():
            vols = await spawn(2)
            seen = {}
            orig = vols[0]["avg"]._pack_and_compress

            async def probe(tree):
                seen.update(current_log_context())
                return await orig(tree)

            vols[0]["avg"]._pack_and_compress = probe
            try:
                committed = await run_rounds(vols, 1)
            finally:
                await teardown(vols)
            return seen, committed, current_log_context()

        seen, committed, after = run(main())
        assert committed == 1
        assert seen.get("round_key") == "avg/sync"
        assert seen.get("trace")
        assert seen.get("round_level") == "flat"
        assert "round_key" not in after

    def test_non_serializable_context_does_not_raise(self):
        rec = logging.LogRecord("x", logging.INFO, __file__, 1, "m", (), None)
        with log_context(weird=object()):
            line = JsonFormatter().format(rec)
        assert json.loads(line)["msg"] == "m"


# -- overhead smoke (satellite) ---------------------------------------------


class TestOverheadSmoke:
    """What telemetry costs, as counts that repeat exactly (a wall-clock
    gate on a shared CPU measured the machine, not the code)."""

    # Per sync round over the whole 3-peer swarm, bf16 wire on the mesh
    # codec: 86. The leader records join, arm, encode, fold, one fold.push
    # per member, commit, health and round (9) and 11 codec ops (35 spans);
    # a member join, encode, wire, fetch and round (5) and 4 codec ops (16).
    # A codec op is four spans (codec.op, .h2d, .run, .d2h), a dense fold
    # on a CPU device one (nothing to place).
    MAX_SPANS_PER_ROUND = 96

    @staticmethod
    async def _swarm(enabled):
        from distributedvolunteercomputing_tpu.ops.mesh_codec import MeshCodec

        vols = await spawn(3, telemetry_enabled=enabled, wire="bf16")
        for v in vols:
            v["avg"]._mesh_codec = MeshCodec(backend="mesh")
            v["avg"]._register_telemetry()
            v["hook"] = []
            v["tele"].tracer.on_record = v["hook"].append
        return vols

    def test_spans_and_observations_per_round_are_bounded(self):
        rounds = 3

        async def main():
            vols = await self._swarm(True)
            try:
                await run_rounds(vols, 1)  # warm-up: compiles, first arming
                for v in vols:
                    v["tele"].tracer.clear()
                    v["hook"].clear()
                    v["obs0"] = _span_observations(v["tele"])
                # a leader may skip a round on a loaded machine: a round
                # that does not commit records fewer spans, never more
                assert await run_rounds(vols, rounds, start=1) >= 1
            finally:
                await teardown(vols)
            return vols

        vols = run(main())
        spans = sum(len(v["tele"].tracer.spans()) for v in vols)
        assert 0 < spans <= rounds * self.MAX_SPANS_PER_ROUND, spans
        for v in vols:
            n = len(v["tele"].tracer.spans())
            # one ring append, one histogram observation and one hook call
            # per ended span, and nothing else
            assert _span_observations(v["tele"]) - v["obs0"] == n == len(v["hook"])
        names = {s["name"] for v in vols for s in v["tele"].tracer.spans()}
        assert {"round", "encode", "wire", "fold", "codec.op", "codec.run"} <= names

    def test_disabled_telemetry_touches_nothing(self, monkeypatch):
        opened = []
        monkeypatch.setattr(T, "annotation", lambda name: opened.append(name))

        async def main():
            vols = await self._swarm(False)
            try:
                await run_rounds(vols, 3)
            finally:
                await teardown(vols)
            return vols

        for v in run(main()):
            assert v["avg"].mesh_codec.stats()["ops_mesh"] > 0  # the phases' sites ran
            assert v["tele"].tracer.spans() == [] and v["hook"] == []
            assert _span_observations(v["tele"]) == 0
            assert v["tele"].recorder.dump() == []
        assert opened == []


    @pytest.mark.parametrize("tracer", [None, "disabled"])
    def test_a_trainer_without_a_live_tracer_stamps_nothing_and_starts_no_watcher(self, tracer, monkeypatch):
        """The timeline of the chip's queue is on where the trainer has an
        enabled tracer and off where it has none: no watcher thread, no queue,
        no list of phases, not one read of the stamps' clock."""
        import threading

        from distributedvolunteercomputing_tpu.models import get_model
        from distributedvolunteercomputing_tpu.training import trainer as trainer_mod

        started, stamps = [], []
        real_thread = threading.Thread

        def counted(*a, **kw):
            started.append(kw.get("name"))
            return real_thread(*a, **kw)

        monkeypatch.setattr(threading, "Thread", counted)
        monkeypatch.setattr(T.ChipTimeline, "step", lambda self, *a: stamps.append(a))
        monkeypatch.setattr(trainer_mod.Trainer, "_timed", lambda self, name: stamps.append(name))
        if tracer == "disabled":
            tracer = T.Tracer(registry=T.MetricsRegistry(), peer_id="off", enabled=False)
        tr = trainer_mod.Trainer(get_model("mnist_mlp"), batch_size=8, optimizer="sgd", lr=1e-2,
                                 tracer=tracer, on_step=lambda trainer, step: None)
        tr.run(steps=12, log_every=4)
        assert tr._timeline is None and tr._phases is None and tr._stamps is None
        assert trainer_mod.WATCHER not in started and stamps == []
        if tracer is not None:
            assert tracer.chip is None and tracer.spans() == []
            assert "swarm.step_seconds" not in tracer.registry.scrape()["metrics"]


def _span_observations(tele) -> int:
    """Observations the span histogram holds, over every span name."""
    scraped = tele.registry.scrape()["metrics"].get("swarm.span_seconds", {})
    return sum(int(v["count"]) for v in scraped.get("values", []))


# -- RPC surface ------------------------------------------------------------


class TestTelemetryRPCs:
    def test_scrape_trace_flight_rpcs(self):
        async def main():
            vols = await spawn(2)
            try:
                await run_rounds(vols, 1)
                client = vols[1]["t"]
                addr = vols[0]["t"].addr
                scrape, _ = await client.call(addr, T.SCRAPE_METHOD, {}, b"")
                trace, _ = await client.call(addr, T.TRACE_METHOD, {}, b"")
                flight, _ = await client.call(addr, T.FLIGHT_METHOD, {}, b"")
            finally:
                await teardown(vols)
            return scrape, trace, flight

        scrape, trace, flight = run(main())
        assert scrape["schema_version"] == T.TELEMETRY_SCHEMA_VERSION
        # The re-registered legacy surfaces are reachable from one scrape.
        assert any(k.startswith("transport.") for k in scrape["metrics"])
        assert "swarm.rounds_ok" in scrape["metrics"]
        assert trace["peer"] == "vol0"
        names = {s["name"] for s in trace["spans"]}
        assert {"round", "fold", "commit"} <= names
        assert isinstance(flight["events"], list)
