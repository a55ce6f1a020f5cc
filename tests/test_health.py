"""Training-health telemetry tests (swarm/health.py): the seeded
random-projection sketch estimator vs directly-computed parameter
dispersion, gradient-mass accounting balance across the deadline / abort /
fence matrix, per-peer contribution-quality attribution and flagging, the
--no-health-probe end-to-end plumbing (no sketch bytes on the heartbeat),
the coord.status["health"] schema walk, and the health-probe overhead
smoke (interleaved arms, like the PR-10 telemetry smoke).
"""

import asyncio
import statistics

import numpy as np
import pytest

from distributedvolunteercomputing_tpu.swarm import health as H
from distributedvolunteercomputing_tpu.swarm import telemetry as T
from distributedvolunteercomputing_tpu.swarm.agg_stream import (
    StreamingAggregator,
    TilePool,
)
from distributedvolunteercomputing_tpu.swarm.averager import SyncAverager
from distributedvolunteercomputing_tpu.swarm.control_plane import ControlPlaneReplica
from distributedvolunteercomputing_tpu.swarm.dht import DHTNode
from distributedvolunteercomputing_tpu.swarm.membership import SwarmMembership
from distributedvolunteercomputing_tpu.swarm.transport import Transport

pytestmark = pytest.mark.health


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


def direct_rel_dispersion(bufs):
    """The offline (hierarchy_bench-style) relative dispersion: RMS
    deviation from the cross-peer mean over the RMS parameter norm —
    exactly what sketch_dispersion estimates from the projections."""
    stack = np.stack([np.asarray(b, np.float64).ravel() for b in bufs])
    dev = stack - stack.mean(axis=0)[None, :]
    rms = float(np.sqrt((dev * dev).sum(axis=1).mean()))
    norm = float(np.sqrt((stack * stack).sum(axis=1).mean()))
    return rms / norm if norm > 0 else 0.0


# -- sketch estimator (satellite: tolerance test at n in {4, 8}) -------------


class TestSketchEstimator:
    # JL with dim=64 distorts pairwise norms by ~1/sqrt(2*64) ~= 9% per
    # pair; the dispersion averages over n peers, so 25% relative is a
    # conservative documented tolerance (typical observed error: <6%).
    TOL = 0.25

    @pytest.mark.parametrize("n_peers", [4, 8])
    def test_dispersion_matches_direct(self, n_peers):
        rng = np.random.default_rng(n_peers)
        seed = H.sketch_seed("m")
        bufs = [
            (rng.standard_normal(20_000) + 0.3 * i).astype(np.float32)
            for i in range(n_peers)
        ]
        sk = [H.params_sketch(b, seed) for b in bufs]
        est = H.sketch_dispersion(sk)["rel"]
        direct = direct_rel_dispersion(bufs)
        assert abs(est - direct) <= self.TOL * direct, (
            f"sketch dispersion {est:.4f} vs direct {direct:.4f} "
            f"(> {self.TOL:.0%} off)"
        )

    def test_degenerate_all_equal_reads_zero(self):
        seed = H.sketch_seed("m")
        buf = np.random.default_rng(0).standard_normal(8_192).astype(np.float32)
        sk = [H.params_sketch(buf, seed) for _ in range(4)]
        d = H.sketch_dispersion(sk)
        assert d["rel"] < 1e-7 and d["rms"] < 1e-7

    def test_subsampled_big_model_still_agrees(self):
        """Models bigger than the sample budget project a seeded
        coordinate subsample — the dispersion estimate stays unbiased."""
        rng = np.random.default_rng(3)
        seed = H.sketch_seed("m")
        bufs = [
            (rng.standard_normal(3 * H.DEFAULT_SKETCH_SAMPLE) + 0.5 * i).astype(
                np.float32
            )
            for i in range(4)
        ]
        est = H.sketch_dispersion([H.params_sketch(b, seed) for b in bufs])["rel"]
        direct = direct_rel_dispersion(bufs)
        assert abs(est - direct) <= 0.3 * direct

    def test_deterministic_and_seed_scoped(self):
        buf = np.random.default_rng(1).standard_normal(10_000).astype(np.float32)
        a = H.params_sketch(buf, H.sketch_seed("m"))
        b = H.params_sketch(buf, H.sketch_seed("m"))
        c = H.params_sketch(buf, H.sketch_seed("other"))
        assert np.array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_dispersion_refuses_mixed_spaces(self):
        assert H.sketch_dispersion([np.zeros(8), np.zeros(16)]) is None
        assert H.sketch_dispersion([np.zeros(8)]) is None


# -- gradient-mass accounting (acceptance: the balance property) -------------


def _feed_streamed(agg, peer, w, buf, chunk_bytes, upto=None):
    data = np.ascontiguousarray(buf, np.float32).tobytes()
    sink = agg.make_sink(peer, w, len(data))
    assert sink is not None
    end = len(data) if upto is None else upto
    for off in range(0, end, chunk_bytes):
        sink(off, len(data), data[off : off + chunk_bytes])
    return sink


def _assert_balanced(rep):
    """The invariant: every armed slot in exactly one bucket, weights sum."""
    assert (
        rep["included_slots"] + rep["excluded_slots"] + rep["aborted_slots"]
        == rep["armed_slots"]
    )
    assert (
        rep["included_weight"] + rep["excluded_weight"] + rep["aborted_weight"]
        == pytest.approx(rep["armed_weight"], abs=1e-6)
    )
    by_outcome = {"included": 0, "excluded": 0, "aborted": 0}
    for rec in rep["per_peer"].values():
        by_outcome[rec["outcome"]] += 1
    assert by_outcome["included"] == rep["included_slots"]
    assert by_outcome["excluded"] == rep["excluded_slots"]
    assert by_outcome["aborted"] == rep["aborted_slots"]


class TestMassAccounting:
    """Property test across the deadline/failover/abort matrix (the PR 3/4
    scenarios): included + excluded + aborted always partitions the armed
    set and their weights sum to the armed weight."""

    N_ELEMS, CB = 230, 64 * 4

    def _agg(self, peers, method="mean"):
        return StreamingAggregator(
            self.N_ELEMS, peers, method, "f32", self.CB,
            kw_fn=lambda n: {"trim": 1} if method == "trimmed_mean" else {},
            pool=TilePool(),
        )

    @pytest.mark.parametrize("method", ["mean", "trimmed_mean"])
    def test_happy_path_full_mass(self, method):
        peers = [f"p{i}" for i in range(4)]
        rng = np.random.default_rng(0)
        bufs = rng.standard_normal((4, self.N_ELEMS)).astype(np.float32)

        async def main():
            agg = self._agg(peers, method)
            agg.add_dense(peers[0], 2.0, bufs[0])
            for i in range(1, 4):
                _feed_streamed(agg, peers[i], 1.0, bufs[i], self.CB).close(True)
            await agg.finalize(peers)
            return agg.mass_report()

        rep = run(main())
        _assert_balanced(rep)
        assert rep["mass_committed_frac"] == 1.0
        assert rep["armed_weight"] == pytest.approx(5.0)
        assert rep["excluded_slots"] == rep["aborted_slots"] == 0

    @pytest.mark.parametrize("method", ["mean", "trimmed_mean"])
    def test_deadline_drop_and_silent_peer(self, method):
        """One peer streams half and stalls past the freeze, one never
        speaks: both land in excluded; the partial peer's declared weight
        is the excluded mass, the silent one balances at weight 0."""
        peers = [f"p{i}" for i in range(5)]
        rng = np.random.default_rng(1)
        bufs = rng.standard_normal((5, self.N_ELEMS)).astype(np.float32)

        async def main():
            agg = self._agg(peers, method)
            for i in range(3):
                _feed_streamed(agg, peers[i], 1.0, bufs[i], self.CB).close(True)
            # p3: half-delivered at the deadline (no close), weight 2.5.
            _feed_streamed(agg, peers[3], 2.5, bufs[3], self.CB, upto=2 * self.CB)
            # p4: silent.
            await agg.finalize(peers[:3])
            return agg.mass_report()

        rep = run(main())
        _assert_balanced(rep)
        assert rep["included_slots"] == 3
        assert rep["excluded_slots"] == 2
        assert rep["excluded_weight"] == pytest.approx(2.5)
        assert rep["per_peer"]["p4"] == {"outcome": "excluded", "weight": 0.0}
        assert rep["mass_committed_frac"] == pytest.approx(3.0 / 5.5)

    def test_abort_after_committed_tiles_is_aborted_mass(self):
        """A streamed push that dies AFTER folding tiles (mean mode: the
        axpy is irreversible) taints the slot — its mass is ABORTED, not
        excluded, and the balance still holds."""
        peers = [f"p{i}" for i in range(4)]
        rng = np.random.default_rng(2)
        bufs = rng.standard_normal((4, self.N_ELEMS)).astype(np.float32)

        async def main():
            agg = self._agg(peers, "mean")
            for i in range(3):
                _feed_streamed(agg, peers[i], 1.0, bufs[i], self.CB).close(True)
            sink = _feed_streamed(agg, peers[3], 4.0, bufs[3], self.CB, upto=2 * self.CB)
            sink.close(False)  # connection died mid-payload
            await agg.finalize(peers[:3])
            return agg.mass_report()

        rep = run(main())
        _assert_balanced(rep)
        assert rep["per_peer"]["p3"]["outcome"] == "aborted"
        assert rep["aborted_weight"] == pytest.approx(4.0)
        assert rep["mass_committed_frac"] == pytest.approx(3.0 / 7.0)

    def test_clean_abort_before_any_tile(self):
        """An abort before the first full tile resets cleanly — still
        accounted as aborted mass for the round unless a retry lands."""
        peers = ["p0", "p1", "p2"]
        rng = np.random.default_rng(3)
        bufs = rng.standard_normal((3, self.N_ELEMS)).astype(np.float32)

        async def main():
            agg = self._agg(peers, "mean")
            for i in range(2):
                _feed_streamed(agg, peers[i], 1.0, bufs[i], self.CB).close(True)
            data = bufs[2].tobytes()
            sink = agg.make_sink("p2", 3.0, len(data))
            sink(0, len(data), data[: self.CB - 4])  # short chunk: poisons
            sink.close(False)
            await agg.finalize(peers[:2])
            return agg.mass_report()

        rep = run(main())
        _assert_balanced(rep)
        assert rep["per_peer"]["p2"]["outcome"] == "aborted"

    def test_fenced_round_still_balances(self):
        """Leader failover: the fenced (superseded) aggregator's report
        stays internally consistent — nothing double-counts."""
        peers = ["p0", "p1", "p2"]
        rng = np.random.default_rng(4)
        bufs = rng.standard_normal((3, self.N_ELEMS)).astype(np.float32)

        async def main():
            agg = self._agg(peers, "mean")
            _feed_streamed(agg, "p0", 1.0, bufs[0], self.CB).close(True)
            _feed_streamed(agg, "p1", 1.5, bufs[1], self.CB, upto=2 * self.CB)
            agg.fence()
            return agg.mass_report()

        rep = run(main())
        _assert_balanced(rep)
        assert rep["included_slots"] == 1
        assert rep["excluded_weight"] == pytest.approx(1.5)

    def test_mass_from_outcomes_dense_round(self):
        rep = H.mass_from_outcomes(
            ["a", "b", "c", "d"], {"a": 1.0, "b": 2.0}, aborted=["c"]
        )
        _assert_balanced(rep)
        assert rep["mass_committed_frac"] == pytest.approx(1.0)  # known mass all landed
        assert rep["per_peer"]["c"]["outcome"] == "aborted"
        assert rep["per_peer"]["d"]["outcome"] == "excluded"


# -- contribution quality ----------------------------------------------------


class TestContributionQuality:
    def test_byzantine_flagged_honest_clean(self):
        tele = T.Telemetry(peer_id="lead")
        m = tele.health
        for r in range(8):
            m.observe_round_quality(
                {"h0": 1.0 + 0.2 * r, "h1": 0.8, "h2": 1.3, "byz": 400.0},
                trace=f"t{r}",
            )
        assert m.flagged_peers() == ["byz"]
        assert m.quality_score("byz") < 0.5
        for p in ("h0", "h1", "h2"):
            assert m.quality_score(p) == 1.0
        evs = tele.recorder.dump(kinds=["peer_quality_flagged"])
        assert evs and evs[0]["peer"] == "byz"

    def test_degenerate_all_equal_flags_nobody(self):
        m = T.Telemetry(peer_id="l").health
        for r in range(6):
            m.observe_round_quality(
                {"a": 0.0, "b": 0.0, "c": 1e-12}, trace=f"t{r}"
            )
        assert m.flagged_peers() == []

    def test_flag_clears_when_evidence_decays(self):
        m = T.Telemetry(peer_id="l").health
        for r in range(5):
            m.observe_round_quality({"a": 1.0, "b": 1.0, "x": 900.0})
        assert m.flagged_peers() == ["x"]
        for r in range(12):
            m.observe_round_quality({"a": 1.0, "b": 1.0, "x": 1.1})
        assert m.flagged_peers() == []

    def test_streaming_window_attribution(self):
        """The window folds accumulate per-slot distance to the aggregate;
        quality_d2 ranks the scaled contributor far above the honest."""
        peers = [f"p{i}" for i in range(4)]
        n_elems, cb = 230, 64 * 4
        rng = np.random.default_rng(5)
        base = rng.standard_normal(n_elems).astype(np.float32)
        tele = T.Telemetry(peer_id="lead")

        async def main():
            agg = StreamingAggregator(
                n_elems, peers, "trimmed_mean", "f32", cb,
                kw_fn=lambda n: {"trim": 1}, pool=TilePool(),
                telemetry=tele,
            )
            for i in range(3):
                _feed_streamed(
                    agg, peers[i], 1.0, base + 0.01 * i, cb
                ).close(True)
            _feed_streamed(agg, peers[3], 1.0, base * 20.0, cb).close(True)
            await agg.finalize(peers)
            return agg.quality_d2()

        q = run(main())
        assert set(q) == set(peers)
        honest_max = max(q[p] for p in peers[:3])
        assert q["p3"] > 50.0 * max(honest_max, 1e-12)

    def test_live_round_flags_scaled_contributor(self):
        """In-process sync swarm, trimmed_mean: a peer contributing a
        scaled tree is flagged by the leader's monitor within a few
        committed rounds, with zero honest flags — the chaos campaign's
        assertion in miniature."""

        async def main():
            vols, boot = [], None
            for i in range(4):
                t = Transport()
                dht = DHTNode(t)
                await dht.start(bootstrap=[boot] if boot else None)
                if boot is None:
                    boot = t.addr
                mem = SwarmMembership(dht, f"vol{i}", ttl=10.0)
                await mem.join()
                tele = T.Telemetry(peer_id=f"vol{i}")
                avg = SyncAverager(
                    t, dht, mem, telemetry=tele, min_group=3,
                    join_timeout=6.0, gather_timeout=8.0,
                    method="trimmed_mean",
                )
                vols.append({"t": t, "dht": dht, "mem": mem, "avg": avg, "tele": tele})
            try:
                for r in range(5):
                    vals = [0.0, 1.0, 2.0, 24.0]  # vol3 scaled
                    await asyncio.gather(
                        *(
                            v["avg"].average(
                                {"w": np.full((8192,), vals[i], np.float32)},
                                round_no=r,
                            )
                            for i, v in enumerate(vols)
                        ),
                        return_exceptions=True,
                    )
            finally:
                for v in vols:
                    try:
                        await v["mem"].leave()
                    except Exception:
                        pass
                    try:
                        await v["dht"].stop()
                    except Exception:
                        pass
                    await v["t"].close()
            return vols

        vols = run(main())
        lead = vols[0]["tele"].health
        assert lead.flagged_peers() == ["vol3"]
        for p in ("vol0", "vol1", "vol2"):
            assert lead.quality_score(p) == 1.0
        # The flag also rode into the membership record fields.
        assert vols[0]["mem"].extra_info.get("health_flagged") == ["vol3"]
        # ... and the mass gauge saw full participation.
        s = lead.summary()
        assert s["mass"]["last"]["mass_committed_frac"] == 1.0
        assert s["sketch"] is not None and len(s["sketch"]["v"]) == H.DEFAULT_SKETCH_DIM

    def test_quality_attribution_on_non_streaming_wire(self):
        """A q8-wire sync round takes the DENSE leader branch (the
        streaming aggregator only arms on f32/bf16) — the quality votes
        must not depend on the wire codec."""

        async def main():
            vols, boot = [], None
            for i in range(4):
                t = Transport()
                dht = DHTNode(t)
                await dht.start(bootstrap=[boot] if boot else None)
                if boot is None:
                    boot = t.addr
                mem = SwarmMembership(dht, f"vol{i}", ttl=10.0)
                await mem.join()
                tele = T.Telemetry(peer_id=f"vol{i}")
                avg = SyncAverager(
                    t, dht, mem, telemetry=tele, min_group=3,
                    join_timeout=6.0, gather_timeout=8.0,
                    method="trimmed_mean", wire="q8",
                )
                vols.append({"t": t, "dht": dht, "mem": mem, "avg": avg, "tele": tele})
            try:
                for r in range(4):
                    vals = [0.0, 1.0, 2.0, 24.0]
                    await asyncio.gather(
                        *(
                            v["avg"].average(
                                {"w": np.full((4096,), vals[i], np.float32)},
                                round_no=r,
                            )
                            for i, v in enumerate(vols)
                        ),
                        return_exceptions=True,
                    )
            finally:
                for v in vols:
                    try:
                        await v["mem"].leave()
                    except Exception:
                        pass
                    try:
                        await v["dht"].stop()
                    except Exception:
                        pass
                    await v["t"].close()
            return vols

        vols = run(main())
        lead = vols[0]["tele"].health
        assert lead.flagged_peers() == ["vol3"]
        for p in ("vol0", "vol1", "vol2"):
            assert lead.quality_score(p) == 1.0


# -- disable plumbing (satellite: --no-health-probe end-to-end) --------------


class TestDisablePlumbing:
    def test_monitor_disabled_is_noop(self):
        tele = T.Telemetry(peer_id="p", health_enabled=False)
        m = tele.health
        m.note_sketch(np.ones(128, np.float32))
        m.observe_round_quality({"a": 1.0, "b": 1.0, "c": 99.0})
        m.note_round_mass(H.mass_from_outcomes(["a"], {"a": 1.0}))
        m.note_codec_error("bf16", 0.01)
        assert m.sketches_computed == 0
        assert m.flagged_peers() == []
        assert m.summary() is None
        assert m.scrape() is None
        assert tele.scrape()["health"] is None

    def test_no_telemetry_implies_no_health(self):
        tele = T.Telemetry(peer_id="p", enabled=False)
        assert not tele.health.enabled

    def test_volunteer_config_plumbs_health_probe(self):
        from distributedvolunteercomputing_tpu.swarm.volunteer import (
            Volunteer,
            VolunteerConfig,
        )

        v = Volunteer(VolunteerConfig(health_probe=False))
        assert v.telemetry.enabled and not v.telemetry.health.enabled
        report = v._build_report()
        assert "telemetry" in report and "health" not in report
        v_on = Volunteer(VolunteerConfig())
        assert v_on.telemetry.health.enabled

    def test_no_sketch_bytes_on_heartbeat_when_disabled(self):
        """End-to-end: a batched cp.exchange beat from a health-disabled
        volunteer carries NO health key (and an enabled one does)."""

        async def main():
            t = Transport()
            dht = DHTNode(t)
            await dht.start(bootstrap=None)
            rep = ControlPlaneReplica(t, dht, rid="cp0", interval=0.5)
            await rep.start()
            seen = {}
            try:
                for pid, health_on in (("voff", False), ("von", True)):
                    tele = T.Telemetry(peer_id=pid, health_enabled=health_on)
                    if health_on:
                        tele.health.note_sketch(
                            np.ones(256, np.float32), trace="tr"
                        )

                    def report_source(tele=tele, pid=pid):
                        # The volunteer's report shape: health only when
                        # the monitor yields a summary.
                        rep = {"peer": pid, "samples_per_sec": 1.0}
                        h = tele.health.summary()
                        if h is not None:
                            rep["health"] = h
                        return rep

                    vt = Transport()
                    vdht = DHTNode(vt)
                    await vdht.start(bootstrap=[t.addr])
                    from distributedvolunteercomputing_tpu.swarm.control_plane import (
                        ControlPlaneClient,
                    )

                    cp = ControlPlaneClient(vt, vdht, pid)
                    mem = SwarmMembership(
                        vdht, pid, ttl=10.0, control_plane=cp,
                        report_source=report_source, telemetry=tele,
                    )
                    await mem.join()
                    await mem._beat_once()
                    assert mem.last_beat_batched, "beat must ride cp.exchange"
                    seen[pid] = dict(rep.latest_metrics.get(pid) or {})
                    await mem.leave()
                    await vdht.stop()
                    await vt.close()
            finally:
                await rep.stop()
                await dht.stop()
                await t.close()
            return seen

        seen = run(main())
        assert "health" not in seen["voff"], "disabled probe leaked sketch bytes"
        assert "health" in seen["von"]
        assert seen["von"]["health"]["sketch"]["v"]


# -- coord.status["health"] schema (satellite: schema walk) ------------------


def _check_types(schema, obj, path=""):
    for key, typ in schema.items():
        assert key in obj, f"missing documented key {path}{key}"
        assert isinstance(obj[key], typ), (
            f"{path}{key}: expected {typ.__name__}, got {type(obj[key]).__name__}"
        )


class TestStatusHealthSchema:
    def test_status_health_schema_walk(self):
        async def main():
            t = Transport()
            dht = DHTNode(t)
            await dht.start(bootstrap=None)
            rep = ControlPlaneReplica(t, dht, rid="cp0", interval=0.5)
            await rep.start()
            try:
                for i, zone in enumerate(("dc-a", "dc-a", "dc-b")):
                    tele = T.Telemetry(peer_id=f"v{i}")
                    tele.health.zone_fn = lambda z=zone: z
                    tele.health.note_sketch(
                        np.full(512, float(i), np.float32), trace="tr1"
                    )
                    tele.health.observe_round_quality(
                        {"v0": 1.0, "v1": 1.1, "byz": 500.0}
                    )
                    tele.health.note_round_mass(
                        H.mass_from_outcomes(
                            ["v0", "v1", "byz"], {"v0": 1.0, "v1": 1.0}
                        )
                    )
                    tele.health.note_codec_error("bf16", 0.004)
                    await rep._rpc_report(
                        {
                            "peer": f"v{i}",
                            "samples_per_sec": 1.0,
                            "telemetry": tele.summary(),
                            "health": tele.health.summary(),
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
        roll = status["health"]
        assert roll is not None
        _check_types(H.STATUS_HEALTH_SCHEMA, roll)
        assert roll["schema_version"] == H.HEALTH_SCHEMA_VERSION
        assert roll["reporting"] == 3
        mixing = roll["mixing"]
        assert mixing["n_sketches"] == 3
        assert mixing["dispersion"]["n"] == 3
        # Two zones reported: per-zone and across-zone dispersion exist.
        assert set(mixing["per_zone"]) == {"dc-a", "dc-b"}
        assert mixing["across_zones"] is not None
        assert roll["mass"]["committed_frac_mean"] == pytest.approx(1.0)
        assert roll["codec"]["bf16"] == pytest.approx(0.004, rel=0.5)
        # The telemetry rollup counts health reporters (v2 schema key).
        t_roll = status["telemetry"]
        assert t_roll["health_reporting"] == 3

    def test_status_health_none_without_reports(self):
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

        assert run(main())["health"] is None

    def test_rollup_zone_dispersion_separates_converged_zones(self):
        """Zone-converged but globally-diverged sketches: per-zone
        dispersion ~0, across-zone dispersion high — the signal the
        hierarchy's cross_zone_every_k exists to converge."""
        seed = H.sketch_seed("m")
        a = H.params_sketch(np.full(4096, 1.0, np.float32), seed)
        b = H.params_sketch(np.full(4096, 9.0, np.float32), seed)
        reports = []
        for i, (zone, sk) in enumerate(
            (("za", a), ("za", a), ("zb", b), ("zb", b))
        ):
            tele = T.Telemetry(peer_id=f"v{i}")
            tele.health.zone_fn = lambda z=zone: z
            s = tele.health.summary()
            s["sketch"] = {
                "trace": "tr", "t": 0.0, "dim": H.DEFAULT_SKETCH_DIM,
                "seed": seed, "v": [float(x) for x in sk],
            }
            reports.append({"peer": f"v{i}", "health": s})
        roll = H.rollup_status(reports)
        mixing = roll["mixing"]
        assert mixing["per_zone"]["za"]["rel"] < 1e-9
        assert mixing["per_zone"]["zb"]["rel"] < 1e-9
        assert mixing["across_zones"]["rel"] > 0.5


# -- overhead smoke (satellite: health probe <5% of commit latency) ----------


class TestHealthOverheadSmoke:
    def test_health_probe_overhead_within_5pct(self):
        """What the health probe costs a round, by what a run can count
        (telemetry itself on in BOTH arms — this isolates the health
        layer): ONE sketch a volunteer and one mass note a committed round
        and none with the probe off, and the bytes a committed round puts
        on the wire within 5% of the probe-off arm's (the probe's only
        wire cost is what it adds to frames; its summary rides the report
        beat). Interleaved arms + medians. The seconds of a round under
        six test workers say nothing about the probe (red at every anchor
        since PR 37 for that reason)."""
        blocks, rounds_per_block, elems = 3, 3, 65_536

        async def spawn(health_on):
            vols, boot = [], None
            for i in range(3):
                t = Transport()
                dht = DHTNode(t)
                await dht.start(bootstrap=[boot] if boot else None)
                if boot is None:
                    boot = t.addr
                mem = SwarmMembership(dht, f"{'on' if health_on else 'off'}{i}", ttl=10.0)
                await mem.join()
                tele = T.Telemetry(
                    peer_id=mem.peer_id, health_enabled=health_on
                )
                avg = SyncAverager(
                    t, dht, mem, telemetry=tele, min_group=2,
                    join_timeout=6.0, gather_timeout=8.0,
                    method="trimmed_mean",
                )
                vols.append({"t": t, "dht": dht, "mem": mem, "avg": avg, "tele": tele})
            return vols

        def sent(vols):
            return sum(v["t"].bytes_sent for v in vols)

        async def run_round(vols, r):
            res = await asyncio.gather(
                *(
                    v["avg"].average(
                        {"w": np.full((elems,), float(i), np.float32)}, round_no=r
                    )
                    for i, v in enumerate(vols)
                ),
                return_exceptions=True,
            )
            return all(x is not None and not isinstance(x, BaseException) for x in res)

        async def teardown(vols):
            for v in vols:
                try:
                    await v["mem"].leave()
                except Exception:
                    pass
                try:
                    await v["dht"].stop()
                except Exception:
                    pass
                await v["t"].close()

        async def main():
            arms = {False: await spawn(False)}
            try:
                arms[True] = await spawn(True)
            except BaseException:
                await teardown(arms[False])
                raise
            wire = {False: [], True: []}   # bytes sent a round that every volunteer committed
            attempted = {False: 0, True: 0}
            try:
                r = 0
                for on in (False, True):  # warmup both arms
                    attempted[on] += 1
                    await run_round(arms[on], r)
                    r += 1
                for _ in range(blocks):
                    for on in (False, True):
                        for _ in range(rounds_per_block):
                            r += 1
                            attempted[on] += 1
                            b0 = sent(arms[on])
                            if await run_round(arms[on], r):
                                wire[on].append(sent(arms[on]) - b0)
                probes = {on: [(v["tele"].health.sketches_computed, v["tele"].health.mass_rounds,
                                v["tele"].health.summary()) for v in arms[on]] for on in arms}
            finally:
                await teardown(arms[False])
                await teardown(arms[True])
            return wire, attempted, probes

        wire, attempted, probes = run(main(), timeout=300)
        need = blocks * rounds_per_block // 2
        assert len(wire[True]) >= need and len(wire[False]) >= need
        # probe calls: none with the probe off; with it on, one sketch a volunteer a round it committed
        assert all(sketches == 0 and mass == 0 and summary is None for sketches, mass, summary in probes[False])
        sketches = [n for n, _, _ in probes[True]]
        assert all(0 < n <= attempted[True] for n in sketches), (sketches, attempted)
        assert sum(sketches) >= 3 * len(wire[True]), (sketches, len(wire[True]))
        masses = [mass for _, mass, _ in probes[True]]   # the round's leader notes its mass, whoever that was
        assert all(mass <= attempted[True] for mass in masses) and sum(masses) >= len(wire[True]), masses
        assert all(summary is not None for _, _, summary in probes[True])
        # bytes a round: three 256 KiB contributions and the result either way
        med_on = statistics.median(wire[True])
        med_off = statistics.median(wire[False])
        assert med_off > 2 * elems * 4
        assert med_on <= med_off * 1.05 + 4096, (
            f"health probe overhead: enabled median {med_on} B a round vs "
            f"disabled {med_off} B — exceeds the 5% budget"
        )
