"""Swarm-watchdog tests: online baselines and anomaly detectors (warm-up
gating, step-change fire, hysteresis no-flap, cooldown, clear-on-heal),
SLO burn-rate windows, the alert lifecycle riding the flight recorder and
the report beat, the incremental flight cursor, Prometheus exposition +
the local /metrics endpoint, the pinned coord.status slo/alerts schema,
the --no-watchdog end-to-end disable contract, and the overhead smoke.

In-process swarms over real localhost TCP (the test_telemetry.py harness
shape); the multi-scenario fault matrix is exercised by
experiments/chaos_soak.py --watchdog.
"""

import asyncio
import statistics
import time

import numpy as np
import pytest

from distributedvolunteercomputing_tpu.swarm import health as H
from distributedvolunteercomputing_tpu.swarm import telemetry as T
from distributedvolunteercomputing_tpu.swarm import watchdog as W
from distributedvolunteercomputing_tpu.swarm.averager import SyncAverager
from distributedvolunteercomputing_tpu.swarm.control_plane import (
    ControlPlaneClient,
    ControlPlaneReplica,
)
from distributedvolunteercomputing_tpu.swarm.dht import DHTNode
from distributedvolunteercomputing_tpu.swarm.membership import SwarmMembership
from distributedvolunteercomputing_tpu.swarm.transport import Transport

pytestmark = pytest.mark.watchdog


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout=timeout))


def make_tree(value: float, elems: int = 4096):
    return {"w": np.full((elems,), value, np.float32)}


# -- online baseline ---------------------------------------------------------


class TestOnlineBaseline:
    def test_warmup_gating(self):
        b = W.OnlineBaseline(warmup=4)
        for x in (1.0, 2.0, 1.5):
            assert b.deviation(100.0) is None  # not ready: never a verdict
            b.observe(x)
        b.observe(1.2)
        assert b.ready
        assert b.deviation(b.mean) == pytest.approx(0.0)

    def test_deviation_floor_on_constant_series(self):
        """An all-equal warm-up (mad 0) must not amplify jitter into
        infinite deviations — the floor is 5% of |mean|."""
        b = W.OnlineBaseline(warmup=4)
        for _ in range(6):
            b.observe(1.0)
        assert b.mad == pytest.approx(0.0)
        assert b.deviation(1.0 + 1e-9) == pytest.approx(0.0, abs=1e-6)
        assert b.deviation(0.5) == pytest.approx(-10.0)  # floor = 0.05

    def test_tracks_mean(self):
        b = W.OnlineBaseline(alpha=0.5, warmup=2)
        for x in (10.0, 10.0, 10.0, 10.0):
            b.observe(x)
        assert b.mean == pytest.approx(10.0)


# -- anomaly detector lifecycle ----------------------------------------------


class TestAnomalyDetector:
    def detector(self, **kw):
        kw.setdefault("direction", "high")
        kw.setdefault("warmup", 4)
        kw.setdefault("cooldown_s", 10.0)
        return W.AnomalyDetector("d", **kw)

    def feed(self, det, values, t0=0.0, dt=1.0):
        events = []
        for i, v in enumerate(values):
            events.extend(det.observe(t0 + i * dt, v))
        return events

    def test_warmup_never_fires(self):
        det = self.detector()
        events = self.feed(det, [1.0, 100.0, 1.0])  # wild values, warming up
        assert events == []
        assert not det.firing()

    def test_step_change_fires_once_deduped(self):
        det = self.detector()
        events = self.feed(det, [1.0] * 6 + [10.0] * 5)
        raised = [e for e in events if e["action"] == "alert_raised"]
        assert len(raised) == 1, "firing alert must be deduplicated"
        assert det.firing()
        assert raised[0]["kind"] == "d" and raised[0]["severity"] == "warn"

    def test_single_blip_does_not_fire(self):
        """min_breaches consecutive out-of-band observations are required:
        one outlier is a blip, not an incident."""
        det = self.detector(min_breaches=2)
        events = self.feed(det, [1.0] * 6 + [10.0] + [1.0] * 4)
        assert events == []

    def test_clear_on_heal_and_hysteresis(self):
        det = self.detector(min_breaches=2, clear_breaches=2)
        events = self.feed(det, [1.0] * 6 + [10.0] * 3 + [1.0] * 3)
        actions = [e["action"] for e in events]
        assert actions == ["alert_raised", "alert_cleared"]
        assert not det.firing()

    def test_no_flap_between_bands(self):
        """Oscillation between the clear band and the fire threshold must
        not flap: clearing needs clear_breaches consecutive IN-CLEAR-BAND
        observations, and a mid-band value resets neither way into a new
        transition."""
        det = self.detector(
            fire_dev=4.0, clear_dev=2.0, min_breaches=2, clear_breaches=3
        )
        base = [1.0] * 8
        # After warm-up on 1.0 (mad -> 0, floor 0.05): 10.0 is far out of
        # band, 1.12 is mid-band (dev ~2.4: below fire, above clear).
        osc = [10.0, 10.0, 1.12, 10.0, 1.12, 10.0, 1.12]
        events = self.feed(det, base + osc)
        raised = [e for e in events if e["action"] == "alert_raised"]
        cleared = [e for e in events if e["action"] == "alert_cleared"]
        assert len(raised) == 1 and len(cleared) == 0
        assert det.firing()

    def test_cooldown_suppresses_reraise(self):
        det = self.detector(
            min_breaches=1, clear_breaches=1, cooldown_s=100.0
        )
        events = []
        vals = [1.0] * 6 + [10.0, 1.0, 10.0, 10.0, 10.0]
        for i, v in enumerate(vals):
            events.extend(det.observe(float(i), v))
        # raise at t=6, clear at t=7; re-raise blocked by the 100s cooldown.
        actions = [e["action"] for e in events]
        assert actions == ["alert_raised", "alert_cleared"]
        # Past the cooldown the same breach fires again.
        events = det.observe(200.0, 10.0)
        assert [e["action"] for e in events] == ["alert_raised"]

    def test_low_direction(self):
        det = self.detector(direction="low")
        events = self.feed(det, [1.0] * 6 + [0.1] * 3)
        assert [e["action"] for e in events] == ["alert_raised"]

    def test_per_key_baselines_independent(self):
        det = self.detector()
        for i in range(6):
            det.observe(float(i), 1.0, key="a")
            det.observe(float(i), 50.0, key="b")
        assert det.observe(9.0, 50.0, key="b") == []  # normal for b
        det.observe(10.0, 50.0, key="a")
        events = det.observe(11.0, 50.0, key="a")  # anomalous for a
        assert [e["action"] for e in events] == ["alert_raised"]

    def test_slow_adoption_eventually_rebaselines(self):
        """A permanent regime shift must eventually clear (the baseline
        crawls toward the new regime at alpha x adopt_frac) instead of
        paging forever."""
        det = self.detector(min_breaches=2, clear_breaches=2, adopt_frac=0.5)
        events = self.feed(det, [1.0] * 6 + [3.0] * 200)
        actions = [e["action"] for e in events]
        assert actions[0] == "alert_raised"
        assert "alert_cleared" in actions


class TestStreakDetector:
    def test_streak_fire_and_clear(self):
        det = W.StreakDetector("s", bad_streak=3, good_streak=2)
        events = []
        seq = [False, True, True, False, True, True, True, True, False, False]
        for i, bad in enumerate(seq):
            events.extend(det.observe(float(i), bad))
        actions = [e["action"] for e in events]
        # The interrupted streak (2 bads) never fires; the 3-streak does,
        # and 2 goods clear it.
        assert actions == ["alert_raised", "alert_cleared"]


class TestStallDetector:
    def test_healthy_new_lows_never_stall(self):
        det = W.StallDetector(window=3, floor=0.02)
        seq = [0.7, 0.68, 0.3, 0.31, 0.1, 0.11, 0.04, 0.05, 0.01]
        events = []
        for i, v in enumerate(seq):
            events.extend(det.observe(float(i), v))
        assert events == [] and not det.firing()

    def test_flat_above_floor_stalls_then_clears(self):
        det = W.StallDetector(window=3, floor=0.02)
        seq = [0.5, 0.3, 0.2, 0.21, 0.22, 0.2]  # no new low for a window
        events = []
        for i, v in enumerate(seq):
            events.extend(det.observe(float(i), v))
        assert [e["action"] for e in events] == ["alert_raised"]
        events = det.observe(10.0, 0.01)  # converged below the floor
        assert [e["action"] for e in events] == ["alert_cleared"]

    def test_repeat_values_are_not_observations(self):
        det = W.StallDetector(window=2, floor=0.02)
        for i in range(20):
            assert det.observe(float(i), 0.5) == []  # frozen series: no ticks
        assert not det.firing()


# -- the volunteer watchdog over a real swarm --------------------------------


async def spawn(n, *, watchdog_enabled=True, **avg_kw):
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
        tele = T.Telemetry(peer_id=f"vol{i}", watchdog_enabled=watchdog_enabled)
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


class TestWatchdogIntegration:
    def test_round_spans_feed_per_level_walls(self):
        """Committed rounds feed the per-level wall baseline + histogram
        through the tracer hook — no averager changes, no new RPCs."""

        async def main():
            vols = await spawn(3)
            try:
                committed = await run_rounds(vols, 2)
            finally:
                await teardown(vols)
            return vols, committed

        vols, committed = run(main())
        assert committed == 2
        summary = vols[0]["tele"].watchdog.summary()
        assert summary["schema_version"] == W.WATCHDOG_SCHEMA_VERSION
        wall = summary["round_wall"]["flat"]
        assert wall["count"] == 2 and wall["sum_s"] > 0
        assert sum(wall["buckets"]) == 2
        assert summary["firing"] == [] and summary["raised_total"] == 0

    def test_alert_lands_in_flight_recorder_with_severity(self):
        tele = T.Telemetry(peer_id="p")
        wd = tele.watchdog
        for _ in range(5):
            wd.observe("mass_frac_drop", 1.0)
        for _ in range(2):
            wd.observe("mass_frac_drop", 0.3)
        assert [a["kind"] for a in wd.alerts()] == ["mass_frac_drop"]
        evs = tele.recorder.dump(kinds=["alert_raised"])
        assert len(evs) == 1
        assert evs[0]["alert"] == "mass_frac_drop"
        assert evs[0]["sev"] == "warn"
        # Registry counter moved too.
        ctr = tele.registry.counter("swarm.watchdog.alerts_total")
        assert ctr.value(alert="mass_frac_drop", action="raised") == 1
        # Heal: clears with sev info.
        for _ in range(3):
            wd.observe("mass_frac_drop", 1.0)
        assert wd.alerts() == []
        assert tele.recorder.dump(kinds=["alert_cleared"])[0]["sev"] == "info"

    def test_wire_volunteer_mass_and_quality_probes(self):
        tele = T.Telemetry(peer_id="p")
        wd = tele.watchdog
        mon = tele.health
        wd.wire_volunteer(health=mon)
        # Mass probe: one observation per NEW mass report, min of the
        # weight and slot views (a silent straggler only moves the slots).
        for _ in range(5):
            mon.note_round_mass(
                H.mass_from_outcomes(["a", "b"], {"a": 1.0, "b": 1.0})
            )
            wd.tick()
        for _ in range(2):
            mon.note_round_mass(H.mass_from_outcomes(["a", "b"], {"a": 1.0}))
            wd.tick()
        assert [a["kind"] for a in wd.alerts()] == ["mass_frac_drop"]
        # Ticks without a new mass report observe nothing (no flap/decay).
        for _ in range(10):
            wd.tick()
        assert [a["kind"] for a in wd.alerts()] == ["mass_frac_drop"]

    def test_byzantine_flag_probe(self):
        tele = T.Telemetry(peer_id="p")
        wd = tele.watchdog
        mon = tele.health
        wd.wire_volunteer(health=mon)
        # Drive the quality monitor until it flags peer "byz".
        for _ in range(6):
            mon.observe_round_quality(
                {"a": 1.0, "b": 1.1, "c": 0.9, "byz": 1e6}
            )
            wd.tick()
        assert "byz" in mon.flagged_peers()
        byz = [a for a in wd.alerts() if a["kind"] == "byzantine_contributor"]
        assert [a["key"] for a in byz] == ["byz"]
        assert byz[0]["severity"] == "page"

    def test_disabled_watchdog_is_noop_and_summary_none(self):
        tele = T.Telemetry(peer_id="p", watchdog_enabled=False)
        wd = tele.watchdog
        assert not wd.enabled
        wd.wire_volunteer(health=tele.health)
        for _ in range(10):
            wd.observe("mass_frac_drop", 0.0)
            wd.tick()
        wd.observe_span({"name": "round", "dur_s": 99.0, "attrs": {}})
        assert wd.summary() is None
        assert wd.alerts() == []
        assert tele.scrape()["watchdog"] is None
        # --no-telemetry implies --no-watchdog.
        tele_off = T.Telemetry(peer_id="p", enabled=False)
        assert not tele_off.watchdog.enabled

    def test_volunteer_config_plumbs_watchdog(self):
        from distributedvolunteercomputing_tpu.swarm.volunteer import (
            Volunteer,
            VolunteerConfig,
        )

        v = Volunteer(VolunteerConfig(watchdog=False))
        assert v.telemetry.enabled and not v.telemetry.watchdog.enabled
        report = v._build_report()
        assert "telemetry" in report and "watchdog" not in report
        v_on = Volunteer(VolunteerConfig())
        assert v_on.telemetry.watchdog.enabled
        assert "watchdog" in v_on._build_report()

    def test_no_alert_bytes_on_heartbeat_when_disabled(self):
        """End-to-end: a batched cp.exchange beat from a watchdog-disabled
        volunteer carries NO watchdog key (and an enabled one does)."""

        async def main():
            t = Transport()
            dht = DHTNode(t)
            await dht.start(bootstrap=None)
            rep = ControlPlaneReplica(t, dht, rid="cp0", interval=0.5)
            await rep.start()
            seen = {}
            try:
                for pid, wd_on in (("woff", False), ("won", True)):
                    tele = T.Telemetry(peer_id=pid, watchdog_enabled=wd_on)

                    def report_source(tele=tele, pid=pid):
                        rep_d = {"peer": pid, "samples_per_sec": 1.0}
                        tele.watchdog.tick()
                        wd = tele.watchdog.summary()
                        if wd is not None:
                            rep_d["watchdog"] = wd
                        return rep_d

                    vt = Transport()
                    vdht = DHTNode(vt)
                    await vdht.start(bootstrap=[t.addr])
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
        assert "watchdog" not in seen["woff"], "disabled watchdog leaked bytes"
        assert "watchdog" in seen["won"]
        assert seen["won"]["watchdog"]["schema_version"] == W.WATCHDOG_SCHEMA_VERSION


# -- SLO burn rates ----------------------------------------------------------


class TestBurnRates:
    def test_burn_math_and_windows(self):
        slo = W.SLO("x", metric="m", bound=1.0, target=0.9,
                    fast_s=60.0, slow_s=300.0)
        tr = W.BurnRateTracker(slo)
        # 200s of good ticks, then 60s of all-bad ticks (1/s).
        t = 0.0
        for _ in range(200):
            tr.observe(t, True, 2.0)
            t += 1.0
        for _ in range(60):
            tr.observe(t, False, 0.0)
            t += 1.0
        res = tr.evaluate(t)
        # Fast window: all bad -> burn = 1.0/0.1 = 10; slow window:
        # 60/260 bad -> ~2.3.
        assert res["burn_fast"] == pytest.approx(10.0, rel=0.05)
        assert res["burn_slow"] == pytest.approx((60 / 260) / 0.1, rel=0.05)
        assert res["burning"]

    def test_short_blip_does_not_burn(self):
        """A fast-window blip with a healthy slow window must NOT page —
        the multi-window AND is the flap suppression."""
        slo = W.SLO("x", metric="m", bound=1.0, target=0.9,
                    fast_s=10.0, slow_s=300.0, fast_burn=2.0, slow_burn=1.0)
        tr = W.BurnRateTracker(slo)
        t = 0.0
        for _ in range(290):
            tr.observe(t, True, 2.0)
            t += 1.0
        for _ in range(5):
            tr.observe(t, False, 0.0)
            t += 1.0
        res = tr.evaluate(t)
        assert res["burn_fast"] >= 2.0  # fast window is screaming...
        assert not res["burning"]       # ...but the slow window vetoes

    def test_min_ticks_gate(self):
        slo = W.SLO("x", metric="m", bound=1.0, target=0.9)
        tr = W.BurnRateTracker(slo)
        tr.observe(0.0, False, 0.0)
        tr.observe(1.0, False, 0.0)
        assert not tr.evaluate(1.0)["burning"], "an empty window must not page"

    def test_swarm_watchdog_slo_burn_alert(self):
        sw = W.SwarmWatchdog(slos=(
            W.SLO("mass_committed_frac", metric="mass_committed_frac",
                  bound=0.9, target=0.9, fast_s=60.0, slow_s=120.0),
        ))
        now = 1000.0
        for i in range(6):
            sw.evaluate(
                [{"peer": "p", "recv_t": now}], health={
                    "mass": {"committed_frac_min": 1.0}
                }, now=now,
            )
            now += 5.0
        assert sw.alerts_status([], now)["n_firing"] == 0
        for i in range(30):
            sw.evaluate(
                [{"peer": "p", "recv_t": now}], health={
                    "mass": {"committed_frac_min": 0.5}
                }, now=now,
            )
            now += 5.0
        alerts = sw.alerts_status([], now)
        kinds = {(a["kind"], a["key"]) for a in alerts["firing"]}
        assert ("slo_burn", "mass_committed_frac") in kinds
        obj = sw.slo_status(now)["objectives"]["mass_committed_frac"]
        assert obj["burning"] and obj["value"] == 0.5

    def test_slo_burn_clears_when_metric_goes_uncomputable(self):
        """A firing slo_burn must CLEAR once its metric disappears (all
        health reporters gone): the time-filtered windows drain, burning
        drops, and the alert plane never contradicts the slo section."""
        sw = W.SwarmWatchdog(slos=(
            W.SLO("mass_committed_frac", metric="mass_committed_frac",
                  bound=0.9, target=0.9, fast_s=60.0, slow_s=120.0),
        ))
        now = 1000.0
        for _ in range(30):
            sw.evaluate(
                [{"peer": "p", "recv_t": now}],
                health={"mass": {"committed_frac_min": 0.5}}, now=now,
            )
            now += 5.0
        assert sw.alerts_status([], now)["n_firing"] == 1
        # Reporters vanish: the metric is uncomputable from here on.
        for _ in range(40):
            sw.evaluate([], health=None, now=now)
            now += 5.0
        assert sw.alerts_status([], now)["n_firing"] == 0, (
            "slo_burn latched after its metric became uncomputable"
        )

    def test_status_freshness_keeps_paging_through_total_outage(self):
        """When EVERY reporter goes dark, the fresh set empties — the
        freshness objective must keep observing a GROWING age from the
        newest report ever seen, not go blind and auto-clear on exactly
        the severest outage."""
        sw = W.SwarmWatchdog(slos=(
            W.SLO("status_freshness", metric="status_age_s", bound=30.0,
                  direction="max", target=0.95, fast_s=60.0, slow_s=120.0),
        ))
        now = 1000.0
        for _ in range(10):
            sw.evaluate([{"peer": "p", "recv_t": now}], now=now)
            now += 5.0
        assert sw.alerts_status([], now)["n_firing"] == 0
        # Total outage: the replica's FRESH_S filter empties the set.
        for _ in range(40):
            sw.evaluate([], now=now)
            now += 5.0
        alerts = sw.alerts_status([], now)
        assert [(a["kind"], a["key"]) for a in alerts["firing"]] == [
            ("slo_burn", "status_freshness")
        ], "freshness objective went blind during a total outage"
        obj = sw.slo_status(now)["objectives"]["status_freshness"]
        assert obj["burning"] and obj["value"] > 30.0

    def test_bw_key_retirement_clears_departed_peer(self):
        """A firing peer_bw_collapse for a peer that then DEPARTS (its key
        vanishes from the bandwidth map) must clear, and the retired key
        frees its detector slot."""
        tele = T.Telemetry(peer_id="p")
        wd = tele.watchdog
        bw = {"peer-a": 8e6}
        wd.wire_volunteer(bandwidths=lambda: dict(bw))
        for _ in range(5):
            wd.tick()
        bw["peer-a"] = 1e4
        wd.tick()
        wd.tick()
        assert [a["key"] for a in wd.alerts()] == ["peer-a"]
        del bw["peer-a"]  # the peer disconnects; its EWMA ages out
        wd.tick()
        assert wd.alerts() == [], "departed peer's alert never cleared"
        det = wd.detectors["peer_bw_collapse"]
        assert "peer-a" not in det._state, "retired key still holds a slot"
        evs = tele.recorder.dump(kinds=["alert_cleared"])
        assert evs and evs[-1]["key"] == "peer-a"

    def test_wall_hist_window_rotates_old_samples_out(self):
        """The per-level wall histograms are WINDOWED (two half-window
        generations), so the p99 SLO sees recent rounds, not lifetime."""
        clock = {"t": 0.0}
        wd = W.Watchdog(peer_id="p", clock=lambda: clock["t"])
        span = {"name": "round", "dur_s": 0.01, "attrs": {"level": "flat"}}
        for _ in range(10):
            wd.observe_span(dict(span))
        assert wd.summary()["round_wall"]["flat"]["count"] == 10
        # Two half-window rotations later, the old generation is gone.
        clock["t"] += W.Watchdog.WALL_WINDOW_S / 2 + 1
        wd.observe_span({**span, "dur_s": 5.0})
        clock["t"] += W.Watchdog.WALL_WINDOW_S / 2 + 1
        wd.observe_span({**span, "dur_s": 5.0})
        wall = wd.summary()["round_wall"]["flat"]
        assert wall["count"] == 2, f"lifetime samples leaked: {wall}"
        assert W.hist_quantile(wall["buckets"], 0.99) >= 5.0

    def test_hist_quantile(self):
        counts = [0] * (len(T.HIST_BUCKETS) + 1)
        counts[5] = 90
        counts[10] = 10
        q99 = W.hist_quantile(counts, 0.99)
        assert q99 == pytest.approx(T.HIST_BUCKETS[10])
        assert W.hist_quantile([0] * len(counts), 0.5) is None


# -- coord.status slo/alerts schema (satellite) ------------------------------


def _walk(schema, obj, path=""):
    for key, typ in schema.items():
        assert key in obj, f"missing documented key {path}{key}"
        typs = typ if isinstance(typ, tuple) else (typ,)
        assert isinstance(obj[key], typs), (
            f"{path}{key}: expected {typs}, got {type(obj[key]).__name__}"
        )


class TestStatusWatchdogSchema:
    def test_status_slo_alerts_schema_walk(self):
        """coord.status carries slo + alerts under the pinned schema, a
        volunteer-reported firing alert shows in the rollup, and the
        telemetry/health sections carry age_s staleness stamps."""

        async def main():
            t = Transport()
            dht = DHTNode(t)
            await dht.start(bootstrap=None)
            rep = ControlPlaneReplica(t, dht, rid="cp0", interval=0.5)
            await rep.start()
            try:
                tele = T.Telemetry(peer_id="v0")
                tele.tracer.record("round", "tr", 0.0, 0.25, level="flat",
                                   ok=True)
                tele.health.note_round_mass(
                    H.mass_from_outcomes(["a"], {"a": 1.0})
                )
                wd = tele.watchdog
                for _ in range(5):
                    wd.observe("mass_frac_drop", 1.0)
                for _ in range(2):
                    wd.observe("mass_frac_drop", 0.2)
                report = {
                    "peer": "v0", "samples_per_sec": 1.0,
                    "telemetry": tele.summary(),
                    "health": tele.health.summary(),
                    "watchdog": wd.summary(),
                }
                await rep._rpc_report(report, b"")
                status1, _ = await rep._rpc_status({}, b"")
                await asyncio.sleep(0.3)
                status, _ = await rep._rpc_status({}, b"")
            finally:
                await rep.stop()
                await dht.stop()
                await t.close()
            return status

        status = run(main())
        for section, schema in W.STATUS_WATCHDOG_SCHEMA.items():
            assert isinstance(status[section], dict)
            _walk(schema, status[section], f"{section}.")
            assert status[section]["schema_version"] == W.WATCHDOG_SCHEMA_VERSION
        for name, obj in status["slo"]["objectives"].items():
            _walk(W.STATUS_SLO_OBJECTIVE_SCHEMA, obj, f"slo.{name}.")
        assert status["slo"]["objectives"], "no objective was evaluated"
        for a in status["alerts"]["firing"]:
            _walk(W.ALERT_SCHEMA, a, "alerts.firing.")
        assert {a["kind"] for a in status["alerts"]["firing"]} == {
            "mass_frac_drop"
        }
        assert status["alerts"]["by_kind"] == {"mass_frac_drop": 1}
        assert status["alerts"]["raised_total"] >= 1
        # age_s stamps on every rollup section (frozen-replica satellite).
        assert isinstance(status["telemetry"]["age_s"], float)
        assert isinstance(status["health"]["age_s"], float)
        assert 0 <= status["telemetry"]["age_s"] < 30.0

    def test_status_watchdog_sections_always_present(self):
        """slo/alerts are dicts even on a report-less replica (the plane
        exists the moment a replica does — unlike telemetry/health which
        stay None until someone reports)."""

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
        assert status["telemetry"] is None and status["health"] is None
        assert isinstance(status["slo"], dict)
        assert isinstance(status["alerts"], dict)
        assert status["alerts"]["firing"] == []


# -- incremental flight cursor (satellite) -----------------------------------


class TestFlightCursor:
    def test_dump_since_seq(self):
        rec = T.FlightRecorder(peer_id="p")
        for i in range(5):
            rec.record("a", i=i)
        cursor = rec.next_seq
        assert cursor == 5
        rec.record("b", i=5)
        new = rec.dump(since_seq=cursor)
        assert [e["kind"] for e in new] == ["b"]
        assert rec.dump(since_seq=rec.next_seq) == []

    def test_flight_rpc_incremental(self):
        async def main():
            server = Transport()
            tele = T.Telemetry(peer_id="s")
            tele.register_rpcs(server)
            await server.start()
            client = Transport()
            tele.recorder.record("round_degraded", key="k1")
            first, _ = await client.call(server.addr, T.FLIGHT_METHOD, {}, b"")
            tele.recorder.record("round_failed", key="k2")
            second, _ = await client.call(
                server.addr, T.FLIGHT_METHOD,
                {"since_seq": first["next_seq"]}, b"",
            )
            third, _ = await client.call(
                server.addr, T.FLIGHT_METHOD,
                {"since_seq": second["next_seq"]}, b"",
            )
            await client.close()
            await server.close()
            return first, second, third

        first, second, third = run(main())
        assert [e["kind"] for e in first["events"]] == ["round_degraded"]
        assert [e["kind"] for e in second["events"]] == ["round_failed"]
        assert second["events"][0]["sev"] == "warn"
        assert third["events"] == [], "repeated dumps must be incremental"

    def test_all_vocabulary_kinds_carry_severity(self):
        rec = T.FlightRecorder(peer_id="p")
        for kind in T.KIND_SEVERITY:
            rec.record(kind)
        for e in rec.dump():
            assert e["sev"] == T.KIND_SEVERITY[e["kind"]]
            assert e["sev"] in W.SEVERITIES
        # Unknown kinds default to info; explicit sev= wins.
        rec.record("custom_thing")
        assert rec.dump()[-1]["sev"] == "info"
        rec.record("custom_thing", sev="page")
        assert rec.dump()[-1]["sev"] == "page"


# -- Prometheus exposition (satellite) ---------------------------------------


class TestProm:
    def test_render_prom_counter_gauge_histogram(self):
        reg = T.MetricsRegistry()
        reg.counter("swarm.c").inc(4, zone="a")
        reg.gauge("swarm.g").set(2.5)
        h = reg.histogram("swarm.h")
        h.observe(0.0015, span="x")
        h.observe(1e9, span="x")
        text = T.render_prom(reg.scrape())
        assert '# TYPE swarm_c counter' in text
        assert 'swarm_c{zone="a"} 4' in text
        assert "swarm_g 2.5" in text
        assert '# TYPE swarm_h histogram' in text
        assert 'swarm_h_count{span="x"} 2' in text
        assert 'le="+Inf"' in text
        # Cumulative buckets: the +Inf bucket equals the count.
        lines = [ln for ln in text.splitlines() if ln.startswith("swarm_h_bucket")]
        assert lines[-1].endswith(" 2")

    def test_prom_rpc(self):
        async def main():
            server = Transport()
            tele = T.Telemetry(peer_id="s")
            tele.registry.counter("swarm.rounds_total").inc(3)
            tele.register_rpcs(server)
            await server.start()
            client = Transport()
            ret, payload = await client.call(
                server.addr, T.PROM_METHOD, {}, b""
            )
            await client.close()
            await server.close()
            return ret, payload

        ret, payload = run(main())
        assert ret["content_type"].startswith("text/plain")
        assert b"swarm_rounds_total 3" in payload

    def test_metrics_http_endpoint(self):
        """--metrics-port end-to-end: a stock HTTP GET /metrics returns
        the Prometheus text; other paths 404."""

        async def main():
            tele = T.Telemetry(peer_id="s")
            tele.registry.gauge("swarm.live").set(1.0)
            srv = T.MetricsHTTPServer(tele, "127.0.0.1", 0)
            host, port = await srv.start()

            async def get(path):
                reader, writer = await asyncio.open_connection(host, port)
                writer.write(
                    f"GET {path} HTTP/1.0\r\nHost: x\r\n\r\n".encode()
                )
                await writer.drain()
                data = await reader.read()
                writer.close()
                return data

            ok = await get("/metrics")
            missing = await get("/nope")
            await srv.close()
            return ok, missing

        ok, missing = run(main())
        assert ok.startswith(b"HTTP/1.0 200")
        assert b"swarm_live 1" in ok
        assert missing.startswith(b"HTTP/1.0 404")


# -- overhead smoke (satellite) ----------------------------------------------


class TestOverheadSmoke:
    def test_watchdog_overhead_within_5pct(self):
        """Rounds with the watchdog enabled (telemetry on in both arms)
        must stay within 5% of watchdog-disabled commit latency — the
        detectors are one baseline update per round plus per-beat probe
        samples. Interleaved arm blocks so sandbox load drift hits both
        arms alike (the telemetry/health smokes' design)."""
        blocks, rounds_per_block, elems = 3, 3, 65_536

        async def main():
            vols_off = await spawn(3, watchdog_enabled=False)
            dts = {False: [], True: []}
            try:
                vols_on = await spawn(3, watchdog_enabled=True)
            except BaseException:
                await teardown(vols_off)
                raise
            for v in vols_on:
                tele = v["tele"]
                tele.watchdog.wire_volunteer(
                    averager=v["avg"], health=tele.health
                )
            arms = {False: vols_off, True: vols_on}
            try:
                r = 0
                for vols in (vols_off, vols_on):  # warmup both arms
                    await run_rounds(vols, 1, elems=elems, start=r)
                    r += 1
                for _ in range(blocks):
                    for enabled in (False, True):
                        for _ in range(rounds_per_block):
                            r += 1
                            t0 = time.perf_counter()
                            ok = await run_rounds(
                                arms[enabled], 1, elems=elems, start=r
                            )
                            if enabled:
                                for v in arms[True]:
                                    v["tele"].watchdog.tick()
                            if ok:
                                dts[enabled].append(time.perf_counter() - t0)
            finally:
                await teardown(vols_off)
                await teardown(vols_on)
            return dts

        dts = run(main(), timeout=300)
        need = blocks * rounds_per_block // 2
        assert len(dts[True]) >= need and len(dts[False]) >= need
        med_on = statistics.median(dts[True])
        med_off = statistics.median(dts[False])
        assert med_on <= med_off * 1.05 + 0.030, (
            f"watchdog overhead: enabled median {med_on:.4f}s vs disabled "
            f"{med_off:.4f}s — exceeds the 5% budget"
        )
