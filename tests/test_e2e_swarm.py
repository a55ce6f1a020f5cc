"""The swarm's base and its end-to-end tests.

First, end to end: real processes, real entrypoints, real churn. This is the
reference's own test shape (SURVEY.md §4): N volunteer PROCESSES on localhost,
a coordinator process, kill -9 mid-run — the whole L6-L2 stack through the
actual CLI entrypoints.

Then the layers under the entrypoints (transport / DHT / membership /
coordinator), in-process over real localhost sockets: the
"multi-node-without-a-cluster" strategy (SURVEY.md §4): every node is a real
asyncio TCP server on 127.0.0.1, so the wire protocol, timeouts, and churn
behavior are exercised for real; only process isolation is elided (the
end-to-end class above has it). They were ``tests/test_swarm_base.py`` until
PR 58 and share this file for the tier-1 run's sake: ``--dist loadfile`` hands
files to its six workers MOST TESTS FIRST, the end-to-end class holds a worker
for eight to ten minutes of protocol waits on 5 s of its own CPU, and as a
file of 19 tests it started 400 s into the run and ended it 170 s after every
other worker had finished (ROADMAP D3). With these 18 quick tests the file is
handed out among the first dozen.
"""

import asyncio
import json
import os
import re
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from distributedvolunteercomputing_tpu.swarm.coordinator import Coordinator
from distributedvolunteercomputing_tpu.swarm.dht import DHTNode
from distributedvolunteercomputing_tpu.swarm.membership import SwarmMembership
from distributedvolunteercomputing_tpu.swarm.transport import RPCError, Transport

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_MLP = ["--model-override", "d_hidden=16"]


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # single CPU device is enough per volunteer
    return env


def start_coordinator(extra=()):
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "coordinator.py"), *extra],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=_env(),
    )
    deadline = time.time() + 30
    while time.time() < deadline:
        line = proc.stdout.readline()
        m = re.match(r"COORDINATOR_READY (\S+)", line or "")
        if m:
            return proc, m.group(1)
    proc.kill()
    raise RuntimeError("coordinator did not become ready")


def start_volunteer(coord_addr, peer_id, extra, env_extra=None, capture=True):
    """``capture=False`` routes output to DEVNULL — for background
    volunteers nobody wait_done()s: an undrained PIPE fills its 64KB kernel
    buffer and blocks the volunteer's next log write mid-run."""
    env = _env()
    if env_extra:
        env.update(env_extra)
    coord = ["--coordinator", coord_addr] if coord_addr else []
    out = subprocess.PIPE if capture else subprocess.DEVNULL
    err = subprocess.STDOUT if capture else subprocess.DEVNULL
    return subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "run_volunteer.py"),
            *coord,
            "--peer-id", peer_id,
            "--batch-size", "16",
            "--lr", "0.01",
            *TINY_MLP,
            *extra,
        ],
        stdout=out, stderr=err, text=True, env=env,
    )


def wait_swarm_alive(coord_addr, n, timeout=180):
    """Poll the coordinator's coord.status until >= n peers are alive —
    deterministic readiness instead of sleep(): under CPU contention a jax
    subprocess can take a minute to come up."""
    import asyncio

    from distributedvolunteercomputing_tpu.swarm.transport import Transport

    host, _, port = coord_addr.rpartition(":")

    async def poll():
        t = Transport()
        try:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                try:
                    ret, _ = await t.call((host, int(port)), "coord.status", timeout=5.0)
                    if int(ret.get("n_alive", 0)) >= n:
                        return True
                except Exception:
                    pass
                await asyncio.sleep(2.0)
            return False
        finally:
            await t.close()

    return asyncio.run(poll())


def wait_trained(metrics_path, records=3, timeout=90):
    """Wait until a volunteer started with ``--metrics metrics_path`` has
    demonstrably TRAINED (its file holds ``records`` lines): a wall-clock
    sleep lands during the JAX compile on a loaded machine and after the run
    has ended on a quiet one."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with open(metrics_path) as fh:
                if sum(1 for _ in fh) >= records:
                    return
        except OSError:
            pass
        time.sleep(0.5)
    raise AssertionError(f"no {records} metrics records in {metrics_path} after {timeout} s: never started training")


def wait_done(proc, timeout=180):
    out, _ = proc.communicate(timeout=timeout)
    for line in out.splitlines():
        if line.startswith("VOLUNTEER_DONE "):
            return json.loads(line[len("VOLUNTEER_DONE "):]), out
    raise AssertionError(f"no VOLUNTEER_DONE in output:\n{out}")


class TestSwarmE2E:
    def test_two_volunteers_sync_averaging(self, tmp_path):
        """Config-2 shape: 2 volunteers, synchronous GradientAverager.

        Runs with the volunteer DEFAULT (overlapped rounds): local steps
        are ~0.2 s while a WAN round is seconds, so a short run completes
        fewer rounds than the blocking cadence would — at least one full
        round (plus the end-of-run drain) is the correct expectation here;
        blocking round-per-cadence counting is covered by the grads-mode
        test below and the config-0 experiment's --no-overlap arm."""
        coord, addr = start_coordinator()
        try:
            common = [
                "--averaging", "sync", "--average-every", "10", "--steps", "40",
                "--join-timeout", "25", "--gather-timeout", "25",
            ]
            v0 = start_volunteer(addr, "vol0", common + ["--seed", "0"])
            v1 = start_volunteer(addr, "vol1", common + ["--seed", "1"])
            s0, out0 = wait_done(v0)
            s1, out1 = wait_done(v1)
            assert s0["rounds_ok"] >= 1, out0
            assert s1["rounds_ok"] >= 1, out1
            assert s0["final_loss"] < 2.5 and s1["final_loss"] < 2.5
        finally:
            coord.kill()

    def test_two_volunteers_grad_averaging_bf16_wire(self):
        """GradientAverager semantics end-to-end: grads averaged every step
        over the bf16 wire; both volunteers converge in lockstep."""
        coord, addr = start_coordinator()
        try:
            common = [
                # grads mode averages EVERY step — keep the run short.
                "--averaging", "sync", "--average-what", "grads", "--wire", "bf16",
                "--steps", "8",
                "--join-timeout", "25", "--gather-timeout", "25",
            ]
            v0 = start_volunteer(addr, "gvol0", common + ["--seed", "0"])
            v1 = start_volunteer(addr, "gvol1", common + ["--seed", "1"])
            s0, out0 = wait_done(v0)
            s1, out1 = wait_done(v1)
            assert s0["rounds_ok"] >= 2, out0
            assert s1["rounds_ok"] >= 2, out1
            assert s0["final_loss"] < 2.5 and s1["final_loss"] < 2.5
        finally:
            coord.kill()

    def test_two_volunteers_sync_steps_per_call(self):
        """--steps-per-call end to end: chunked on-device stepping between
        averaging points, rounds still complete at the step cadence."""
        coord, addr = start_coordinator()
        try:
            common = [
                "--averaging", "sync", "--average-every", "10",
                "--steps-per-call", "5", "--steps", "40",
                "--join-timeout", "25", "--gather-timeout", "25",
            ]
            v0 = start_volunteer(addr, "spc0", common + ["--seed", "0"])
            v1 = start_volunteer(addr, "spc1", common + ["--seed", "1"])
            s0, out0 = wait_done(v0)
            s1, out1 = wait_done(v1)
            assert s0["rounds_ok"] >= 1, out0
            assert s1["rounds_ok"] >= 1, out1
            assert s0["steps"] == 40 and s1["steps"] == 40, (out0, out1)
            assert s0["final_loss"] < 2.5 and s1["final_loss"] < 2.5, (out0, out1)
        finally:
            coord.kill()

    def test_heterogeneous_volunteers_interval_cadence(self):
        """Wall-clock averaging cadence end to end: volunteers with 8x
        different batch sizes (heterogeneous speed, the config-4 shape)
        rendezvous on absolute 0.5s boundaries instead of step counts. Both
        must complete rounds — under a step cadence with these speeds the
        fast peer would sit parked at every rendezvous."""
        coord, addr = start_coordinator()
        try:
            common = [
                # A short interval so even an unloaded machine (tiny-MLP CPU
                # steps can run in ~1-2ms) crosses several boundaries within
                # 500 steps; the first boundary only ARMS post-compile.
                "--averaging", "sync", "--average-interval-s", "0.5",
                "--steps", "500",
                "--join-timeout", "25", "--gather-timeout", "25",
            ]
            v0 = start_volunteer(addr, "hvol0", common + ["--seed", "0", "--batch-size", "8"])
            v1 = start_volunteer(addr, "hvol1", common + ["--seed", "1", "--batch-size", "64"])
            s0, out0 = wait_done(v0)
            s1, out1 = wait_done(v1)
            assert s0["rounds_ok"] >= 1, out0
            assert s1["rounds_ok"] >= 1, out1
            assert s0["final_loss"] < 2.5 and s1["final_loss"] < 2.5, (out0, out1)
        finally:
            coord.kill()

    def test_interval_cadence_rendezvous_under_clock_skew(self):
        """r4 VERDICT #9: the wall-clock cadence assumed NTP sync. One
        volunteer's clock is skewed +6s (DVC_CLOCK_SKEW_S — far more than
        any boundary tolerance at a 0.5s interval); peer clock-offset
        estimation (swarm/clocksync.py) must pull both onto consensus time
        so rounds still complete. Without the correction the skewed peer
        arms boundaries 12 intervals ahead and the swarm never rendezvouses
        inside join_timeout."""
        coord, addr = start_coordinator()
        try:
            common = [
                "--averaging", "sync", "--average-interval-s", "0.5",
                "--steps", "500",
                "--join-timeout", "25", "--gather-timeout", "25",
            ]
            v0 = start_volunteer(addr, "skew0", common + ["--seed", "0"],
                                 env_extra={"DVC_CLOCK_SKEW_S": "6"})
            v1 = start_volunteer(addr, "skew1", common + ["--seed", "1"])
            s0, out0 = wait_done(v0)
            s1, out1 = wait_done(v1)
            assert s0["rounds_ok"] >= 1, out0
            assert s1["rounds_ok"] >= 1, out1
        finally:
            coord.kill()

    def test_two_volunteers_grad_averaging_powersgd_wire(self):
        """Rank-4 PowerSGD wire end-to-end through the real entrypoints:
        grads averaged every step as (P, Q) factor pairs with error
        feedback; both volunteers converge in lockstep (the mnist proxy's
        gradients are heavily low-rank, so rank 4 tracks the dense run)."""
        coord, addr = start_coordinator()
        try:
            common = [
                "--averaging", "sync", "--average-what", "grads",
                "--wire", "powersgd", "--psgd-rank", "4",
                "--steps", "8",
                "--join-timeout", "25", "--gather-timeout", "25",
            ]
            v0 = start_volunteer(addr, "pvol0", common + ["--seed", "0"])
            v1 = start_volunteer(addr, "pvol1", common + ["--seed", "1"])
            s0, out0 = wait_done(v0)
            s1, out1 = wait_done(v1)
            assert s0["rounds_ok"] >= 2, out0
            assert s1["rounds_ok"] >= 2, out1
            assert s0["final_loss"] < 2.5 and s1["final_loss"] < 2.5, (out0, out1)
        finally:
            coord.kill()

    def test_two_volunteers_sync_outer_optimizer(self):
        """DiLoCo-style outer Nesterov over sync params rounds, end to end
        through the real entrypoints: rounds complete and losses stay sane
        (the outer step must contract toward consensus, not diverge)."""
        coord, addr = start_coordinator()
        try:
            common = [
                "--averaging", "sync", "--average-every", "10", "--steps", "60",
                "--outer-optimizer", "nesterov", "--outer-lr", "0.7",
                "--outer-momentum", "0.9",
                "--join-timeout", "25", "--gather-timeout", "25",
            ]
            v0 = start_volunteer(addr, "ov0", common + ["--seed", "0"])
            v1 = start_volunteer(addr, "ov1", common + ["--seed", "1"])
            s0, out0 = wait_done(v0)
            s1, out1 = wait_done(v1)
            assert s0["rounds_ok"] >= 1, out0
            assert s1["rounds_ok"] >= 1, out1
            assert s0["final_loss"] == s0["final_loss"], out0  # not NaN
            assert s0["final_loss"] < 2.5 and s1["final_loss"] < 2.5, (out0, out1)
        finally:
            coord.kill()

    def test_two_volunteers_gossip_averaging(self):
        """Config-3 shape at process level (2 volunteers): gossip partners
        are selected from membership records' avg_ns — the exact plumbing a
        round-3 bug broke (records carried only the model name, every round
        skipped). The in-process regression lives in test_averaging; this
        guards the entrypoint wiring."""
        coord, addr = start_coordinator()
        try:
            # 72 steps (9 gossip opportunities): under load the two
            # processes' lifetimes skew (one compiles while the other
            # trains) and gossip needs overlap — a short run can leave
            # BOTH sides with zero mixed rounds purely by timing.
            common = [
                "--averaging", "gossip", "--average-every", "8", "--steps", "72",
                "--join-timeout", "30", "--gather-timeout", "30",
            ]
            v0 = start_volunteer(addr, "gos0", common + ["--seed", "0"])
            v1 = start_volunteer(addr, "gos1", common + ["--seed", "1"])
            s0, out0 = wait_done(v0)
            s1, out1 = wait_done(v1)
            # gossip needs the partner's record + published params; at least
            # one mixed round proves the entrypoint plumbing (the r03 bug
            # yielded exactly 0). Both sides usually mix several times, but
            # under single-core contention a side can miss its windows —
            # asserting >=1 keeps the guard without the timing flake.
            assert s0["rounds_ok"] + s1["rounds_ok"] >= 1, out0 + out1
            assert s0["final_loss"] < 2.5 and s1["final_loss"] < 2.5
        finally:
            coord.kill()

    def test_two_volunteers_with_in_slice_mesh(self):
        """Each volunteer process owns a 4-device virtual slice (forced CPU
        devices) and runs the SHARDED step (--mesh dp=2,tp=2 --fsdp) while
        sync-averaging over the WAN tier — the per-volunteer-slice contract:
        in-slice parallelism is invisible to the swarm."""
        coord, addr = start_coordinator()
        try:
            common = [
                "--averaging", "sync", "--average-every", "8", "--steps", "24",
                "--join-timeout", "25", "--gather-timeout", "25",
                "--mesh", "dp=2,tp=2", "--fsdp",
            ]
            env4 = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
            v0 = start_volunteer(addr, "mesh0", common + ["--seed", "0"], env_extra=env4)
            v1 = start_volunteer(addr, "mesh1", common + ["--seed", "1"], env_extra=env4)
            s0, out0 = wait_done(v0)
            s1, out1 = wait_done(v1)
            assert s0["rounds_ok"] + s1["rounds_ok"] >= 1, out0 + out1
            assert s0["final_loss"] < 2.5 and s1["final_loss"] < 2.5
        finally:
            coord.kill()

    def test_kitchen_sink_auth_topk_churn(self, tmp_path):
        """The features compose: HMAC-authenticated swarm, grads-mode sync
        averaging over the top-k sparse wire with error feedback, kill -9
        churn mid-run — survivors keep averaging and finish."""
        secret = tmp_path / "swarm.key"
        secret.write_text("kitchen-sink\n")
        coord, addr = start_coordinator(["--secret-file", str(secret)])
        vols = []
        try:
            victim_metrics = str(tmp_path / "ks2.jsonl")
            common = [
                "--averaging", "sync", "--average-what", "grads",
                "--wire", "topk", "--topk-frac", "0.25",
                "--steps", "30", "--min-group", "2",
                "--join-timeout", "20", "--gather-timeout", "10",
                "--secret-file", str(secret),
            ]
            vols = [
                start_volunteer(
                    addr, f"ks{i}",
                    common + ["--seed", str(i)]
                    + (["--metrics", victim_metrics] if i == 2 else []),
                )
                for i in range(3)
            ]
            # Kill only once the victim has demonstrably TRAINED: a kill
            # during JAX compile quietly degrades this to a 2-node test.
            wait_trained(victim_metrics)
            vols[2].send_signal(signal.SIGKILL)
            s0, out0 = wait_done(vols[0])
            s1, out1 = wait_done(vols[1])
            assert s0["rounds_ok"] >= 1, out0
            assert s1["rounds_ok"] >= 1, out1
            assert s0["final_loss"] < 2.5 and s1["final_loss"] < 2.5
        finally:
            coord.kill()
            for v in vols:
                if v.poll() is None:
                    v.kill()

    def test_peer_bootstrap_no_coordinator(self):
        """Fully decentralized: every volunteer runs a DHT node, so a second
        volunteer can bootstrap off the FIRST volunteer's address — no
        coordinator process anywhere. The coordinator is a convenience
        (stable rendezvous + metrics sink), not a dependency."""
        import socket

        common = [
            "--averaging", "sync", "--average-every", "6", "--steps", "60",
            "--join-timeout", "25", "--gather-timeout", "25",
        ]
        va = start_volunteer(
            None, "boot-a", common + ["--seed", "0", "--port", "47821"]
        )
        # Volunteers print no READY line; poll the port until A's transport
        # is listening (the DHT bootstrap ping is single-attempt, so racing
        # it would fail spuriously on a slow start).
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                socket.create_connection(("127.0.0.1", 47821), timeout=1.0).close()
                break
            except OSError:
                time.sleep(0.5)
        else:
            va.kill()
            raise AssertionError("volunteer A never started listening")
        vb = start_volunteer("127.0.0.1:47821", "boot-b", common + ["--seed", "1"])
        sa, outa = wait_done(va)
        sb, outb = wait_done(vb)
        assert sa["rounds_ok"] + sb["rounds_ok"] >= 1, outa + outb

    def test_multi_coordinator_bootstrap_survives_dead_first(self):
        """--coordinator addr1,addr2: volunteers join through the SECOND
        coordinator when the first is already dead — coordinator death must
        not strand rejoining volunteers."""
        from distributedvolunteercomputing_tpu.swarm.volunteer import _parse_addrs

        assert _parse_addrs("h1:1,h2:2") == [("h1", 1), ("h2", 2)]
        assert _parse_addrs(None) == []
        with pytest.raises(ValueError, match="host:port"):
            _parse_addrs("nocolon")

        coord, addr = start_coordinator()
        try:
            # dead-first: a port nothing listens on, then the live one
            both = f"127.0.0.1:1,{addr}"
            common = [
                "--averaging", "sync", "--average-every", "8", "--steps", "24",
                "--join-timeout", "25", "--gather-timeout", "25",
            ]
            v0 = start_volunteer(both, "mc0", common + ["--seed", "0"])
            v1 = start_volunteer(both, "mc1", common + ["--seed", "1"])
            s0, out0 = wait_done(v0)
            s1, out1 = wait_done(v1)
            assert s0["rounds_ok"] + s1["rounds_ok"] >= 1, out0 + out1
        finally:
            coord.kill()

    def test_swarm_secret_locks_out_intruder(self, tmp_path):
        """--secret-file end-to-end: secret-holding volunteers average
        normally; a volunteer WITHOUT the secret cannot participate (its
        frames fail the transport HMAC everywhere)."""
        secret = tmp_path / "swarm.key"
        secret.write_text("e2e-test-secret\n")
        coord, addr = start_coordinator(["--secret-file", str(secret)])
        try:
            common = [
                "--averaging", "sync", "--average-every", "8", "--steps", "24",
                "--join-timeout", "15", "--gather-timeout", "15",
            ]
            v0 = start_volunteer(
                addr, "auth0", common + ["--seed", "0", "--secret-file", str(secret)]
            )
            v1 = start_volunteer(
                addr, "auth1", common + ["--seed", "1", "--secret-file", str(secret)]
            )
            intruder = start_volunteer(addr, "intruder", common + ["--seed", "2"])
            s0, out0 = wait_done(v0)
            s1, out1 = wait_done(v1)
            assert s0["rounds_ok"] + s1["rounds_ok"] >= 1, out0 + out1
            assert s0["final_loss"] < 2.5 and s1["final_loss"] < 2.5
            # The intruder either dies on join or finishes having never
            # completed a round — it must not have averaged with anyone.
            try:
                si, outi = wait_done(intruder, timeout=120)
            except Exception:  # died/hung before a summary = locked out
                intruder.kill()
            else:
                assert si["rounds_ok"] == 0, outi
        finally:
            coord.kill()

    def test_churn_kill9_survivors_finish(self):
        """Kill -9 one of three volunteers mid-run; survivors keep averaging."""
        coord, addr = start_coordinator()
        try:
            common = [
                "--averaging", "sync", "--average-every", "8", "--steps", "48",
                "--min-group", "2", "--join-timeout", "20", "--gather-timeout", "10",
            ]
            vols = [start_volunteer(addr, f"vol{i}", common + ["--seed", str(i)]) for i in range(3)]
            time.sleep(12)  # let it train into the averaging phase
            vols[2].send_signal(signal.SIGKILL)  # un-graceful death
            s0, out0 = wait_done(vols[0])
            s1, out1 = wait_done(vols[1])
            assert s0["rounds_ok"] >= 1, out0
            assert s1["rounds_ok"] >= 1, out1
        finally:
            coord.kill()
            for v in vols:
                if v.poll() is None:
                    v.kill()

    def test_byzantine_lora_swarm_survives_corrupt_volunteer(self):
        """Config-5 shape (BASELINE.json:11): llama_lora volunteers under
        Byzantine-tolerant averaging, one volunteer contributing garbage
        (its real adapter tree scaled 1000x — well-formed frames, so only
        robust aggregation can catch it). Honest survivors must keep
        finite, sane losses; the shared frozen base (init_seed) is what
        makes their adapter averages meaningful."""
        tiny_llama = [
            "--model", "llama_lora",
            "--model-override", "vocab=128", "--model-override", "max_len=16",
            "--model-override", "d_model=32", "--model-override", "n_heads=2",
            "--model-override", "n_kv_heads=2", "--model-override", "n_layers=2",
            "--model-override", "d_ff=64", "--model-override", "lora_rank=2",
        ]
        coord, addr = start_coordinator()
        vols = []
        try:
            common = [
                "--averaging", "byzantine", "--method", "trimmed_mean",
                "--average-every", "6", "--steps", "24", "--batch-size", "8",
                "--min-group", "4", "--max-group", "4", "--lr", "0.005",
                "--join-timeout", "25", "--gather-timeout", "25", *tiny_llama,
            ]

            def start(peer_id, extra, env_extra=None):
                env = _env()
                env.update(env_extra or {})
                return subprocess.Popen(
                    [sys.executable, os.path.join(REPO, "run_volunteer.py"),
                     "--coordinator", addr, "--peer-id", peer_id, *common, *extra],
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
                )

            vols = [start(f"honest{i}", ["--seed", str(i)]) for i in range(3)]
            vols.append(
                start("byz", ["--seed", "9"], {"DVC_CHAOS_CONTRIB_SCALE": "1000.0"})
            )
            summaries = []
            for v in vols[:3]:
                s, out = wait_done(v, timeout=240)
                summaries.append((s, out))
            for s, out in summaries:
                assert s["rounds_ok"] >= 2, out
                # ln(128) ~ 4.85 at init; adopting the 1000x-scaled garbage
                # would blow the loss up (or NaN). Trimmed mean must hold.
                assert s["final_loss"] == s["final_loss"], out  # not NaN
                assert s["final_loss"] < 6.5, out
        finally:
            coord.kill()
            for v in vols:
                if v.poll() is None:
                    v.kill()

    def test_rejoiner_converges_despite_poisoned_state_pull(self):
        """Adversarial state sync (the trust model's residual risk,
        state_sync.py:31-40): a byzantine provider announces a wildly
        inflated step — so every rejoiner targets it — and serves IN-RANGE
        garbage (its real params sign-flipped: finite, magnitude-bounded,
        invisible to the sanity guard). The rejoiner must adopt the poison
        (verified from its log) and then converge anyway: its next
        byzantine rounds contract it to the robust aggregate, and the
        honest-majority trimmed mean discards its outlier contribution."""
        coord, addr = start_coordinator()
        vols = []
        try:
            common = [
                "--averaging", "byzantine", "--method", "trimmed_mean",
                "--average-every", "6", "--min-group", "2",
                "--join-timeout", "20", "--gather-timeout", "15",
            ]

            # Providers run effectively forever (killed at teardown; only the
            # rejoiner is awaited) — under CPU contention a jax subprocess
            # can take a minute to come up, and a provider that finishes and
            # LEAVES before the rejoiner's pull would vacuously pass the
            # no-candidates path instead of exercising the poisoned pull.
            # capture=False: nobody drains their output.
            #
            # Topology is deliberately minimal (1 honest + poisoner +
            # rejoiner): every extra jax process on the one shared core
            # stretches the honest leader's round cadence from seconds to
            # minutes, and the rejoiner's begin-wait windows stop aligning
            # with it (observed as flaky 'no begin from leader' skips at
            # 4-5 processes).
            #
            # Order matters too: the honest peer FIRST, poisoner only after
            # it's alive. Startup pulls are how the poison spreads — an
            # honest peer booting after the poisoner would pull the lie
            # itself and re-announce the inflated step under its own
            # (honest) id, and the rejoiner would then pull honest params
            # from it (observed in an earlier run of this test).
            # --steps is effectively unbounded: on a QUIET machine this tiny
            # model trains at thousands of steps/s, so a "large" finite
            # budget (4000) is gone in seconds and the providers are dead
            # before the rejoiner's jax import finishes — observed as the
            # rejoiner pulling fine and then failing every round against an
            # empty swarm.
            vols = [start_volunteer(
                addr, "honest0", common + ["--steps", "100000000", "--seed", "0"],
                capture=False,
            )]
            assert wait_swarm_alive(addr, 1), "honest provider never came up"
            # Lie far above any honest announce in this test's lifetime
            # (the poisoner adds it to its own live step, so it stays ahead
            # of honest peers training at the same rate).
            vols.append(start_volunteer(
                addr, "poisoner",
                common + ["--steps", "100000000", "--seed", "9"],
                {"DVC_CHAOS_STATE_POISON": "1000000000,-1"}, capture=False,
            ))
            assert wait_swarm_alive(addr, 2), "poisoner never came up"
            time.sleep(3)  # join -> state announce gap
            # Blocking rounds (--no-overlap): the rejoiner's local steps are
            # ~ms each post-adoption, so overlapped mode would fire exactly
            # ONE round attempt for the whole run — whether it aligns with
            # the honest leader's next begin is a coin flip. Blocking mode
            # retries at every cadence until one round completes.
            rejoiner = start_volunteer(
                addr, "rejoiner",
                common + ["--no-overlap", "--steps", "120", "--seed", "5"],
            )
            vols.append(rejoiner)
            s, out = wait_done(rejoiner, timeout=240)
            # The poisoned pull actually happened: targeted the liar's step.
            m = re.search(r"pulled state at step (\d+) from poisoner", out)
            assert m, f"rejoiner never pulled from the poisoner:\n{out[-2000:]}"
            # The lie is 1e9 (far above any honest announce, comfortably
            # inside int32 for the adopted step counter).
            assert int(m.group(1)) > 900_000_000, m.group(0)
            # ...and robust rounds contracted it back to the swarm anyway.
            assert s["rounds_ok"] >= 1, out
            assert s["final_loss"] == s["final_loss"], out  # not NaN
            assert s["final_loss"] < 1.5, out  # well under the ~2.3 chance line
        finally:
            coord.kill()
            for v in vols:
                if v.poll() is None:
                    v.kill()

    def test_sigterm_preemption_graceful(self, tmp_path):
        """SIGTERM (TPU-VM preemption notice) -> checkpoint + clean exit."""
        ckpt, metrics = str(tmp_path / "ckpt"), str(tmp_path / "preempt.jsonl")
        v = start_volunteer_standalone = subprocess.Popen(
            [
                sys.executable, os.path.join(REPO, "run_volunteer.py"),
                "--peer-id", "preempt-me", "--steps", "100000", "--batch-size", "16",
                *TINY_MLP, "--checkpoint-dir", ckpt, "--metrics", metrics,
            ],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=_env(),
        )
        wait_trained(metrics, records=10)  # well into training
        v.send_signal(signal.SIGTERM)
        summary, out = wait_done(v, timeout=60)
        assert v.returncode == 0, out
        assert summary["steps"] > 0
        assert os.path.isdir(ckpt) and os.listdir(ckpt), "no checkpoint written"

    def test_checkpoint_resume(self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        base = ["--steps", "20", "--checkpoint-dir", ckpt, *TINY_MLP, "--batch-size", "8"]
        v1 = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "run_volunteer.py"), *base],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=_env(),
        )
        s1, out1 = wait_done(v1)
        assert s1["steps"] == 20, out1
        v2 = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "run_volunteer.py"),
             "--steps", "5", "--checkpoint-dir", ckpt, *TINY_MLP, "--batch-size", "8"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=_env(),
        )
        s2, out2 = wait_done(v2)
        assert s2["steps"] == 25, f"resume failed (expected 20+5):\n{out2}"


def test_async_checkpoint_roundtrip(tmp_path):
    """save_async writes the same restorable snapshot as save, off-thread."""
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.training import checkpoint
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    ckpt = str(tmp_path / "ck")
    t1 = Trainer(get_model("mnist_mlp", d_hidden=16), batch_size=8, seed=3)
    t1.run(steps=7, log_every=0)
    assert checkpoint.save_async(t1, ckpt)
    assert checkpoint.wait_pending_saves(t1)
    assert checkpoint.latest_step(ckpt) == 7

    t2 = Trainer(get_model("mnist_mlp", d_hidden=16), batch_size=8, seed=99)
    assert checkpoint.maybe_restore(t2, ckpt)
    assert int(t2.state.step) == 7
    import jax
    import numpy as np

    for a, b in zip(
        jax.tree_util.tree_leaves(t1.state.params),
        jax.tree_util.tree_leaves(t2.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the layers under the entrypoints, in-process over real localhost sockets --------------


def run(coro):
    return asyncio.run(coro)


@pytest.mark.transport
class TestTransport:
    def test_echo_roundtrip(self):
        async def main():
            server = Transport()

            async def echo(args, payload):
                return {"got": args["x"]}, payload[::-1]

            server.register("echo", echo)
            addr = await server.start()
            client = Transport()
            ret, payload = await client.call(addr, "echo", {"x": 42}, b"abc")
            await server.close()
            return ret, payload

        ret, payload = run(main())
        assert ret == {"got": 42}
        assert payload == b"cba"

    def test_survives_garbage_frames(self):
        """Frame-parser fuzz: raw TCP garbage — bad magic, truncated
        headers, oversize lengths, invalid JSON meta, non-dict JSON meta —
        must each produce a clean drop (no task crash), and the server must
        keep serving legitimate RPCs afterwards."""
        import json as _json
        import zlib

        from distributedvolunteercomputing_tpu.swarm.transport import (
            _HEADER, MAGIC, VERSION,
        )

        def frame(meta_b: bytes, payload: bytes = b"", magic=MAGIC, version=VERSION):
            crc = zlib.crc32(payload) & 0xFFFFFFFF
            return (
                _HEADER.pack(magic, version, 1, len(meta_b), len(payload), crc)
                + meta_b + payload
            )

        garbage = [
            b"\x00" * 64,                                  # not a frame at all
            frame(b"{}", magic=b"XX"),                     # bad magic
            frame(b"{}", version=99),                      # bad version
            frame(b"not json at all"),                     # invalid JSON meta
            frame(_json.dumps([1, 2, 3]).encode()),        # JSON, not an object
            frame(_json.dumps("str").encode()),            # JSON scalar meta
            _HEADER.pack(MAGIC, VERSION, 1, 10, 0, 0),     # truncated: no meta
            _HEADER.pack(MAGIC, VERSION, 1, 0, 1 << 62, 0),  # absurd payload len
            frame(b"[" * 100_000 + b"1" + b"]" * 100_000),  # parser stack bomb
        ]

        async def main():
            server = Transport()

            async def echo(args, payload):
                return {"ok": True}, payload

            server.register("echo", echo)
            addr = await server.start()
            for g in garbage:
                reader, writer = await asyncio.open_connection(*addr)
                writer.write(g)
                try:
                    await writer.drain()
                    # EOF makes a server blocked on readexactly for bytes
                    # that will never come fail fast (IncompleteReadError)
                    # instead of stalling this test for the full timeout.
                    writer.write_eof()
                    # Server replies with an error frame or just drops us;
                    # either way the connection ends without wedging.
                    await asyncio.wait_for(reader.read(1 << 16), timeout=5)
                except (ConnectionResetError, BrokenPipeError, asyncio.TimeoutError):
                    pass
                finally:
                    writer.close()
            # The real client still works after every garbage volley.
            client = Transport()
            ret, payload = await client.call(addr, "echo", {"x": 1}, b"ok")
            await server.close()
            return ret, payload

        ret, payload = run(main())
        assert ret == {"ok": True}
        assert payload == b"ok"

    def test_large_binary_payload(self):
        async def main():
            server = Transport()

            async def double(args, payload):
                arr = np.frombuffer(payload, np.float32) * 2
                return {}, arr.tobytes()

            server.register("double", double)
            addr = await server.start()
            client = Transport()
            data = np.arange(300_000, dtype=np.float32)
            _, resp = await client.call(addr, "double", payload=data.tobytes())
            await server.close()
            return data, np.frombuffer(resp, np.float32)

        data, resp = run(main())
        np.testing.assert_allclose(resp, data * 2)

    def test_auth_roundtrip_and_rejection(self):
        """Shared-secret HMAC frame auth: matching secrets work end-to-end;
        a client with the wrong secret (or none) is rejected — the whole
        swarm tier crosses this transport, so this one gate is what keeps
        identity spoofing out of the Byzantine first-write-wins rule."""

        async def main():
            server = Transport(secret=b"s3kr1t")

            async def echo(args, payload):
                return {"got": args["x"]}, payload

            server.register("echo", echo)
            addr = await server.start()

            ok_client = Transport(secret=b"s3kr1t")
            ret, payload = await ok_client.call(addr, "echo", {"x": 1}, b"hi")
            assert ret == {"got": 1} and payload == b"hi"

            outcomes = {}
            for name, client in (
                ("wrong", Transport(secret=b"wrong")),
                ("none", Transport()),
            ):
                try:
                    # The server drops unauthenticated frames; from the
                    # client side that surfaces as an error or a dead
                    # connection — never a successful call.
                    await client.call(addr, "echo", {"x": 2}, b"x", timeout=5.0)
                    outcomes[name] = "accepted"
                except (
                    RPCError, OSError, asyncio.IncompleteReadError,
                    asyncio.TimeoutError, TimeoutError,
                ):
                    outcomes[name] = "rejected"
            await server.close()
            return outcomes

        assert run(main()) == {"wrong": "rejected", "none": "rejected"}

    def test_auth_client_rejects_unauthenticated_server(self):
        """Auth is mutual: a secret-holding client refuses responses from a
        server that can't sign them (e.g. a man-in-the-middle without the
        secret)."""

        async def main():
            server = Transport()  # no secret: cannot sign responses

            async def echo(args, payload):
                return {}, payload

            server.register("echo", echo)
            addr = await server.start()
            client = Transport(secret=b"s3kr1t")
            try:
                await client.call(addr, "echo", {}, b"x", timeout=5.0)
                outcome = "accepted"
            except (RPCError, OSError, asyncio.TimeoutError, TimeoutError):
                outcome = "rejected"
            await server.close()
            return outcome

        assert run(main()) == "rejected"

    def test_auth_timestamp_window(self):
        """Frames outside the auth window are rejected (bounds replay)."""

        async def main():
            server = Transport(secret=b"k", auth_window=0.0)  # everything stale

            async def echo(args, payload):
                return {}, payload

            server.register("echo", echo)
            addr = await server.start()
            client = Transport(secret=b"k")
            try:
                await client.call(addr, "echo", {}, b"", timeout=5.0)
                outcome = "accepted"
            except (RPCError, OSError, asyncio.TimeoutError, TimeoutError):
                outcome = "rejected"
            await server.close()
            return outcome

        assert run(main()) == "rejected"

    def test_auth_rejects_replayed_request_frame(self):
        """A captured request frame (e.g. a membership heartbeat) re-sent
        within the auth window must be refused: every legitimate request
        carries a fresh rid inside the MAC'd meta, so the server treats an
        already-accepted MAC as a replay."""
        import json as _json
        import time as _time
        import zlib as _zlib

        from distributedvolunteercomputing_tpu.swarm.transport import (
            _HEADER, MAGIC, TYPE_ERR, TYPE_REQ, TYPE_RESP, VERSION,
        )

        async def main():
            server = Transport(secret=b"s3kr1t")
            calls = []

            async def ping(args, payload):
                calls.append(args)
                return {"ok": True}, b""

            server.register("ping", ping)
            addr = await server.start()
            # A second node in the same swarm (same secret): the captured
            # frame must be unusable there too (cross-node replay).
            other = Transport(secret=b"s3kr1t")

            async def ping2(args, payload):
                calls.append(("other", args))
                return {"ok": True}, b""

            other.register("ping", ping2)
            other_addr = await other.start()
            # Craft ONE authenticated request frame (what an eavesdropper
            # inside the window holds), then send the identical bytes twice
            # on two fresh connections.
            signer = Transport(secret=b"s3kr1t")
            meta = {
                "rid": "feedfacefeedface", "method": "ping", "args": {"n": 1},
                "dst": [addr[0], addr[1]], "ts": round(_time.time(), 3),
            }
            meta["auth"] = signer._mac(TYPE_REQ, meta, b"")
            meta_b = _json.dumps(meta).encode()
            frame = _HEADER.pack(
                MAGIC, VERSION, TYPE_REQ, len(meta_b), 0,
                _zlib.crc32(b"") & 0xFFFFFFFF,
            ) + meta_b

            async def send_raw(to):
                reader, writer = await asyncio.open_connection(*to)
                try:
                    writer.write(frame)
                    await writer.drain()
                    return await signer._read_frame(reader)
                finally:
                    writer.close()

            ftype1, meta1, _ = await send_raw(addr)
            ftype2, meta2, _ = await send_raw(addr)
            ftype3, meta3, _ = await send_raw(other_addr)
            await server.close()
            await other.close()
            assert ftype1 == TYPE_RESP and meta1["ret"] == {"ok": True}
            # same-node replay: rejected by the seen-MAC cache
            assert ftype2 == TYPE_ERR and "replay" in meta2.get("error", "")
            # cross-node replay: rejected by the MAC'd dst binding
            assert ftype3 == TYPE_ERR and "different node" in meta3.get("error", "")
            assert len(calls) == 1  # the handler ran exactly once, on one node

        run(main())

    def test_dst_alias_matching(self):
        """The MAC'd destination must match this node: port exactly, host
        by legitimate alias (advertised, bound, loopback). Distinct nodes'
        alias sets can't collide — same machine implies distinct ports."""
        t = Transport(host="0.0.0.0", advertise_host="10.1.2.3")
        t._port = 7000
        assert t._dst_is_me(["10.1.2.3", 7000])   # advertised
        assert t._dst_is_me(["0.0.0.0", 7000])    # bound
        assert t._dst_is_me(["127.0.0.1", 7000])  # loopback dial
        assert t._dst_is_me(["localhost", 7000])
        assert not t._dst_is_me(["10.9.9.9", 7000])   # another machine
        assert not t._dst_is_me(["10.1.2.3", 7001])   # another node, same host
        assert not t._dst_is_me(None)                 # frame without dst
        assert not t._dst_is_me(["10.1.2.3"])         # malformed

    def test_unknown_method_raises(self):
        async def main():
            server = Transport()
            addr = await server.start()
            client = Transport()
            try:
                with pytest.raises(RPCError, match="no such method"):
                    await client.call(addr, "nope")
            finally:
                await server.close()

        run(main())

    def test_handler_exception_propagates(self):
        async def main():
            server = Transport()

            async def boom(args, payload):
                raise ValueError("kaboom")

            server.register("boom", boom)
            addr = await server.start()
            client = Transport()
            try:
                with pytest.raises(RPCError, match="kaboom"):
                    await client.call(addr, "boom")
            finally:
                await server.close()

        run(main())

    def test_dead_peer_times_out(self):
        async def main():
            client = Transport()
            with pytest.raises((OSError, asyncio.TimeoutError)):
                await client.call(("127.0.0.1", 1), "ping", timeout=2.0)

        run(main())


async def _spawn_swarm(n, bootstrap_first=True):
    nodes = []
    for i in range(n):
        node = DHTNode(Transport())
        boot = [nodes[0].transport.addr] if (nodes and bootstrap_first) else []
        await node.start(bootstrap=boot)
        nodes.append(node)
    return nodes


async def _teardown(nodes):
    for n in nodes:
        await n.transport.close()


class TestDHT:
    def test_store_get_across_nodes(self):
        async def main():
            nodes = await _spawn_swarm(5)
            try:
                await nodes[1].store("model_version", {"step": 120}, ttl=30)
                seen = await nodes[4].get_value("model_version")
                return seen
            finally:
                await _teardown(nodes)

        assert run(main()) == {"step": 120}

    def test_subkey_merge_from_different_writers(self):
        async def main():
            nodes = await _spawn_swarm(4)
            try:
                for i, node in enumerate(nodes):
                    await node.store("peers", {"rank": i}, subkey=f"peer{i}", ttl=30)
                views = [await n.get("peers") for n in nodes]
                return views
            finally:
                await _teardown(nodes)

        views = run(main())
        for view in views:
            assert set(view) == {"peer0", "peer1", "peer2", "peer3"}
            assert view["peer2"] == {"rank": 2}

    def test_expiry(self):
        async def main():
            nodes = await _spawn_swarm(3)
            try:
                await nodes[0].store("ephemeral", "x", ttl=0.5)
                now = await nodes[2].get_value("ephemeral")
                await asyncio.sleep(0.8)
                later = await nodes[2].get_value("ephemeral", default="GONE")
                return now, later
            finally:
                await _teardown(nodes)

        now, later = run(main())
        assert now == "x"
        assert later == "GONE"

    def test_survives_node_death(self):
        async def main():
            nodes = await _spawn_swarm(6)
            try:
                await nodes[1].store("k", "v", ttl=30)
                # kill half the swarm, including the bootstrap node
                for victim in nodes[:3]:
                    await victim.transport.close()
                return await nodes[4].get_value("k", default="LOST")
            finally:
                await _teardown(nodes[3:])

        # replication factor K=8 > swarm size, so every node holds a replica
        assert run(main()) == "v"


class TestMembership:
    def test_join_heartbeat_leave(self):
        async def main():
            nodes = await _spawn_swarm(3)
            try:
                members = [
                    SwarmMembership(node, f"vol{i}", ttl=2.0) for i, node in enumerate(nodes)
                ]
                for m in members:
                    await m.join()
                alive = await members[0].alive_peers()
                await members[2].leave()
                after_leave = await members[0].alive_peers()
                return alive, after_leave
            finally:
                await _teardown(nodes)

        alive, after_leave = run(main())
        assert set(alive) == {"vol0", "vol1", "vol2"}
        assert set(after_leave) == {"vol0", "vol1"}

    def test_crashed_peer_expires(self):
        async def main():
            nodes = await _spawn_swarm(3)
            try:
                members = [
                    SwarmMembership(node, f"vol{i}", ttl=1.2) for i, node in enumerate(nodes)
                ]
                for m in members:
                    await m.join()
                # simulate kill -9: no leave(), just stop heartbeats + socket
                members[1]._heartbeat_task.cancel()
                await nodes[1].transport.close()
                await asyncio.sleep(1.6)
                alive = await members[0].alive_peers()
                return alive
            finally:
                await _teardown([nodes[0], nodes[2]])

        alive = run(main())
        assert "vol1" not in alive
        assert {"vol0", "vol2"} <= set(alive)


class TestCoordinator:
    def test_status_aggregates(self):
        async def main():
            coord = Coordinator()
            caddr = await coord.start()
            try:
                nodes = []
                for i in range(3):
                    node = DHTNode(Transport())
                    await node.start(bootstrap=[caddr])
                    nodes.append(node)
                    m = SwarmMembership(node, f"vol{i}", ttl=10.0)
                    await m.join()
                    await node.transport.call(
                        caddr,
                        "coord.report",
                        {"peer": f"vol{i}", "step": 10 * i, "samples_per_sec": 100.0},
                    )
                status, _ = await coord._rpc_status({}, b"")
                await _teardown(nodes)
                return status
            finally:
                await coord.close()

        status = run(main())
        assert status["n_alive"] == 3
        assert status["swarm_samples_per_sec"] == pytest.approx(300.0)
