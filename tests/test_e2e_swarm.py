"""End-to-end swarm tests: real processes, real entrypoints, real churn.

This is the reference's own test shape (SURVEY.md §4): N volunteer PROCESSES
on localhost, a coordinator process, kill -9 mid-run — the whole L6-L2 stack
through the actual CLI entrypoints.
"""

import json
import os
import re
import signal
import subprocess
import sys
import time

import pytest

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
            # Kill only once the victim has demonstrably TRAINED (metrics
            # records exist): a wall-clock sleep can land the kill during
            # JAX compile, quietly degrading this to a 2-node test.
            deadline = time.time() + 90
            while time.time() < deadline:
                try:
                    if sum(1 for _ in open(victim_metrics)) >= 3:
                        break
                except OSError:
                    pass
                time.sleep(1.0)
            else:
                raise AssertionError("victim volunteer never started training")
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
        ckpt = str(tmp_path / "ckpt")
        v = start_volunteer_standalone = subprocess.Popen(
            [
                sys.executable, os.path.join(REPO, "run_volunteer.py"),
                "--peer-id", "preempt-me", "--steps", "100000", "--batch-size", "16",
                *TINY_MLP, "--checkpoint-dir", ckpt,
            ],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=_env(),
        )
        time.sleep(15)  # well into training
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
