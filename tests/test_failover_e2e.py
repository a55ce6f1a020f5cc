"""Kill-at-phase e2e: real volunteer PROCESSES through the actual CLI
entrypoints, the leader SIGKILLs itself at an instrumented round phase
(DVC_CHAOS_LEADER_DIE_PHASE), and the survivors must commit via failover
recovery and finish their runs.

Slow lane (subprocess jax startup is ~a minute per volunteer under sandbox
contention); the fast in-process twin of this matrix is
tests/test_failover.py::TestKillAtPhase.
"""

import json
import os
import re
import signal
import subprocess
import sys
import time

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.failover]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_MLP = ["--model-override", "d_hidden=16"]


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def start_coordinator():
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "coordinator.py")],
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
    env = _env()
    if env_extra:
        env.update(env_extra)
    out = subprocess.PIPE if capture else subprocess.DEVNULL
    err = subprocess.STDOUT if capture else subprocess.DEVNULL
    return subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "run_volunteer.py"),
            "--coordinator", coord_addr,
            "--peer-id", peer_id,
            "--batch-size", "16",
            "--lr", "0.01",
            *TINY_MLP,
            *extra,
        ],
        stdout=out, stderr=err, text=True, env=env,
    )


def wait_done(proc, timeout=300):
    out, _ = proc.communicate(timeout=timeout)
    for line in out.splitlines():
        if line.startswith("VOLUNTEER_DONE "):
            return json.loads(line[len("VOLUNTEER_DONE "):]), out
    raise AssertionError(f"no VOLUNTEER_DONE in output:\n{out[-3000:]}")


def wait_swarm_alive(coord_addr, n, timeout=180):
    """Poll coord.status until >= n peers are alive (deterministic
    readiness — a jax subprocess can take a minute to come up)."""
    import asyncio

    from distributedvolunteercomputing_tpu.swarm.transport import Transport

    host, _, port = coord_addr.rpartition(":")

    async def poll():
        t = Transport()
        try:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                try:
                    ret, _ = await t.call(
                        (host, int(port)), "coord.status", timeout=5.0
                    )
                    if int(ret.get("n_alive", 0)) >= n:
                        return True
                except Exception:
                    pass
                await asyncio.sleep(2.0)
            return False
        finally:
            await t.close()

    return asyncio.run(poll())


@pytest.mark.parametrize(
    "phase", ["pre_arm", "mid_stream", "post_partial_commit", "pre_fetch"]
)
def test_leader_sigkill_at_phase_survivors_recover(phase):
    """Peer 'a0' sorts first, so it leads every round it joins — and
    SIGKILLs itself at ``phase`` of its first led round. 'b1' and 'c2'
    must depose it, recover that round via the successor, and finish the
    run with healthy rounds afterwards (no EF on the f32 wire; the
    bit-level EF check across a recovered round is in-process:
    test_failover.py::test_ef_residual_bitwise_across_recovered_round)."""
    coord, addr = start_coordinator()
    common = [
        "--averaging", "sync", "--average-every", "5", "--steps", "900",
        "--max-group", "4",
        "--join-timeout", "20", "--gather-timeout", "15",
    ]
    vols = []
    try:
        # Survivors first: the doomed leader's first led round must contain
        # BOTH of them (a 2-member round would leave one survivor — below
        # min_group, correctly unrecoverable), so a0 starts only once b1/c2
        # are alive, and requires a 3-member group for its own rounds.
        # DVC_STEP_DELAY_MS stretches the survivors' runs so they are still
        # training when a0 (a jax subprocess can take a minute to come up)
        # joins, dies, and must be recovered from.
        slow = {"DVC_STEP_DELAY_MS": "50"}
        vols.append(start_volunteer(
            addr, "b1", [*common, "--min-group", "2"], env_extra=slow,
        ))
        vols.append(start_volunteer(
            addr, "c2", [*common, "--min-group", "2"], env_extra=slow,
        ))
        assert wait_swarm_alive(addr, 2), "survivors never came up"
        # The doomed leader's output goes to DEVNULL: nobody drains its
        # pipe after the SIGKILL, and a filled pipe would stall it BEFORE
        # the instrumented phase.
        vols.append(start_volunteer(
            addr, "a0", [*common, "--min-group", "3"],
            env_extra={"DVC_CHAOS_LEADER_DIE_PHASE": phase}, capture=False,
        ))
        rc = vols[2].wait(timeout=300)
        assert rc == -signal.SIGKILL, f"leader exited {rc}, expected SIGKILL"
        summaries = [wait_done(v)[0] for v in vols[:2]]
    finally:
        coord.kill()
        for v in vols:
            if v.poll() is None:
                v.kill()
    for s in summaries:
        assert s.get("rounds_ok", 0) >= 1, s
    recovered = [s.get("failover", {}).get("rounds_recovered", 0) for s in summaries]
    deposed = [s.get("failover", {}).get("leaders_deposed", 0) for s in summaries]
    assert any(r >= 1 for r in recovered), (recovered, summaries)
    assert all(d >= 1 for d in deposed), (deposed, summaries)
