"""Shard-holder kill-at-phase e2e: real volunteer PROCESSES through the
actual CLI entrypoints (--zone-shards), a shard-holding leader SIGKILLs
itself at an instrumented round phase (DVC_CHAOS_LEADER_DIE_PHASE) or
mid-re-shard (DVC_CHAOS_SHARD_DIE_PHASE=mid_resharding), and:

  - the survivors of its shard-scoped group commit the round via leader
    failover (the round commits THROUGH the loss), and
  - its zone-mate re-shards at generation+1 and recovers the dead
    holder's shard from its runner-up replica — without restarting the
    epoch (the mate's own run finishes normally, recovery gauges on its
    VOLUNTEER_DONE line).

Topology per cell: zone "dc" holds TWO sharded volunteers (the doomed
holder, advertising shard 0, and its mate on shard 1 — ids searched so
the 2-member HRW map splits 1/1); zones "zb"/"zc" hold one sharded
volunteer each (a singleton zone owns every shard and advertises its
primary, 0), so the cross-rotation shard-0 group is exactly {victim,
xb1, xc2} with the victim sorting first — it leads every round it joins.

Slow lane (subprocess jax startup is ~a minute per volunteer under
sandbox contention); the fast in-process twin of this matrix is
tests/test_sharding.py (TestShardedRounds + the mid_resharding manager
kill in TestReshardRecovery).
"""

import json
import os
import re
import signal
import subprocess
import sys
import time

import pytest

from distributedvolunteercomputing_tpu.swarm.sharding import ShardMap

pytestmark = [pytest.mark.slow, pytest.mark.sharding, pytest.mark.failover]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_MLP = ["--model-override", "d_hidden=16"]
NAMESPACE = "mnist_mlp/params"


def _dc_pair():
    """Deterministic id search: a zone-"dc" pair whose k=2 HRW map gives
    the a-prefixed member (the doomed leader — it must sort before the
    xb1/xc2 survivors) shard 0 and the mate shard 1."""
    for trial in range(4000):
        va, vm = f"a{trial:04d}", f"m{trial:04d}"
        m = ShardMap(
            members=(va, vm), k=2, gen=0, domain=f"dc|{NAMESPACE}"
        )
        if m.shards_of(va) == [0] and m.shards_of(vm) == [1]:
            return va, vm
    raise AssertionError("no balanced dc pair found")


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


def start_volunteer(coord_addr, peer_id, zone, extra, env_extra=None,
                    capture=True):
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
            "--zone", zone,
            "--zone-shards", "2",
            "--averaging", "sync", "--average-every", "5", "--steps", "900",
            "--group-size", "3", "--cross-zone-every-k", "1",
            "--max-group", "4",
            "--join-timeout", "20", "--gather-timeout", "15",
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


def _spawn_base(addr, mate_id, survivor_delay_ms=50):
    """The three long-lived volunteers every cell shares: the dc mate and
    the two singleton-zone shard-0 survivors, runs stretched so they are
    still training when the late-joining victim dies on them. Cells with
    a longer churn sequence before the kill (mid_resharding waits for a
    newcomer's full jax startup first) pass a bigger survivor delay, so
    the survivors' 900 steps still span the death."""
    slow = {"DVC_STEP_DELAY_MS": str(survivor_delay_ms)}
    # The mate's rounds all SKIP (it advertises shard 1 and is the only
    # s1 holder), so unlike the round-throttled survivors it would race
    # through its steps and exit before the late-starting victim even
    # dies — stretch it so its run spans the whole kill window.
    vols = [
        start_volunteer(addr, mate_id, "dc",
                        ["--min-group", "2"],
                        env_extra={"DVC_STEP_DELAY_MS": "200"}),
        start_volunteer(addr, "xb1", "zb",
                        ["--min-group", "2"], env_extra=slow),
        start_volunteer(addr, "xc2", "zc",
                        ["--min-group", "2"], env_extra=slow),
    ]
    assert wait_swarm_alive(addr, 3), "base swarm never came up"
    return vols


@pytest.mark.parametrize(
    "phase", ["pre_arm", "mid_stream", "post_partial_commit"]
)
def test_shard_holder_sigkill_at_leader_phase(phase):
    """The victim (dc's shard-0 holder, smallest id) leads its shard-0
    cross group and SIGKILLs itself at ``phase``. xb1/xc2 must depose it
    and commit through the loss; the dc mate must re-shard and recover
    shard 0 from its replica, finishing its run with nothing missing."""
    victim_id, mate_id = _dc_pair()
    coord, addr = start_coordinator()
    vols = []
    victim = None
    try:
        vols = _spawn_base(addr, mate_id)
        # The victim is throttled too: unthrottled it blasts its 900
        # steps in ~20s, cheap-skipping every round as a singleton
        # before the survivors' shard adverts even reach its membership
        # snapshot — and exits 0 instead of dying at the phase point.
        victim = start_volunteer(
            addr, victim_id, "dc", ["--min-group", "3"],
            env_extra={"DVC_CHAOS_LEADER_DIE_PHASE": phase,
                       "DVC_STEP_DELAY_MS": "100"}, capture=False,
        )
        rc = victim.wait(timeout=300)
        assert rc == -signal.SIGKILL, f"victim exited {rc}, expected SIGKILL"
        summaries = [wait_done(v)[0] for v in vols]
    finally:
        coord.kill()
        for v in vols + ([victim] if victim is not None else []):
            if v.poll() is None:
                v.kill()
    mate, b1, c2 = summaries
    # The round commits through the loss: survivors deposed the dead
    # leader and recovered its round.
    for s in (b1, c2):
        assert s.get("rounds_ok", 0) >= 1, s
    recovered = [s.get("failover", {}).get("rounds_recovered", 0)
                 for s in (b1, c2)]
    deposed = [s.get("failover", {}).get("leaders_deposed", 0)
               for s in (b1, c2)]
    assert any(r >= 1 for r in recovered), (recovered, summaries)
    assert all(d >= 1 for d in deposed), (deposed, summaries)
    # The shard comes back without an epoch restart: the mate saw the
    # churn (victim joined, then died), re-sharded past its initial map,
    # and finished holding everything it owns.
    assert mate.get("shard_reshardings", 0) >= 2, mate
    assert mate.get("shard_missing", -1) == 0, mate
    assert mate.get("shard_recoveries_failed", -1) == 0, mate
    assert mate.get("steps", 0) >= 900, mate  # full run, no restart


def test_shard_holder_sigkill_mid_resharding():
    """The fourth matrix column: the victim dies INSIDE a fenced
    re-shard (triggered by a newcomer joining its zone). The drop-after-
    phase protocol means its old copies were still intact at death, so
    the zone's survivors re-shard again and recover cleanly."""
    victim_id, mate_id = _dc_pair()
    coord, addr = start_coordinator()
    vols = []
    victim = newcomer = None
    try:
        vols = _spawn_base(addr, mate_id, survivor_delay_ms=150)
        victim = start_volunteer(
            addr, victim_id, "dc", ["--min-group", "2"],
            env_extra={"DVC_CHAOS_SHARD_DIE_PHASE": "mid_resharding",
                       "DVC_STEP_DELAY_MS": "100"},
            capture=False,
        )
        assert wait_swarm_alive(addr, 4), "victim never came up"
        # Zone churn: a newcomer joins dc — every dc holder re-shards to
        # adopt it, and the victim dies at that re-shard's phase point.
        # The newcomer runs SLOWER than the mate so it outlives it: if it
        # left first, the mate's final re-shard would hand it the
        # departed newcomer's shard with nobody left to pull from, and
        # the shard_missing==0 exit assertion would race the dissolve.
        newcomer = start_volunteer(
            addr, f"n{mate_id}", "dc", ["--min-group", "2"],
            env_extra={"DVC_STEP_DELAY_MS": "250"}, capture=False,
        )
        rc = victim.wait(timeout=300)
        assert rc == -signal.SIGKILL, f"victim exited {rc}, expected SIGKILL"
        summaries = [wait_done(v)[0] for v in vols]
    finally:
        coord.kill()
        for v in vols + [p for p in (victim, newcomer) if p is not None]:
            if v.poll() is None:
                v.kill()
    mate, b1, c2 = summaries
    # Survivors' rounds keep committing (the shard-0 group re-forms
    # without the dead holder at the next rotations).
    for s in (b1, c2):
        assert s.get("rounds_ok", 0) >= 1, s
    # The mate re-sharded at least three times (initial, victim/newcomer
    # churn, victim loss) and holds everything it owns — nothing was
    # stranded by the mid-re-shard death, and nobody restarted anything.
    assert mate.get("shard_reshardings", 0) >= 3, mate
    assert mate.get("shard_missing", -1) == 0, mate
    assert mate.get("shard_recoveries_failed", -1) == 0, mate
    assert mate.get("steps", 0) >= 900, mate
