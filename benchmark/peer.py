"""The stub peer: a load generator for the round protocol, not a trainer.

One child process of the runner, started with ``JAX_PLATFORMS=cpu`` before the
runner imports JAX. It hosts the coordinator (the swarm's bootstrap node) and
one stub peer built from the program's own ``Transport``, ``DHTNode``,
``SwarmMembership`` and ``make_averager`` (as ``tests/test_averaging.py``
builds them), and calls ``average(tree, round_no, weight)`` in a loop. It
trains nothing and compiles no model, so it is never what a round waits for.
What it contributes, with what weight, under which peer id and with which
advertisement is data: the ``peers`` entry of the traffic file. The protocol
is the program's, so a PR that changes the protocol changes both ends together.

It enters the rendezvous when the volunteer does: the runner writes one line
to this process's standard input at each launch, and one ``average()`` call
follows. That is a peer on the volunteer's own step cadence. It cannot simply
wait at the rendezvous: a rendezvous record outlives its round by up to 60 s
(``matchmaking.py``: ``ttl=60.0``), so a leader that comes back at once forms
a ghost group with the volunteer's stale record and is stuck in it when the
volunteer arrives (seen on the chip, PR 25).

Prints ``PEER_READY host:port`` (the coordinator's address) once the
coordinator listens, and ``PEER_DONE {json}`` on SIGTERM.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


async def _serve(args: argparse.Namespace) -> dict:
    import jax
    import numpy as np

    from benchmark import datagen
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.swarm.averager import make_averager
    from distributedvolunteercomputing_tpu.swarm.coordinator import Coordinator
    from distributedvolunteercomputing_tpu.swarm.dht import DHTNode
    from distributedvolunteercomputing_tpu.swarm.membership import SwarmMembership
    from distributedvolunteercomputing_tpu.swarm.transport import Transport

    with open(args.config) as fh:
        cfg = json.load(fh)
    with open(args.traffic) as fh:
        traffic = json.load(fh)
    spec = traffic["peers"][args.peer]
    vol = {**cfg["volunteer"], **traffic["volunteer"]}

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop.set)

    coord = Coordinator("127.0.0.1", 0)
    host, port = await coord.start()
    print(f"PEER_READY {host}:{port}", flush=True)

    model = cfg["registry_model"]
    average_what = vol.get("average_what", "params")
    transport = Transport("127.0.0.1", 0)
    await transport.start()
    dht = DHTNode(transport)
    await dht.start(bootstrap=[(host, port)])
    membership = SwarmMembership(
        dht, spec["peer_id"],
        extra_info={
            "model": model,
            "avg_ns": f"{model}/{average_what}",
            **spec.get("advertise", {}),
        },
    )
    await membership.join()
    averager = make_averager(
        vol["averaging"], transport, dht, membership,
        min_group=vol.get("min_group", 2),
        join_timeout=float(spec["join_timeout"]),
        gather_timeout=float(spec["gather_timeout"]),
        wire=vol.get("wire", "f32"),
        namespace=f"{model}/{average_what}",
    )

    bundle = get_model(model, **cfg.get("model_overrides", {}))
    shapes = bundle.avg_select(jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    first = spec["first_contribution"]
    if first["kind"] != "seeded_normal":
        raise ValueError(f"unknown first_contribution kind {first['kind']!r}")
    tree = await asyncio.to_thread(
        datagen.seeded_tree, shapes,
        datagen.peer_seed(args.seed, args.peer), float(first["scale"]),
    )
    # The volunteer's own weight on the happy path (volunteer.py:575-583), so
    # the expected result is the plain mean.
    weight = float(vol["batch_size"] * vol["average_every"])

    rounds = {"ok": 0, "none": 0, "missed": 0}
    launches: asyncio.Queue = asyncio.Queue()

    def read_launches() -> None:
        for _ in sys.stdin:
            loop.call_soon_threadsafe(launches.put_nowait, True)

    threading.Thread(target=read_launches, daemon=True).start()

    async def rounds_forever() -> None:
        round_no = 0
        while True:
            while not launches.empty():
                # A launch that came while the last round still ran has been
                # missed: entering for it now would be the ghost group again.
                launches.get_nowait()
                rounds["missed"] += 1
            await launches.get()
            round_no += 1
            got = await averager.average(tree_box[0], round_no=round_no, weight=weight)
            if got is None:
                rounds["none"] += 1
                continue
            rounds["ok"] += 1
            # A peer that makes no progress of its own: it contributes the last
            # average from now on, which keeps the volunteer's loss meaningful.
            tree_box[0] = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), got)

    tree_box = [tree]
    task = asyncio.create_task(rounds_forever())
    stopper = asyncio.create_task(stop.wait())
    try:
        await asyncio.wait({task, stopper}, return_when=asyncio.FIRST_COMPLETED)
        if task.done():
            task.result()  # a crashed loop must not look like a clean stop
    finally:
        for t in (task, stopper):
            t.cancel()
        await asyncio.gather(task, stopper, return_exceptions=True)
        try:
            await membership.leave()
        except Exception:  # noqa: BLE001 - leaving is best effort at exit
            pass
        await dht.stop()
        await transport.close()
        await coord.close()
    return {
        "peer_id": spec["peer_id"], "weight": weight, **rounds,
        "bytes_sent": transport.bytes_sent, "bytes_received": transport.bytes_received,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--peer", type=int, default=0)
    args = ap.parse_args()
    summary = asyncio.run(_serve(args))
    print("PEER_DONE " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
