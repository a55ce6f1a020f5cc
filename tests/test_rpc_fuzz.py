"""RPC-argument fuzz: junk args through every registered swarm method.

The transport-level fuzz (test_e2e_swarm.py, TestTransport) proves malformed FRAMES can't
kill a node; this layer proves malformed ARGUMENTS can't either. Handler
exceptions are contained by the serve loop (they come back as error
frames), so the property under test is: after a volley of junk calls to
every registered method, the node still answers legitimate RPCs — no
handler wedges the loop, corrupts shared state, or crashes the process.
WAN peers are untrusted by design (SURVEY.md §1 L3); these are exactly the
messages a buggy or hostile peer would send.
"""

import asyncio

import numpy as np
import pytest

from distributedvolunteercomputing_tpu.swarm.averager import (
    ButterflyAverager,
    ByzantineAverager,
    GossipAverager,
    SyncAverager,
)
from distributedvolunteercomputing_tpu.swarm.dht import DHTNode
from distributedvolunteercomputing_tpu.swarm.membership import SwarmMembership
from distributedvolunteercomputing_tpu.swarm.transport import RPCError, Transport

from tests.test_averaging import make_tree, spawn_volunteers, teardown


def run(coro):
    # Fuzz volleys intentionally leave handlers parked on timeouts; give the
    # whole scenario more headroom than test_averaging's default 60s.
    return asyncio.run(asyncio.wait_for(coro, timeout=240))

JUNK_ARGS = [
    {},
    {"epoch": None},
    {"epoch": "x" * 10_000, "key": ["list"], "id": -1},
    {"peer": {"nested": "dict"}, "epoch": "e1", "weight": "NaN", "token": 7},
    {"peer": "p", "epoch": "e1", "weight": float("inf"), "key": None},
]

JUNK_PAYLOADS = [b"\x00" * 17, np.arange(5, dtype=np.float64).tobytes()]


async def volley(client, addr, methods):
    """Throw every junk (args, payload) combo at every method; errors are
    expected (refusals ARE the contract) — crashes/timeouts are not."""
    for method in methods:
        for args in JUNK_ARGS:
            for payload in JUNK_PAYLOADS:
                try:
                    # Short timeout: some handlers legitimately PARK junk
                    # (sync.fetch waits for a result that never comes) —
                    # the property is no-crash, not fast-refusal.
                    await asyncio.wait_for(
                        client.call(addr, method, args, payload), timeout=1.5
                    )
                except (RPCError, OSError, asyncio.TimeoutError, TimeoutError):
                    pass  # refusal or drop: the contract
                except asyncio.IncompleteReadError:
                    pass


class TestDHTFuzz:
    def test_dht_survives_junk_rpcs(self):
        async def main():
            t = Transport()
            node = DHTNode(t)
            await node.start(bootstrap=None)
            client = Transport()
            await volley(client, t.addr, ["dht.ping", "dht.store", "dht.find"])
            # Node still functional: a legitimate store+find round-trips.
            await node.store("k", {"v": 1}, ttl=30)
            got = await node.get("k")
            await t.close()
            return got

        got = run(main())
        assert got and got.get("", {}) == {"v": 1} or any(
            v == {"v": 1} for v in got.values()
        )


class TestAveragerFuzz:
    @pytest.mark.parametrize("cls,methods", [
        (SyncAverager, ["sync.contribute", "sync.fetch"]),
        (ByzantineAverager, ["byz.contribute"]),
        (GossipAverager, ["gossip.exchange"]),
        (ButterflyAverager, ["bfly.exchange"]),
    ])
    def test_averager_survives_junk_then_averages(self, cls, methods):
        async def main():
            vols = await spawn_volunteers(2, cls, min_group=2)
            try:
                client = Transport()
                for _, _, _, avg in vols:
                    await volley(client, avg.transport.addr, methods)
                return await asyncio.gather(
                    *(
                        avg.average(make_tree(float(i)), 1)
                        for i, (_, _, _, avg) in enumerate(vols)
                    )
                )
            finally:
                await teardown(vols)

        results = run(main())
        if cls in (SyncAverager, ByzantineAverager):
            # Consensus modes: every member adopts the weighted mean of
            # {0.0, 1.0} trees.
            for r in results:
                assert r is not None
                np.testing.assert_allclose(r["w"], 0.5, rtol=1e-5)
        else:
            # Pairwise modes (gossip mixes against published state;
            # butterfly may degrade): at least one member completes a round
            # post-volley, and nothing non-finite leaks out of the mixes.
            assert any(r is not None for r in results)
            for r in results:
                if r is not None:
                    assert np.isfinite(np.asarray(r["w"])).all()


@pytest.mark.transport
class TestChunkedFrameFuzz:
    """Chunk-framing fuzz (ISSUE 3 satellite): truncated mid-stream,
    corrupted chunk CRC, duplicated/reordered chunk indices, and framing
    that overruns the declared total. The server must reject each without
    wedging the event loop — and for the attributable shapes (CRC, index)
    WITHOUT dropping the connection, since the explicit per-chunk lengths
    keep the stream in sync."""

    @staticmethod
    def _chunked_frames(rid, method, payload, chunk, mutate=None):
        """Raw wire bytes for one chunked request; ``mutate(i, idx, data,
        crc) -> (idx, data, crc)`` lets a case corrupt exactly one chunk."""
        import json as _json
        import zlib as _zlib

        from distributedvolunteercomputing_tpu.swarm.transport import (
            _CHUNK, _HEADER, MAGIC, TYPE_REQ, VERSION,
        )

        pieces = [payload[i : i + chunk] for i in range(0, len(payload), chunk)]
        meta = {"rid": rid, "method": method, "args": {}, "chunks": len(pieces)}
        meta_b = _json.dumps(meta).encode()
        out = [
            _HEADER.pack(MAGIC, VERSION, TYPE_REQ, len(meta_b), len(payload), 0),
            meta_b,
        ]
        for i, data in enumerate(pieces):
            idx, crc = i, _zlib.crc32(data) & 0xFFFFFFFF
            if mutate is not None:
                idx, data, crc = mutate(i, idx, data, crc)
            out.append(_CHUNK.pack(idx, len(data), crc))
            out.append(bytes(data))
        return b"".join(out)

    def test_bad_chunks_rejected_without_wedging(self):
        from distributedvolunteercomputing_tpu.swarm.transport import (
            TYPE_ERR, TYPE_RESP,
        )

        payload = bytes(range(256)) * 64  # 16 KB over 4 KB chunks
        CH = 4096

        def corrupt_crc(i, idx, data, crc):
            if i == 2:
                bad = bytearray(data)
                bad[0] ^= 0xFF
                return idx, bytes(bad), crc  # crc of the TRUE bytes: mismatch
            return idx, data, crc

        def duplicate_index(i, idx, data, crc):
            return (1 if i == 2 else idx), data, crc

        def reorder_index(i, idx, data, crc):
            remap = {1: 2, 2: 1}
            return remap.get(i, idx), data, crc

        cases = [
            ("crc", corrupt_crc, "CRC"),
            ("dup", duplicate_index, "duplicated/reordered"),
            ("reorder", reorder_index, "duplicated/reordered"),
        ]

        async def main():
            server = Transport()

            async def echo(args, payload):
                return {"n": len(payload)}, b""

            server.register("echo", echo)
            addr = await server.start()
            probe = Transport()  # parses response frames for us
            try:
                for name, mutate, expect in cases:
                    reader, writer = await asyncio.open_connection(*addr)
                    try:
                        writer.write(self._chunked_frames(
                            f"rid-{name}", "echo", payload, CH, mutate
                        ))
                        await writer.drain()
                        ftype, meta, _ = await asyncio.wait_for(
                            probe._read_frame(reader), timeout=5
                        )
                        assert ftype == TYPE_ERR, (name, meta)
                        assert expect in meta.get("error", ""), (name, meta)
                        assert meta.get("rid") == f"rid-{name}", (
                            "rejection must be attributable", meta)
                        # SAME connection still serves: a clean chunked
                        # request right behind the rejected one succeeds.
                        writer.write(self._chunked_frames(
                            "rid-ok", "echo", payload, CH
                        ))
                        await writer.drain()
                        ftype, meta, _ = await asyncio.wait_for(
                            probe._read_frame(reader), timeout=5
                        )
                        assert ftype == TYPE_RESP and meta["ret"]["n"] == len(payload), (
                            name, meta)
                    finally:
                        writer.close()
            finally:
                await server.close()

        run(main())

    def test_truncated_and_overrun_streams_drop_cleanly(self):
        async def main():
            server = Transport()

            async def echo(args, payload):
                return {"n": len(payload)}, b""

            server.register("echo", echo)
            addr = await server.start()
            payload = b"z" * 16384
            try:
                # Truncated mid-stream: header promises 4 chunks, the sender
                # dies after 1.5 — the server must drop the conn without
                # wedging (IncompleteReadError containment).
                frames = self._chunked_frames("rid-t", "echo", payload, 4096)
                reader, writer = await asyncio.open_connection(*addr)
                writer.write(frames[: len(frames) // 2])
                await writer.drain()
                writer.write_eof()
                await asyncio.wait_for(reader.read(1 << 16), timeout=5)
                writer.close()
                # Overrun: a chunk whose length exceeds the declared total —
                # the incremental size cap must kill the connection (the
                # stream position past it is untrustworthy).
                import json as _json
                import zlib as _zlib

                from distributedvolunteercomputing_tpu.swarm.transport import (
                    _CHUNK, _HEADER, MAGIC, TYPE_REQ, VERSION,
                )

                meta_b = _json.dumps(
                    {"rid": "rid-o", "method": "echo", "args": {}, "chunks": 2}
                ).encode()
                reader, writer = await asyncio.open_connection(*addr)
                writer.write(
                    _HEADER.pack(MAGIC, VERSION, TYPE_REQ, len(meta_b), 100, 0)
                )
                writer.write(meta_b)
                big = b"x" * 4096  # 4096 > the declared 100-byte total
                writer.write(_CHUNK.pack(0, len(big), _zlib.crc32(big) & 0xFFFFFFFF))
                writer.write(big)
                await writer.drain()
                writer.write_eof()
                await asyncio.wait_for(reader.read(1 << 16), timeout=5)
                writer.close()
                # After both volleys the node still answers legit RPCs.
                client = Transport()
                ret, _ = await client.call(addr, "echo", {}, payload)
                assert ret["n"] == len(payload)
                await client.close()
            finally:
                await server.close()

        run(main())


class TestClockSyncFuzz:
    def test_clock_probe_survives_junk_then_estimates(self):
        """clock.probe (swarm/clocksync.py) joins the fuzzed surface: junk
        args/payloads must not wedge the responder, and a peer's estimate()
        against it still lands after the volley. Also adversarial REPLIES:
        a peer returning junk 't' shrinks the sample, never crashes."""
        async def main():
            from tests.test_averaging import _solo_stack
            from distributedvolunteercomputing_tpu.swarm.clocksync import ClockSync

            t1, dht1, mem1 = await _solo_stack("cs1")
            cs1 = ClockSync(t1, mem1)
            # Second node bootstrapped into the same swarm.
            t2 = Transport()
            dht2 = DHTNode(t2)
            await dht2.start(bootstrap=[t1.addr])
            mem2 = SwarmMembership(dht2, "cs2", ttl=10.0)
            await mem2.join()
            cs2 = ClockSync(t2, mem2)
            try:
                client = Transport()
                await volley(client, t1.addr, ["clock.probe"])
                # Responder still sane; estimation across the pair works.
                off = await cs2.estimate()
                assert cs2.last_estimate_t is not None, "no peer was sampled"
                assert abs(off) < 2.0  # same host: near-zero offset
                # Adversarial reply: junk 't' values shrink the sample.
                async def evil_probe(args, payload):
                    return {"t": "not-a-float"}, b""

                t1.register("clock.probe", evil_probe)
                before = cs2.offset
                await cs2.estimate()
                # A non-coercible 't' drops the sample entirely: the
                # offset must be EXACTLY unchanged, not merely close.
                assert cs2.offset == before
            finally:
                for t, mem in ((t1, mem1), (t2, mem2)):
                    try:
                        await mem.leave()
                    except Exception:
                        pass
                    await t.close()

        run(main())
