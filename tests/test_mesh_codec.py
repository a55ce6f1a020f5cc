"""On-mesh data path (ops.mesh_codec): equivalence, sharding, fallback.

Covers the ISSUE-6 rework:
- bf16 encode/decode BIT-compatible with the native host codec (finite
  values), fused decode+axpy within f32 ulp tolerance (FMA contraction);
- ``MeshCodec.aggregate`` equivalent to ``ops.robust.aggregate`` for ALL 7
  robust methods (device sorting-network / weighted-mean paths for the
  decomposable ones, documented host delegation for the coupled ones);
- the same equivalence through an 8-virtual-device codec mesh (shard_map +
  NamedSharding path, including non-divisible sizes -> padding);
- the Pallas kernel lowering in interpret mode (CPU) against the host
  codec;
- MeshMeanFolder: chunk-staged device accumulation == the host fold, and
  a mid-round device failure DEGRADES to host without losing folded mass;
- StreamingAggregator parity: mesh-codec rounds match host-codec rounds
  for mean and window methods on both elementwise wires;
- PowerSGD on-mesh power iteration: wire + error-feedback residual match
  the host path across a warm-started round pair;
- a small-shape smoke of experiments/codec_bench.py that fails loudly if
  the on-mesh arm regresses to/below host throughput.
"""

import asyncio
import os

import numpy as np
import pytest

from distributedvolunteercomputing_tpu import native
from distributedvolunteercomputing_tpu.ops import mesh_codec, robust
from distributedvolunteercomputing_tpu.swarm.agg_stream import (
    StreamingAggregator,
    TilePool,
)

pytestmark = pytest.mark.mesh_codec

METHOD_KW = [
    ("mean", {}),
    ("mean", {"weights": np.array([1.0, 2.0, 0.5, 1.5, 1.0, 3.0])}),
    ("median", {}),
    ("trimmed_mean", {"trim": 1}),
    ("trimmed_mean", {"trim": 2}),
    ("krum", {}),
    ("bulyan", {}),
    ("geometric_median", {}),
    ("centered_clip", {}),
]


@pytest.fixture(scope="module")
def codec():
    """One forced-mesh codec per module: jit caches stay warm across tests."""
    return mesh_codec.MeshCodec(backend="mesh")


@pytest.fixture
def np_rng():
    return np.random.default_rng(7)


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


class TestBackendSelection:
    def test_auto_is_host_on_cpu_platform(self):
        # The tier-1 platform is CPU (conftest pins it): auto must not
        # silently put every swarm test on the jit path.
        assert mesh_codec.MeshCodec().backend == "host"

    def test_host_backend_never_touches_devices(self, np_rng):
        c = mesh_codec.MeshCodec(backend="host")
        x = np_rng.standard_normal(1000).astype(np.float32)
        assert np.array_equal(c.encode_bf16(x), native.f32_to_bf16(x))
        assert c.stats()["ops_mesh"] == 0
        assert c.stats()["ops_host"] >= 1

    def test_default_configure_roundtrip(self):
        mesh_codec.reset()
        try:
            assert mesh_codec.get_default().backend in ("host", "mesh")
            c = mesh_codec.configure(backend="host")
            assert mesh_codec.get_default() is c
        finally:
            mesh_codec.reset()


class TestBf16Codec:
    def test_encode_bit_compatible(self, codec, np_rng):
        x = np_rng.standard_normal(100003).astype(np.float32) * 1e3
        assert np.array_equal(codec.encode_bf16(x), native.f32_to_bf16(x))

    def test_decode_bit_compatible(self, codec, np_rng):
        bits = np_rng.integers(0, 1 << 16, 5001).astype(np.uint16)
        # Mask NaN patterns: quiet-bit canonicalization may legally differ.
        f = native.bf16_to_f32(bits)
        finite = np.isfinite(f)
        got = codec.decode_bf16(bits)
        assert np.array_equal(got[finite], f[finite])

    def test_decode_out_param(self, codec, np_rng):
        x = np_rng.standard_normal(4096).astype(np.float32)
        bits = native.f32_to_bf16(x)
        out = np.empty(4096, np.float32)
        res = codec.decode_bf16(bits, out=out)
        assert res is out or np.shares_memory(res, out)
        assert np.array_equal(out, native.bf16_to_f32(bits))

    def test_decode_axpy_matches_host_within_ulp(self, codec, np_rng):
        x = np_rng.standard_normal(40000).astype(np.float32)
        bits = native.f32_to_bf16(x)
        acc = np_rng.standard_normal(40000).astype(np.float32)
        got = codec.decode_axpy(acc.copy(), bits, 0.7)
        ref = acc.copy()
        native.weighted_sum_inplace(ref, native.bf16_to_f32(bits), 0.7)
        # FMA contraction differs between XLA and the host axpy: 1-2 ulp.
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_special_values_roundtrip(self, codec):
        x = np.array([0.0, -0.0, 1e-40, -1e-40, 3.4e38, -3.4e38, 1.5, -2.5],
                     np.float32)
        assert np.array_equal(codec.encode_bf16(x), native.f32_to_bf16(x))


class TestAggregateEquivalence:
    @pytest.mark.parametrize("method,kw", METHOD_KW,
                             ids=[f"{m}-{i}" for i, (m, _) in enumerate(METHOD_KW)])
    def test_matches_host(self, codec, np_rng, method, kw):
        stack = np_rng.standard_normal((6, 2000)).astype(np.float32)
        got = codec.aggregate(stack, method, **kw)
        ref = robust.aggregate(stack, method, **kw)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-6)

    def test_odd_peer_counts(self, codec, np_rng):
        for n in (2, 3, 5, 8):
            stack = np_rng.standard_normal((n, 257)).astype(np.float32)
            for method, kw in (("median", {}), ("trimmed_mean", {"trim": (n - 1) // 2})):
                if method == "trimmed_mean" and kw["trim"] == 0:
                    continue
                got = codec.aggregate(stack, method, **kw)
                ref = robust.aggregate(stack, method, **kw)
                np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-6,
                                           err_msg=f"{method} n={n}")

    def test_nan_row_is_trimmed_like_host(self, codec, np_rng):
        """A NaN-filled byzantine row must be DROPPED by the device
        trimmed mean exactly as the host path drops it (numpy sorts NaN
        last; the sorting network maps NaN -> +inf to reproduce that) —
        min/max NaN propagation would otherwise poison every coordinate."""
        stack = np_rng.standard_normal((6, 500)).astype(np.float32)
        stack[2] = np.nan  # one attacker: trim=1 drops it on both paths
        got = codec.aggregate(stack, "trimmed_mean", trim=1)
        ref = robust.aggregate(stack, "trimmed_mean", trim=1)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-6)
        # Median: the device path treats NaN as +inf (strictly more robust
        # than numpy's NaN-propagating median); it must stay finite.
        assert np.isfinite(codec.aggregate(stack, "median")).all()

    def test_infeasible_trim_raises_like_host(self, codec, np_rng):
        stack = np_rng.standard_normal((4, 64)).astype(np.float32)
        with pytest.raises(ValueError):
            codec.aggregate(stack, "trimmed_mean", trim=2)

    def test_aggregate_bits_fused_decode(self, codec, np_rng):
        stack = np_rng.standard_normal((5, 3000)).astype(np.float32)
        bits = np.stack([native.f32_to_bf16(r) for r in stack])
        got = codec.aggregate_bits(bits, "trimmed_mean", trim=1)
        dec = np.stack([native.bf16_to_f32(r) for r in bits])
        ref = robust.aggregate(dec, "trimmed_mean", trim=1)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-6)


class TestShardedMesh:
    """The shard_map + NamedSharding path over the 8-virtual-device mesh."""

    @pytest.fixture(scope="class")
    def sharded(self, eight_devices):
        from distributedvolunteercomputing_tpu.parallel.mesh import make_mesh

        return mesh_codec.MeshCodec(mesh=make_mesh(dp=2, sp=2, tp=2), backend="mesh")

    def test_encode_decode_padding(self, sharded, np_rng):
        for n in (8, 64, 100001):  # 100001 exercises the pad-to-ndev path
            x = np_rng.standard_normal(n).astype(np.float32)
            bits = sharded.encode_bf16(x)
            assert np.array_equal(bits, native.f32_to_bf16(x))
            assert np.array_equal(sharded.decode_bf16(bits), native.bf16_to_f32(bits))

    def test_window_folds(self, sharded, np_rng):
        stack = np_rng.standard_normal((6, 4099)).astype(np.float32)
        for method, kw in (("median", {}), ("trimmed_mean", {"trim": 1}),
                           ("mean", {"weights": np.arange(1.0, 7.0)})):
            got = sharded.aggregate(stack, method, **kw)
            ref = robust.aggregate(stack, method, **kw)
            np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-6)

    def test_folder_on_sharded_mesh(self, sharded, np_rng):
        tile = 1024  # divisible by ndev=8
        n_elems, n_tiles = 4000, 4
        folder = sharded.mean_folder(n_elems, tile, n_tiles, "bf16")
        assert folder is not None
        ref = np.zeros(n_elems, np.float32)
        for peer in range(3):
            buf = np_rng.standard_normal(n_elems).astype(np.float32)
            bits = native.f32_to_bf16(buf)
            for t in range(n_tiles):
                e0 = t * tile
                n = min(tile, n_elems - e0)
                folder.add(t, 0.5 + peer, bits[e0 : e0 + n].tobytes())
                native.weighted_sum_inplace(
                    ref[e0 : e0 + n], native.bf16_to_f32(bits[e0 : e0 + n]),
                    0.5 + peer,
                )
        out = folder.result()
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_indivisible_tile_returns_no_folder(self, sharded):
        assert sharded.mean_folder(100, 7, 15, "bf16") is None


class TestPallasInterpret:
    """The Pallas kernel bodies, interpreted on CPU (compiled on TPU)."""

    @pytest.fixture(scope="class")
    def pallas_codec(self):
        return mesh_codec.MeshCodec(backend="mesh", pallas="interpret")

    def test_encode_kernel(self, pallas_codec, np_rng):
        n = mesh_codec._PALLAS_ROWS * mesh_codec._PALLAS_LANES  # one block
        x = np_rng.standard_normal(n).astype(np.float32)
        assert np.array_equal(pallas_codec.encode_bf16(x), native.f32_to_bf16(x))

    def test_decode_axpy_kernel(self, pallas_codec, np_rng):
        n = mesh_codec._PALLAS_ROWS * mesh_codec._PALLAS_LANES
        x = np_rng.standard_normal(n).astype(np.float32)
        bits = native.f32_to_bf16(x)
        acc = np_rng.standard_normal(n).astype(np.float32)
        got = pallas_codec.decode_axpy(acc.copy(), bits, 0.3)
        ref = acc.copy()
        native.weighted_sum_inplace(ref, native.bf16_to_f32(bits), 0.3)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_offsize_buffers_take_jnp_body(self, pallas_codec, np_rng):
        x = np_rng.standard_normal(1000).astype(np.float32)  # not block-tiled
        assert np.array_equal(pallas_codec.encode_bf16(x), native.f32_to_bf16(x))


class TestMeanFolder:
    def _feed(self, folder, bufs, weights, tile, wire="bf16"):
        n_elems = bufs.shape[1]
        ref = np.zeros(n_elems, np.float32)
        for p in range(bufs.shape[0]):
            bits = native.f32_to_bf16(bufs[p])
            dec = native.bf16_to_f32(bits)
            for e0 in range(0, n_elems, tile):
                n = min(tile, n_elems - e0)
                if folder.add(e0 // tile, weights[p], bits[e0 : e0 + n].tobytes()):
                    folder.flush()
                native.weighted_sum_inplace(ref[e0 : e0 + n], dec[e0 : e0 + n],
                                            weights[p])
        return ref

    def test_chunked_equals_host_fold(self, codec, np_rng):
        bufs = np_rng.standard_normal((4, 10000)).astype(np.float32)
        folder = codec.mean_folder(10000, 2048, 5, "bf16")
        ref = self._feed(folder, bufs, [0.5, 1.0, 2.0, 0.25], 2048)
        np.testing.assert_allclose(folder.result(), ref, rtol=1e-5, atol=1e-6)

    def test_dense_feed(self, codec, np_rng):
        folder = codec.mean_folder(5000, 1024, 5, "f32")
        buf = np_rng.standard_normal(5000).astype(np.float32)
        folder.add_dense(buf, 1.5)
        np.testing.assert_allclose(folder.result(), 1.5 * buf, rtol=1e-5, atol=1e-6)

    def test_device_failure_mid_round_degrades_without_losing_mass(self, np_rng):
        """A mesh shrink between flushes: the folder pulls the last good
        device accumulator to host and keeps folding — the round's already-
        folded mass survives the degrade."""
        c = mesh_codec.MeshCodec(backend="mesh")
        folder = c.mean_folder(8192, 2048, 4, "bf16")
        bufs = np_rng.standard_normal((2, 8192)).astype(np.float32)
        ref = np.zeros(8192, np.float32)
        # Peer 0 folds on device...
        bits0 = native.f32_to_bf16(bufs[0])
        for t in range(4):
            folder.add(t, 1.0, bits0[t * 2048 : (t + 1) * 2048].tobytes())
        folder.flush()
        native.weighted_sum_inplace(ref, native.bf16_to_f32(bits0), 1.0)
        assert not c.degraded
        # ...then the slice dies; peer 1 folds on host.
        c.inject_failure()
        bits1 = native.f32_to_bf16(bufs[1])
        for t in range(4):
            folder.add(t, 2.0, bits1[t * 2048 : (t + 1) * 2048].tobytes())
        folder.flush()
        native.weighted_sum_inplace(ref, native.bf16_to_f32(bits1), 2.0)
        assert c.degraded
        np.testing.assert_allclose(folder.result(), ref, rtol=1e-5, atol=1e-6)
        assert c.stats()["fallbacks"] == 1

    def test_dense_feed_after_degrade_lands_in_host_acc(self, np_rng):
        """The add_dense/degrade race guard: once the accumulator migrated
        to host, a dense feed must fold THERE — folding into a fresh device
        accumulator would silently drop its mass at result()."""
        c = mesh_codec.MeshCodec(backend="mesh")
        folder = c.mean_folder(4096, 1024, 4, "f32")
        buf0 = np_rng.standard_normal(4096).astype(np.float32)
        folder.add_dense(buf0, 1.0)  # device
        c.inject_failure(1)
        # Force the migration via a failing staged flush:
        folder.add(0, 1.0, buf0[:1024].tobytes())
        folder.flush()
        assert c.degraded
        buf1 = np_rng.standard_normal(4096).astype(np.float32)
        folder.add_dense(buf1, 2.0)  # must land in the HOST accumulator
        ref = 1.0 * buf0 + 2.0 * buf1
        ref[:1024] += buf0[:1024]
        np.testing.assert_allclose(folder.result(), ref, rtol=1e-5, atol=1e-5)


class TestStreamingAggregatorParity:
    """Full streaming rounds: mesh-codec result == host-codec result."""

    @pytest.mark.parametrize("method", ["mean", "trimmed_mean", "median"])
    @pytest.mark.parametrize("wire", ["f32", "bf16"])
    def test_round_parity(self, codec, np_rng, method, wire):
        n_peers, n_elems, chunk = 4, 24000, 1 << 14
        kw = {"trim": 1} if method == "trimmed_mean" else {}
        bufs = np_rng.standard_normal((n_peers, n_elems)).astype(np.float32)
        ws = np_rng.uniform(0.5, 2.0, n_peers)

        async def one(c):
            peers = [f"p{i}" for i in range(n_peers)]
            agg = StreamingAggregator(
                n_elems, peers, method, wire, chunk,
                kw_fn=lambda n, _kw=kw: dict(_kw), pool=TilePool(), codec=c,
            )
            esz = 4 if wire == "f32" else 2
            wires = [
                bufs[p].tobytes() if wire == "f32"
                else native.f32_to_bf16(bufs[p]).tobytes()
                for p in range(n_peers)
            ]
            sinks = [
                agg.make_sink(peers[p], float(ws[p]), n_elems * esz)
                for p in range(n_peers)
            ]
            total = n_elems * esz
            for off in range(0, total, chunk):
                for p in range(n_peers):
                    sinks[p](off, total, wires[p][off : off + chunk])
                await asyncio.sleep(0)
            for s in sinks:
                s.close(True)
            out = await agg.finalize(peers)
            return out, agg.gauges()

        mesh_out, mesh_g = run(one(codec))
        host_out, host_g = run(one(mesh_codec.MeshCodec(backend="host")))
        np.testing.assert_allclose(mesh_out, host_out, rtol=2e-5, atol=1e-5)
        assert mesh_g["codec_backend"] == "mesh"
        assert host_g["codec_backend"] == "host"
        if method == "mean":
            assert mesh_g["folder_flushes"] >= 1

    def test_mid_round_degrade_still_commits(self, np_rng):
        """The chaos contract at the aggregator level: a mesh failure mid-
        stream degrades to host and the round still commits correctly."""
        c = mesh_codec.MeshCodec(backend="mesh")
        c.inject_failure(1)

        async def main():
            peers = ["a", "b"]
            n_elems, chunk = 40000, 1 << 15
            agg = StreamingAggregator(
                n_elems, peers, "mean", "bf16", chunk,
                kw_fn=lambda n: {}, pool=TilePool(), codec=c,
            )
            bufs = np_rng.standard_normal((2, n_elems)).astype(np.float32)
            wires = [native.f32_to_bf16(b).tobytes() for b in bufs]
            sinks = [agg.make_sink(p, 1.0, n_elems * 2) for p in peers]
            for off in range(0, n_elems * 2, chunk):
                for i in range(2):
                    sinks[i](off, n_elems * 2, wires[i][off : off + chunk])
                await asyncio.sleep(0)
            for s in sinks:
                s.close(True)
            out = await agg.finalize(peers)
            dec = np.stack([native.bf16_to_f32(np.frombuffer(w, np.uint16))
                            for w in wires])
            np.testing.assert_allclose(out, dec.mean(axis=0), rtol=1e-5, atol=1e-5)
            assert agg.gauges()["codec_backend"] == "host"

        run(main())
        assert c.degraded


class TestPowerSGDOnMesh:
    def test_wire_and_ef_residual_identity_across_round(self, codec, np_rng):
        """The satellite's EF-identity check: a warm-started round pair
        through the on-mesh power iteration produces the same wire
        reconstruction AND the same error-feedback residual as the host
        path (QR is LAPACK on both here; tolerance covers accumulation
        order)."""
        from distributedvolunteercomputing_tpu.swarm import powersgd

        class Spec:
            def __init__(self, shape):
                self.shape = shape
                self.size = int(np.prod(shape))

        specs = [Spec((32, 16)), Spec((60,)), Spec((12, 24))]
        total = sum(s.size for s in specs)
        host_c = powersgd.PowerSGDCodec(specs, rank=3, seed=1)
        mesh_c = powersgd.PowerSGDCodec(specs, rank=3, seed=1, mesh_codec=codec)
        ef_host = np.zeros(total, np.float32)
        ef_mesh = np.zeros(total, np.float32)
        for _ in range(2):  # round 2 exercises the warm-started Q
            grad = np_rng.standard_normal(total).astype(np.float32)
            wire_h = host_c.encode(grad + ef_host)
            sent_h = powersgd.decode(wire_h, max_floats=total)
            ef_host = (grad + ef_host) - sent_h
            wire_m = mesh_c.encode(grad + ef_mesh)
            sent_m = powersgd.decode(wire_m, max_floats=total, mesh_codec=codec)
            ef_mesh = (grad + ef_mesh) - sent_m
        np.testing.assert_allclose(sent_m, sent_h, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(ef_mesh, ef_host, rtol=1e-4, atol=1e-5)

    def test_lowrank_reconstruct(self, codec, np_rng):
        p = np_rng.standard_normal((50, 4)).astype(np.float32)
        q = np_rng.standard_normal((30, 4)).astype(np.float32)
        np.testing.assert_allclose(
            codec.lowrank_reconstruct(p, q), (p @ q.T).ravel(),
            rtol=1e-5, atol=1e-6,
        )


class TestAveragerSurface:
    def test_stats_carry_codec_backend(self):
        """Averager.stats() surfaces the per-volunteer backend selection
        (ROADMAP: 'selected per-volunteer at startup and surfaced in
        stats()')."""
        from distributedvolunteercomputing_tpu.swarm.averager import SyncAverager
        from distributedvolunteercomputing_tpu.swarm.dht import DHTNode
        from distributedvolunteercomputing_tpu.swarm.membership import SwarmMembership
        from distributedvolunteercomputing_tpu.swarm.transport import Transport

        async def main():
            t = Transport()
            dht = DHTNode(t)
            await dht.start()
            mem = SwarmMembership(dht, "s1")
            try:
                avg = SyncAverager(
                    t, dht, mem, mesh_codec=mesh_codec.MeshCodec(backend="host")
                )
                st = avg.stats()
                assert st["mesh_codec"]["backend"] == "host"
                assert "degraded" in st["mesh_codec"]
            finally:
                await t.close()

        run(main())


class TestCodecBenchSmoke:
    """Small-shape regression guard over the codec bench harness: the
    on-mesh window fold must stay at least as fast as the host baseline
    (the ISSUE's '>=1x regression fails loudly' smoke); the full grid
    lives in experiments/results/codec_bench.json."""

    def test_window_fold_not_slower_than_host(self):
        from experiments.codec_bench import run_config

        c = mesh_codec.MeshCodec(backend="mesh")
        # Best-of-2 rows on the ratio: single-core CI boxes jitter, and the
        # first row's device arm pays the jit compiles.
        rows = [run_config(4, 1.0, "trimmed_mean", chunk_bytes=1 << 17,
                           repeats=2, codec=c) for _ in range(2)]
        ratio = max(r["ratios"]["encode_fold"] for r in rows)
        assert ratio >= 1.0, (
            f"on-mesh encode+fold regressed below host baseline: "
            f"{ratio}x (need >= 1x) — {rows[-1]}"
        )

    def test_mean_fold_no_cliff(self):
        """The mean path is memory-bound near parity on small CPU hosts
        (the window estimators are where the mesh wins on 2 cores); guard
        it against falling off a cliff rather than against parity."""
        from experiments.codec_bench import run_config

        c = mesh_codec.MeshCodec(backend="mesh")
        rows = [run_config(4, 1.0, "mean", chunk_bytes=1 << 17,
                           repeats=2, codec=c) for _ in range(2)]
        ratio = max(r["ratios"]["encode_fold"] for r in rows)
        assert ratio >= 0.25, (
            f"on-mesh mean encode+fold collapsed: {ratio}x vs host — "
            f"{rows[-1]}"
        )
