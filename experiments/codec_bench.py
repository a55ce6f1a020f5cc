"""Swarm codec bench: host-only vs on-mesh encode/decode + fold throughput.

The committed artifact behind the ISSUE-6 on-mesh data-path rework
(``experiments/results/codec_bench.json``): measures the chip-side half of
an averaging round — the work PRs 2–3 left on single-threaded host numpy —
for the two backends ``ops.mesh_codec`` selects between:

- ``host``  — the pre-rework path: ``native.f32_to_bf16`` per contribution,
  then per-peer ``bf16_to_f32`` decode + ``weighted_sum_inplace`` axpy
  (mean) or per-tile decode + ``ops.robust`` window estimators
  (trimmed_mean) — exactly what the streaming aggregator runs when the
  codec is inactive.
- ``mesh``  — ``MeshCodec``: one fused device pass per op (bitcast + widen
  + fold), the mean path through ``MeshMeanFolder``'s batched
  scatter-add over chunk-grained tiles, the window path through the
  sorting-network estimator with the bf16 decode fused in.

Phases, reported separately and combined (the acceptance line is the
COMBINED encode+fold throughput at 64 MB contributions):

- ``encode``: one volunteer's f32 -> bf16 wire pack of its contribution;
- ``fold``:   the leader consuming all n peers' bf16 wire bytes into the
  round aggregate (decode + mean axpy / window estimator per tile).

Tiles are the transport's wire chunks (1 MiB), matching agg_stream.

Usage:
    python experiments/codec_bench.py          # full grid + artifact
    python experiments/codec_bench.py --quick  # small sanity run

The default tier-1 suite runs a small-shape smoke of this harness
(tests/test_mesh_codec.py::TestCodecBenchSmoke) that FAILS LOUDLY when the
on-mesh arm regresses to (or below) host throughput — the same
regression-guard pattern as the transport and aggregation bench smokes.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributedvolunteercomputing_tpu.utils.jaxenv import force_host_devices  # noqa: E402

RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
CHUNK_BYTES = 1 << 20  # transport default: tiles == wire chunks


def _best_of(fn, repeats: int):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_host(bits: np.ndarray, weights: np.ndarray, method: str, kw: dict,
               chunk_bytes: int, repeats: int) -> dict:
    """The host data path: native codec + numpy/native folds, tile-grained
    exactly as the streaming aggregator runs them."""
    from distributedvolunteercomputing_tpu import native
    from distributedvolunteercomputing_tpu.ops import robust

    n_peers, n_elems = bits.shape
    tile = chunk_bytes // 2  # bf16 elements per wire chunk
    src = native.bf16_to_f32(bits[0])  # a representative f32 contribution

    encode_s = _best_of(lambda: native.f32_to_bf16(src), repeats)

    def fold():
        if method == "mean":
            acc = np.zeros(n_elems, np.float32)
            total_w = float(weights.sum())
            for p in range(n_peers):
                for e0 in range(0, n_elems, tile):
                    x = native.bf16_to_f32(bits[p, e0 : e0 + tile])
                    native.weighted_sum_inplace(
                        acc[e0 : e0 + x.size], x, float(weights[p]) / total_w
                    )
            return acc
        out = np.empty(n_elems, np.float32)
        for e0 in range(0, n_elems, tile):
            win = np.stack(
                [native.bf16_to_f32(bits[p, e0 : e0 + tile]) for p in range(n_peers)]
            )
            out[e0 : e0 + win.shape[1]] = robust.aggregate(win, method, **kw)
        return out

    fold_s = _best_of(fold, repeats)
    return {"encode_s": round(encode_s, 6), "fold_s": round(fold_s, 6),
            "result": fold()}


def bench_mesh(bits: np.ndarray, weights: np.ndarray, method: str, kw: dict,
               chunk_bytes: int, repeats: int, codec) -> dict:
    """The on-mesh data path through MeshCodec / MeshMeanFolder."""
    from distributedvolunteercomputing_tpu import native

    n_peers, n_elems = bits.shape
    tile = chunk_bytes // 2
    n_tiles = -(-n_elems // tile)
    src = native.bf16_to_f32(bits[0])

    encode_s = _best_of(lambda: codec.encode_bf16(src), repeats)

    def fold():
        if method == "mean":
            folder = codec.mean_folder(n_elems, tile, n_tiles, "bf16")
            assert folder is not None, "mesh folder unavailable (degraded codec?)"
            total_w = float(weights.sum())
            for p in range(n_peers):
                raw = bits[p]
                for t in range(n_tiles):
                    e0 = t * tile
                    if folder.add(t, float(weights[p]) / total_w,
                                  raw[e0 : e0 + tile].tobytes()):
                        folder.flush()
            return folder.result()
        # PRODUCTION shape for the window path: chunks decode on the host
        # as they arrive (agg_stream fills f32 windows), the fold runs on
        # device — measure exactly that, not the fused decode+fold below.
        out = np.empty(n_elems, np.float32)
        for e0 in range(0, n_elems, tile):
            win = np.stack(
                [native.bf16_to_f32(bits[p, e0 : e0 + tile])
                 for p in range(n_peers)]
            )
            out[e0 : e0 + win.shape[1]] = codec.aggregate(win, method, **kw)
        return out

    fold_s = _best_of(fold, repeats)
    row = {"encode_s": round(encode_s, 6), "fold_s": round(fold_s, 6),
           "result": fold()}
    if method != "mean":
        # The FUSED variant (aggregate_bits: bf16 decode folded into the
        # device estimator) — what a bits-resident window pipeline would
        # get; reported separately so the headline stays the shipped path.
        def fold_fused():
            out = np.empty(n_elems, np.float32)
            for e0 in range(0, n_elems, tile):
                win = np.ascontiguousarray(bits[:, e0 : e0 + tile])
                out[e0 : e0 + win.shape[1]] = codec.aggregate_bits(
                    win, method, **kw
                )
            return out

        row["fold_fused_s"] = round(_best_of(fold_fused, repeats), 6)
    return row


def run_config(n_peers: int, payload_mb: float, method: str,
               chunk_bytes: int = CHUNK_BYTES, repeats: int = 2,
               codec=None) -> dict:
    from distributedvolunteercomputing_tpu import native
    from distributedvolunteercomputing_tpu.ops import mesh_codec

    if codec is None:
        codec = mesh_codec.MeshCodec(backend="mesh")
    n_elems = int(payload_mb * (1 << 20)) // 4
    rng = np.random.default_rng(0)
    weights = rng.uniform(0.5, 2.0, n_peers)
    # Contributions materialize directly as bf16 wire bits: the bench
    # measures the codec+fold path, not the rng.
    bits = np.stack(
        [
            native.f32_to_bf16(rng.standard_normal(n_elems).astype(np.float32))
            for _ in range(n_peers)
        ]
    )
    kw = {"trim": max(1, n_peers // 4)} if method == "trimmed_mean" else {}
    host = bench_host(bits, weights, method, kw, chunk_bytes, repeats)
    mesh = bench_mesh(bits, weights, method, kw, chunk_bytes, repeats, codec)
    # Equivalence is part of the bench contract: a fast wrong answer banks
    # nothing. bf16 decode is exact; fold order differs -> f32 ulp-scale.
    np.testing.assert_allclose(
        mesh.pop("result"), host.pop("result"), rtol=2e-5, atol=1e-5
    )
    payload_bytes = n_elems * 4
    host_s = host["encode_s"] + host["fold_s"]
    mesh_s = mesh["encode_s"] + mesh["fold_s"]
    return {
        "n_peers": n_peers,
        "payload_mb": payload_mb,
        "method": method,
        "host": host,
        "mesh": mesh,
        # throughput over the CONTRIBUTION bytes each phase touches:
        # encode crosses one payload, fold crosses n.
        "host_mb_s": round((payload_mb * (1 + n_peers)) / max(host_s, 1e-9), 1),
        "mesh_mb_s": round((payload_mb * (1 + n_peers)) / max(mesh_s, 1e-9), 1),
        "ratios": {
            "encode": round(host["encode_s"] / max(mesh["encode_s"], 1e-9), 2),
            "fold": round(host["fold_s"] / max(mesh["fold_s"], 1e-9), 2),
            "encode_fold": round(host_s / max(mesh_s, 1e-9), 2),
        },
        "payload_bytes": payload_bytes,
    }


def _feed_mean_folder(folder, bits, weights, tile, n_tiles):
    total_w = float(weights.sum())
    for p in range(bits.shape[0]):
        raw = bits[p]
        for t in range(n_tiles):
            e0 = t * tile
            if folder.add(t, float(weights[p]) / total_w,
                          raw[e0 : e0 + tile].tobytes()):
                folder.flush()
    return folder.result()


def _assert_ring_interpret_equivalence(mesh, n_devices: int) -> None:
    """Correctness half of the fused-arm contract: the PALLAS ring kernel
    (interpret mode — the exact grid schedule and DMA descriptors the
    silicon path compiles) must match the host fold bit-for-bit at a small
    shape. The throughput arms below run the xla lowering; this pins the
    kernel itself inside the same bench run."""
    from distributedvolunteercomputing_tpu import native
    from distributedvolunteercomputing_tpu.ops import mesh_codec

    codec = mesh_codec.MeshCodec(
        mesh=mesh, backend="mesh", pallas="interpret", collective="ring"
    )
    tile, n_tiles = 256 * n_devices, 4
    n_elems = tile * n_tiles
    folder = codec.mean_folder(n_elems, tile, n_tiles, "bf16")
    assert folder.kind == "ring", f"ring folder not selected: {folder.kind}"
    # The interpreted kernel, folding at flush (no eager ingest).
    folder._lower_cfg, folder._eager = "interpret", False
    rng = np.random.default_rng(3)
    weights = rng.uniform(0.2, 1.0, 3)
    bits = np.stack(
        [native.f32_to_bf16(rng.standard_normal(n_elems).astype(np.float32))
         for _ in range(3)]
    )
    got = _feed_mean_folder(folder, bits, weights, tile, n_tiles)
    ref = np.zeros(n_elems, np.float32)
    total_w = float(weights.sum())
    for p in range(3):
        native.weighted_sum_inplace(
            ref, native.bf16_to_f32(bits[p]), float(weights[p]) / total_w
        )
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-5)
    assert not codec.degraded, f"interpret ring degraded: {codec.degrade_reason}"


def run_fused_config(n_peers: int, payload_mb: float,
                     chunk_bytes: int = CHUNK_BYTES, repeats: int = 2) -> dict:
    """The fused-pipeline arm (ISSUE 18): ring collective folder
    (ops/mesh_collective.py) vs the PR 5 staged folder, BOTH on the same
    multi-device mesh — the mean fold is the only phase that differs, so
    the ratio isolates the fused reduce pipeline. Returns None on a
    1-device mesh, where the ring degenerates to the plain fold and the
    comparison measures nothing."""
    import jax

    from distributedvolunteercomputing_tpu import native
    from distributedvolunteercomputing_tpu.ops import mesh_codec
    from distributedvolunteercomputing_tpu.parallel.mesh import make_mesh

    n_devices = len(jax.devices())
    tile = chunk_bytes // 2
    if n_devices < 2 or tile % n_devices:
        return None
    mesh = make_mesh(dp=n_devices)
    _assert_ring_interpret_equivalence(mesh, n_devices)

    n_elems = int(payload_mb * (1 << 20)) // 4
    n_tiles = -(-n_elems // tile)
    rng = np.random.default_rng(0)
    weights = rng.uniform(0.5, 2.0, n_peers)
    bits = np.stack(
        [native.f32_to_bf16(rng.standard_normal(n_elems).astype(np.float32))
         for _ in range(n_peers)]
    )
    staged = mesh_codec.MeshCodec(mesh=mesh, backend="mesh", collective="off")
    ring = mesh_codec.MeshCodec(mesh=mesh, backend="mesh", collective="ring")

    def fold(codec):
        folder = codec.mean_folder(n_elems, tile, n_tiles, "bf16")
        return _feed_mean_folder(folder, bits, weights, tile, n_tiles)

    # Warm both jit caches AND check xla-lowering equivalence in-bench.
    ref = fold(staged)
    np.testing.assert_allclose(fold(ring), ref, rtol=2e-5, atol=1e-5)
    src = native.bf16_to_f32(bits[0])
    encode_s = _best_of(lambda: ring.encode_bf16(src), repeats)
    staged_s = _best_of(lambda: fold(staged), repeats)
    ring_s = _best_of(lambda: fold(ring), repeats)
    ring_folder = ring.mean_folder(n_elems, tile, n_tiles, "bf16")
    row = {
        "n_peers": n_peers,
        "payload_mb": payload_mb,
        "devices": n_devices,
        "ring_lower": ring_folder._lower_cfg,
        "encode_s": round(encode_s, 6),
        "staged_fold_s": round(staged_s, 6),
        "ring_fold_s": round(ring_s, 6),
        "staged_mb_s": round(payload_mb * n_peers / max(staged_s, 1e-9), 1),
        "ring_mb_s": round(payload_mb * n_peers / max(ring_s, 1e-9), 1),
        "ratios": {
            "fold": round(staged_s / max(ring_s, 1e-9), 2),
            "encode_fold": round(
                (encode_s + staged_s) / max(encode_s + ring_s, 1e-9), 2
            ),
        },
    }
    return row


def run_bench(peers=(8, 16), payloads_mb=(8, 64), methods=("mean", "trimmed_mean"),
              chunk_bytes: int = CHUNK_BYTES, repeats: int = 2) -> dict:
    import jax

    from distributedvolunteercomputing_tpu import native
    from distributedvolunteercomputing_tpu.ops import mesh_codec

    codec = mesh_codec.MeshCodec(backend="mesh")
    rows = []
    for method in methods:
        for n_peers in peers:
            for mb in payloads_mb:
                row = run_config(n_peers, mb, method, chunk_bytes, repeats, codec)
                rows.append(row)
                print(
                    f"{method:12s} n={n_peers:2d} {mb:3g}MB  "
                    f"encode {row['host']['encode_s']*1e3:7.1f}ms -> "
                    f"{row['mesh']['encode_s']*1e3:7.1f}ms "
                    f"({row['ratios']['encode']}x)  "
                    f"fold {row['host']['fold_s']*1e3:8.1f}ms -> "
                    f"{row['mesh']['fold_s']*1e3:8.1f}ms "
                    f"({row['ratios']['fold']}x)  "
                    f"combined {row['ratios']['encode_fold']}x",
                    flush=True,
                )
    fused_rows = []
    for mb in payloads_mb:
        row = run_fused_config(max(peers), mb, chunk_bytes, repeats)
        if row is None:
            print("fused arm skipped: 1-device mesh (ring degenerates to "
                  "the plain fold)", flush=True)
            break
        fused_rows.append(row)
        marker = "" if row["ratios"]["fold"] >= 1.0 else \
            "  ** BELOW STAGED FLOOR **"
        print(
            f"fused        n={row['n_peers']:2d} {mb:3g}MB  "
            f"fold {row['staged_fold_s']*1e3:8.1f}ms -> "
            f"{row['ring_fold_s']*1e3:8.1f}ms "
            f"({row['ratios']['fold']}x vs staged, "
            f"{row['devices']} devices, {row['ring_lower']} lowering)"
            f"{marker}",
            flush=True,
        )
    return {
        "bench": "swarm_codec_host_vs_mesh",
        "host": platform.node(),
        "python": platform.python_version(),
        "unix_time": round(time.time(), 1),
        "chunk_bytes": chunk_bytes,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "native_available": native.available(),
        "codec": codec.stats(),
        "rows": rows,
        # staged-vs-ring on the same mesh; [] when 1-device made the
        # comparison meaningless (never silently measured-as-tied).
        "fused_rows": fused_rows,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="small sanity run")
    ap.add_argument("--out", default=os.path.join(RESULTS, "codec_bench.json"))
    ap.add_argument("--devices", type=int, default=8,
                    help="force at least N host (CPU) devices so the fused "
                         "ring arm has a real mesh to reduce over; ignored "
                         "on platforms with native multi-chip (0 = off)")
    args = ap.parse_args()
    # The bench compares backends, not platforms: run the mesh arm on
    # whatever jax platform is active (CPU in the sandbox, the TPU slice
    # on hardware) and say which in the artifact.
    if args.devices:
        force_host_devices(args.devices)
    from distributedvolunteercomputing_tpu import native

    native.ensure_built()
    kw = {}
    if args.quick:
        kw = dict(peers=(4,), payloads_mb=(2,), chunk_bytes=1 << 18, repeats=2)
    result = run_bench(**kw)
    os.makedirs(RESULTS, exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=2)
        fh.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
