"""Fused ring reduce pipeline over the volunteer's local codec mesh.

The PR 5 staged data path (``ops.mesh_codec.MeshMeanFolder``) element-splits
every arriving wire chunk across the codec axis before folding: the host
slices each chunk into per-device columns (a strided device_put) and ONE
scatter-add folds the batch — so fold ingest is bounded by the single host's
PCIe, not the slice. This module keeps the whole reduce path resident on the
device mesh (the mesh-networks paper's position): chunks land WHOLE on
devices, round-robin over the 1-D "codec" view of the local ``dp*sp*tp``
mesh, and a ring reduce-scatter turns the per-device partial folds into the
element-sharded accumulator layout the staged folder already maintains — so
``result()``, the degraded-slice contract, and the aggregator's
re-normalization are inherited unchanged.

The kernel (``_ring_fold_kernel``) is ONE ``pallas_call`` whose grid is the
ring schedule: grid step ``s`` on device ``d`` decodes the bf16 wire tiles'
slice for shard ``b = (d - s - 1) mod ndev``, folds it into the f32 partial,
and forwards the previous step's partial to the right ring neighbor via
inter-chip send/recv DMA semaphores. Compute and DMA are double-buffered
(two partial slots): the decode+fold for step ``s`` runs while step
``s-1``'s partial is in flight, so fold throughput scales with slice size.
Each wire element is decoded exactly once across the whole grid. A second
kernel (``_ring_ag_kernel``) is the matching ring all-gather used by
``result()`` — one device pass reassembles the full accumulator so the
round result crosses the host link once.

Lowering ladder (the codec's pallas mode decides):

- ``compiled``  — the Pallas kernel on TPU silicon: remote DMA, an entry
  barrier with both ring neighbors, and a REGULAR capacity-semaphore
  handshake (a partial slot is overwritten only after its last send
  completed; the interpreter serializes and needs neither). Compiled for a
  described v5e by tests/test_tpu_compile.py, run against the host fold on
  four chips by ``chip_smoke.py --chips 4``.
- ``interpret`` — the SAME kernel body interpreted on CPU: tier-1 tests and
  the dryrun_multichip gate cover the exact grid schedule, DMA descriptors,
  and fold math bit-for-bit against the host path. The interpreter accepts
  what Mosaic refuses, so it says nothing about whether the kernel compiles.
- ``xla``       — the same math and placement with the collective lowered
  by XLA (``lax.psum_scatter`` / ``lax.all_gather``) instead of the hand
  ring: the fast CPU lowering (interpret-mode Pallas is a Python emulator)
  and the fallback when the kernel's working set exceeds the VMEM cap.

Degrade contract (inherited from ``MeshMeanFolder``): the first device
failure pulls the last good accumulator to host and replays the in-flight
batch with host numpy — the round commits through a mesh shrink, and the
codec permanently degrades so the next round starts on host.
"""

from __future__ import annotations

import functools
import logging
from typing import List, Tuple

import numpy as np

from distributedvolunteercomputing_tpu.ops.mesh_codec import (
    MeshCodecError,
    MeshMeanFolder,
    _bf16_widen,
    _jnp,
)

log = logging.getLogger("dvc.mesh_collective")

# Compiled-mode working-set cap: buffers above this fall back to the xla
# lowering rather than risk a VMEM OOM mid-round (the ring kernel keeps two
# partial slots + the scratch partial + the accumulator shard resident).
# 10 MB: set with the kernel (PR 18) and the one value any run has used;
# ``_lower_for`` counts against it what the kernel allocates. At the 1 MiB
# chunk on four chips it covers three tiles.
_VMEM_CAP_BYTES = 10 << 20
_LANES = 128  # vector lane width: the kernel's (rows, lanes) block minor dim


def ring_available(codec) -> bool:
    """True when ``codec`` routes mean folds through the ring collective
    (active mesh backend, ring selected, >= 2 devices on the codec axis)."""
    if not codec.active or codec._collective != "ring":
        return False
    codec._ensure_mesh()
    return codec._ndev >= 2


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _neighbor_barrier(left, right):
    """Both ring neighbors are inside this kernel before anything is
    written into their memory: a remote DMA or semaphore signal that lands
    before the target entered would hit whatever lives at those addresses."""
    from jax.experimental.pallas import tpu as pltpu

    barrier = pltpu.get_barrier_semaphore()
    for peer in (left, right):
        pltpu.semaphore_signal(
            barrier, 1, device_id=peer, device_id_type=pltpu.DeviceIdType.LOGICAL
        )
    pltpu.semaphore_wait(barrier, 2)


def _ring_fold_kernel(
    nd,
    per_dev,
    n_tiles,
    compiled,
    tiles_ref,
    ws_ref,
    bits_ref,
    acc_ref,
    o_ref,
    buf_ref,
    ctmp_ref,
    bits_vmem,
    bits_sem,
    send_sem,
    recv_sem,
    cap_sem,
):
    """One grid step == one ring step: decode + fold + forward, overlapped.

    Device ``d`` at step ``s`` works shard ``b = (d - s - 1) mod nd``: it
    starts the DMA forwarding step ``s-1``'s partial to the right neighbor,
    then (while that DMA is in flight) pulls its local chunks' ``b``-slice
    from HBM, decodes it and folds it into the scratch partial, then waits
    the DMA and adds the scratch into the freshly received slot. The
    partial for shard ``b`` terminates at device ``b`` on the last step,
    where it folds into the resident accumulator shard.

    Layout: every (tile, shard) slice is a ``(rows, lanes)`` block, so the
    vector work runs on full (sublane, lane) tiles and tiles/chunks/shards
    are picked by dynamic index on untiled leading dims only. The wire bits
    stay in HBM (``bits_ref``, ``[per_dev, nd, rows, lanes]``) and reach
    VMEM by DMA; partials, accumulator and output live in VMEM.

    ``compiled`` adds what only silicon needs: the entry barrier, and the
    capacity handshake closing the one-step-ahead race (a slot is
    re-targeted only after the right neighbor confirms its send from that
    slot completed). The interpreter executes each DMA at ``start`` in
    lockstep across devices and has no remote semaphore signal.
    """
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    jnp = _jnp()
    s = pl.program_id(0)
    d = jax.lax.axis_index("codec")
    right = jax.lax.rem(d + 1, nd)
    left = jax.lax.rem(d + nd - 1, nd)
    slot = jax.lax.rem(s, 2)
    prev = jax.lax.rem(s + 1, 2)
    b = jax.lax.rem(d - s - 1 + 2 * nd, nd)

    load = pltpu.make_async_copy(bits_ref.at[:, b], bits_vmem, bits_sem)
    load.start()
    fwd = pltpu.make_async_remote_copy(
        src_ref=buf_ref.at[prev],
        dst_ref=buf_ref.at[slot],
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=right,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )

    if compiled:

        @pl.when(s == 0)
        def _enter():
            _neighbor_barrier(left, right)

        @pl.when(s > 0)
        def _window_open():
            # Right neighbor finished sending FROM the slot this send
            # targets (its previous-step wait signalled us).
            pltpu.semaphore_wait(cap_sem, 1)

    @pl.when(s > 0)
    def _forward():
        fwd.start()

    # Fused decode+fold for this step's shard slice — runs while the DMA is
    # in flight. Across the nd grid steps the slices partition tile_elems,
    # so every wire element is decoded exactly once.
    ctmp_ref[...] = jnp.zeros(ctmp_ref.shape, jnp.float32)
    load.wait()

    def _fold_one(i, carry):
        t = tiles_ref[i]
        ctmp_ref[t] = ctmp_ref[t] + ws_ref[i] * _bf16_widen(bits_vmem[i])
        return carry

    jax.lax.fori_loop(0, per_dev, _fold_one, 0)

    @pl.when(s == 0)
    def _seed():
        buf_ref[0] = ctmp_ref[...]

    @pl.when(s > 0)
    def _accumulate():
        fwd.wait()
        buf_ref[slot] = buf_ref[slot] + ctmp_ref[...]

    if compiled:

        @pl.when(s < nd - 1)
        def _window_grant():
            # My send from buf[prev] completed (fwd.wait above covers the
            # send side at s>0; at s==0 the slot is virgin): the left
            # neighbor may target it next step.
            pltpu.semaphore_signal(
                cap_sem, 1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )

    @pl.when(s == nd - 1)
    def _emit():
        o_ref[...] = acc_ref[...] + buf_ref[slot]


def _ring_ag_kernel(nd, compiled, x_ref, o_ref, copy_sem, send_sem, recv_sem):
    """Ring all-gather: step ``s`` forwards the block received at ``s-1``
    (own block at ``s==0``) to the right neighbor. Input and output stay in
    HBM and every move is a DMA, so the kernel holds nothing in VMEM. Every
    step's DMA targets a distinct block slot on the receiver, so no
    capacity handshake is needed — the send/recv semaphores alone order
    the chain."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    d = jax.lax.axis_index("codec")
    right = jax.lax.rem(d + 1, nd)
    blk = jax.lax.rem(d - s + 2 * nd, nd)

    @pl.when(s == 0)
    def _own():
        own = pltpu.make_async_copy(x_ref, o_ref.at[d], copy_sem)
        own.start()
        own.wait()
        if compiled:
            _neighbor_barrier(jax.lax.rem(d + nd - 1, nd), right)

    fwd = pltpu.make_async_remote_copy(
        src_ref=o_ref.at[blk],
        dst_ref=o_ref.at[blk],
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=right,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    fwd.start()
    fwd.wait()


# ---------------------------------------------------------------------------
# folder
# ---------------------------------------------------------------------------


class RingMeanFolder(MeshMeanFolder):
    """``MeshMeanFolder`` with the flush/result device halves replaced by
    the fused ring pipeline. Staging, host bookkeeping, the degraded-slice
    replay, and the accumulator layout ([n_tiles, tile_elems] with elements
    split over the codec axis) are all inherited — the aggregator cannot
    tell the folders apart except through ``kind`` and the gauges."""

    kind = "ring"

    def __init__(self, codec, n_elems, tile_elems, n_tiles, wire):
        super().__init__(codec, n_elems, tile_elems, n_tiles, wire)
        if wire != "bf16":
            raise ValueError("ring folder is bf16-wire only")
        codec._ensure_mesh()
        if codec._ndev < 2:
            raise ValueError("ring folder needs >= 2 devices")
        if tile_elems % codec._ndev:
            raise ValueError("tile_elems must split over the codec axis")
        self.shard = tile_elems // codec._ndev
        self.ring_flushes = 0
        self._lower_cfg = self._resolve_lower(codec)
        # Surface the lowering choice on the codec so coord.status can see
        # it: a fleet quietly re-lowered to xla by the VMEM estimate looks
        # identical to one running the kernel otherwise.
        codec.ring_lower = self._lower_cfg
        if codec.ring_lower_effective is None:
            codec.ring_lower_effective = self._lower_cfg
        # Eager ingest (xla lowering): every chunk is ALSO put to its column
        # shard at add() time, so the host-link crossing overlaps chunk
        # arrival and flush() folds device-resident bits with no host
        # consolidation pass. The raw bytes stay staged regardless — they
        # are the degrade-replay source of truth.
        self._eager = self._lower_cfg == "xla"
        self._pending: List = []
        self._eager_broken = False
        self._pad_chunk = None

    # -- lowering ---------------------------------------------------------

    @staticmethod
    def _resolve_lower(codec) -> str:
        """The codec's Pallas mode is the ring's: the kernel compiled,
        the kernel interpreted, or the collective left to XLA."""
        return "xla" if codec._pallas_mode == "off" else codec._pallas_mode

    def _lower_for(self, per_dev: int) -> str:
        """The flush lowering for one batch size: compiled falls back to
        xla when the kernel working set would blow VMEM (two partial slots
        + scratch partial + acc shard + out, f32, plus one shard slice of
        the local chunks' u16 bits)."""
        lower = self._lower_cfg
        if lower != "compiled":
            return lower
        buf_bytes = self.n_tiles * self.shard * 4
        est = 5 * buf_bytes + 2 * per_dev * self.shard
        if est > _VMEM_CAP_BYTES:
            self._note_vmem_fallback("flush", est)
            return "xla"
        self.codec.ring_lower_effective = lower
        return lower

    def _note_vmem_fallback(self, site: str, est: int) -> None:
        """Book a compiled->xla re-lowering on the codec gauges and warn
        exactly once per codec — the fallback is correct but should never
        be silent, or a whole fleet re-lowered to xla reads as if the
        kernel were live."""
        codec = self.codec
        reason = "%s working set %.1fMB > VMEM cap %.0fMB" % (
            site,
            est / (1 << 20),
            _VMEM_CAP_BYTES / (1 << 20),
        )
        codec.ring_lower_effective = "xla"
        codec.ring_lower_fallback = reason
        codec.ring_vmem_fallbacks += 1
        if not codec._ring_vmem_warned:
            codec._ring_vmem_warned = True
            log.warning("ring lowering fell back compiled->xla: %s", reason)

    # -- eager ingest (xla lowering) --------------------------------------

    def add(self, tile: int, weight: float, data: bytes) -> bool:
        dev = None
        if self._eager and not self._eager_broken and self._host_acc is None:
            try:
                dev = self._eager_put(data)
            except Exception:  # noqa: BLE001 — the flush degrades with context
                self._eager_broken = True
        with self._lock:
            self._staged.append((tile, float(weight), data))
            self._staged_bytes += len(data)
            if self._staged_bytes > self.peak_staged_bytes:
                self.peak_staged_bytes = self._staged_bytes
            if self._eager:
                self._pending.append(dev)
            return self._staged_bytes >= self.flush_bytes

    def _eager_put(self, data: bytes):
        import jax
        from jax.sharding import PartitionSpec as P

        arr = np.frombuffer(data, np.uint16)
        if arr.size != self.tile_elems:  # short tail chunk: pad like _batch_arrays
            pad = np.zeros(self.tile_elems, np.uint16)
            pad[: arr.size] = arr
            arr = pad
        # Flat 1-D split: every device's slice is one contiguous memcpy
        # (the staged path's [kb, row] column split strides per row).
        return jax.device_put(arr, self.codec._sharding(P("codec")))

    def flush(self) -> None:
        with self._lock:
            batch, self._staged = self._staged, []
            pend, self._pending = self._pending, []
            self._staged_bytes = 0
        if not batch:
            return
        self.flushes += 1
        self.codec._run(
            lambda: self._flush_dev(batch, pend),
            lambda: self._flush_host(batch),
            "ring_flush", sum(len(data) for _, _, data in batch) // self.esz,
        )

    # -- flush ------------------------------------------------------------

    def _flush_dev(self, batch: List[Tuple[int, float, bytes]], pend=None) -> bool:
        import jax
        from jax.sharding import PartitionSpec as P

        codec = self.codec
        codec._ensure_mesh()
        nd = codec._ndev
        if self._eager:
            if self._eager_broken or pend is None or any(d is None for d in pend):
                raise MeshCodecError("eager ingest lost chunks (device put failed)")
            return self._flush_eager(batch, pend)
        # Bucket the PER-DEVICE chunk count to a power of two (same
        # compile-count bound as the staged folder); the batch dim must
        # split evenly over the codec axis for whole-chunk placement.
        per_dev = 1 << max(-(-len(batch) // nd) - 1, 0).bit_length()
        kb = per_dev * nd
        tiles, ws, raw = self._batch_arrays(batch, kb)
        x = raw.view(np.uint16)
        lower = self._lower_for(per_dev)
        fn = codec._jit(
            ("ring_flush", lower, kb, self.n_tiles, self.tile_elems),
            lambda: self._build_flush(lower, per_dev),
        )
        # Whole-chunk placement: batch rows split over the codec axis
        # (contiguous rows per device — no host element-splitting).
        xd = jax.device_put(x, codec._sharding(P("codec", None)))
        meta_spec = P() if lower == "xla" else P("codec")
        td = jax.device_put(tiles, codec._sharding(meta_spec))
        wd = jax.device_put(ws, codec._sharding(meta_spec))
        with self._lock:
            if self._host_acc is not None:
                raise MeshCodecError("folder already degraded")  # -> host()
            acc = self._device_acc()
            self._acc = fn(acc, xd, td, wd)
        self.ring_flushes += 1
        return True

    def _flush_eager(self, batch, pend) -> bool:
        """Fold the device-resident eager chunks: per-chunk row scatter-adds
        into the donated accumulator shard — the wire bytes cross the host
        link exactly once (at add() time) and the fold reads them exactly
        once. No consolidation pass, no exchange: every chunk already sits
        column-split on its owners."""
        codec = self.codec
        kb = 1 << max(len(batch) - 1, 0).bit_length()
        tiles = np.zeros(kb, np.int32)
        ws = np.zeros(kb, np.float32)
        tiles[: len(batch)] = [t for t, _, _ in batch]
        ws[: len(batch)] = [w for _, w, _ in batch]
        chunks = list(pend)
        if kb > len(chunks):
            if self._pad_chunk is None:
                self._pad_chunk = self._eager_put(b"")
            chunks += [self._pad_chunk] * (kb - len(chunks))
        fn = codec._jit(
            ("ring_eager", kb, self.n_tiles, self.tile_elems),
            lambda: self._build_eager(kb),
        )
        with self._lock:
            if self._host_acc is not None:
                raise MeshCodecError("folder already degraded")  # -> host()
            acc = self._device_acc()
            self._acc = fn(acc, tiles, ws, *chunks)
        self.ring_flushes += 1
        return True

    def _build_eager(self, kb: int):
        from jax.sharding import PartitionSpec as P

        codec = self.codec

        def body(a, t_, w_, *xs):
            # Each x is this device's [shard] slice of one chunk: one
            # dynamic row update per chunk, nothing widened twice, no
            # batch-matrix materialization at any width.
            for i, x in enumerate(xs):
                a = a.at[t_[i]].add(w_[i] * _bf16_widen(x))
            return a

        in_specs = (P(None, "codec"), P(), P()) + (P("codec"),) * kb
        return codec._shard_map(
            body, in_specs, P(None, "codec"), "body_ring_rows", donate_argnums=(0,)
        )

    def _block(self) -> Tuple[int, int, int]:
        """The accumulator shard as the kernels see it: ``(n_tiles, rows,
        lanes)``, each (tile, shard) slice one (rows, lanes) block — full
        vector tiles where the shard allows it (always, at the real 1 MiB
        wire chunk), with tiles on an untiled leading dim."""
        lanes = _LANES if self.shard % _LANES == 0 else self.shard
        return (self.n_tiles, self.shard // lanes, lanes)

    def _build_flush(self, lower: str, per_dev: int):
        import jax
        from jax.sharding import PartitionSpec as P

        jnp = _jnp()
        codec = self.codec
        nd = codec._ndev
        shard = self.shard
        n_tiles = self.n_tiles

        if lower == "xla":

            def body(a, x_, t_, w_):
                # Same schedule, XLA collective: the reduce-scatter runs on
                # the RAW bf16 bits (an all_to_all moving half the bytes a
                # f32 partial exchange would), then the decode+fold is
                # column-local — never a full-width f32 partial per device.
                # x_ local [per_dev, nd*shard] u16; t_/w_ replicated [kb].
                xs = x_.reshape(per_dev, nd, shard)
                mine = jax.lax.all_to_all(
                    xs, "codec", split_axis=1, concat_axis=0, tiled=False
                )
                # [nd, per_dev, shard]: every chunk's slice of my columns,
                # source-device-major == the global batch row order. The
                # fold scatter-adds straight into the donated accumulator —
                # no per-device partial buffer exists at any width.
                mine = mine.reshape(per_dev * nd, shard)
                return a.at[t_].add(w_[:, None] * _bf16_widen(mine))

        else:
            compiled = lower == "compiled"
            block = self._block()
            _, rows, lanes = block
            kern = functools.partial(
                _ring_fold_kernel, nd, per_dev, n_tiles, compiled
            )

            def body(a, x_, t_, w_):
                from jax.experimental import pallas as pl
                from jax.experimental.pallas import tpu as pltpu

                out = pl.pallas_call(
                    kern,
                    grid=(nd,),
                    out_shape=jax.ShapeDtypeStruct(block, jnp.float32),
                    in_specs=[
                        pl.BlockSpec(memory_space=pltpu.SMEM),
                        pl.BlockSpec(memory_space=pltpu.SMEM),
                        pl.BlockSpec(memory_space=pl.ANY),
                        pl.BlockSpec(memory_space=pltpu.VMEM),
                    ],
                    out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                    scratch_shapes=[
                        pltpu.VMEM((2,) + block, jnp.float32),
                        pltpu.VMEM(block, jnp.float32),
                        pltpu.VMEM((per_dev, rows, lanes), jnp.uint16),
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.REGULAR,
                    ],
                    interpret=not compiled,
                    compiler_params=_compiler_params(compiled, collective_id=0),
                )(
                    t_, w_,
                    x_.reshape(per_dev, nd, rows, lanes),
                    a.reshape(block),
                )
                return out.reshape(n_tiles, shard)

        # The pallas ring folds each device's OWN chunks step by step
        # (tiles/ws row-sharded); the xla all_to_all hands every device all
        # kb chunks' column slices, so it reads the full (tiny) tiles/ws.
        meta_spec = P() if lower == "xla" else P("codec")
        return codec._shard_map(
            body,
            (P(None, "codec"), P("codec", None), meta_spec, meta_spec),
            P(None, "codec"),
            f"body_ring_fold_{lower}",
            donate_argnums=(0,),
        )

    # -- result -----------------------------------------------------------

    def result(self) -> np.ndarray:
        """Flush the tail, then reassemble the sharded accumulator with the
        ring all-gather — one device pass, one host fetch. A device lost
        mid-gather degrades to the inherited sharded host gather. The xla
        lowering skips the device all-gather: XLA's host pull of a sharded
        array already fetches each shard exactly once, and replicating the
        full accumulator on every device first is pure extra traffic."""
        self.flush()
        with self._lock:
            acc = self._acc
        if acc is None or not self.codec.active or self._lower_cfg == "xla":
            return super().result()

        def dev() -> np.ndarray:
            fn = self.codec._jit(
                ("ring_ag", self._lower_cfg, self.n_tiles, self.tile_elems),
                self._build_gather,
            )
            full = np.asarray(fn(acc))
            with self._lock:
                self._acc = None
            return full.ravel()[: self.n_elems].copy()

        return self.codec._run(
            dev, lambda: super(RingMeanFolder, self).result(), "ring_gather", self.n_elems
        )

    def _build_gather(self):
        import jax
        from jax.sharding import PartitionSpec as P

        jnp = _jnp()
        codec = self.codec
        nd = codec._ndev
        shard = self.shard
        n_tiles = self.n_tiles
        compiled = self._lower_cfg == "compiled"
        block = self._block()
        kern = functools.partial(_ring_ag_kernel, nd, compiled)

        def body(a):
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            o = pl.pallas_call(
                kern,
                grid=(nd - 1,),
                out_shape=jax.ShapeDtypeStruct((nd,) + block, jnp.float32),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[pltpu.SemaphoreType.DMA] * 3,
                interpret=not compiled,
                compiler_params=_compiler_params(compiled, collective_id=1),
            )(a.reshape(block))
            o = o.reshape(nd, n_tiles, shard)
            return jnp.swapaxes(o, 0, 1).reshape(n_tiles, nd * shard)

        return codec._shard_map(body, (P(None, "codec"),), P(None, None), "body_ring_gather")


def _compiler_params(compiled: bool, collective_id: int):
    """The compiled lowering's kernels signal neighbors (barrier semaphore
    ``collective_id``) and move data by remote DMA, which must not be
    DCE'd; the interpreter takes no params."""
    if not compiled:
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(has_side_effects=True, collective_id=collective_id)
