"""On-mesh swarm data path: the wire codec and the robust tile folds run on
the volunteer's local accelerator mesh instead of single-threaded host numpy.

PRs 2–3 made the NETWORK side of an averaging round 3–86× faster, which
left the chip-side data path — bf16↔f32 wire codec, PowerSGD power
iterations, and the per-tile robust folds in ``swarm/agg_stream.py`` — as
the round bottleneck: all of it ran as host-CPU numpy while the volunteer's
TPU slice sat idle between train steps. This module moves those ops onto
the slice:

- **bf16 pack/unpack** (``encode_bf16`` / ``decode_bf16`` /
  ``decode_axpy``): one fused XLA pass (bitcast + widen + axpy) instead of
  the host's decode-then-axpy two-pass, optionally lowered through a Pallas
  kernel on TPU backends (``_enc_kernel`` / ``_dec_axpy_kernel``).
- **window folds** (``aggregate``): coordinate-wise estimators (median,
  trimmed_mean) over an ``[n_peers, tile]`` window run as an UNROLLED
  Batcher sorting network over the peer axis — n is tiny (a round's group),
  so the network is ~n·log²n elementwise min/max passes that XLA fuses and
  parallelizes over the tile dim, where a host column sort is serial.
  Weighted mean folds as one fused multiply-sum.
- **mean accumulation** (``MeshMeanFolder``): the streaming leader's O(D)
  mean accumulator lives ON DEVICE as an ``[n_tiles, tile]`` buffer;
  arriving wire chunks stage as raw bytes and fold in batches via one
  scatter-add (fused bf16-decode + weighted add), overlapped with arrival.
- **PowerSGD** (``low_rank_iterate`` / ``lowrank_reconstruct``): the per-
  tensor ``QR(M·Q)`` / ``MᵀP`` power-iteration matmuls and the decoder's
  ``P·Qᵀ`` reconstruction.

Placement and decomposition policy (mirrors ``ops.robust._TILE_MODES``):

=================  ==========================================================
method             on-mesh path
=================  ==========================================================
mean               device (fused weighted multiply-sum / scatter-add folder)
median             device (sorting network over the peer axis)
trimmed_mean       device (sorting network; trim rows dropped from the sum)
krum / bulyan      host — selection needs float64 pairwise d² (accumulated
                   tile-wise on host by the streaming aggregator) and a
                   discrete argsort pick; shipping rows to device buys
                   nothing over the d²-precomputed host path
geometric_median   host — Weiszfeld's data-dependent early exit
centered_clip      host — data-dependent per-iteration clip radii
=================  ==========================================================

Sharding: every device op runs under ``shard_map`` over a 1-D **codec view**
of the volunteer's ``(dp, sp, pp, ep, tp)`` mesh — the flat f32/bf16 wire
buffers have no model axes, so the natural placement is an even split of the
element dim across ALL local chips (``NamedSharding(P("codec"))``); window
stacks split their tile dim the same way with the peer dim replicated. A
single-device mesh degenerates to plain jit with zero overhead, so one code
path serves the 8-chip slice and the laptop volunteer alike.

Backend selection happens ONCE per volunteer at startup (``configure``), in
``choose_data_path``: ``"mesh"`` when the default jax backend is TPU silicon
(``utils.jaxenv.tpu_backend``), ``"host"`` otherwise — the host path
delegates straight to ``native``/``ops.robust`` numpy, so a CPU-platform
tier-1 run never pays a jit compile it didn't ask for. A constructor
argument forces a path (benches, equivalence tests).

Degraded-slice fallback (mesh-networks paper, PAPERS.md: slice-level
failures are a normal operating mode, not a crash): every device op runs
through ``_run``, and the FIRST device failure — a chip dropping out of the
local mesh, a PJRT runtime error, an injected chaos fault — permanently
degrades this codec to the host backend, replays the failed op on host, and
surfaces the reason in ``stats()``. Mid-round state is handled by the
callers: the stateless codec ops re-run losslessly; ``MeshMeanFolder`` pulls
its last good device accumulator back to host and keeps folding there, so a
round in flight COMMITS through a mesh shrink instead of dying with it.
A kernel that fails to trace or compile is NOT a lost device: its first
call raises ``MeshKernelError`` (``_jit``), which ``_run`` lets through, so
a broken kernel stops the volunteer instead of hiding behind the host path.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from distributedvolunteercomputing_tpu.utils.logging import errstr, get_logger

log = get_logger(__name__)

# Stage this many raw wire bytes before a MeshMeanFolder flush: big enough
# to amortize a device dispatch over many tiles, small enough that folding
# stays overlapped with arrival (a 64 MB contribution flushes ~4 times).
FOLDER_FLUSH_BYTES = 16 << 20


class MeshCodecError(RuntimeError):
    """An injected (chaos) or real device failure inside a mesh op."""


class MeshKernelError(RuntimeError):
    """A device program failed to trace or compile on its first call — a
    bug in the kernel or the installation, never absorbed as a degrade."""


def _batcher_pairs(m: int) -> List[Tuple[int, int]]:
    """Batcher odd-even mergesort compare-exchange pairs for m rows
    (m a power of two) — the static sorting network the window estimators
    unroll over the peer axis."""
    pairs: List[Tuple[int, int]] = []

    def merge(lo: int, cnt: int, r: int) -> None:
        step = r * 2
        if step < cnt:
            merge(lo, cnt, step)
            merge(lo + r, cnt, step)
            for i in range(lo + r, lo + cnt - r, step):
                pairs.append((i, i + r))
        else:
            pairs.append((lo, lo + r))

    def sort(lo: int, cnt: int) -> None:
        if cnt > 1:
            half = cnt // 2
            sort(lo, half)
            sort(lo + half, cnt - half)
            merge(lo, cnt, 1)

    sort(0, m)
    return pairs


# ---------------------------------------------------------------------------
# Pallas kernels (TPU path for the hot bf16 pack/unpack + axpy fold)
# ---------------------------------------------------------------------------
#
# The jnp bodies below already fuse into single XLA passes; the Pallas
# versions exist for the TPU backend, where explicit (rows, 128)-lane
# blocking keeps the codec's VMEM footprint bounded and off the train
# step's working set. They are gated (``_pallas_mode``): compiled on TPU
# silicon, interpreted under ``pallas="interpret"`` (CPU equivalence
# tests), and skipped otherwise.

_PALLAS_LANES = 128
_PALLAS_ROWS = 512  # block = (512, 128) f32 -> 256 KB VMEM per operand


def _enc_kernel(x_ref, o_ref):
    import jax

    o_ref[...] = jax.lax.bitcast_convert_type(
        x_ref[...].astype(_jnp().bfloat16), _jnp().uint16
    )


def _dec_axpy_kernel(b_ref, a_ref, w_ref, o_ref):
    o_ref[...] = a_ref[...] + w_ref[0, 0] * _bf16_widen(b_ref[...])


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under a stable ``__name__``: jit names its program after it,
    and a profiler trace is read by program name. Every codec program is
    ``encode_*``, ``decode_*`` or ``body_<op>``."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _jnp():
    import jax.numpy as jnp

    return jnp


def _bf16_widen(bits):
    """THE fused bf16-bits -> f32 expression every device body shares
    (decode, decode+axpy, folder flush, window aggregate_bits) — one home,
    so the lowering can't drift between call sites."""
    import jax

    return jax.lax.bitcast_convert_type(bits, _jnp().bfloat16).astype(_jnp().float32)


def choose_data_path(n_devices: int) -> Tuple[str, str, str]:
    """``(backend, pallas, collective)`` for a codec over ``n_devices`` local
    devices, from what the process can observe. On TPU silicon: the mesh
    backend, the compiled Pallas codec kernels, and the ring fold where the
    codec axis has a neighbour to send to. Elsewhere: the host path, no
    kernels, the staged folder. The one place this is decided: a
    ``MeshCodec`` argument overrides its part, a test patches this."""
    from distributedvolunteercomputing_tpu.utils.jaxenv import tpu_backend

    if not tpu_backend():
        return "host", "off", "off"
    return "mesh", "compiled", "ring" if n_devices >= 2 else "off"


class MeshCodec:
    """One volunteer's on-mesh codec + fold engine (or its host fallback).

    ``backend`` ("mesh" | "host"), ``pallas`` ("compiled" | "interpret" |
    "off": the bf16 kernels' lowering) and ``collective`` ("ring" | "off":
    the fused reduce pipeline, ops.mesh_collective) are each what
    ``choose_data_path`` says unless given — benches and equivalence tests
    force the device path on the CPU platform that way. ``mesh`` is the
    volunteer's training Mesh; its devices are re-viewed as the 1-D codec
    axis. ``None`` uses the default jax device only.
    """

    def __init__(
        self,
        mesh=None,
        backend: Optional[str] = None,
        pallas: Optional[str] = None,
        collective: Optional[str] = None,
    ):
        for name, value, allowed in (
            ("backend", backend, ("mesh", "host")),
            ("pallas", pallas, ("compiled", "interpret", "off")),
            ("collective", collective, ("ring", "off")),
        ):
            if value is not None and value not in allowed:
                raise ValueError(f"unknown mesh-codec {name} {value!r}")
        n_devices = 1 if mesh is None else int(np.asarray(mesh.devices).size)
        auto_backend, auto_pallas, auto_collective = choose_data_path(n_devices)
        self._backend = backend or auto_backend
        self._pallas_mode = pallas or auto_pallas
        self._collective = collective or auto_collective
        self._lock = threading.Lock()
        self._mesh_arg = mesh
        self._codec_mesh = None  # built lazily on first device op
        self._ndev = 1
        self._host_args = False  # see _ensure_mesh
        self._jit_cache: Dict[tuple, Callable] = {}
        self._compiled_once: set = set()  # (key, arg signature), see _jit
        self.degraded = False
        self.degrade_reason = ""
        self._fail_injected = 0
        # Optional flight recorder (anything with .record(kind, **fields));
        # attached by the volunteer so a degrade event lands in the
        # telemetry plane's ring buffer beside the depositions and fences.
        self.recorder = None
        # Optional span tracer (swarm/telemetry.py ``Tracer``), attached the
        # same way: each device op is a ``codec.op`` span under the round
        # that called it, split into what the host waited for, in order:
        # ``codec.h2d``, ``codec.run`` (the program's wait in the chip's
        # in-order queue and its execution), ``codec.d2h``.
        self.tracer = None
        # gauges
        self.ops_mesh = 0
        self.ops_host = 0
        self.fallbacks = 0
        # Ring-lowering gauges, written by RingMeanFolder: the configured
        # lowering, the last lowering actually used, and how many flushes
        # were quietly re-lowered to xla by the VMEM estimate. Without
        # these a fleet re-lowered to xla by the VMEM cap (or a mis-sized
        # estimate) is indistinguishable from one running the kernel.
        self.ring_lower: Optional[str] = None
        self.ring_lower_effective: Optional[str] = None
        self.ring_lower_fallback: Optional[str] = None
        self.ring_vmem_fallbacks = 0
        self._ring_vmem_warned = False

    @property
    def backend(self) -> str:
        return "host" if self.degraded else self._backend

    @property
    def active(self) -> bool:
        """True when device ops are live (mesh backend, not degraded)."""
        return self._backend == "mesh" and not self.degraded

    def stats(self) -> dict:
        return {
            "backend": self.backend,
            "configured": self._backend,
            "devices": self._ndev if self._codec_mesh is not None else None,
            "pallas": self._pallas_mode,
            "collective": self._collective,
            "ops_mesh": int(self.ops_mesh),
            "ops_host": int(self.ops_host),
            "fallbacks": int(self.fallbacks),
            "degraded": bool(self.degraded),
            "degrade_reason": self.degrade_reason,
            "ring_lower": self.ring_lower,
            "ring_lower_effective": self.ring_lower_effective,
            "ring_lower_fallback": self.ring_lower_fallback,
            "ring_vmem_fallbacks": int(self.ring_vmem_fallbacks),
        }

    # -- failure handling --------------------------------------------------

    def inject_failure(self, n: int = 1) -> None:
        """Chaos hook: the next ``n`` device ops raise (a synthetic mesh
        shrink / chip loss), exercising the degrade-to-host path."""
        with self._lock:
            self._fail_injected += int(n)

    def _check_injected(self) -> None:
        with self._lock:
            if self._fail_injected > 0:
                self._fail_injected -= 1
                raise MeshCodecError("injected mesh failure (chaos)")

    def _degrade(self, e: BaseException) -> None:
        with self._lock:
            if self.degraded:
                return  # idempotent: late racers must not re-log/re-count
            self.degraded = True
            self.degrade_reason = errstr(e)
            self.fallbacks += 1
        log.warning(
            "mesh codec degraded to host backend: %s — this volunteer "
            "continues on the host data path", errstr(e),
        )
        if self.recorder is not None:
            # Flight recorder (swarm/telemetry.py): a slice loss mid-round
            # is front-page post-mortem material.
            try:
                self.recorder.record("codec_degraded", reason=errstr(e))
            except Exception:  # noqa: BLE001 — recording must not affect the fallback
                pass

    def _phase(self, name: str, **attrs: Any):
        """Span + profiler annotation (``Tracer.phase``) around a part of a
        device op; nothing without a tracer."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.phase(name, **attrs)

    def _run(self, op: Callable, host: Callable, name: str, elems: int):
        """Run ``op`` on device, falling back to ``host`` (and permanently
        degrading) when the DEVICE fails: a runtime error out of the
        backend, or an injected fault. The stateless codec ops lose nothing
        in the fallback — the same inputs re-run on host. Anything else —
        ``MeshKernelError`` from a program that would not trace or compile,
        a Python error in the op — is a bug and propagates. ``name`` and
        ``elems`` label the op's ``codec.op`` span."""
        if not self.active:
            self.ops_host += 1
            return host()
        import jax

        try:
            with self._phase("codec.op", op=name, elems=int(elems)):
                self._check_injected()
                out = op()
        except (MeshCodecError, jax.errors.JaxRuntimeError) as e:
            # Chip loss must not kill the round.
            self._degrade(e)
            self.ops_host += 1
            return host()
        self.ops_mesh += 1
        return out

    def _call(self, fn: Callable, *args):
        """Dispatch a device program and wait until its result is ready: the
        wait the blocking host read made anyway, taken apart from the copy
        (``_fetch``) so that queueing and running show without the transfer."""
        import jax

        with self._phase("codec.run"):
            return jax.block_until_ready(fn(*args))

    def _fetch(self, dev) -> np.ndarray:
        """The host copy of a ready device result."""
        with self._phase("codec.d2h"):
            return np.asarray(dev)

    # -- device plumbing ---------------------------------------------------

    def _ensure_mesh(self):
        """The 1-D codec Mesh (lazy: building it touches the backend)."""
        if self._codec_mesh is None:
            import jax
            from jax.sharding import Mesh

            if self._mesh_arg is not None:
                devices = np.asarray(self._mesh_arg.devices).reshape(-1)
            else:
                devices = np.asarray(jax.devices()[:1])
            self._codec_mesh = Mesh(devices, ("codec",))
            self._ndev = devices.size
            # One CPU device: XLA:CPU consumes aligned numpy zero-copy, and
            # an explicit device_put would just be a memcpy. On a chip the
            # same hand-over is a transfer inside the call; made explicit
            # there, it is timed as ``codec.h2d`` and not as the program.
            self._host_args = devices.size == 1 and devices[0].platform == "cpu"
        return self._codec_mesh

    def _sharding(self, spec):
        from jax.sharding import NamedSharding

        return NamedSharding(self._ensure_mesh(), spec)

    def _put_flat(self, arr: np.ndarray):
        """Pad a flat host array to an ndev multiple and place it split over
        the codec axis. Returns (device_array, original_size). On a single
        CPU device the host array is handed to jit directly (_ensure_mesh)."""
        import jax
        from jax.sharding import PartitionSpec as P

        self._ensure_mesh()
        n = arr.size
        with self._phase("codec.h2d"):
            pad = (-n) % self._ndev
            if pad:
                arr = np.pad(arr, (0, pad))
            if self._host_args:
                return arr, n
            return jax.device_put(arr, self._sharding(P("codec"))), n

    def _put_stack(self, stack: np.ndarray):
        """[n, T] host stack placed with the tile dim split over the codec
        axis (peers replicated). Returns (device_array, original_T)."""
        import jax
        from jax.sharding import PartitionSpec as P

        self._ensure_mesh()
        t = stack.shape[1]
        with self._phase("codec.h2d"):
            pad = (-t) % self._ndev
            if pad:
                stack = np.pad(stack, ((0, 0), (0, pad)))
            if self._host_args:
                return stack, t
            return jax.device_put(stack, self._sharding(P(None, "codec"))), t

    def _jit(self, key: tuple, build: Callable[[], Callable]) -> Callable:
        """The cached device program for ``key``. Its first call per
        argument signature is the one that traces and compiles (execution
        is dispatched asynchronously and fails later, at the host read): a
        failure there raises ``MeshKernelError`` so ``_run`` cannot mistake
        a kernel the compiler refuses for a lost chip."""
        fn = self._jit_cache.get(key)
        if fn is None:
            program = build()

            @functools.wraps(program)  # keeps the program's name and the program
            def fn(*args):
                sig = (key, tuple((a.shape, a.dtype) for a in args))
                if sig in self._compiled_once:
                    return program(*args)
                try:
                    out = program(*args)
                except Exception as e:
                    raise MeshKernelError(
                        f"mesh codec program {key} failed on first use: {errstr(e)}"
                    ) from e
                self._compiled_once.add(sig)
                return out

            self._jit_cache[key] = fn
        return fn

    def _shard_map(self, fn, in_specs, out_specs, name: str, **jit_kw):
        """jit(shard_map(fn)) over the codec mesh — the SNIPPETS.md [2]
        wrapping pattern. All codec ops are elementwise over the sharded
        dim, so replication checking has nothing to reject; it stays off to
        keep scatter ops eligible. ``name`` is the program's: a trace shows
        it as ``jit_<name>`` (see ``_named``)."""
        import jax

        wrapped = jax.shard_map(
            fn, mesh=self._ensure_mesh(), in_specs=in_specs,
            out_specs=out_specs, check_vma=False,
        )
        return jax.jit(_named(wrapped, name), **jit_kw)

    # -- pallas inner bodies ----------------------------------------------

    def _pallas_encode_local(self, x):
        """Local-shard bf16 pack through the Pallas kernel; caller
        guarantees the shard size divides the (rows, lanes) blocking."""
        import jax
        from jax.experimental import pallas as pl

        jnp = _jnp()
        rows = x.size // _PALLAS_LANES
        x2 = x.reshape(rows, _PALLAS_LANES)
        grid = rows // _PALLAS_ROWS
        return pl.pallas_call(
            _enc_kernel,
            out_shape=jax.ShapeDtypeStruct((rows, _PALLAS_LANES), jnp.uint16),
            in_specs=[pl.BlockSpec((_PALLAS_ROWS, _PALLAS_LANES), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((_PALLAS_ROWS, _PALLAS_LANES), lambda i: (i, 0)),
            grid=(grid,),
            interpret=self._pallas_mode == "interpret",
        )(x2).reshape(-1)

    def _pallas_dec_axpy_local(self, bits, acc, w):
        import jax
        from jax.experimental import pallas as pl

        jnp = _jnp()
        rows = bits.size // _PALLAS_LANES
        b2 = bits.reshape(rows, _PALLAS_LANES)
        a2 = acc.reshape(rows, _PALLAS_LANES)
        w2 = w.reshape(1, 1)
        grid = rows // _PALLAS_ROWS
        return pl.pallas_call(
            _dec_axpy_kernel,
            out_shape=jax.ShapeDtypeStruct((rows, _PALLAS_LANES), jnp.float32),
            in_specs=[
                pl.BlockSpec((_PALLAS_ROWS, _PALLAS_LANES), lambda i: (i, 0)),
                pl.BlockSpec((_PALLAS_ROWS, _PALLAS_LANES), lambda i: (i, 0)),
                pl.BlockSpec((1, 1), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((_PALLAS_ROWS, _PALLAS_LANES), lambda i: (i, 0)),
            grid=(grid,),
            interpret=self._pallas_mode == "interpret",
        )(b2, a2, w2).reshape(-1)

    def _pallas_eligible(self, n: int) -> bool:
        """Pallas blocking needs every local shard to tile (rows, lanes)
        exactly; off-size buffers take the jnp body instead of padding
        twice."""
        self._ensure_mesh()
        block = self._ndev * _PALLAS_ROWS * _PALLAS_LANES
        return self._pallas_mode != "off" and n > 0 and n % block == 0

    # -- bf16 wire codec ---------------------------------------------------

    def encode_bf16(self, buf: np.ndarray) -> np.ndarray:
        """float32 [n] -> uint16 [n] bf16 bit patterns (round-to-nearest-
        even — bit-compatible with ``native.f32_to_bf16`` on finite
        values)."""
        from distributedvolunteercomputing_tpu import native

        buf = np.ascontiguousarray(buf, np.float32).ravel()

        def dev() -> np.ndarray:
            import jax
            from jax.sharding import PartitionSpec as P

            jnp = _jnp()
            use_pallas = self._pallas_eligible(buf.size)

            def body(x):
                if use_pallas:
                    return self._pallas_encode_local(x)
                return jax.lax.bitcast_convert_type(
                    x.astype(jnp.bfloat16), jnp.uint16
                )

            fn = self._jit(
                ("enc", use_pallas),
                lambda: self._shard_map(body, (P("codec"),), P("codec"), "encode_bf16"),
            )
            x, n = self._put_flat(buf)
            return self._fetch(self._call(fn, x))[:n]

        return self._run(dev, lambda: native.f32_to_bf16(buf), "encode_bf16", buf.size)

    def decode_bf16(self, bits: np.ndarray, out: Optional[np.ndarray] = None) -> np.ndarray:
        """uint16 bf16 bit patterns -> float32 (exact: bf16 ⊂ f32)."""
        from distributedvolunteercomputing_tpu import native

        bits = np.ascontiguousarray(bits, np.uint16).ravel()

        def dev() -> np.ndarray:
            import jax
            from jax.sharding import PartitionSpec as P

            jnp = _jnp()

            def body(b):
                return _bf16_widen(b)

            fn = self._jit(
                ("dec",),
                lambda: self._shard_map(body, (P("codec"),), P("codec"), "decode_bf16"),
            )
            b, n = self._put_flat(bits)
            res = self._fetch(self._call(fn, b))[:n]
            if out is not None:
                out[: res.size] = res
                return out[: res.size]
            return res

        return self._run(
            dev, lambda: native.bf16_to_f32(bits, out=out), "decode_bf16", bits.size
        )

    def decode_axpy(self, acc: np.ndarray, bits: np.ndarray, w: float) -> np.ndarray:
        """acc + w · decode(bits) in ONE fused device pass (the host path
        pays a decode allocation plus a second axpy pass). Returns the new
        accumulator; the host fallback mutates ``acc`` in place and returns
        it — callers must use the return value either way."""
        from distributedvolunteercomputing_tpu import native

        acc = np.ascontiguousarray(acc, np.float32).ravel()
        bits = np.ascontiguousarray(bits, np.uint16).ravel()
        if acc.size != bits.size:
            raise ValueError(f"decode_axpy size mismatch: {acc.size} vs {bits.size}")

        def dev() -> np.ndarray:
            import jax
            from jax.sharding import PartitionSpec as P

            jnp = _jnp()
            use_pallas = self._pallas_eligible(acc.size)

            def body(a, b, wv):
                if use_pallas:
                    return self._pallas_dec_axpy_local(b, a, wv)
                return a + wv[0] * _bf16_widen(b)

            fn = self._jit(
                ("dec_axpy", use_pallas),
                lambda: self._shard_map(
                    body, (P("codec"), P("codec"), P()), P("codec"), "body_dec_axpy"
                ),
            )
            a, n = self._put_flat(acc)
            b, _ = self._put_flat(bits)
            return self._fetch(self._call(fn, a, b, np.float32([w])))[:n]

        def host() -> np.ndarray:
            native.weighted_sum_inplace(acc, native.bf16_to_f32(bits), float(w))
            return acc

        return self._run(dev, host, "dec_axpy", acc.size)

    # -- window folds ------------------------------------------------------

    def aggregate(self, stack: np.ndarray, method: str, **kw) -> np.ndarray:
        """``ops.robust.aggregate`` with the decomposable estimators run on
        the mesh (see the module placement table); every other method — and
        every failure — takes the host path unchanged, so this is always
        safe to call wherever ``robust.aggregate`` was."""
        from distributedvolunteercomputing_tpu.ops import robust

        host = lambda: robust.aggregate(stack, method, **kw)  # noqa: E731
        if method not in ("mean", "median", "trimmed_mean") or stack.ndim != 2:
            self.ops_host += 1
            return robust.aggregate(stack, method, **kw)
        n = stack.shape[0]
        if method == "trimmed_mean":
            trim = int(kw.get("trim", 1))
            if 2 * trim >= n:
                raise ValueError(f"trim={trim} too large for n={n}")
            if trim == 0:
                method, kw = "mean", {}
        if method == "mean" and n == 1:
            # Degenerate window: device round-trip buys nothing.
            self.ops_host += 1
            return robust.aggregate(stack, method, **kw)

        def dev() -> np.ndarray:
            s = np.ascontiguousarray(stack, np.float32)
            if method == "mean":
                w = kw.get("weights")
                wn = (
                    np.asarray(w, np.float64) / np.asarray(w, np.float64).sum()
                    if w is not None
                    else np.full(n, 1.0 / n)
                ).astype(np.float32)
                fn = self._jit(("wmean", n), self._build_wmean)
                d, t = self._put_stack(s)
                return self._fetch(self._call(fn, d, wn))[:t]
            trim = int(kw.get("trim", 1)) if method == "trimmed_mean" else None
            key = (method, n, trim)
            fn = self._jit(key, lambda: self._build_window(method, n, trim))
            d, t = self._put_stack(s)
            return self._fetch(self._call(fn, d))[:t]

        return self._run(dev, host, method, stack.size)

    def _build_wmean(self) -> Callable:
        from jax.sharding import PartitionSpec as P

        def body(s, w):
            return (s * w[:, None]).sum(axis=0)

        return self._shard_map(body, (P(None, "codec"), P()), P("codec"), "body_wmean")

    def _build_window(self, method: str, n: int, trim: Optional[int]) -> Callable:
        """Sorting-network window estimator over the peer axis: rows are
        unrolled into separate [T] arrays so every compare-exchange is two
        fusable elementwise ops (an ``.at[].set`` formulation scatters and
        is ~50× slower on the CPU backend, measured)."""
        from jax.sharding import PartitionSpec as P

        jnp = _jnp()
        m = 1 << max(n - 1, 0).bit_length() if n > 1 else 1
        pairs = _batcher_pairs(m) if m > 1 else []

        def body(s):
            # NaN -> +inf BEFORE the network: jnp.minimum/maximum PROPAGATE
            # NaN, so one NaN-filled byzantine row would otherwise poison
            # every row of the coordinate — the exact failure the robust
            # estimator exists to absorb. +inf reproduces numpy's sort
            # order (NaN sorts last), so trimming drops the attacker the
            # same way the host path does; a NaN count beyond the trim
            # yields inf instead of host's NaN — both are poisoned, and
            # inf at least names the direction.
            s = jnp.where(jnp.isnan(s), jnp.inf, s)
            rows = [s[i] for i in range(n)]
            rows += [jnp.full_like(rows[0], jnp.inf)] * (m - n)
            for i, j in pairs:
                a, b = rows[i], rows[j]
                rows[i] = jnp.minimum(a, b)
                rows[j] = jnp.maximum(a, b)
            if method == "median":
                return (rows[(n - 1) // 2] + rows[n // 2]) * jnp.float32(0.5)
            kept = rows[trim : n - trim]
            return sum(kept[1:], kept[0]) / jnp.float32(len(kept))

        return self._shard_map(body, (P(None, "codec"),), P("codec"), f"body_{method}")

    def aggregate_bits(self, bits_stack: np.ndarray, method: str, **kw) -> np.ndarray:
        """Window fold straight from bf16 wire bits [n, T] — the decode
        fuses into the estimator on device; host decodes then folds."""
        from distributedvolunteercomputing_tpu import native
        from distributedvolunteercomputing_tpu.ops import robust

        def host_decode() -> np.ndarray:
            return np.stack([native.bf16_to_f32(row) for row in bits_stack])

        if not self.active:
            self.ops_host += 1
            return robust.aggregate(host_decode(), method, **kw)

        def dev_decode() -> np.ndarray:
            import jax
            from jax.sharding import PartitionSpec as P

            jnp = _jnp()

            def body(b):
                return _bf16_widen(b)

            fn = self._jit(
                ("dec2d",),
                lambda: self._shard_map(
                    body, (P(None, "codec"),), P(None, "codec"), "decode_bf16_stack"
                ),
            )
            d, t = self._put_stack(np.ascontiguousarray(bits_stack, np.uint16))
            return self._fetch(self._call(fn, d))[:, :t]

        dec = self._run(dev_decode, host_decode, "decode_bf16_stack", bits_stack.size)
        return self.aggregate(dec, method, **kw)

    # -- PowerSGD ----------------------------------------------------------

    def low_rank_iterate(
        self, mat: np.ndarray, q: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One PowerSGD power iteration on device:
        P = QR-orthonormalize(M·Q), Q' = Mᵀ·P (Q' carries the scale)."""

        def dev() -> Tuple[np.ndarray, np.ndarray]:
            import jax

            jnp = _jnp()

            def body(m_, q_):
                p_, _ = jnp.linalg.qr(m_ @ q_)
                return p_, m_.T @ p_

            # Matmul + QR want the whole matrix: replicated compute (the
            # matrices are one TENSOR's, small next to the flat buffer; the
            # elementwise codec ops are where the sharding pays).
            fn = self._jit(("psgd_iter",), lambda: jax.jit(_named(body, "body_psgd_iter")))
            p, q_new = self._call(
                fn,
                np.ascontiguousarray(mat, np.float32),
                np.ascontiguousarray(q, np.float32),
            )
            return (
                np.ascontiguousarray(self._fetch(p), np.float32),
                np.ascontiguousarray(self._fetch(q_new), np.float32),
            )

        def host() -> Tuple[np.ndarray, np.ndarray]:
            p, _ = np.linalg.qr((mat @ q).astype(np.float32, copy=False))
            p = np.ascontiguousarray(p, np.float32)
            return p, mat.T @ p

        return self._run(dev, host, "psgd_iter", mat.size)

    def lowrank_reconstruct(self, p: np.ndarray, q: np.ndarray) -> np.ndarray:
        """Dense rank-r reconstruction (P·Qᵀ).ravel() — the decoder's hot
        matmul when contributions arrive."""

        def dev() -> np.ndarray:
            import jax

            fn = self._jit(
                ("psgd_rec",),
                lambda: jax.jit(_named(lambda a, b: a @ b.T, "body_psgd_rec")),
            )
            return self._fetch(
                self._call(
                    fn,
                    np.ascontiguousarray(p, np.float32),
                    np.ascontiguousarray(q, np.float32),
                )
            ).ravel()

        return self._run(
            dev, lambda: (p @ q.T).ravel(), "psgd_rec", p.shape[0] * q.shape[0]
        )

    # -- streaming mean folder --------------------------------------------

    def mean_folder(
        self, n_elems: int, tile_elems: int, n_tiles: int, wire: str
    ) -> Optional["MeshMeanFolder"]:
        """A device mean folder for one round, or None when this codec
        can't host one (inactive, or the tile dim doesn't split over the
        codec axis — chunk sizes and device counts are both powers of two
        in practice, so the None case is the host backend).

        With the ring collective enabled (and a bf16 wire on >= 2 devices)
        the folder is the fused ring pipeline (ops.mesh_collective): chunks
        land WHOLE on devices and decode+fold+forward run in one device
        pass, instead of the staged element-split scatter-add. On one
        device the ring degenerates to a plain fold — the staged folder IS
        that plain fold, so it is returned unchanged."""
        if not self.active:
            return None
        self._ensure_mesh()
        if tile_elems % self._ndev:
            return None
        if self._collective == "ring" and wire == "bf16" and self._ndev >= 2:
            from distributedvolunteercomputing_tpu.ops import mesh_collective

            return mesh_collective.RingMeanFolder(
                self, n_elems, tile_elems, n_tiles, wire
            )
        return MeshMeanFolder(self, n_elems, tile_elems, n_tiles, wire)


class MeshMeanFolder:
    """Device-resident mean accumulator for one streaming round.

    The streaming aggregator's mean mode stages arriving wire chunks as raw
    bytes (zero decode on the frame-reader thread) and flushes them in
    batches: ONE jitted scatter-add decodes the whole batch (bf16 bitcast +
    widen, fused) and folds it into an ``[n_tiles, tile_elems]`` device
    accumulator. Short tail chunks zero-pad to a full tile (zeros fold
    harmlessly); per-tile WEIGHT tallies stay host-side in the aggregator
    (scalar work). ``result()`` flushes the remainder and pulls the flat
    accumulator back once.

    Degrade contract: a flush that fails mid-round pulls the last good
    device accumulator to host and folds the failed batch (and everything
    after it) with host numpy — the round commits through a mesh shrink.
    Only if the accumulated state itself is unrecoverable does the round
    fail, and the codec is degraded either way so the next round starts on
    host."""

    kind = "staged"  # vs "ring" (ops.mesh_collective.RingMeanFolder)

    def __init__(
        self, codec: MeshCodec, n_elems: int, tile_elems: int, n_tiles: int, wire: str
    ):
        if wire not in ("f32", "bf16"):
            raise ValueError(f"mean folder needs an elementwise wire, got {wire!r}")
        self.codec = codec
        self.n_elems = int(n_elems)
        self.tile_elems = int(tile_elems)
        self.n_tiles = int(n_tiles)
        self.wire = wire
        self.esz = 4 if wire == "f32" else 2
        self._lock = threading.Lock()
        self._staged: List[Tuple[int, float, bytes]] = []
        self._staged_bytes = 0
        # High-water of raw wire bytes held between flushes: the aggregator
        # adds this to its peak-held gauge (staged chunks are real resident
        # memory the O(D) accumulator accounting alone would hide).
        self.peak_staged_bytes = 0
        self.flush_bytes = FOLDER_FLUSH_BYTES
        self._acc = None  # device [n_tiles, tile_elems] f32, set lazily
        self._host_acc: Optional[np.ndarray] = None  # degraded-mode shadow
        self.flushes = 0

    # -- staging (called under the aggregator's lock) ----------------------

    def add(self, tile: int, weight: float, data: bytes) -> bool:
        """Stage one verified wire chunk; True when a flush is due (the
        caller spawns ``flush`` on a worker, off the frame-reader)."""
        with self._lock:
            self._staged.append((tile, float(weight), data))
            self._staged_bytes += len(data)
            if self._staged_bytes > self.peak_staged_bytes:
                self.peak_staged_bytes = self._staged_bytes
            return self._staged_bytes >= self.flush_bytes

    def add_dense(self, buf: np.ndarray, weight: float) -> None:
        """Fold a complete dense f32 contribution (leader's own / parked)."""
        buf = np.ascontiguousarray(buf, np.float32).ravel()
        if buf.size != self.n_elems:
            raise ValueError(f"dense feed size {buf.size} != {self.n_elems}")

        def dev() -> bool:
            pad = self.n_tiles * self.tile_elems - self.n_elems
            x = np.pad(buf, (0, pad)).reshape(self.n_tiles, self.tile_elems)

            def body(a, x_, w_):
                return a + w_[0] * x_

            fn = self.codec._jit(
                ("folder_dense", self.n_tiles, self.tile_elems),
                lambda: self._fold_jit(body, n_in=1),
            )
            with self._lock:
                if self._host_acc is not None:
                    # A concurrent flush already migrated the accumulator
                    # to host (mid-round degrade): folding into a fresh
                    # device acc would silently DROP this mass at result().
                    raise MeshCodecError("folder already degraded")  # -> host()
                acc = self._device_acc()
                self._acc = fn(acc, self._put(x), np.float32([weight]))
            return True

        def host() -> bool:
            with self._lock:
                self._to_host_locked()
                from distributedvolunteercomputing_tpu import native

                native.weighted_sum_inplace(
                    self._host_acc[: self.n_elems], buf, float(weight)
                )
            return True

        self.codec._run(dev, host, "folder_dense", self.n_elems)

    # -- device plumbing ---------------------------------------------------

    def _put(self, arr: np.ndarray):
        import jax
        from jax.sharding import PartitionSpec as P

        if self.codec._host_args:
            return arr  # see MeshCodec._ensure_mesh
        with self.codec._phase("codec.h2d"):
            return jax.device_put(arr, self.codec._sharding(P(None, "codec")))

    def _fold_jit(self, body, n_in: int):
        from jax.sharding import PartitionSpec as P

        specs = (P(None, "codec"),) * (1 + n_in) + (P(),) * 1
        return self.codec._shard_map(
            body, specs, P(None, "codec"), "body_folder_dense", donate_argnums=(0,)
        )

    def _device_acc(self):
        if self._acc is None:
            import jax
            from jax.sharding import PartitionSpec as P

            self._acc = jax.device_put(
                np.zeros((self.n_tiles, self.tile_elems), np.float32),
                self.codec._sharding(P(None, "codec")),
            )
        return self._acc

    def _to_host_locked(self) -> None:
        """Adopt the host shadow accumulator (degraded mode), folding in
        whatever the device holds. Raises only when the device state is
        truly unrecoverable — then the round fails loudly rather than
        committing without the mass already folded."""
        if self._host_acc is None:
            if self._acc is not None:
                self._host_acc = np.asarray(self._acc).ravel().copy()
                self._acc = None
            else:
                self._host_acc = np.zeros(self.n_tiles * self.tile_elems, np.float32)

    def _decode_host(self, data: bytes) -> np.ndarray:
        from distributedvolunteercomputing_tpu import native

        if self.wire == "f32":
            return np.frombuffer(data, np.float32)
        return native.bf16_to_f32(np.frombuffer(data, np.uint16))

    # -- folding -----------------------------------------------------------

    def _pop_staged(self) -> List[Tuple[int, float, bytes]]:
        with self._lock:
            batch, self._staged = self._staged, []
            self._staged_bytes = 0
        return batch

    def _batch_arrays(self, batch: List[Tuple[int, float, bytes]], kb: int):
        """(tiles [kb] i32, ws [kb] f32, raw [kb, row_bytes] u8) — the
        staged batch as padded host arrays. Padding rows carry weight 0
        into tile 0: a no-op fold. Shared by the staged scatter-add and the
        ring collective flush (one home for the wire-chunk layout)."""
        k = len(batch)
        tiles = np.zeros(kb, np.int32)
        ws = np.zeros(kb, np.float32)
        tiles[:k] = [t for t, _, _ in batch]
        ws[:k] = [w for _, w, _ in batch]
        row_bytes = self.tile_elems * self.esz
        raw = np.zeros((kb, row_bytes), np.uint8)
        for i, (_, _, data) in enumerate(batch):
            raw[i, : len(data)] = np.frombuffer(data, np.uint8)
        return tiles, ws, raw

    def _flush_dev(self, batch: List[Tuple[int, float, bytes]]) -> bool:
        """Device half of flush: the PR 5 staged path — batch element-split
        over the codec axis, ONE jitted scatter-add (bf16 decode fused).
        Overridden by the ring collective folder."""
        # Pad the batch to the next power of two: the scatter-add jits
        # per batch LENGTH, and chunk arrival makes that length
        # arbitrary — bucketing bounds the compile count at ~log(max
        # batch).
        k = len(batch)
        kb = 1 << max(k - 1, 0).bit_length()
        tiles, ws, raw = self._batch_arrays(batch, kb)

        if self.wire == "f32":
            x = raw.view(np.float32)

            def body(a, x_, t_, w_):
                return a.at[t_].add(w_[:, None] * x_)
        else:
            x = raw.view(np.uint16)

            def body(a, x_, t_, w_):
                return a.at[t_].add(w_[:, None] * _bf16_widen(x_))

        from jax.sharding import PartitionSpec as P

        fn = self.codec._jit(
            ("folder_flush", self.wire, kb, self.tile_elems),
            lambda: self.codec._shard_map(
                body,
                (P(None, "codec"), P(None, "codec"), P(), P()),
                P(None, "codec"),
                "body_folder_flush",
                donate_argnums=(0,),
            ),
        )
        with self._lock:
            if self._host_acc is not None:
                raise MeshCodecError("folder already degraded")  # -> host()
            acc = self._device_acc()
            self._acc = fn(acc, self._put(x), tiles, ws)
        return True

    def _flush_host(self, batch: List[Tuple[int, float, bytes]]) -> bool:
        """Host half of flush: the degraded-slice replay — the SAME batch
        folds with host numpy, committing the in-flight round."""
        from distributedvolunteercomputing_tpu import native

        with self._lock:
            self._to_host_locked()
            acc = self._host_acc
            for tile, w, data in batch:
                e0 = tile * self.tile_elems
                x = self._decode_host(data)
                native.weighted_sum_inplace(acc[e0 : e0 + x.size], x, w)
        return True

    def flush(self) -> None:
        """Fold every staged chunk (worker-thread context)."""
        batch = self._pop_staged()
        if not batch:
            return
        self.flushes += 1
        self.codec._run(
            lambda: self._flush_dev(batch), lambda: self._flush_host(batch),
            "folder_flush", sum(len(data) for _, _, data in batch) // self.esz,
        )

    def result(self) -> np.ndarray:
        """Flush the tail and return the flat RAW accumulator [n_elems]
        (per-tile re-normalization stays with the aggregator — one
        implementation for the device and host paths)."""
        self.flush()
        with self._lock:
            if self._host_acc is not None:
                return self._host_acc[: self.n_elems]
            if self._acc is None:
                return np.zeros(self.n_elems, np.float32)
            out = np.asarray(self._acc).ravel()[: self.n_elems].copy()
            self._acc = None
            return out

    @property
    def device_bytes(self) -> int:
        return self.n_tiles * self.tile_elems * 4


# ---------------------------------------------------------------------------
# process-wide default (one codec per volunteer process)
# ---------------------------------------------------------------------------

_default: Optional[MeshCodec] = None
_default_lock = threading.Lock()


def get_default() -> MeshCodec:
    """The process's codec; built on first use with ``choose_data_path``'s
    selection (host unless the default backend is TPU silicon)."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = MeshCodec()
    return _default


def configure(
    mesh=None,
    backend: Optional[str] = None,
    pallas: Optional[str] = None,
    collective: Optional[str] = None,
) -> MeshCodec:
    """Select THIS volunteer's codec at startup (the per-volunteer
    selection surfaced in stats()): called by the volunteer once its local
    training mesh exists, before the first averaging round."""
    global _default
    with _default_lock:
        _default = MeshCodec(
            mesh=mesh, backend=backend, pallas=pallas, collective=collective
        )
    return _default


def reset() -> None:
    """Drop the process default (tests)."""
    global _default
    with _default_lock:
        _default = None
