#!/usr/bin/env python
"""Chip smoke: the quickest proof that the volunteer trainer starts on the TPU.

    python chip_smoke.py            # one chip: phases 1-3
    python chip_smoke.py --chips 4  # one four-chip host: the mesh phase only

Default (one chip), one child process after another, each alone on the chip:

1. *solo*: ``run_volunteer.py --model gpt2_small --averaging none`` at full
   width for a few steps — device is a TPU, every loss finite, last loss
   below the first, the train step compiled once.
2. *kernels*: the Pallas kernels compiled for the chip, against their
   references — flash attention fwd+bwd vs the XLA core, the codec's bf16
   encode / decode-axpy vs the native host codec bit for bit; the lowered
   text must hold a ``tpu_custom_call``.
3. *round*: ``coordinator.py`` + two volunteers, ``--averaging sync --wire
   bf16``: one on the chip, its peer on the CPU (the chip belongs to one
   process) — one committed round, codec backend ``mesh``, not degraded,
   bytes on the wire.

``--chips 4`` runs only what exists across chips, in ONE process driving all
four: the gpt2_small step on ``dp=2,tp=2`` against the same seed and batch
on one device (losses step by step, leaves spread as their specs say), and
``RingMeanFolder``'s compiled ring kernels against the host fold.

This parent never imports jax. Chip children run under ``JAX_PLATFORMS=tpu``,
so a machine without a chip fails at backend init instead of training on
the CPU. Any failed check raises: the script exits non-zero and the last
line — ``{"ok": true, "device": {...}}`` — is never printed. Children's logs
land in ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import re
import signal
import subprocess
import sys
import time
from typing import List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
CHUNK_ELEMS = (1 << 20) // 2  # one 1 MiB wire chunk of bf16: a real round's tile


@dataclasses.dataclass
class Config:
    """One smoke run. The defaults are the real thing; tests shrink the model
    and name the platform they expect (``tests/test_jaxenv.py``)."""

    platform: str = "tpu"  # JAX_PLATFORMS of every chip child, and what it must report
    # MeshCodec kwargs for the children that build one; () = auto selection,
    # which on the chip is the mesh backend with compiled kernels.
    codec_kwargs: Tuple[Tuple[str, str], ...] = ()
    out_dir: str = os.path.join(REPO, "chiprun_out", "chip_smoke")
    model: str = "gpt2_small"
    model_overrides: Tuple[str, ...] = ()  # none: full width, full depth
    seed: int = 0
    lr: float = 1e-3  # run_volunteer.py's own default
    # phase 1: the same few sequences every step, so the loss MUST fall if
    # the update works (the synthetic stream draws fresh batches of a
    # 50k-token bigram task: flat within noise over a dozen steps).
    solo_steps: int = 12
    batch_size: int = 8
    vocab: int = 50257  # gpt2_small's, for the data file
    seq_len: int = 1024
    solo_timeout_s: float = 600.0
    # phase 2
    attn_shape: Tuple[int, int, int, int] = (8, 12, 1024, 64)
    kernels_timeout_s: float = 300.0
    # phase 3: both volunteers average once, after their last step. The chip
    # volunteer runs phase 1's configuration again (same --steps: the LR
    # schedule's constants are part of the compiled step), so its train step
    # should come from the cache. The CPU peer completes the round, it is
    # not timed — minimal steps, timeouts generous enough for its slow
    # compile and steps.
    peer_steps: int = 2
    peer_batch_size: int = 1
    round_join_timeout_s: float = 600.0
    round_gather_timeout_s: float = 300.0
    round_timeout_s: float = 900.0
    # --chips 4
    mesh: str = "dp=2,tp=2"
    mesh_steps: int = 4
    mesh_loss_rtol: float = 1e-3  # bf16 compute, reductions re-ordered by tp
    ring_tile_elems: int = CHUNK_ELEMS
    ring_tiles: int = 3
    mesh_timeout_s: float = 900.0

    @property
    def on_chip(self) -> bool:
        """On the chip the kernels run compiled and the codec's auto
        selection is the mesh backend; off it (tests) the same kernels run
        interpreted and auto selection is the host backend. Every check
        holds the children to one or the other, never to either."""
        return self.platform == "tpu"


# ---------------------------------------------------------------------------
# parent: processes
# ---------------------------------------------------------------------------


class Child:
    """One child process in its own session, output to a log file; a context
    manager that kills it (whole process group) on the way out."""

    def __init__(self, cfg: Config, name: str, argv: Sequence[str], platform: str):
        os.makedirs(cfg.out_dir, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(cfg.out_dir, f"{name}.log")
        env = dict(os.environ, JAX_PLATFORMS=platform, PYTHONUNBUFFERED="1")
        self._log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, *argv], cwd=REPO, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def output(self) -> str:
        with open(self.log_path, errors="replace") as fh:
            return fh.read()

    def wait_for(self, pattern: str, timeout_s: float) -> "re.Match[str]":
        """Block until a line of the log matches ``pattern``."""
        deadline = time.monotonic() + timeout_s
        while True:
            m = re.search(pattern, self.output(), re.M)
            if m:
                return m
            if self.proc.poll() is not None or time.monotonic() > deadline:
                raise RuntimeError(
                    f"{self.name}: no line matching {pattern!r} (rc="
                    f"{self.proc.poll()}); log tail:\n{self.output()[-3000:]}"
                )
            time.sleep(0.2)

    def result(self, marker: str, timeout_s: float) -> dict:
        """Wait for a clean exit and return the JSON after ``marker``."""
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise RuntimeError(
                f"{self.name}: still running after {timeout_s:.0f}s; log tail:\n"
                f"{self.output()[-3000:]}"
            ) from None
        self.wall_s = time.monotonic() - self.t0
        out = self.output()
        m = re.search(rf"^{marker} (\{{.*\}})$", out, re.M)
        if rc != 0 or m is None:
            raise RuntimeError(
                f"{self.name}: rc={rc}, {marker} line "
                f"{'missing' if m is None else 'present'}; log tail:\n{out[-3000:]}"
            )
        return json.loads(m.group(1))

    def __enter__(self) -> "Child":
        return self

    def __exit__(self, *exc) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self._log.close()


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


def _report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _volunteer_argv(cfg: Config, *extra: str) -> List[str]:
    argv = [os.path.join(REPO, "run_volunteer.py"), "--model", cfg.model,
            "--lr", str(cfg.lr)]
    for kv in cfg.model_overrides:
        argv += ["--model-override", kv]
    return argv + list(extra)


def _self_child(cfg: Config, name: str) -> Child:
    """This script again as child ``name`` (``_CHILDREN``), handed ``cfg``."""
    return Child(cfg, name, [
        os.path.abspath(__file__), "--child", name, json.dumps(dataclasses.asdict(cfg)),
    ], cfg.platform)


def _check_device(cfg: Config, device: dict, who: str) -> None:
    _check(
        device["platform"] == cfg.platform,
        f"{who} ran on platform {device['platform']!r}, expected {cfg.platform!r}",
    )


def _fresh(path: str) -> str:
    """``path`` with no file there (the trainer appends to its metrics)."""
    if os.path.exists(path):
        os.unlink(path)
    return path


def _losses(metrics_path: str) -> Tuple[List[float], List[float]]:
    """(per-step losses, per-step wall seconds) from a trainer metrics
    stream. With a metrics sink the trainer reads each step's loss back to
    the host, so consecutive records are one finished step apart."""
    recs = [json.loads(line) for line in open(metrics_path)]
    steps = [r for r in recs if "loss" in r and "event" not in r]
    ts = [r["t"] for r in steps]
    return [r["loss"] for r in steps], [b - a for a, b in zip(ts, ts[1:])]


# ---------------------------------------------------------------------------
# parent: phases
# ---------------------------------------------------------------------------


def _write_lm_data(cfg: Config, path: str) -> None:
    """One batch of seeded random token sequences as the ``--data`` file."""
    import numpy as np

    toks = np.random.default_rng(cfg.seed).integers(
        0, cfg.vocab, (cfg.batch_size, cfg.seq_len + 1), dtype=np.int32
    )
    np.savez(path, tokens=toks[:, :-1], targets=toks[:, 1:])


def phase_solo(cfg: Config) -> dict:
    """Phase 1: one volunteer, no averaging, through the normal entry point."""
    os.makedirs(cfg.out_dir, exist_ok=True)
    metrics = _fresh(os.path.join(cfg.out_dir, "solo.metrics.jsonl"))
    data = os.path.join(cfg.out_dir, "solo.data.npz")
    _write_lm_data(cfg, data)
    with Child(cfg, "solo", _volunteer_argv(
        cfg, "--averaging", "none", "--batch-size", str(cfg.batch_size),
        "--steps", str(cfg.solo_steps), "--seed", str(cfg.seed),
        "--data", data, "--metrics", metrics,
    ), cfg.platform) as child:
        summary = child.result("VOLUNTEER_DONE", cfg.solo_timeout_s)
    losses, step_s = _losses(metrics)
    _check_device(cfg, summary["device"], "solo volunteer")
    _check(len(losses) == cfg.solo_steps, f"{len(losses)} losses for {cfg.solo_steps} steps")
    _check(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses[0]} -> {losses[-1]}")
    comp = summary["compile"]
    _check(
        comp["program_compiles"] == 1,
        f"{comp['program']} compiled {comp['program_compiles']} times, expected once",
    )
    _report(
        "solo", ok=True, device=summary["device"], steps=summary["steps"],
        losses=[round(x, 4) for x in losses],
        # First interval holds the compile; the rest are steady steps.
        step_wall_s=[round(x, 4) for x in step_s],
        compile=comp, peak_bytes_in_use=summary["peak_bytes_in_use"],
        wall_s=round(child.wall_s, 1),
    )
    return summary["device"]


def phase_kernels(cfg: Config) -> dict:
    """Phase 2: the Pallas kernels on silicon against their references."""
    with _self_child(cfg, "kernels") as child:
        r = child.result("CHILD_RESULT", cfg.kernels_timeout_s)
    _check_device(cfg, r["device"], "kernel child")
    for name in ("flash", "encode", "decode_axpy"):
        _check(
            r[name]["tpu_custom_call"] == cfg.on_chip,
            f"{name}: tpu_custom_call={r[name]['tpu_custom_call']} on {cfg.platform}",
        )
    for name, err in r["flash"]["errors"].items():
        _check(
            err["max_abs_err"] <= r["flash"]["tolerance"] * max(1.0, err["ref_max_abs"]),
            f"flash {name}: max|err| {err['max_abs_err']} vs ref max {err['ref_max_abs']}",
        )
    _check(r["encode"]["bit_exact"], "codec encode differs from native.f32_to_bf16")
    _check(r["decode_axpy"]["bit_exact"], "codec decode_axpy differs from native.bf16_to_f32")
    _report("kernels", ok=True, wall_s=round(child.wall_s, 1), **r)
    return r["device"]


def phase_round(cfg: Config) -> dict:
    """Phase 3: one real averaging round — chip volunteer + CPU peer."""
    with contextlib.ExitStack() as children:
        coord = children.enter_context(
            Child(cfg, "coordinator", [os.path.join(REPO, "coordinator.py")], "cpu")
        )
        addr = coord.wait_for(r"^COORDINATOR_READY (\S+)$", 60.0).group(1)
        common = (
            "--coordinator", addr, "--averaging", "sync", "--wire", "bf16",
            "--join-timeout", str(cfg.round_join_timeout_s),
            "--gather-timeout", str(cfg.round_gather_timeout_s),
        )
        # The slow peer first; both wait for each other in matchmaking.
        peer = children.enter_context(Child(cfg, "round.peer", _volunteer_argv(
            cfg, *common, "--peer-id", "cpu-peer", "--seed", str(cfg.seed + 1),
            "--batch-size", str(cfg.peer_batch_size),
            "--steps", str(cfg.peer_steps), "--average-every", str(cfg.peer_steps),
        ), "cpu"))
        chip = children.enter_context(Child(cfg, "round.chip", _volunteer_argv(
            cfg, *common, "--peer-id", "chip", "--seed", str(cfg.seed),
            "--batch-size", str(cfg.batch_size),
            "--steps", str(cfg.solo_steps), "--average-every", str(cfg.solo_steps),
        ), cfg.platform))
        s = chip.result("VOLUNTEER_DONE", cfg.round_timeout_s)
        p = peer.result("VOLUNTEER_DONE", cfg.round_timeout_s)
    _check_device(cfg, s["device"], "chip volunteer")
    _check(p["device"]["platform"] == "cpu", f"peer ran on {p['device']}")
    codec = s["mesh_codec"]
    _check(s["rounds_ok"] >= 1, f"chip volunteer committed {s['rounds_ok']} rounds")
    _check(p["rounds_ok"] >= 1, f"peer committed {p['rounds_ok']} rounds")
    want_backend = "mesh" if cfg.on_chip else "host"
    _check(
        codec["backend"] == want_backend,
        f"codec backend {codec['backend']!r}, expected {want_backend!r}",
    )
    _check(not codec["degraded"], f"codec degraded: {codec['degrade_reason']}")
    _check(
        s["wan_bytes_sent"] > 0 and s["wan_bytes_received"] > 0,
        f"no bytes on the wire: sent {s['wan_bytes_sent']}, got {s['wan_bytes_received']}",
    )
    _check(math.isfinite(s["final_loss"]), f"final loss {s['final_loss']}")
    _report(
        "round", ok=True, device=s["device"], rounds_ok=s["rounds_ok"],
        rounds_skipped=s["rounds_skipped"], mesh_codec=codec, native=s["native"],
        wan_bytes_sent=s["wan_bytes_sent"], wan_bytes_received=s["wan_bytes_received"],
        # program_seconds: seconds, not tens, when phase 1's step was a hit.
        compile=s["compile"], peak_bytes_in_use=s["peak_bytes_in_use"],
        final_loss=s["final_loss"], wall_s=round(chip.wall_s, 1),
        peer={"device": p["device"], "wall_s": round(peer.wall_s, 1),
              "rounds_ok": p["rounds_ok"], "native": p["native"]},
    )
    return s["device"]


def phase_mesh(cfg: Config) -> dict:
    """--chips 4: the sharded step vs one device, and the compiled ring
    kernels vs the host fold — one process driving all four chips."""
    with _self_child(cfg, "mesh") as child:
        r = child.result("CHILD_RESULT", cfg.mesh_timeout_s)
    _check_device(cfg, r["device"], "mesh child")
    _check(r["device"]["device_count"] >= 4, f"mesh child saw {r['device']}")
    step = r["step"]
    for i, (a, b) in enumerate(zip(step["losses_sharded"], step["losses_single"])):
        _check(
            math.isfinite(a) and abs(a - b) <= cfg.mesh_loss_rtol * abs(b),
            f"step {i + 1}: sharded loss {a} vs single-device {b}",
        )
    _check(step["leaves_off_spec"] == [], f"leaves off their spec: {step['leaves_off_spec']}")
    _check(step["leaves_split"] > 0, "no leaf is actually split across devices")
    ring = r["ring"]
    _check(ring["kind"] == "ring", f"folder kind {ring['kind']!r}")
    st = ring["stats"]
    want_lower = "compiled" if cfg.on_chip else "interpret"
    _check(
        st["ring_lower_effective"] == want_lower,
        f"ring lowering {st['ring_lower_effective']!r}, expected {want_lower!r}",
    )
    _check(st["ring_vmem_fallbacks"] == 0, f"{st['ring_vmem_fallbacks']} VMEM fallbacks")
    _check(not st["degraded"], f"codec degraded: {st['degrade_reason']}")
    _check(st["devices"] == 4, f"codec mesh spans {st['devices']} devices")
    _check(ring["ring_flushes"] >= 1, "the ring kernel never ran")
    _check(ring["matches_host"], f"ring fold differs from host: max|err| {ring['max_abs_err']}")
    _report("mesh", ok=True, wall_s=round(child.wall_s, 1), **r)
    return r["device"]


# ---------------------------------------------------------------------------
# children (these import jax)
# ---------------------------------------------------------------------------


def _child_kernels(p: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedvolunteercomputing_tpu import native
    from distributedvolunteercomputing_tpu.ops import attention, mesh_codec
    from distributedvolunteercomputing_tpu.ops.pallas_attention import flash_attention
    from distributedvolunteercomputing_tpu.utils.jaxenv import device_record

    def has_custom_call(fn, *args) -> bool:
        return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()

    # -- flash attention fwd+bwd, interpret/compiled resolved by the backend
    kq, kk, kv, kc = jax.random.split(jax.random.PRNGKey(p["seed"]), 4)
    shape = tuple(p["attn_shape"])
    q, k, v, cot = (
        jax.random.normal(key, shape, jnp.bfloat16) for key in (kq, kk, kv, kc)
    )

    def flash(q, k, v):
        return flash_attention(q, k, v, True)

    attention.set_attention_impl("xla")  # the reference core, whatever auto says

    def xla(q, k, v):
        return attention.attention_core_local(q, k, v, causal=True)

    def fwd_bwd(core):
        def loss(q, k, v):
            return jnp.sum(core(q, k, v).astype(jnp.float32) * cot.astype(jnp.float32))

        def run(q, k, v):
            return core(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        return run

    t0 = time.perf_counter()
    out_f, grads_f = jax.block_until_ready(jax.jit(fwd_bwd(flash))(q, k, v))
    flash_first_call_s = time.perf_counter() - t0
    out_x, grads_x = jax.jit(fwd_bwd(xla))(q, k, v)
    errors = {}
    for name, a, b in zip(
        ("out", "dq", "dk", "dv"), (out_f, *grads_f), (out_x, *grads_x)
    ):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if not np.isfinite(a).all():
            raise AssertionError(f"flash {name} has non-finite values")
        errors[name] = {
            "max_abs_err": float(np.abs(a - b).max()),
            "ref_max_abs": float(np.abs(b).max()),
        }
    # What auto routing picks for this shape inside the flagship step.
    attention.set_attention_impl("auto")
    auto_core = "flash" if attention._route_to_flash(q, k, True, None) else "xla"

    # -- codec kernels through the codec a volunteer would build
    native_built = native.ensure_built()
    codec = mesh_codec.MeshCodec(**dict(p["codec_kwargs"]))
    n = 16 * 512 * 128  # whole (512, 128) blocks: the Pallas-eligible size
    if codec.active and not codec._pallas_eligible(n):
        raise AssertionError(f"codec kernels not eligible: pallas={codec._pallas_mode}")
    rng = np.random.default_rng(p["seed"])
    buf = (rng.standard_normal(n) * rng.choice([1e-3, 1.0, 1e4], n)).astype(np.float32)
    bits = codec.encode_bf16(buf)
    ref_bits = native.f32_to_bf16(buf)
    dec = codec.decode_axpy(np.zeros(n, np.float32), ref_bits, 1.0)
    x = jax.ShapeDtypeStruct((n,), jnp.float32)
    b = jax.ShapeDtypeStruct((n,), jnp.uint16)
    w = jax.ShapeDtypeStruct((1,), jnp.float32)
    return {
        "device": device_record(),
        "native": "built" if native_built else "numpy",
        "flash": {
            "shape": shape, "dtype": "bfloat16", "causal": True,
            "tpu_custom_call": has_custom_call(fwd_bwd(flash), q, k, v),
            "first_call_s": round(flash_first_call_s, 2),
            # |flash - xla| <= tolerance * max(1, max|xla|): both cores run
            # bf16 matmuls with f32 accumulation, rounding p differently.
            "tolerance": 3e-2,
            "errors": errors,
            "auto_routes_to": auto_core,
        },
        "codec": codec.stats(),
        "encode": {
            "tpu_custom_call": has_custom_call(codec._pallas_encode_local, x),
            "bit_exact": bool(np.array_equal(bits, ref_bits)),
        },
        "decode_axpy": {
            "tpu_custom_call": has_custom_call(codec._pallas_dec_axpy_local, b, x, w),
            "bit_exact": bool(np.array_equal(
                dec.view(np.uint32), native.bf16_to_f32(ref_bits).view(np.uint32)
            )),
        },
    }


def _child_mesh(p: dict) -> dict:
    import jax
    import numpy as np

    from distributedvolunteercomputing_tpu import native
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.ops import mesh_codec
    from distributedvolunteercomputing_tpu.parallel.mesh import make_mesh, parse_mesh_spec
    from distributedvolunteercomputing_tpu.training.trainer import Trainer
    from distributedvolunteercomputing_tpu.utils.jaxenv import device_record

    overrides = {}
    for kv in p["model_overrides"]:
        key, _, val = kv.partition("=")
        overrides[key] = json.loads(val)
    mesh = make_mesh(**parse_mesh_spec(p["mesh"]))
    mesh_devices = set(mesh.devices.flat)

    # -- (a) the sharded step vs one device: same seed, same batches
    def run(tag: str, mesh_arg) -> Tuple[List[float], Trainer]:
        path = _fresh(os.path.join(p["out_dir"], f"mesh.{tag}.metrics.jsonl"))
        t = Trainer(
            get_model(p["model"], **overrides), batch_size=p["batch_size"],
            optimizer="adam", seed=p["seed"], mesh=mesh_arg, metrics_path=path,
        )
        t.run(steps=p["mesh_steps"], log_every=0)
        t.metrics.close()
        return _losses(path)[0], t

    losses_sharded, t = run("sharded", mesh)
    off_spec, n_split = [], 0
    leaves = jax.tree_util.tree_leaves_with_path(t.state.params)
    specs = jax.tree_util.tree_leaves(t._param_shardings)
    for (path, leaf), want in zip(leaves, specs):
        # One distinct slice of the array per device group the spec names.
        n_slices = len({str(s.index) for s in leaf.addressable_shards})
        want_slices = math.prod(
            mesh.shape[a]
            for axes in want.spec if axes is not None
            for a in ((axes,) if isinstance(axes, str) else axes)
        )
        if (
            set(leaf.sharding.device_set) != mesh_devices
            or not leaf.sharding.is_equivalent_to(want, leaf.ndim)
            or n_slices != want_slices
        ):
            off_spec.append(
                f"{jax.tree_util.keystr(path)}: {leaf.sharding} vs {want.spec}, "
                f"{n_slices} slices"
            )
        n_split += want_slices > 1
    step_compile = t.compile_summary()
    del t
    losses_single, t1 = run("single", None)
    single_devices = {
        d for leaf in jax.tree_util.tree_leaves(t1.state.params)
        for d in leaf.sharding.device_set
    }
    del t1

    # -- (b) compiled ring kernels vs the host fold, on the codec mesh the
    # volunteer would configure from this training mesh
    codec = mesh_codec.configure(mesh=mesh, **dict(p["codec_kwargs"]))
    tile, n_tiles = p["ring_tile_elems"], p["ring_tiles"]
    n_elems = n_tiles * tile - tile // 3  # ragged tail: a short last chunk
    folder = codec.mean_folder(n_elems, tile, n_tiles, "bf16")
    rng = np.random.default_rng(p["seed"])
    ref = np.zeros(n_elems, np.float32)
    for w in (1.0, 0.5, 0.25):
        bits = native.f32_to_bf16(rng.standard_normal(n_elems).astype(np.float32))
        for i in range(n_tiles):
            folder.add(i, w, bits[i * tile : (i + 1) * tile].tobytes())
        folder.flush()  # one ring pass per peer
        native.weighted_sum_inplace(ref, native.bf16_to_f32(bits), w)
    got = folder.result()  # the ring all-gather
    return {
        "device": device_record(),
        "step": {
            "mesh": p["mesh"], "losses_sharded": losses_sharded,
            "losses_single": losses_single, "leaves": len(leaves),
            "leaves_split": n_split, "leaves_off_spec": off_spec,
            "single_device_count": len(single_devices), "compile": step_compile,
        },
        "ring": {
            "kind": folder.kind, "tile_elems": tile, "n_tiles": n_tiles,
            "ring_flushes": getattr(folder, "ring_flushes", 0),
            "max_abs_err": float(np.abs(got - ref).max()),
            "matches_host": bool(np.allclose(got, ref, rtol=1e-5, atol=1e-6)),
            "stats": codec.stats(),
        },
    }


_CHILDREN = {"kernels": _child_kernels, "mesh": _child_mesh}


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def run_smoke(cfg: Config, chips: int) -> dict:
    """All phases for ``chips``; returns the device the last child reported."""
    phases = (phase_mesh,) if chips == 4 else (phase_solo, phase_kernels, phase_round)
    for phase in phases:
        device = phase(cfg)
    return device


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--child", nargs=2, metavar=("NAME", "JSON"), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        name, params = args.child
        print("CHILD_RESULT " + json.dumps(_CHILDREN[name](json.loads(params))), flush=True)
        return 0
    t0 = time.monotonic()
    device = run_smoke(Config(), args.chips)
    print(json.dumps({"phase": "all", "wall_s": round(time.monotonic() - t0, 1)}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
