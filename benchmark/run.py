#!/usr/bin/env python3
"""One run of one cell: this process IS the volunteer.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It builds a ``VolunteerConfig`` from the cell's configuration file and traffic
file and runs the body of ``run_volunteer()`` (``Volunteer(cfg)``, signal
handlers, ``asyncio.run(vol.run())``), so the data path, telemetry, watchdog,
health probe and codec are on as a volunteer has them. ``benchmark/probe.py``
watches from outside. The last line of standard output is the result.

A rehearsal for a machine without a chip,

    JAX_PLATFORMS=cpu python3 benchmark/run.py --rehearse tiny-rehearsal:round-2peer-bf16 \
        --seed 1 --seconds 5 --trace 0

runs a configuration no cell names and prints counts and ``correct`` only.
"""

import time

_T_PROCESS = time.perf_counter()  # before any other import: set-up counts from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, REPO_ROOT)

PACKAGE = "distributedvolunteercomputing_tpu"
WORK_DIR = os.path.join(REPO_ROOT, ".bench_work")  # git-ignored, inside the checkout
DATA_ROWS = 2048


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


class PeerProcess:
    """The stub peer's child process (``benchmark/peer.py``)."""

    def __init__(self, config_path: str, traffic_path: str, seed: int, log_path: str):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
        env.pop("XLA_FLAGS", None)
        self._log = open(log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "peer.py"),
             "--config", config_path, "--traffic", traffic_path, "--seed", str(seed)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=self._log, env=env,
            text=True,
        )
        self.lines: list = []
        self._ready = threading.Event()
        self.addr = None
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            self.lines.append(line)
            if line.startswith("PEER_READY "):
                self.addr = line.split(" ", 1)[1]
                self._ready.set()
        self._ready.set()  # the stream ended: wake a waiter either way

    def wait_ready(self, timeout: float) -> str:
        if not self._ready.wait(timeout) or self.addr is None:
            raise RuntimeError(f"the stub peer did not come up: {self.lines[-5:]}")
        return self.addr

    def launch(self) -> None:
        """Tell the stub that the volunteer is entering a round."""
        try:
            self.proc.stdin.write("launch\n")
            self.proc.stdin.flush()
        except (OSError, ValueError):
            pass  # the stub is gone: the round fails and is counted as failed

    def stop(self) -> dict:
        """SIGTERM, wait for the end, return its ``PEER_DONE`` summary."""
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._reader.join(timeout=10)
        self._log.close()
        for line in reversed(self.lines):
            if line.startswith("PEER_DONE "):
                return json.loads(line.split(" ", 1)[1])
        return {}


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", help="a cell of BENCHMARK.json")
    ap.add_argument("--rehearse", metavar="CONFIG:TRAFFIC",
                    help="run a configuration file no cell names, on whatever device "
                         "there is; prints counts and `correct` only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if bool(args.workload) == bool(args.rehearse):
        ap.error("give --workload or --rehearse")
    return args


def init_seed_of(cfg: dict, seed: int) -> int:
    """The seed of the initial parameters. A swarm's volunteers all start from
    the TASK's parameters (``VolunteerConfig.init_seed``) and differ in their
    data; a configuration that names the task's seed (``init_seed``) gets it
    in every run, so that ``--seed`` moves the data and the noise and not the
    router the held experts' rows follow. The others draw both from ``--seed``."""
    return int(cfg.get("init_seed", seed))


def median(xs):
    xs = list(xs)
    return statistics.median(xs) if xs else None


def main(argv=None) -> int:
    args = parse_args(argv)
    if importlib.util.find_spec(PACKAGE) is None:
        print(f"benchmark: the program ({PACKAGE}) is not in {REPO_ROOT}", file=sys.stderr)
        return 2
    from benchmark.manifest import Manifest

    manifest = Manifest(REPO_ROOT)
    if args.workload:
        cell = manifest.cell(args.workload)
        rehearsal = False
    else:
        config_name, _, traffic_name = args.rehearse.partition(":")
        cell = {"name": f"rehearsal.{config_name}.{traffic_name}", "config": config_name,
                "traffic": traffic_name, "chips": None}
        rehearsal = True
    cfg = manifest.load_config(cell["config"])
    traffic = manifest.load_traffic(cell["traffic"])
    chips = int(cell["chips"] or cfg["chips"])
    seconds = float(args.seconds if args.seconds is not None else manifest.run_seconds)
    if rehearsal:
        if not cfg.get("rehearsal"):
            print("benchmark: --rehearse takes a configuration marked 'rehearsal'",
                  file=sys.stderr)
            return 2
        if chips > 1 and os.environ.get("JAX_PLATFORMS", "") == "cpu":
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}"
            )
    work = os.path.join(WORK_DIR, cell["name"])
    os.makedirs(work, exist_ok=True)

    peer = None
    try:
        if traffic.get("peers"):
            # Before JAX is imported here, and in parallel with our own start-up.
            peer = PeerProcess(
                manifest.config_path(cell["config"]),
                os.path.join(manifest.bench_dir, "traffic", f"{cell['traffic']}.json"),
                args.seed, os.path.join(work, "peer.log"),
            )
        return _run(args, manifest, cell, cfg, traffic, chips, seconds, rehearsal, work, peer)
    finally:
        if peer is not None:
            summary = peer.stop()
            log(f"stub peer: {summary}")


def _run(args, manifest, cell, cfg, traffic, chips, seconds, rehearsal, work, peer) -> int:
    parts = {"python_start_s": time.perf_counter() - _T_PROCESS}
    t = time.perf_counter()
    import jax

    from benchmark import datagen, flops, readers, references, result
    from benchmark import trace as trace_mod
    from benchmark.probe import Probe
    from distributedvolunteercomputing_tpu.swarm.volunteer import Volunteer, VolunteerConfig

    parts["imports_s"] = time.perf_counter() - t
    # Bringing up the TPU runtime took 7-12 s of every run on the chip host and
    # varied by a quarter from one run to the next (my chip runs, PR 25), which
    # alone moved a 22 s set-up by 10%. Nothing in this repository can move it,
    # so it is timed by itself and left out of `setup_s`.
    t = time.perf_counter()
    devices = jax.devices()
    backend_init_s = parts["backend_init_s"] = time.perf_counter() - t
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    peak = None
    if not rehearsal:
        try:
            peak = flops.peak_for(device["platform"], device["kind"])
        except flops.UnknownDevice as e:
            print(f"benchmark: {e}", file=sys.stderr)
            return 1
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1

    ref = references.load(cfg["family"])
    sizes = ref.sizes(cfg)
    t = time.perf_counter()
    data_path = datagen.write_token_file(
        os.path.join(work, "tokens.npz"), args.seed, DATA_ROWS, sizes["seq_len"], sizes["vocab"]
    )
    parts["data_file_s"] = time.perf_counter() - t

    fields = {**cfg["volunteer"], **traffic["volunteer"]}
    if fields.get("averaging", "none") != "none":
        # As run_volunteer.py does: the native host core is built or loaded
        # before the event loop exists.
        from distributedvolunteercomputing_tpu import native

        t = time.perf_counter()
        native.ensure_built()
        parts["native_build_s"] = time.perf_counter() - t
    if peer is not None:
        t = time.perf_counter()
        fields["coordinator"] = peer.wait_ready(120.0)
        parts["peer_wait_s"] = time.perf_counter() - t
    vcfg = VolunteerConfig(
        model=cfg["registry_model"], model_overrides=dict(cfg.get("model_overrides", {})),
        data_path=data_path, seed=args.seed, init_seed=init_seed_of(cfg, args.seed), **fields,
    )
    log(f"seeds: data and noise {vcfg.seed}, initial parameters {vcfg.init_seed}")
    vol = Volunteer(vcfg)
    probe = Probe(vol, cfg, traffic, seconds=seconds, trace=bool(args.trace),
                  workdir=work, seed=args.seed,
                  on_launch=peer.launch if peer is not None else None)
    probe.parts = parts
    start = vol.start

    async def start_then_install() -> None:
        t0 = time.perf_counter()
        await start()
        parts["volunteer_start_s"] = time.perf_counter() - t0  # join, init, state sync
        probe.install()
        parts["train_begin_s"] = time.perf_counter() - _T_PROCESS

    vol.start = start_then_install
    vol.install_signal_handlers()
    summary = asyncio.run(vol.run())
    if probe.phase != "done":
        print(f"benchmark: the run ended in phase {probe.phase!r} before the window closed",
              file=sys.stderr)
        return 1
    # The benchmark's own device work comes after the window and after its
    # counters are read (`Probe._close_window`: `probe.after`, the peak memory
    # they hold is the program's), and the volunteer has stopped: the check
    # frees the training state first (`Probe.release_training_state`) and runs
    # on the initial parameters alone. Nothing below reads `trainer.state`.
    probe.reference_check()
    probe.finish()

    # -- reduce -----------------------------------------------------------------
    win, before, after = probe.window, probe.before, probe.after
    window_s = win["t1"] - win["t0"]
    steps = win["step1"] - win["step0"]
    tokens_per_step = vcfg.batch_size * sizes["seq_len"]
    merged_at = {}  # round index -> the loop's clock when its result was swapped in
    ok_rounds = [r for r in probe.rounds if r.get("ok")]
    for r, m in zip(ok_rounds, probe.merges):
        merged_at[r["index"]] = m["t"]
    launched = [r for r in probe.rounds if win["t0"] <= r["t0"] <= win["t1"]]
    in_window = [r for r in launched
                 if r.get("ok") and merged_at.get(r["index"], math.inf) <= win["t1"]]
    failed_rounds = [r for r in launched if "t1" in r and not r["ok"]]
    window_losses = [x for x in probe.losses if x["from"] == "loop"]
    final_loss = float(summary.get("final_loss", float("nan")))
    loss_reads = [x["loss"] for x in probe.losses] + [final_loss]
    nonfinite = sum(1 for x in loss_reads if not math.isfinite(x))

    comp_b, comp_a = before["compile"], after["compile"]
    stats = {
        "compile.setup_seconds": comp_b["seconds"],
        "compile.setup_programs": comp_b["programs"],
        "compile.setup_cache_hits": comp_b["cache_hits"],
        "compile.setup_cache_misses": comp_b["cache_misses"],
        "compile.window_programs": comp_a["programs"] - comp_b["programs"],
        "setup.backend_init_s": backend_init_s,
        "memory.peak_bytes": after["memory_peak_bytes"],  # read before the reference check
        "bytes.window": after["bytes"] - before["bytes"],
        "rounds.in_window": len(in_window),
        "rounds.launched": len(launched),
        "steps.window": steps,
    }
    if "codec" in after:
        for key in ("degraded", "ring_vmem_fallbacks", "ops_mesh", "ops_host", "fallbacks"):
            stats[f"codec.{key}"] = int(after["codec"][key]) - (
                int(before["codec"][key]) if key.startswith("ops") else 0
            )
        stats["codec.backend_is_mesh"] = int(after["codec"]["backend"] == "mesh")
        for key in ("rounds_ok", "rounds_skipped", "rounds_degraded"):
            stats[f"averager.{key}"] = after[key] - before[key]

    # -- correct ------------------------------------------------------------------
    checks = {
        "reference": bool(probe.reference.get("ok")),
        "losses_finite": nonfinite == 0,
        "loss_band": math.isfinite(final_loss) and (
            final_loss - probe.reference.get("loss", math.inf)
            <= cfg["loss_band"]["last_minus_first_max"]
        ),
        "no_compile_in_window": stats["compile.window_programs"] == 0,
        "warmup_complete": not probe.window.get("warmup_incomplete", False),
    }
    if traffic.get("peers"):
        checks["round_mean"] = bool(probe.round_check.get("ok"))
        checks["rounds_full"] = (
            stats.get("averager.rounds_degraded", 0) == 0 and not failed_rounds
            and len(in_window) >= 1
        )
        if chips == 1:
            checks["codec_not_degraded"] = (
                stats.get("codec.degraded", 0) + stats.get("codec.ring_vmem_fallbacks", 0) == 0
            )
    correct = all(checks.values())
    attempted = steps + len(launched)
    failed = len(failed_rounds) + nonfinite

    # -- metrics --------------------------------------------------------------------
    tok_s_chip = steps * tokens_per_step / window_s / chips
    e2e = {
        # One number under two names, and the manifest's `workloads` say which
        # a cell reports: a cell with rounds in flight repeats to about 1%, a
        # steady one to 0.005%, and one bound over both would hide a 3% loss
        # of the compiled step.
        "tok_s_chip": tok_s_chip,
        "round_tok_s_chip": tok_s_chip,
        "setup_s": win["t0"] - _T_PROCESS - backend_init_s,
        # From a round's launch to the next round's launch (or the window's
        # end, which is the eve of one), so that what the transport moves
        # after the call returns (a leader serves the result to its members
        # then) is counted with the round it belongs to.
        "wire_MB_round": median(
            ((probe.rounds[r["index"] + 1]["bytes0"]
              if r["index"] + 1 < len(probe.rounds)
              and probe.rounds[r["index"] + 1]["t0"] <= win["t1"]
              else after["bytes"]) - r["bytes0"]) / 1e6
            for r in in_window
        ),
    }
    parts["warmup_steps_and_round_s"] = win["t0"] - _T_PROCESS - parts.get("train_begin_s", 0.0)
    trace_obj = None
    if args.trace and probe.trace_info.get("file"):
        t = time.perf_counter()
        trace_obj = trace_mod.Trace.from_xplane(probe.trace_info["file"])
        log(f"trace read in {time.perf_counter() - t:.1f} s")
        with open(os.path.join(work, "trace_described.txt"), "w") as fh:
            fh.write(trace_mod.describe(trace_obj, top=40) + "\n")
    step_name = re.fullmatch(r"jit\((.+)\)", comp_a["program"]).group(1)
    run = {
        "cell": cell, "config": cfg, "traffic": traffic, "chips": chips, "peak": peak,
        "stats": stats, "rounds": in_window, "trace": trace_obj,
        "spans": [s for s in vol.telemetry.tracer.spans()
                  if win["wall0"] <= s["t0"] <= win["wall1"]],
        "step_program": rf"^jit_{re.escape(step_name)}(\(|$)",
        "tokens_per_step": tokens_per_step,
        "flops_per_token": flops.train_flops_per_token(
            probe.n_params, sizes["n_layer"], sizes["seq_len"], sizes["d_model"]),
        "window": win,
    }

    metrics = {}
    if rehearsal:
        pass  # counts and `correct` only: no CPU time under a metric's name
    elif args.trace:
        for m in manifest.metrics_for(cell["name"], "per_layer"):
            value = readers.compute(manifest.layer_metric_path(m["name"]), run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in manifest.metrics_for(cell["name"], "end_to_end"):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # The device as JAX reports it at the end: the process's peak, the
    # reference check included (`device.peak_hbm_GB` is the program's alone).
    device["memory_peak_bytes"] = probe.peak_bytes()
    breakdown = None
    if args.trace and trace_obj is not None:
        bi = trace_mod.busy_idle(trace_obj)
        if bi is not None and not rehearsal:
            device["busy_s"], device["window_s"] = bi["busy_s"], bi["window_s"]
        breakdown = {
            "device_ops": trace_mod.top_ops(trace_obj),
            "idle_gaps": trace_mod.idle_gaps(
                trace_obj, gap_labels(trace_obj, probe, vol.telemetry.tracer.spans())),
        }
        log("trace: " + json.dumps({"busy_idle": bi, "collective": trace_mod.collective_time(trace_obj)}))

    log("set-up parts: " + json.dumps({k: round(v, 3) for k, v in parts.items()}))
    log("checks: " + json.dumps(checks))
    log("stats: " + json.dumps(stats))
    log("reference: " + json.dumps(probe.reference) + " round: " + json.dumps(probe.round_check))
    all_spans = vol.telemetry.tracer.spans()
    log("rounds: " + json.dumps([
        {"step": r["step"], "at_s": round(r["t0"] - win["t0"], 2),
         "wall_s": round(r.get("t1", math.nan) - r["t0"], 3),
         "merged_at_s": round(merged_at.get(r["index"], math.nan) - win["t0"], 2),
         "ok": r.get("ok"), "MB": round((r.get("bytes1", 0) - r["bytes0"]) / 1e6, 3),
         "in_window": r in in_window,
         "spans": {s["name"]: round(s["dur_s"], 3) for s in all_spans
                   if s.get("dur_s") is not None
                   and r["wall0"] - 0.01 <= s["t0"] <= r.get("wall1", math.inf)}}
        for r in probe.rounds]))
    log("host intervals: " + json.dumps([
        [name, round(a - win["t0"], 2), round(b - a, 3)] for name, a, b in probe.host_intervals]))
    log("losses: " + json.dumps(probe.losses + [{"step": win["step1"], "loss": final_loss,
                                                 "from": "summary"}]))
    log(f"window: {window_s:.3f} s, {steps} steps, {len(window_losses)} loop loss reads; "
        f"programs in trace: "
        f"{trace_mod.program_totals(trace_obj) if trace_obj is not None else None}")
    log("end to end" + (" (a rehearsal: not device numbers)" if rehearsal else "") + ": "
        + json.dumps(e2e))
    print(result.dumps(result.build(correct, attempted, failed, metrics, device, breakdown)),
          flush=True)
    return 0


def gap_labels(trace_obj, probe, spans):
    """What the host was doing, on the trace's clock, most specific first:
    the loop iterations that launched and merged a round (``launch``,
    ``merge``), then the round's telemetry spans (``round:<span>``), then the
    averager call as a whole (``round:other``)."""
    begin = trace_obj.marks("bench:trace_begin")
    info = probe.trace_info
    if not begin or "t0" not in info:
        return []
    # The begin mark was taken inside its annotation: host clocks -> trace ns.
    from_pc = lambda t: begin[0].start_ns + (t - info["t0"]) * 1e9  # noqa: E731
    from_wall = lambda t: begin[0].start_ns + (t - info["wall0"]) * 1e9  # noqa: E731
    labels = [(name, (from_pc(a), from_pc(b))) for name, a, b in probe.host_intervals]
    for s in spans:
        if s["name"] == "round" or s.get("dur_s") is None:
            continue  # the round span covers all the others
        labels.append((f"round:{s['name']}", (from_wall(s["t0"]), from_wall(s["t0"] + s["dur_s"]))))
    for call in trace_obj.marks("bench:averager_call"):
        labels.append(("round:other", (call.start_ns, call.end_ns)))
    return labels


if __name__ == "__main__":
    sys.exit(main())
