#!/usr/bin/env python
"""The five-config experiment matrix (BASELINE.json:7-11), as real localhost
swarms through the actual CLI entrypoints.

Each config launches a coordinator + N `run_volunteer.py` processes on
127.0.0.1 (CPU backend — the swarm/averaging tier is host-side by design;
SURVEY.md §1 maps the WAN tier to DCN, not the chip), records every
volunteer's VOLUNTEER_DONE summary plus wall-clock into
``experiments/results/config{N}.jsonl``, and writes a machine-readable
``experiments/results/summary.json`` whose rows back BASELINE.md.

Model sizes are scaled-down proxies (SURVEY.md §7 step 6 prescribes proxy
models in the sandbox); the averaging MODES and swarm shapes are the real
thing:

  1  mnist_mlp          1 volunteer   local SGD (no averaging)
  2  cifar10_resnet18   2 volunteers  synchronous GradientAverager
  3  bert_mlm           4 volunteers  async gossip
  4  gpt2_small         4 volunteers  butterfly, heterogeneous speeds
                                      (per-volunteer batch sizes)
  5  llama_lora         4 volunteers  byzantine (trimmed mean) + kill -9 churn

Config 0 is the overlap throughput experiment (VERDICT r2 #2): a
2-volunteer sync swarm at --average-every 10 with overlapped rounds must
sustain >= 90% of the single-volunteer no-averaging samples/sec.

Configs 6-7 re-run configs 1-2 through the REAL-data path: a deterministic
.npz (experiments/make_npz.py) driven via --data, plus the separate
held-out eval stream (config 6).

Configs 2-5 carry a per-proxy --target-loss in record mode, so every row
reports time-to-target-loss alongside fixed-budget throughput.

Run:  python experiments/run_matrix.py            # all configs
      python experiments/run_matrix.py --config 3 # one config
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(REPO, "experiments", "results")


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
        line = proc.stdout.readline() or ""
        if line.startswith("COORDINATOR_READY "):
            return proc, line.split()[1]
    proc.kill()
    raise RuntimeError("coordinator did not become ready")


def start_volunteer(coord, peer_id, args, extra_env=None):
    env = _env()
    if extra_env:
        env.update(extra_env)
    return subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "run_volunteer.py"),
            "--coordinator", coord, "--peer-id", peer_id, *args,
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
    )


def wait_done(proc, timeout):
    out, _ = proc.communicate(timeout=timeout)
    for line in out.splitlines():
        if line.startswith("VOLUNTEER_DONE "):
            return json.loads(line[len("VOLUNTEER_DONE "):]), out
    return None, out


def run_swarm(
    name, vol_specs, timeout=600, kill_after=None, chaos_peer=None, slow_peer=None,
    tolerate_missing=False,
):
    """Launch a swarm; vol_specs = [(peer_id, [cli args]), ...].

    ``kill_after``: (seconds, peer_index) — SIGKILL that volunteer mid-run
    (the config-5 churn). ``chaos_peer``: (peer_id, scale) — that volunteer
    contributes its tree scaled by ``scale`` (the DVC_CHAOS_CONTRIB_SCALE
    byzantine fault-injection hook). ``slow_peer``: (peer_id, delay_ms) —
    that volunteer's steps are slowed by the DVC_STEP_DELAY_MS heterogeneity
    hook. Returns (peer_id, summary|None, wall_s).
    """

    def _extra_env(pid):
        env = {}
        if chaos_peer and pid == chaos_peer[0]:
            env["DVC_CHAOS_CONTRIB_SCALE"] = chaos_peer[1]
        if slow_peer and pid == slow_peer[0]:
            env["DVC_STEP_DELAY_MS"] = slow_peer[1]
        return env or None

    coord, addr = start_coordinator()
    t0 = time.monotonic()
    rows = []
    try:
        vols = [
            (pid, start_volunteer(addr, pid, args, extra_env=_extra_env(pid)))
            for pid, args in vol_specs
        ]
        if kill_after is not None:
            delay, idx = kill_after
            time.sleep(delay)
            print(f"[{name}] kill -9 {vols[idx][0]} (churn injection)", flush=True)
            vols[idx][1].send_signal(signal.SIGKILL)
        for pid, proc in vols:
            try:
                summary, out = wait_done(proc, timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                summary, out = None, "(timeout)"
            if summary is None and (kill_after is None or pid != vols[kill_after[1]][0]):
                tail = "\n".join(out.splitlines()[-15:])
                if not tolerate_missing:
                    raise RuntimeError(
                        f"[{name}] volunteer {pid} produced no summary:\n{tail}"
                    )
                # Straggler-tolerant mode (scale16's 16-contended-process
                # regime): record the survivor data, mark this one dead.
                print(f"[{name}] volunteer {pid} produced no summary (recorded as dead):\n{tail}", flush=True)
            rows.append((pid, summary, time.monotonic() - t0))
    finally:
        coord.kill()
        for _, proc in vols:
            if proc.poll() is None:
                proc.kill()
    return rows


def record(config_key, rows, extra=None):
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, f"{config_key}.jsonl")
    with open(path, "w") as fh:
        for pid, summary, wall in rows:
            fh.write(json.dumps({"peer": pid, "wall_s": round(wall, 2), **(summary or {"dead": True})}) + "\n")
        if extra:
            fh.write(json.dumps({"derived": extra}) + "\n")
    alive = [s for _, s, _ in rows if s]
    agg = {
        "volunteers": len(rows),
        "finished": len(alive),
        "samples_per_sec_per_volunteer": round(
            sum(s["samples_per_sec"] for s in alive) / max(len(alive), 1), 2
        ),
        "final_loss_mean": round(sum(s["final_loss"] for s in alive) / max(len(alive), 1), 4),
        "wall_s_max": round(max(w for _, _, w in rows), 1),
        "rounds_ok_total": sum(int(s.get("rounds_ok", 0)) for s in alive),
        "rounds_skipped_total": sum(int(s.get("rounds_skipped", 0)) for s in alive),
    }
    # Time-to-target-loss (the metric's second half, BASELINE.json:2): each
    # volunteer reports its first crossing of the per-config target; the row
    # aggregates mean crossing wall time over the volunteers that crossed.
    with_target = [s for s in alive if s.get("target_loss") is not None]
    if with_target:
        crossed = [s["target_crossed_s"] for s in with_target
                   if s.get("target_crossed_s") is not None]
        agg["target_loss"] = with_target[0]["target_loss"]
        agg["crossed"] = f"{len(crossed)}/{len(with_target)}"
        agg["time_to_target_s_mean"] = (
            round(sum(crossed) / len(crossed), 2) if crossed else None
        )
    if extra:
        agg.update(extra)
    print(f"[{config_key}] {json.dumps(agg)}", flush=True)
    return agg


# --------------------------------------------------------------- configs ----

TINY_RESNET = ["--model-override", "stage_sizes=[1,1]", "--model-override", "widths=[8,16]",
               "--model-override", "stem_width=8", "--model-override", "groups=2"]
TINY_BERT = ["--model-override", "vocab=256", "--model-override", "max_len=32",
             "--model-override", "d_model=64", "--model-override", "n_heads=2",
             "--model-override", "n_layers=2", "--model-override", "d_ff=128"]
TINY_GPT2 = ["--model-override", "vocab=256", "--model-override", "max_len=32",
             "--model-override", "d_model=64", "--model-override", "n_heads=2",
             "--model-override", "n_layers=2", "--model-override", "d_ff=128"]
TINY_LLAMA = ["--model-override", "vocab=256", "--model-override", "max_len=32",
              "--model-override", "d_model=64", "--model-override", "n_heads=4",
              "--model-override", "n_kv_heads=4", "--model-override", "n_layers=2",
              "--model-override", "d_ff=128", "--model-override", "lora_rank=4"]
TIMEOUTS = ["--join-timeout", "25", "--gather-timeout", "25"]

# Per-proxy time-to-target targets (VERDICT r3 #4): the loss the dense-f32
# run reached at the fixed 60-step budget in the committed round-3 matrix
# (summary.json final_loss_mean, rounded up one notch so a healthy run
# crosses just before the end). Config 1 keeps its stop-at-target semantics;
# configs 2-5 train the full budget and RECORD the first crossing.
def _target(loss: float) -> list:
    return ["--target-loss", str(loss), "--target-mode", "record"]


def config1():
    rows = run_swarm("config1", [
        ("solo", ["--model", "mnist_mlp", "--averaging", "none", "--steps", "300",
                  "--batch-size", "32", "--lr", "0.01", "--target-loss", "0.15"]),
    ])
    return record("config1_mnist_localsgd", rows)


def config2():
    common = ["--model", "cifar10_resnet18", *TINY_RESNET, "--averaging", "sync",
              "--average-every", "10", "--steps", "60", "--batch-size", "16",
              "--lr", "0.005", *TIMEOUTS, *_target(2.3)]
    rows = run_swarm("config2", [
        (f"res{i}", common + ["--seed", str(i)]) for i in range(2)
    ])
    return record("config2_resnet_sync", rows)


def config3():
    common = ["--model", "bert_mlm", *TINY_BERT, "--averaging", "gossip",
              "--average-every", "10", "--steps", "60", "--batch-size", "16",
              "--lr", "0.003", *TIMEOUTS, *_target(5.6)]
    rows = run_swarm("config3", [
        (f"bert{i}", common + ["--seed", str(i)]) for i in range(4)
    ])
    return record("config3_bert_gossip", rows)


def _config4_swarm(name: str, cadence: list) -> list:
    """Config 4's swarm — heterogeneous volunteers: same data budget per
    optimizer step is not required by butterfly, each contributes its own
    weight. The speed spread comes from per-volunteer batch sizes (a v4-8
    vs v5e-4 swarm in miniature, BASELINE.json:10). ONE roster shared by
    the step-cadence and wall-clock-cadence arms, so 'same swarm, only the
    cadence differs' holds by construction."""
    base = ["--model", "gpt2_small", *TINY_GPT2, "--averaging", "butterfly",
            *cadence, "--lr", "0.003", *TIMEOUTS, *_target(4.4)]
    return run_swarm(name, [
        ("fast0", base + ["--steps", "60", "--batch-size", "8", "--seed", "0"]),
        ("fast1", base + ["--steps", "60", "--batch-size", "8", "--seed", "1"]),
        ("slow0", base + ["--steps", "60", "--batch-size", "32", "--seed", "2"]),
        ("slow1", base + ["--steps", "60", "--batch-size", "32", "--seed", "3"]),
    ])


def config4():
    rows = _config4_swarm("config4", ["--average-every", "10"])
    return record("config4_gpt2_butterfly_hetero", rows)


def config4b():
    """Config 4 on the WALL-CLOCK cadence (r4 VERDICT #6). The step cadence
    parks fast volunteers at every rendezvous once speeds diverge —
    interval_ab measured it completing ZERO rounds under an 8x speed
    spread while the interval cadence ran at full speed. Identical swarm
    (shared roster, _config4_swarm); only the cadence flag differs
    (boundaries at absolute 20s multiples of swarm-consensus time, rounds
    weighted by steps-since-merge). Measured 2026-07-31: crossed 3/4 ->
    4/4, rounds 18/6 -> 56/0, time-to-target 299 -> 232 s."""
    rows = _config4_swarm("config4b", ["--average-interval-s", "20"])
    return record("config4b_gpt2_butterfly_hetero_interval", rows)


def config5():
    common = ["--model", "llama_lora", *TINY_LLAMA, "--averaging", "byzantine",
              "--method", "trimmed_mean", "--average-every", "8", "--steps", "64",
              "--batch-size", "8", "--lr", "0.005", "--min-group", "2",
              *TIMEOUTS, *_target(6.1)]
    rows = run_swarm(
        "config5",
        [(f"lora{i}", common + ["--seed", str(i)]) for i in range(4)],
        kill_after=(25.0, 3),  # churn: one volunteer dies un-gracefully
    )
    return record("config5_llama_lora_byzantine_churn", rows)


def _ensure_npz(task: str) -> str:
    """Generate the deterministic dataset file (experiments/make_npz.py) if
    it isn't there yet; returns its path. Regenerable data — not committed."""
    path = os.path.join(RESULTS, f"data_{task}.npz")
    if not os.path.exists(path):
        subprocess.run(
            [sys.executable, os.path.join(REPO, "experiments", "make_npz.py"),
             "--task", task, "--out", path],
            check=True, env=_env(),
        )
    return path


def config6_file_mnist():
    """Config 1 driven through the REAL-data path (--data .npz): file load,
    per-peer shuffle sharding, and the separate held-out eval stream
    (--eval-every) all exercised end to end."""
    path = _ensure_npz("mnist")
    rows = run_swarm("config6", [
        ("solo-file", ["--model", "mnist_mlp", "--averaging", "none",
                       "--data", path, "--steps", "300", "--batch-size", "32",
                       "--lr", "0.01", "--target-loss", "0.15",
                       "--eval-every", "50", "--eval-batches", "4"]),
    ])
    return record("config6_mnist_localsgd_file", rows)


def config7_file_resnet():
    """Config 2 over the file-backed data path: 2-volunteer sync swarm where
    both volunteers shard the SAME .npz's shuffle order per peer id."""
    path = _ensure_npz("cifar10")
    common = ["--model", "cifar10_resnet18", *TINY_RESNET, "--averaging", "sync",
              "--data", path, "--average-every", "10", "--steps", "60",
              "--batch-size", "16", "--lr", "0.005", *TIMEOUTS, *_target(2.3)]
    rows = run_swarm("config7", [
        (f"resf{i}", common + ["--seed", str(i)]) for i in range(2)
    ])
    return record("config7_resnet_sync_file", rows)


def config0_overlap():
    """Overlap throughput: 2-volunteer sync at --average-every 10 must hold
    >= 90% of the no-averaging samples/sec (VERDICT r2 #2 done-criterion).

    The no-averaging baseline is TWO concurrent volunteers (averaging none):
    on a shared localhost the processes contend for the same cores, so a
    single-process baseline would charge that contention to the averager.
    The blocking variant (--no-overlap) runs too, so the JSONL records what
    the overlap actually buys."""
    base = ["--model", "mnist_mlp", "--model-override", "d_hidden=512",
            "--steps", "120", "--batch-size", "32", "--lr", "0.005"]

    def mean_sps(rows):
        return sum(s["samples_per_sec"] for _, s, _ in rows if s) / len(rows)

    none_rows = run_swarm("overlap/baseline", [
        ("none0", base + ["--averaging", "none"]),
        ("none1", base + ["--averaging", "none"]),
    ])
    sync = base + ["--averaging", "sync", "--average-every", "10", *TIMEOUTS]
    ov_rows = run_swarm("overlap/overlapped", [
        ("ov0", sync + ["--overlap", "--seed", "0"]),
        ("ov1", sync + ["--overlap", "--seed", "1"]),
    ])
    bl_rows = run_swarm("overlap/blocking", [
        ("bl0", sync + ["--no-overlap", "--seed", "0"]),
        ("bl1", sync + ["--no-overlap", "--seed", "1"]),
    ])
    base_sps, ov_sps, bl_sps = mean_sps(none_rows), mean_sps(ov_rows), mean_sps(bl_rows)
    agg = record(
        "config0_overlap_throughput", none_rows + ov_rows + bl_rows,
        extra={
            "baseline_sps": round(base_sps, 2),
            "overlap_sps": round(ov_sps, 2),
            "blocking_sps": round(bl_sps, 2),
            "overlap_throughput_ratio": round(ov_sps / base_sps, 3),
            "blocking_throughput_ratio": round(bl_sps / base_sps, 3),
        },
    )
    return agg


def config8_kitchen_sink_r4():
    """Round-4 second-session feature composition as ONE swarm: PowerSGD
    grad wire is grads-mode-only while the outer optimizer and wall-clock
    cadence are params-mode, so this runs the params-mode trio — byzantine
    (trimmed-mean) aggregation x DiLoCo outer Nesterov x --average-interval-s
    x --steps-per-call — on the gpt2 proxy with kill -9 churn, proving the
    new features compose with each other AND with the robust path under
    failure. A separate 3-volunteer grads-mode arm (2 honest + 1
    chaos-scaled — the minimum where trimmed mean can actually reject the
    byzantine row) runs powersgd under byzantine aggregation."""
    common = ["--model", "gpt2_small", *TINY_GPT2, "--averaging", "byzantine",
              "--method", "trimmed_mean", "--average-interval-s", "8",
              "--steps-per-call", "4", "--outer-optimizer", "nesterov",
              "--steps", "120", "--batch-size", "8", "--lr", "0.003",
              "--min-group", "2", *TIMEOUTS, *_target(4.4)]
    rows = run_swarm(
        "config8/params_trio",
        [(f"sink{i}", common + ["--seed", str(i)]) for i in range(4)],
        timeout=900,  # 4 contending volunteers + wall-clock rounds
        kill_after=(30.0, 3),  # churn under the new cadence
    )
    agg = record("config8_outer_interval_spc_byz_churn", rows)

    # Shared base for the grads-mode byzantine-with-attacker arms. The
    # EXPLICIT trim=1 is load-bearing (r5 review finding): the derived
    # default trim = n_peers//4 is ZERO for the 3-peer groups these arms
    # spend most rounds in (3 starters, or 4 minus a kill), i.e. a plain
    # mean that would include the attacker at full weight — the explicit
    # value is clamped per-round to what the group size admits.
    byz_grads = ["--model", "gpt2_small", *TINY_GPT2, "--averaging", "byzantine",
                 "--method", "trimmed_mean", "--method-kw", "trim=1",
                 "--average-what", "grads",
                 "--steps", "30", "--batch-size", "8", "--lr", "0.003",
                 "--min-group", "2", *TIMEOUTS]
    gcommon = byz_grads + ["--wire", "powersgd", "--psgd-rank", "4"]
    grows = run_swarm(
        "config8/psgd_byz",
        [("psgd0", gcommon + ["--seed", "0"]),
         ("psgd1", gcommon + ["--seed", "1"]),
         ("psgd2", gcommon + ["--seed", "2"])],
        chaos_peer=("psgd2", "-3.0"),  # byzantine-valued contributions
    )
    agg2 = record("config8_psgd_byzantine_wire", grows)

    # r5: the 1-bit sign wire under the same byzantine-with-attacker shape
    # AND kill -9 churn — grads mode, a x-3 chaos peer plus an un-graceful
    # death among four volunteers; honest survivors must converge.
    scommon = byz_grads + ["--wire", "sign"]
    srows = run_swarm(
        "config8/sign_byz",
        [(f"sgn{i}", scommon + ["--seed", str(i)]) for i in range(4)],
        chaos_peer=("sgn3", "-3.0"),
        kill_after=(25.0, 2),
    )
    agg3 = record("config8_sign_byzantine_churn", srows)
    return {"params_trio": agg, "psgd_byz": agg2, "sign_byz": agg3}


CONFIGS = {
    0: config0_overlap, 1: config1, 2: config2, 3: config3, 4: config4, 5: config5,
    6: config6_file_mnist, 7: config7_file_resnet, 8: config8_kitchen_sink_r4,
    9: config4b,  # config 4's wall-clock-cadence arm (r4 VERDICT #6)
}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", type=int, default=None, choices=sorted(CONFIGS),
                    help="run one config (default: all)")
    args = ap.parse_args()
    todo = [args.config] if args.config is not None else sorted(CONFIGS)
    summary = {}
    for n in todo:
        t0 = time.monotonic()
        summary[f"config{n}"] = CONFIGS[n]()
        summary[f"config{n}"]["experiment_wall_s"] = round(time.monotonic() - t0, 1)
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "summary.json")
    existing = {}
    if os.path.exists(path):
        with open(path) as fh:
            existing = json.load(fh)
    existing.update(summary)
    with open(path, "w") as fh:
        json.dump(existing, fh, indent=1, sort_keys=True)
    print(json.dumps(existing, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
