"""Grouped matrix multiplication on the chip: ``jax.lax.ragged_dot`` against
megablox ``gmm`` at OLMoE-1B-7B's expert shapes, and the memory trial of the
``olmoe-solo`` cell's reference check.

    chiprun -- python experiments/gmm_sweep.py             # the PR 28 table
    python experiments/gmm_sweep.py --tokens 64 --experts 8 --top-k 2 --k 128 --n 128 --iters 1

Rows are the S x 8 routed assignments of one step (16,384 tokens, top-8 of 64
experts: 131,072), sorted by expert; group sizes come from a seeded top-8 of
random router logits, so they are uneven as a step's are. Measured in bf16:
the forward product alone, and forward + backward (gradients of both
operands), at ``[rows, 2048] x [64, 2048, 1024]`` (gate, up) and ``[rows,
1024] x [64, 1024, 2048]`` (down). One JSON line per measurement on stdout,
all of them in ``chiprun_out/gmm_sweep.json``. ``models/olmoe.py`` takes its
grouped matmul from this table (PERF.md, Findings of PR 28). ``--trace``
also profiles one forward + backward of each implementation and prints the
device's operation names, which the benchmark's readers match by prefix.
``--memory`` allocates what the chip holds at the reference check and reads
``memory_stats()``. A CPU run checks the paths, not the speeds.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops

PEAK_BF16 = flops.PEAKS["TPU v5 lite"]["bf16_flops"]
DEFAULT_TILINGS = "128x128x128;256x512x512;512x512x512;512x1024x1024;512x2048x512"


def _time(fn, args, iters: int) -> float:
    """Milliseconds a call, after two warm-up calls, synced at the end."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def group_sizes_for(seed: int, tokens: int, experts: int, top_k: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((tokens, experts)).astype(np.float32)
    top = np.argpartition(-logits, top_k - 1, axis=-1)[:, :top_k]
    return np.bincount(top.reshape(-1), minlength=experts).astype(np.int32)


def make_impl(name: str, tiling):
    if name == "ragged_dot":
        return lambda lhs, rhs, gs: jax.lax.ragged_dot(lhs, rhs, gs)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    interpret = jax.default_backend() != "tpu"
    return lambda lhs, rhs, gs: gmm(
        lhs, rhs, gs, preferred_element_type=lhs.dtype, tiling=tiling, interpret=interpret
    )


def fwd(impl):
    return jax.jit(impl)


def fwd_bwd(impl):
    def f(lhs, rhs, gs, cot):
        def loss(lhs, rhs):
            return jnp.sum(impl(lhs, rhs, gs).astype(jnp.float32) * cot)

        return jax.value_and_grad(loss, argnums=(0, 1))(lhs, rhs)

    return jax.jit(f)


def op_names(trace_dir: str, top: int = 12):
    from benchmark import trace as tr

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        return []
    t = tr.Trace.from_xplane(files[-1])
    planes = t.device_planes()
    if not planes:
        return []
    ops = planes[0].line(tr.OPS_LINE)
    totals = {}
    for e in (ops.events if ops is not None else []):
        key = e.name[:160]
        n, d = totals.get(key, (0, 0.0))
        totals[key] = (n + 1, d + e.dur_ns)
    out = sorted(totals.items(), key=lambda kv: -kv[1][1])[:top]
    return [{"op": k, "calls": n, "ms": d / 1e6} for k, (n, d) in out]


def memory_trial(args) -> dict:
    """What the chip holds at the reference check: the training state (12
    bytes a parameter), the initial parameters again (4) and two gradient
    trees (8), in leaves no larger than the model's largest (an expert
    stack: 64 x 2048 x 1024 floats). Then 256 MB blocks until the allocator
    refuses: the room left for the check's temporaries."""
    dev = jax.local_devices()[0]
    n_params = args.params
    leaf = 64 * 2048 * 1024
    held = []
    out = {"what": "memory_trial", "params": n_params,
           "bytes_limit": (dev.memory_stats() or {}).get("bytes_limit")}
    for label, floats in (("state", 3 * n_params), ("initial_params", n_params),
                          ("two_gradient_trees", 2 * n_params)):
        left = floats
        try:
            while left > 0:
                n = min(leaf, left)
                held.append(jax.block_until_ready(jnp.zeros((n,), jnp.float32)))
                left -= n
        except Exception as e:  # noqa: BLE001 - the allocator's refusal is the reading
            out[f"failed_at_{label}"] = repr(e)[:200]
            break
        out[f"in_use_after_{label}"] = (dev.memory_stats() or {}).get("bytes_in_use")
    extra = 0
    try:
        while extra < 16:
            held.append(jax.block_until_ready(jnp.zeros((64 * 1024 * 1024,), jnp.float32)))
            extra += 1
    except Exception:  # noqa: BLE001
        pass
    out["room_left_256MB_blocks"] = extra
    out["room_left_GB"] = extra * 0.268435456
    out["peak_bytes_in_use"] = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--tilings", default=DEFAULT_TILINGS)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=28)
    ap.add_argument("--trace", type=int, default=1)
    ap.add_argument("--memory", type=int, default=0)
    ap.add_argument("--params", type=int, default=625_616_896)
    ap.add_argument("--out", default="chiprun_out/gmm_sweep.json")
    args = ap.parse_args()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    results = []

    def emit(rec):
        rec["device"] = device
        results.append(rec)
        print(json.dumps(rec), flush=True)

    if args.memory:
        emit(memory_trial(args))
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out.replace(".json", ".memory.json"), "w") as fh:
            json.dump(results, fh, indent=1)
        return 0

    gs_np = group_sizes_for(args.seed, args.tokens, args.experts, args.top_k)
    rows = int(gs_np.sum())
    gs = jnp.asarray(gs_np)
    emit({"what": "group_sizes", "rows": rows, "max": int(gs_np.max()), "min": int(gs_np.min()),
          "mean": float(gs_np.mean())})
    key = jax.random.PRNGKey(args.seed)
    impls = [("ragged_dot", None)] + [
        ("megablox", tuple(int(x) for x in t.split("x"))) for t in args.tilings.split(";") if t
    ]
    for k_dim, n_dim in ((args.k, args.n), (args.n, args.k)):
        k1, k2, k3, key = jax.random.split(key, 4)
        lhs = jax.random.normal(k1, (rows, k_dim), jnp.bfloat16)
        rhs = (jax.random.normal(k2, (args.experts, k_dim, n_dim), jnp.float32) * 0.02).astype(jnp.bfloat16)
        cot = jax.random.normal(k3, (rows, n_dim), jnp.float32)
        n_flops = 2.0 * rows * k_dim * n_dim
        want = None
        for name, tiling in impls:
            rec = {"what": "gmm", "impl": name, "tiling": tiling, "rows": rows, "k": k_dim,
                   "n": n_dim, "experts": args.experts}
            try:
                impl = make_impl(name, tiling)
                f_ms = _time(fwd(impl), (lhs, rhs, gs), args.iters)
                fb_ms = _time(fwd_bwd(impl), (lhs, rhs, gs, cot), args.iters)
                got = np.asarray(fwd(impl)(lhs, rhs, gs)[: 4096].astype(jnp.float32))
                if want is None:
                    want = got
                rec.update(
                    fwd_ms=f_ms, fwd_bwd_ms=fb_ms,
                    fwd_share_of_peak=n_flops / (f_ms * 1e-3) / PEAK_BF16,
                    fwd_bwd_share_of_peak=3 * n_flops / (fb_ms * 1e-3) / PEAK_BF16,
                    max_abs_diff_vs_first=float(np.max(np.abs(got - want))),
                )
            except Exception as e:  # noqa: BLE001 - a refusal is a row of the table
                rec["error"] = repr(e)[:400]
            emit(rec)
        if args.trace:
            for name, tiling in (impls[0], impls[-2] if len(impls) > 2 else impls[-1]):
                try:
                    f = fwd_bwd(make_impl(name, tiling))
                    jax.block_until_ready(f(lhs, rhs, gs, cot))
                    d = os.path.join("chiprun_out", "gmm_trace", f"{name}_{k_dim}x{n_dim}")
                    jax.profiler.start_trace(d)
                    for _ in range(3):
                        jax.block_until_ready(f(lhs, rhs, gs, cot))
                    jax.profiler.stop_trace()
                    emit({"what": "ops", "impl": name, "tiling": tiling, "k": k_dim, "n": n_dim,
                          "calls_traced": 3, "ops": op_names(d)})
                except Exception as e:  # noqa: BLE001
                    emit({"what": "ops", "impl": name, "error": repr(e)[:400]})
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(results, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
