"""What PR 28 added to the yardstick: the OLMoE reference's own consistency, the
sparse-expert FLOP counts against hand sums, each new reader on a hand-made
trace or span list, the manifest with the new entries, and the rehearsal
configuration through the runner's whole path on the CPU."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import datagen, flops, flops_moe, moe_trace, readers, references
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.references import olmoe as ref
from benchmark.trace import Trace

M = Manifest(REPO_ROOT)
CFG = M.load_config("olmoe-1b-7b")
TINY = M.load_config("tiny-rehearsal-olmoe")


# -- the reference ---------------------------------------------------------------


def tiny_params(seed=3, scale=3.0):
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(TINY["registry_model"], **TINY["model_overrides"])
    ref.check_config(bundle.config, TINY)
    params = bundle.init(jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(lambda x: x * scale if x.ndim > 1 else x, params)
    return bundle, params, datagen.lm_arrays(5, 2, 16, TINY["vocab_size"])


def test_reference_gradient_agrees_with_finite_differences():
    """Its ``jax.grad`` against central differences of its own loss, along a
    seeded direction in every leaf. The routes are held at those of the
    unmoved parameters: the top-k is piecewise constant, the loss jumps where
    a token changes expert, and a difference quotient across a jump measures
    the jump (``test_routes_given_equal_routes_computed`` ties the two)."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", False)
    _, params, batch = tiny_params()
    hp = ref.hyper(TINY)
    tokens, targets = batch["tokens"], batch["targets"]
    _, routes = ref.loss(params, tokens, targets, hp, with_routes=True)
    grads = jax.grad(ref.loss)(params, tokens, targets, hp)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    g_leaves = jax.tree_util.tree_leaves(grads)
    rng = np.random.default_rng(0)
    loss = jax.jit(lambda p: ref.loss(p, tokens, targets, hp, routes))
    for i, (leaf, g) in enumerate(zip(leaves, g_leaves)):
        direction = rng.standard_normal(leaf.shape).astype(np.float32)
        direction /= np.linalg.norm(direction)
        eps = 1e-3 * float(jnp.linalg.norm(leaf))
        moved = lambda s: jax.tree_util.tree_unflatten(  # noqa: E731
            treedef, leaves[:i] + [leaf + s * eps * direction] + leaves[i + 1:])
        fd = (float(loss(moved(1.0))) - float(loss(moved(-1.0)))) / (2 * eps)
        want = float(jnp.sum(g * direction))
        assert fd == pytest.approx(want, rel=0.05, abs=2e-4), (i, fd, want)


def test_routes_given_equal_routes_computed():
    import jax

    _, params, batch = tiny_params()
    hp = ref.hyper(TINY)
    tokens, targets = batch["tokens"], batch["targets"]
    loss, routes = ref.loss(params, tokens, targets, hp, with_routes=True)
    assert routes.shape == (2, tokens.size, TINY["num_experts_per_tok"])
    fn = ref.make_loss_and_grad(TINY)
    l0, g0 = fn(params, tokens, targets)
    l1, g1 = fn(params, tokens, targets, routes)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6) == pytest.approx(float(loss), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
    # other routes are another function: the last choice moved to the next expert
    other = routes.at[..., -1].set((routes[..., -1] + 1) % TINY["num_experts"])
    assert abs(float(fn(params, tokens, targets, other)[0]) - float(l0)) > 1e-4


def test_reference_sizes_and_config_check():
    from distributedvolunteercomputing_tpu.models import get_model

    assert ref.sizes(CFG) == {"n_layer": 1, "d_model": 2048, "seq_len": 4096, "vocab": 50304}
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    ref.check_config(bundle.config, CFG)
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        ref.check_config(bundle.config, dict(CFG, num_experts_per_tok=2))
    with pytest.raises(ValueError, match="layers"):
        ref.check_config(get_model(CFG["registry_model"]).config, CFG)  # 16 layers
    with pytest.raises(ValueError, match="norm_topk_prob"):
        ref.check_config(bundle.config, dict(CFG, norm_topk_prob=True))
    with pytest.raises(ValueError, match="coefficients"):
        ref.check_config(dataclasses.replace(bundle.config, z_coef=0.0), CFG)


def test_configuration_file_is_what_the_program_runs_with_its_cut_listed():
    """``test_yardstick_manifest.py``'s check of a configuration, for one whose
    ``reduced`` is not empty (see conftest.py)."""
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    entry = M.config_entry("olmoe-1b-7b")
    assert CFG["source"] == entry["source"]
    assert CFG["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    references.load(CFG["family"]).check_config(bundle.config, CFG)
    assert set(CFG["volunteer"]) <= {f.name for f in dataclasses.fields(VolunteerConfig)}
    # every published key of the catalog row, under its own name, at its value
    published = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
                 "hidden_size": 2048, "intermediate_size": 1024, "max_position_embeddings": 4096,
                 "model_type": "olmoe", "norm_topk_prob": False, "num_attention_heads": 16,
                 "num_experts": 64, "num_experts_per_tok": 8, "num_key_value_heads": 16,
                 "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
                 "tie_word_embeddings": False, "vocab_size": 50304}
    assert {k: CFG[k] for k in published} == published and CFG["num_hidden_layers"] == 1


# -- FLOPs and bytes ---------------------------------------------------------------


def test_sparse_flop_counts_against_a_hand_sum():
    d, f, e, k, v, t = 2048, 1024, 64, 8, 50304, 4096
    layer_all = 4 * d * d + e * 3 * d * f + e * d + 4 * d
    assert layer_all == 419_569_664 and e * 3 * d * f == 402_653_184
    assert flops_moe.total_params(CFG) == layer_all + 2 * v * d + d == 625_616_896
    active = 4 * d * d + k * 3 * d * f + e * d + d * v
    assert flops_moe.active_params(CFG) == active == 170_262_528
    assert flops_moe.train_flops_per_token_active(CFG, t) == 6 * active + 12 * 1 * t * d
    # the published depth: 16 layers, 6.9 B parameters of which 1.3 B work on a token
    full = dict(CFG, num_hidden_layers=16, model_overrides={})
    assert flops_moe.total_params(full) == 16 * layer_all + 2 * v * d + d == 6_919_161_856
    assert flops_moe.active_params(full) == 16 * (active - d * v) + d * v == 1_178_861_568
    # one grouped matmul call of a step of 16,384 tokens
    assert flops_moe.gmm_flops(CFG, 16384) == 2 * 131072 * d * f
    assert flops_moe.gmm_bytes(CFG, 16384) == 2 * (131072 * d + 131072 * f + e * d * f)
    peak = flops.PEAKS["TPU v5 lite"]
    assert flops_moe.hbm_bytes_per_s(peak) == 819e9
    assert flops_moe.hbm_bytes_per_s({"bf16_flops": 1.0}) is None
    least = flops_moe.gmm_least_seconds(CFG, 16384, peak["bf16_flops"], 819e9)
    assert least == pytest.approx(2 * 131072 * d * f / 197e12) == pytest.approx(2.7906e-3, rel=1e-4)


def test_the_program_holds_as_many_parameters_as_the_count_says():
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    assert n == flops_moe.total_params(CFG)
    # flops.train_flops_per_token counts every parameter: 3.67 times the work
    s = ref.sizes(CFG)
    dense = flops.train_flops_per_token(n, s["n_layer"], s["seq_len"], s["d_model"])
    assert dense / flops_moe.train_flops_per_token_active(CFG, s["seq_len"]) == pytest.approx(3.43, abs=0.01)


# -- the readers ---------------------------------------------------------------------

GMM = "%gmm.16 = bf16[131072,1024]{1,0:T(8,128)(2,1)} custom-call(s32[]{:T(128)} %a, s32[65]{0} %b)"
TGMM = "%tgmm.2 = bf16[64,1024,2048]{2,1,0:T(8,128)(2,1)} custom-call(s32[]{:T(128)} %a)"
JVP = "%transpose_jvp_jit_gmm___.2 = bf16[131072,2048]{1,0} custom-call(s32[]{:T(128)} %a)"
RAGGED = "%ragged-dot-none.1 = bf16[131072,2048]{1,0:T(8,128)(2,1)} custom-call(s32[1]{0} %a)"
META = "%ragged-dot-metadata = (s32[65]{0}, s32[319]{0}) custom-call(s32[64]{0} %a)"
SORT = "%sort.3 = (s32[131072]{0}, s32[131072]{0}) sort(%a, %b)"
GATHER = "%fusion.37 = bf16[131072,2048]{1,0:T(8,128)(2,1)} fusion(bf16[16384,2048]{1,0} %x, s32[131072]{0} %i), kind=kCustom"
SILU_UP = "%fusion.243 = (bf16[131072,1024]{1,0:T(8,128)(2,1)}, bf16[131072,1024]{1,0}) fusion(%g, %u), kind=kLoop"
TOKENS = "%fusion.219 = bf16[16384,8]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[131072,2048]{1,0} %rows)"
HEAD = "%select_add_fusion.2 = f32[2048,50304]{1,0:T(8,128)} fusion(%x)"
FLASH = "%dvc_flash_fwd.11 = (bf16[4,16,4096,128]{3,2,1,0}) custom-call(%q)"


def make_trace(ops):
    return Trace.from_json({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_step(7)", 1_000_000, 300_000_000],
                ["jit_step(7)", 302_000_000, 200_000_000],
                ["jit_step(7)", 503_000_000, 250_000_000],   # ends after the window
            ]},
            {"name": "XLA Ops", "events": ops},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench:trace_begin", 0, 900_000], ["bench:trace_end", 600_000_000, 10],
        ]}]},
    ]})


def run_of(ops, spans=(), **more):
    return {"trace": make_trace(ops), "step_program": r"^jit_step\(", "spans": list(spans),
            "stats": {}, "config": CFG, "tokens_per_step": 16384, "chips": 1,
            "peak": flops.PEAKS["TPU v5 lite"], **more}


MS = 1_000_000
STEP_OPS = [
    [GMM, 2 * MS, 4 * MS], [TGMM, 10 * MS, 4 * MS], [JVP, 20 * MS, 4 * MS], [SORT, 30 * MS, 1 * MS],
    [HEAD, 40 * MS, 50 * MS], [FLASH, 100 * MS, 5 * MS],
    [GMM, 310 * MS, 6 * MS], [META, 320 * MS, 0.02 * MS],
    [GATHER, 330 * MS, 4.4 * MS], [SILU_UP, 340 * MS, 2.4 * MS], [TOKENS, 350 * MS, 0.3 * MS],
    [GMM, 510 * MS, 9 * MS],                                  # in the step the window cuts
]


def test_gmm_names_the_chip_prints_are_recognised():
    hits = [moe_trace.GMM_RE.search(n) is not None for n in (
        "gmm.16", "tgmm.2", "jvp_jit_gmm__.2", "transpose_jvp_jit_gmm___.2",
        "transpose_jvp_jit_tgmm___.2", "ragged-dot-none.1", "ragged-dot-none")]
    assert all(hits)
    for other in ("ragged-dot-metadata.1", "fusion.224", "dvc_flash_bwd.2", "sort.3",
                  "convolution_add_fusion.9", "select_add_fusion.2", "ogmm_fusion.1x"):
        assert not moe_trace.GMM_RE.search(other), other
    assert moe_trace.ROUTING_RE.search("sort.3") and moe_trace.ROUTING_RE.search("ragged-dot-metadata")
    assert not moe_trace.ROUTING_RE.search("fusion.3")


def test_operations_over_the_routed_rows_are_told_by_their_result():
    """The row movers have no name of their own (``%fusion.37``): the S x k
    routed rows leading their result tell them from the rest of the step."""
    rows = moe_trace.routed_rows(run_of([]))
    assert rows == 16384 * 8
    for text in (GATHER, SILU_UP, SORT, GMM, JVP, "%add_any.4 = bf16[131072,2048]{1,0} add(%a, %b)"):
        assert moe_trace.leads_with(text, rows), text
    for text in (TOKENS, HEAD, FLASH, TGMM, META, "%while.3 = (s32[]{:T(128)}, bf16[131072,2048]{1,0}) while(%t)",
                 "%fusion.9 = bf16[1310720,2048]{1,0} fusion(%x)", "bench:trace_begin"):
        assert not moe_trace.leads_with(text, rows), text
    assert not moe_trace.leads_with(GATHER, None)
    assert moe_trace.routed_rows(dict(run_of([]), config=M.load_config("gpt2-medium"))) is None


def test_moe_device_ms_is_the_time_in_the_layers_operations_over_whole_steps():
    got = readers.compute(M.layer_metric_path("moe.device_ms"), run_of(STEP_OPS))
    assert got == pytest.approx((4 + 4 + 4 + 1 + 6 + 0.02 + 4.4 + 2.4) / 2)
    # an operation that spans others (a sort's loop over its passes) counts once
    nested = [[GMM, 2 * MS, 4 * MS], [SORT, 30 * MS, 3 * MS],
              ["%fusion.80 = s32[131072]{0:T(1024)S(1)} fusion(%a)", 31 * MS, 1 * MS]]
    got = readers.compute(M.layer_metric_path("moe.device_ms"), run_of(nested))
    assert got == pytest.approx((4 + 3) / 2)


def test_gmm_roofline_is_least_time_over_time_taken():
    got = readers.compute(M.layer_metric_path("moe.gmm_roofline"), run_of(STEP_OPS))
    least_ms = 2 * 131072 * 2048 * 1024 / 197e12 * 1e3
    assert got == pytest.approx(100 * 4 * least_ms / (4 + 4 + 4 + 6))
    assert got < 100
    ragged = run_of([[RAGGED, 2 * MS, 5.2 * MS], [META, 1 * MS, 0.02 * MS]])
    assert readers.compute(M.layer_metric_path("moe.gmm_roofline"), ragged) == pytest.approx(
        100 * least_ms / 5.2)


@pytest.mark.parametrize("metric", ["moe.device_ms", "moe.gmm_roofline"])
def test_a_program_with_no_grouped_matmul_reports_nothing(metric):
    """A dense model, or the parent of PR 28: no such operation -> None."""
    path = M.layer_metric_path(metric)
    dense = run_of([[HEAD, 40 * MS, 50 * MS], [FLASH, 100 * MS, 5 * MS], [SORT, 30 * MS, 1 * MS]])
    assert readers.compute(path, dense) is None
    assert readers.compute(path, run_of([])) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), trace=None)) is None


def test_mfu_active_is_active_flops_over_the_steps_median_time():
    got = readers.compute(M.layer_metric_path("step.mfu_active"), run_of(STEP_OPS))
    per_token = 6 * 170_262_528 + 12 * 1 * 4096 * 2048
    assert got == pytest.approx(100 * 16384 * per_token / (0.25 * 197e12))  # median of 300, 200 ms
    assert got < 100
    dense = run_of(STEP_OPS)
    dense["config"] = M.load_config("gpt2-medium")
    assert readers.compute(M.layer_metric_path("step.mfu_active"), dense) is None
    assert readers.compute(M.layer_metric_path("step.mfu_active"),
                           dict(run_of(STEP_OPS), trace=None)) is None


def route_span(t0, load_max, dropped, load_mean=2048.0):
    return {"trace": "loop", "name": "moe.route", "t0": t0, "dur_s": 1e-5, "parent": "loop.log_sync",
            "attrs": {"step": 50, "moe_load_max": load_max, "moe_load_mean": load_mean,
                      "moe_dropped": dropped, "aux_loss": 8.0, "lm_loss": 11.0}}


def test_routing_readers_read_the_moe_route_spans():
    spans = [route_span(1.0, 2300.0, 0.0), route_span(2.0, 2500.0, 0.0), route_span(3.0, 4096.0, 0.0),
             {"trace": "loop", "name": "loop.log_sync", "t0": 1.0, "dur_s": 0.2, "attrs": {"step": 50}}]
    run = run_of([], spans)
    assert readers.compute(M.layer_metric_path("moe.load_max_over_mean"), run) == pytest.approx(2500 / 2048)
    assert readers.compute(M.layer_metric_path("moe.dropped"), run) == 0.0
    run = run_of([], [route_span(1.0, 2300.0, 3.0), route_span(2.0, 2300.0, 4.0)])
    assert readers.compute(M.layer_metric_path("moe.dropped"), run) == 7.0
    for metric in ("moe.load_max_over_mean", "moe.dropped"):   # a dense model has no such span
        assert readers.compute(M.layer_metric_path(metric), run_of([], spans[-1:])) is None
        assert readers.compute(M.layer_metric_path(metric), run_of([], [])) is None


# -- the manifest ----------------------------------------------------------------------

NEW_METRICS = {"step.mfu_active": ("%", "higher", "device_trace"),
               "moe.device_ms": ("ms", "lower", "device_trace"),
               "moe.gmm_roofline": ("%", "higher", "device_trace"),
               "moe.load_max_over_mean": ("ratio", "lower", "program_span"),
               "moe.dropped": ("count", "lower", "program_span")}


def test_manifest_holds_the_new_configuration_cell_and_metrics():
    M.check()
    cell = M.cell("olmoe-solo")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmoe-1b-7b", "solo", 1)
    assert len(cell["why"]) <= 200
    per_layer = {m["name"]: m for m in M.metrics_for("olmoe-solo", "per_layer")}
    for name, (unit, better, source) in NEW_METRICS.items():
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert m["layer"] == "compiled step" and m["moves"] == "tok_s_chip"
        assert m["workloads"] == ["olmoe-solo"]
    for shared in ("loop.step_gap_ms", "step.device_ms", "device.idle_share", "device.peak_hbm_GB"):
        assert per_layer[shared]["workloads"][-1] == "olmoe-solo"
    # 6 N over all parameters would read over 100% for a sparse model
    assert "step.mfu" not in per_layer
    e2e = {m["name"] for m in M.metrics_for("olmoe-solo", "end_to_end")}
    assert e2e == {"tok_s_chip", "setup_s"}
    assert M.doc["per_layer"][-5:] == [next(m for m in M.doc["per_layer"] if m["name"] == n)
                                       for n in NEW_METRICS]
    assert M.doc["workloads"][-1] is cell and M.doc["configs"][-1]["name"] == "olmoe-1b-7b"


def test_reference_check_limits_are_written_with_their_readings():
    rc = CFG["reference_check"]
    assert (rc["sequences"], rc["seq_len"]) == (2, 1024)
    assert 0 < rc["grad_rel_err"] <= 0.15 and 0 < rc["loss_atol"] <= 0.01
    assert "PROVISIONAL" not in rc["why"] and "flipped" in rc["why"] and "8-bit" in rc["why"]


# -- the rehearsal, end to end -----------------------------------------------------------


def test_rehearsal_cell_runs_end_to_end_on_the_cpu(tmp_path):
    """``tiny-rehearsal-olmoe:solo`` through ``benchmark/run.py``: volunteer,
    probe, window, a traced run, the reference check, the result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "benchmark", "run.py"), "--rehearse",
         "tiny-rehearsal-olmoe:solo", "--seed", "3000000019", "--seconds", "3", "--trace", "1"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert '"reference": true' in out.stderr and '"no_compile_in_window": true' in out.stderr
