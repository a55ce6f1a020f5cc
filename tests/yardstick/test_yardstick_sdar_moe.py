"""What PR 60 added to the yardstick: the SDAR reference's own consistency (its
gradient, its mask, its noise), the parameter sum and the kept pairs against
hand sums and brute force, each new reader on a hand-made trace or span list,
the manifest with the new entries (and what three older tests asserted of the
manifest's tail, run as they stand against the manifest less this PR's
entries: see tests/conftest.py), and the rehearsal configuration through the
runner's whole path on the CPU."""

import dataclasses
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops, flops_sdar_moe, readers, references, sdar_trace
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.references import sdar_moe as ref
from benchmark.trace import Trace

M = Manifest(REPO_ROOT)
CFG = M.load_config("sdar-30b-a3b-chat")
TINY = M.load_config("tiny-rehearsal-sdar")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "sdar-solo-4k"


# -- the reference ---------------------------------------------------------------


def tiny_params(seed=3, scale=3.0):
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(TINY["registry_model"], **TINY["model_overrides"])
    ref.check_config(bundle.config, TINY)
    params = bundle.init(jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(lambda x: x * scale if x.ndim > 2 else x, params)
    tokens = np.random.default_rng(5).integers(0, TINY["vocab_size"] - 1, (2, 32))
    return bundle, params, tokens


def test_reference_gradient_agrees_with_finite_differences():
    """Its ``jax.grad`` against central differences of its own loss, along a
    seeded direction in every leaf, the routes held at those of the unmoved
    parameters (the top-k is piecewise constant), the noise at the harness's key."""
    import jax
    import jax.numpy as jnp

    _, params, tokens = tiny_params()
    hp = ref.hyper(TINY)
    _, routes = ref.loss(params, tokens, tokens, hp, with_routes=True)
    grads = jax.grad(ref.loss)(params, tokens, tokens, hp, routes)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    rng = np.random.default_rng(0)
    loss = jax.jit(lambda p: ref.loss(p, tokens, tokens, hp, routes))
    for i, (leaf, g) in enumerate(zip(leaves, jax.tree_util.tree_leaves(grads))):
        direction = rng.standard_normal(leaf.shape).astype(np.float32)
        direction /= np.linalg.norm(direction)
        eps = min(1e-2 * max(float(jnp.linalg.norm(leaf)), 1.0), 3e-2)

        def at(step, i=i, leaf=leaf, direction=direction):
            moved = list(leaves)
            moved[i] = leaf + step * direction
            return float(loss(jax.tree_util.tree_unflatten(treedef, moved)))

        numeric = (at(eps) - at(-eps)) / (2 * eps)
        analytic = float(jnp.vdot(g, direction))
        assert numeric == pytest.approx(analytic, rel=0.08, abs=3e-3), jax.tree_util.keystr(
            jax.tree_util.tree_leaves_with_path(params)[i][0])


def test_reference_mask_is_the_three_rules_and_keeps_l_squared_plus_l_bd_pairs():
    for l, bd in ((8, 4), (16, 4), (24, 12), (64, 32), (48, 1)):
        mask = np.asarray(ref.three_part_mask(l, bd))
        blk = np.arange(l) // bd
        for i in range(2 * l):
            for j in range(2 * l):
                bi, bj = blk[i % l], blk[j % l]
                want = (bj <= bi if j < l else False) if i < l else (bj < bi if j < l else bj == bi)
                assert mask[i, j] == want, (l, bd, i, j)
        assert mask.sum() == l * l + l * bd == ref.kept_pairs(l, bd) == flops_sdar_moe.kept_pairs(l, bd)
        causal = 2 * l * (2 * l + 1) // 2
        assert flops_sdar_moe.causal_pairs_over_the_rows(l) == causal == 2 * l * l + l


def test_reference_noise_is_one_rate_a_block_from_the_harness_key():
    import jax

    masked, rate = ref.noise(jax.random.PRNGKey(0), 3, 64, 4, 1e-3)
    rate = np.asarray(rate).reshape(3, 16, 4)
    assert np.all(rate == rate[..., :1]) and rate.min() >= 1e-3 and rate.max() <= 1.0
    again, _ = ref.noise(jax.random.PRNGKey(0), 3, 64, 4, 1e-3)
    other, _ = ref.noise(jax.random.PRNGKey(1), 3, 64, 4, 1e-3)
    assert np.array_equal(np.asarray(masked), np.asarray(again)) and not np.array_equal(
        np.asarray(masked), np.asarray(other))


def test_reference_attention_is_a_softmax_over_the_mask_one_head_at_a_time():
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 4, 16, 8))
    k, v = (jax.random.normal(kk, (1, 2, 16, 8)) for kk in ks[1:])
    mask = ref.three_part_mask(8, 4)
    got = np.asarray(ref._attention(q, k, v, mask))
    for h in range(4):
        s = np.asarray(q[0, h] @ k[0, h // 2].T) / np.sqrt(8)
        s = np.where(np.asarray(mask), s, -np.inf)
        p = np.exp(s - s.max(1, keepdims=True))
        want = (p / p.sum(1, keepdims=True)) @ np.asarray(v[0, h // 2])
        np.testing.assert_allclose(got[0, h], want, rtol=1e-5, atol=1e-5)
    assert jnp.isfinite(got).all()


def test_reference_sizes_and_config_check():
    assert ref.sizes(CFG) == {"n_layer": 5, "d_model": 2048, "seq_len": 4096, "vocab": 18992}
    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    ref.check_config(bundle.config, CFG)
    for attr, bad, word in (("block_length", 8, "block_length"), ("mask_id", 5, "mask_id"), ("eps_t", 0.01, "eps_t"),
                            ("aux_coef", 0.0, "aux_coef"), ("expert_offset", 16, "expert_offset"),
                            ("rope_theta", 1e4, "rope_theta")):
        with pytest.raises(ValueError, match=word):
            ref.check_config(dataclasses.replace(bundle.config, **{attr: bad}), CFG)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        ref.check_config(bundle.config, dict(CFG, norm_topk_prob=False))
    with pytest.raises(ValueError, match="unknown variant"):
        ref.loss({}, None, None, ref.hyper(CFG), variant="nothing")


def test_configuration_file_is_what_the_program_runs_with_its_cut_listed():
    """``test_yardstick_manifest.py``'s check of a configuration, for one whose
    ``reduced`` is not empty (tests/conftest.py marks that case), and every
    number of the catalog row under its own key."""
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    entry = M.config_entry("sdar-30b-a3b-chat")
    assert CFG["source"] == entry["source"] and entry["file"] == "benchmark/configs/sdar-30b-a3b-chat.json"
    assert CFG["reduced"] == entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    assert CFG["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert (CFG["num_hidden_layers"], CFG["num_experts"], CFG["vocab_size"]) == (5, 16, 18992)
    assert (CFG["family"], CFG["registry_model"]) == ("sdar_moe", "sdar_30b_a3b")
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    references.load(CFG["family"]).check_config(bundle.config, CFG)
    assert set(CFG["volunteer"]) <= {f.name for f in dataclasses.fields(VolunteerConfig)}
    assert CFG["volunteer"] == {"batch_size": 2, "optimizer": "adam", "lr": 0.001, "steps": 1000000,
                                "warmup_steps": 2000, "mesh": ""}
    assert "eight chips share each layer" in CFG["deployment"]
    for key in ("block_length", "schedule", "mask_id", "qk_norm", "aux_coefficients", "seq_len", "row_order",
                "batch_size", "lr_warmup"):
        assert key in CFG["assumed"], key
    for key in ("block_length", "schedule", "mask_id", "seq_len"):
        assert CFG["assumed"][key]["why"], key  # none guessed silently
    assert CFG["assumed"]["block_length"]["value"] == 4 and CFG["assumed"]["mask_id"]["value"] == 18991
    assert CFG["assumed"]["schedule"]["value"]["eps_t"] == 0.001
    assert CFG["assumed"]["aux_coefficients"]["load_balancing"] == 0.001
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "SDAR-30B-A3B-Chat")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key  # every width, list and nested group whole


# -- parameters, FLOPs, pairs and bytes -----------------------------------------------------


def test_the_program_holds_as_many_parameters_as_the_sum_says():
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    def counted(**overrides):
        shapes = jax.eval_shape(get_model("sdar_30b_a3b", **overrides).init, jax.random.PRNGKey(0))
        return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))

    layer = (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 128) + 2 * 2048 + 2048 * 128 + 16 * 3 * 2048 * 768
    assert layer == 18_874_624 + 4_096 + 262_144 + 75_497_472 == 94_638_336
    total = 5 * layer + 2 * 18_992 * 2048 + 2048
    assert total == 550_984_960 == flops_sdar_moe.total_params(CFG) == counted(**CFG["model_overrides"])
    assert CFG["parameters"]["counted_by_the_program"] == total and CFG["parameters"]["by_layer"] == [layer] * 5
    # the published model: every layer, every expert, the whole vocabulary
    full = dict(CFG, num_hidden_layers=48, num_experts=128, vocab_size=151936)
    assert flops_sdar_moe.total_params(full) == counted() == 30_532_122_624 == CFG["parameters"]["at_published_sizes"]
    # six layers: what does not fit at the check (24 bytes a parameter)
    assert counted(**dict(CFG["model_overrides"], n_layers=6)) * 24 > 15.4e9


def test_flops_a_data_token_against_a_hand_sum():
    d, hd, heads, kv, f, v = 2048, 128, 32, 4, 768, 18992
    a_row = 5 * (d * heads * hd + 2 * d * kv * hd + heads * hd * d + d * 128 + (8 * 16 / 128) * 3 * d * f)
    assert flops_sdar_moe.active_params_a_row(CFG) == pytest.approx(a_row)
    pairs = 4096 * 4096 + 4096 * 4
    want = 6 * (2 * a_row + d * v) + 12 * hd * 5 * heads * pairs / 4096
    assert flops_sdar_moe.train_flops_per_token(CFG, 4096) == pytest.approx(want)
    assert 2.6e9 < want < 2.8e9  # 21.9 TFLOP a step of 8,192 data tokens
    # the kernels, as they run: 4 D forward and 10 D backward a kept pair a head, over the 2L rows' bytes
    assert flops_sdar_moe.kernel_flops(CFG, 4096, 2, False) == 4 * hd * 2 * heads * pairs
    assert flops_sdar_moe.kernel_flops(CFG, 4096, 2, True) == 10 * hd * 2 * heads * pairs
    rows = 2 * 8192 * hd * 2
    assert flops_sdar_moe.kernel_bytes(CFG, 4096, 2, False) == rows * (2 * heads + 2 * kv)
    assert flops_sdar_moe.kernel_bytes(CFG, 4096, 2, True) == rows * (5 * heads + 2 * kv)
    least = flops_sdar_moe.kernel_least_seconds(CFG, 4096, 2, True, 197e12, 819e9)
    assert least == pytest.approx(10 * hd * 2 * heads * pairs / 197e12)  # the FLOPs, not the bytes


# -- the readers -----------------------------------------------------------------------

BD_FWD = "%dvc_flash_bd_fwd.3 = (bf16[2,8192,4096]{2,1,0}, f32[2,32,16,1,512]{4,3,2,1,0}) custom-call(%q, %k, %v)"
BD_BWD = "%dvc_flash_bd_bwd.1 = (bf16[2,8192,4096]{2,1,0}) custom-call(%q)"
FULL_FWD = "%dvc_flash_fwd.4 = (bf16[4,48,8192,128]{3,2,1,0}) custom-call(%q)"
WIN_BWD = "%dvc_flash_win_bwd.3 = (bf16[4,64,8192,128]{3,2,1,0}) custom-call(%q)"
HEAD = "%select_add_fusion.2 = f32[2048,18992]{1,0:T(8,128)} fusion(%x)"
MS = 1_000_000


def make_trace(ops):
    return Trace.from_json({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_step(7)", 1_000_000, 400_000_000],
                ["jit_step(7)", 402_000_000, 400_000_000],
                ["jit_step(7)", 803_000_000, 450_000_000],   # ends after the window
            ]},
            {"name": "XLA Ops", "events": ops},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench:trace_begin", 0, 900_000], ["bench:trace_end", 900_000_000, 10],
        ]}]},
    ]})


def route_span(t0, **attrs):
    return {"trace": "loop", "name": "moe.route", "t0": t0, "dur_s": 1e-5,
            "attrs": {"step": 10, "moe_load_max": 1300.0, "moe_load_mean": 1024.0, "moe_dropped": 0.0, **attrs}}


def run_of(ops, spans=(), **more):
    return {"trace": make_trace(ops), "step_program": r"^jit_step\(", "spans": list(spans),
            "stats": {}, "config": CFG, "tokens_per_step": 8192, "chips": 1,
            "peak": flops.PEAKS["TPU v5 lite"], **more}


STEP_OPS = [
    [BD_FWD, 2 * MS, 7 * MS], [BD_FWD, 30 * MS, 7 * MS], [BD_BWD, 60 * MS, 15 * MS], [HEAD, 300 * MS, 20 * MS],
    [BD_FWD, 500 * MS, 8 * MS], [BD_BWD, 600 * MS, 17 * MS],
    [BD_BWD, 900 * MS, 99 * MS],                              # in the step the window cuts
]


def test_kernel_names_tell_the_block_diffusion_calls_from_the_others():
    for name in ("dvc_flash_bd_fwd.11", "dvc_flash_bd_bwd", "dvc_flash_bd_bwd.3"):
        assert sdar_trace.KERNEL_RE.match(name), name
    for name in ("dvc_flash_fwd.4", "dvc_flash_win_bwd.2", "fusion.3", "xdvc_flash_bd_fwd", "dvc_flash_bdx"):
        assert not sdar_trace.KERNEL_RE.match(name), name
    # attention.roofline's pattern (the full and windowed kernels') does not take them: this cell is not on its list
    roofline = importlib.util.spec_from_file_location("r", M.layer_metric_path("attention.roofline"))
    module = importlib.util.module_from_spec(roofline)
    roofline.loader.exec_module(module)
    assert not module.KERNEL_RE.match("dvc_flash_bd_fwd.3") and module.KERNEL_RE.match("dvc_flash_fwd.3")


def test_bd_device_ms_and_roofline_over_whole_steps():
    run = run_of(STEP_OPS)
    assert readers.compute(M.layer_metric_path("attention.bd_device_ms"), run) == pytest.approx(
        (7 + 7 + 15 + 8 + 17) / 2)
    pairs = 4096 * 4096 + 4096 * 4
    fwd_ms = 4 * 128 * 2 * 32 * pairs / 197e12 * 1e3
    bwd_ms = 10 * 128 * 2 * 32 * pairs / 197e12 * 1e3
    got = readers.compute(M.layer_metric_path("attention.bd_roofline"), run)
    assert got == pytest.approx(100 * (3 * fwd_ms + 2 * bwd_ms) / (7 + 7 + 15 + 8 + 17)) and 0 < got < 100


@pytest.mark.parametrize("metric", ["attention.bd_device_ms", "attention.bd_roofline"])
def test_a_program_with_no_block_diffusion_kernel_reports_nothing(metric):
    """Every other model, or the parent of PR 60: no such kernel -> None, and no error."""
    path = M.layer_metric_path(metric)
    others = run_of([[FULL_FWD, 100 * MS, 27 * MS], [WIN_BWD, 200 * MS, 52 * MS], [HEAD, 300 * MS, 50 * MS]])
    assert readers.compute(path, others) is None
    assert readers.compute(path, run_of([])) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), trace=None)) is None
    # another family's arithmetic knows no kept pairs: the kernels' time is still a time, their roofline nothing
    elsewhere = readers.compute(path, dict(run_of(STEP_OPS), config=M.load_config("laguna-xs2")))
    assert (elsewhere is None) == metric.endswith("roofline")


@pytest.mark.parametrize("metric,key,values,want", [
    ("attention.bd_tiles_share", "attention_bd_tiles_share", (0.588, 0.588, 0.588), 0.588),
    ("diffusion.head_rows_share", "diffusion_head_rows_share", (0.5, 0.5), 0.5),
])
def test_the_step_s_own_counters_are_read_from_the_route_spans(metric, key, values, want):
    path = M.layer_metric_path(metric)
    spans = [route_span(float(i), **{key: v}) for i, v in enumerate(values)]
    spans.append({"trace": "loop", "name": "loop.log_sync", "t0": 1.0, "dur_s": 0.2, "attrs": {key: 9.0}})
    assert readers.compute(path, run_of([], spans)) == pytest.approx(want)
    # a model without the objective (or the parent) records no such attribute
    assert readers.compute(path, run_of([], [route_span(1.0)])) is None
    assert readers.compute(path, run_of([])) is None


def test_mfu_model_and_the_span_readers_take_this_cell_as_they_stand():
    """``step.mfu_model`` finds ``benchmark.flops_sdar_moe`` by the family and
    counts DATA tokens; ``moe.*``'s span readers read this cell's route spans."""
    got = readers.compute(M.layer_metric_path("step.mfu_model"), run_of(STEP_OPS))
    per_token = flops_sdar_moe.train_flops_per_token(CFG, 4096)
    assert got == pytest.approx(100 * 8192 * per_token / (0.4 * 197e12)) and 0 < got < 100
    spans = [route_span(1.0, moe_rows_moved=5 * 49152.0, moe_rows_held=80000.0),
             route_span(2.0, moe_rows_moved=5 * 49152.0, moe_rows_held=84000.0)]
    run = run_of([], spans)
    assert readers.compute(M.layer_metric_path("moe.dropped"), run) == 0.0
    assert readers.compute(M.layer_metric_path("moe.load_max_over_mean"), run) == pytest.approx(1300 / 1024)
    assert readers.compute(M.layer_metric_path("moe.rows_moved_over_held"), run) == pytest.approx(10 * 49152 / 164000)


# -- the manifest ----------------------------------------------------------------------

NEW_METRICS = {"attention.bd_device_ms": ("ms", "lower", "device_trace"),
               "attention.bd_roofline": ("%", "higher", "device_trace"),
               "attention.bd_tiles_share": ("ratio", "lower", "program_counter"),
               "diffusion.head_rows_share": ("ratio", "lower", "program_span")}
LISTS = ("loop.step_gap_ms", "step.device_ms", "device.idle_share", "device.peak_hbm_GB", "step.mfu_model",
         "moe.load_max_over_mean", "moe.dropped", "moe.rows_moved_over_held", "moe.share_device_ms",
         "lifecycle.ready_s", "lifecycle.net_s", "lifecycle.init_s", "lifecycle.step_build_s",
         "lifecycle.first_step_s", "lifecycle.trace_lower_s", "lifecycle.cache_load_s",
         "scope.attention_ms", "scope.moe_ms", "scope.loss_head_ms", "scope.optimizer_ms", "scope.other_ms",
         "scope.recompute_share", "scope.unresolved_share")


def test_manifest_holds_the_new_configuration_cell_and_metrics():
    M.check()
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sdar-30b-a3b-chat", "solo", 1)
    assert len(cell["why"]) <= 200 and "1,024 rows" in cell["why"] and "in LR warm-up" in cell["why"]
    per_layer = {m["name"]: m for m in M.metrics_for(CELL, "per_layer")}
    for name, (unit, better, source) in NEW_METRICS.items():
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert m["layer"] == "compiled step" and m["moves"] == "tok_s_chip" and m["workloads"] == [CELL]
    for shared in LISTS:
        assert per_layer[shared]["workloads"][-2:] == ["kimi-linear-solo-8k", CELL], shared
    # their readers count another model's shape, or a layer kind this one has not: the cell stays out
    for other in ("attention.device_ms", "attention.roofline", "step.mfu", "step.mfu_held", "step.mfu_active",
                  "scope.mixer_ms", "scope.mlp_ms", "moe.bias_spread", "moe.chunks_extra", "moe.act_zero_share",
                  "conv.device_ms", "ssm.device_ms", "kda.device_ms", "device.collective_share"):
        assert other not in per_layer, other
    assert set(per_layer) == set(LISTS) | set(NEW_METRICS) | {
        "lifecycle.compile_s", "lifecycle.cache_misses", "lifecycle.backend_init_s"}
    e2e = {m["name"]: m for m in M.metrics_for(CELL, "end_to_end")}
    assert set(e2e) == {"tok_s_chip", "setup_s"} and e2e["tok_s_chip"]["workloads"][-1] == CELL
    assert [m["name"] for m in M.doc["per_layer"][-4:]] == list(NEW_METRICS) and len(M.doc["per_layer"]) == 72
    assert M.doc["workloads"][-1] is cell and M.doc["configs"][-1]["name"] == "sdar-30b-a3b-chat"
    assert len(M.doc["workloads"]) == 11 and sum(w["chips"] == 4 for w in M.doc["workloads"]) == 1
    assert len(M.doc["configs"]) == 10


def less_this_pr(root=REPO_ROOT):
    """The manifest as PR 59 left it: without this PR's four metrics, its cell
    (on every list) and its configuration."""
    view = Manifest(root)
    doc = json.loads(json.dumps(view.doc))
    doc["per_layer"] = [dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m
                        for m in doc["per_layer"][:-4]]
    doc["end_to_end"] = [dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m
                         for m in doc["end_to_end"]]
    doc["workloads"], doc["configs"] = doc["workloads"][:-1], doc["configs"][:-1]
    view.doc = doc
    return view


@pytest.mark.parametrize("test,args", [
    ("test_manifest_holds_the_nine_scope_metrics_at_its_end", ()),
    ("test_manifest_as_the_kimi_tests_asserted_it_nine_places_up",
     ("test_manifest_holds_the_new_configuration_cell_and_metrics",)),
    ("test_manifest_as_the_kimi_tests_asserted_it_nine_places_up",
     ("test_manifest_tail_as_the_nemotron_tests_asserted_it_three_metrics_and_a_cell_up",)),
])
def test_manifest_as_the_collective_pairs_tests_asserted_it_before_this_cell(test, args, monkeypatch):
    """``test_yardstick_collective_pairs.py``'s three manifest cases
    (tests/conftest.py marks them: they assert that PR 57's two metrics END
    ``per_layer``, 68 entries, and run the scope tests two places up), run as
    they stand against the manifest less this PR's entries; against the
    manifest as it is each fails on the tail alone."""
    pairs = importlib.import_module("test_yardstick_collective_pairs")
    monkeypatch.setattr(pairs, "M", less_this_pr())
    monkeypatch.setattr(pairs, "Manifest", less_this_pr)
    pairs.test_manifest_as_the_scope_tests_asserted_it_two_places_up(test, args, monkeypatch)
    monkeypatch.setattr(pairs, "M", M)
    monkeypatch.setattr(pairs, "Manifest", Manifest)
    with pytest.raises(AssertionError):
        pairs.test_manifest_as_the_scope_tests_asserted_it_two_places_up(test, args, monkeypatch)


def test_reference_check_limits_are_written_with_their_readings():
    rc = CFG["reference_check"]
    assert rc["sequences"] == 1 and rc["seq_len"] in (2048, 4096)
    assert 0 < rc["grad_rel_err"] <= 0.15 and 0 < rc["loss_atol"] <= 0.05
    for word in ("flipped", "e4m3", "bfloat16", "left_out"):
        assert word in rc["why"], word
    for variant in ref.VARIANTS:
        assert variant in rc["left_out"], variant
    assert "PROVISIONAL" not in json.dumps(CFG)
    band = CFG["loss_band"]
    assert band["last_minus_first_max"] >= 1.0 and "seed" in band["why"]  # the 1 / t weight spreads one step's loss


# -- the rehearsal, end to end -----------------------------------------------------------


def test_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``tiny-rehearsal-sdar:solo`` through ``benchmark/run.py``: volunteer,
    probe, window, a traced run, the reference check, the result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "benchmark", "run.py"), "--rehearse",
         "tiny-rehearsal-sdar:solo", "--seed", "3000000019", "--seconds", "3", "--trace", "1"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert '"reference": true' in out.stderr and '"no_compile_in_window": true' in out.stderr
    # the configuration names the task's initial parameters (PR 69): `--seed` moves the data and the noise only
    assert "seeds: data and noise 3000000019, initial parameters 3100000013" in out.stderr
