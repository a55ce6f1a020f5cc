"""What PR 35 added to the yardstick: the SmallThinker reference's own
consistency, the FLOP and pair counts against hand sums, each new reader on a
hand-made trace or span list (and the older readers on this cell's kernel
names and loops), the manifest with the new entries (and what
``test_yardstick_laguna.py`` asserted of the manifest's tail and of Laguna's
lists, one place up: see tests/conftest.py), and the rehearsal configuration
through the runner's whole path on the CPU."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import datagen, family_flops, flops, flops_smallthinker, readers, references
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.references import smallthinker as ref
from benchmark.trace import Trace

M = Manifest(REPO_ROOT)
CFG = M.load_config("smallthinker-21b-a3b")
TINY = M.load_config("tiny-rehearsal-smallthinker")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "smallthinker-solo-16k"


# -- the reference ---------------------------------------------------------------


def tiny_params(seed=3, scale=3.0):
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(TINY["registry_model"], **TINY["model_overrides"])
    ref.check_config(bundle.config, TINY)
    params = bundle.init(jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x if "ln_" in jax.tree_util.keystr(path) else x * scale, params)
    return bundle, params, datagen.lm_arrays(5, 2, 24, TINY["vocab_size"])


def test_reference_gradient_agrees_with_finite_differences():
    """Its ``jax.grad`` against central differences of its own loss, along a
    seeded direction in every leaf, the routes held at those of the unmoved
    parameters (the top-k is piecewise constant)."""
    import jax
    import jax.numpy as jnp

    _, params, batch = tiny_params()
    hp = ref.hyper(TINY)
    tokens, targets = batch["tokens"], batch["targets"]
    _, routes = ref.loss(params, tokens, targets, hp, with_routes=True)
    grads = jax.grad(ref.loss)(params, tokens, targets, hp, routes)
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
        assert fd == pytest.approx(want, rel=0.05, abs=3e-4), (i, fd, want)


@pytest.mark.parametrize("windowed", [True, False])
def test_reference_attention_is_a_softmax_over_the_kept_keys_one_head_at_a_time(windowed, monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(ref, "ATTN_BLOCK", 4)  # three blocks of queries
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 14, 12, 8))
    k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 12, 8)) for i in (1, 2))
    got = ref._attention(q, k, v, 5, jnp.asarray(windowed))
    for h in (0, 6, 7, 13):  # seven query heads read one key/value head
        for i in range(12):
            js = [j for j in range(12) if j <= i and (i - 5 < j or not windowed)]
            s = jnp.stack([q[0, h, i] @ k[0, h // 7, j] for j in js]) / np.sqrt(8)
            want = jax.nn.softmax(s) @ jnp.stack([v[0, h // 7, j] for j in js])
            np.testing.assert_allclose(np.asarray(got[0, h, i]), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_reference_rotary_switches_off_exactly_where_the_list_says_no_position():
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 9, 8))
    assert np.array_equal(np.asarray(ref._rope(x, 1.5e6, jnp.float32(0.0))), np.asarray(x))
    turned = np.asarray(ref._rope(x, 1.5e6, jnp.float32(1.0)))
    assert np.array_equal(turned[..., 0, :], np.asarray(x)[..., 0, :]) and not np.allclose(turned, x)
    # the half-split convention against complex multiplication
    inv = 1.0 / 1.5e6 ** (np.arange(0, 8, 2) / 8)
    z = (np.asarray(x)[..., :4] + 1j * np.asarray(x)[..., 4:]) * np.exp(1j * np.arange(9)[:, None] * inv[None, :])
    np.testing.assert_allclose(turned, np.concatenate([z.real, z.imag], axis=-1), rtol=1e-5, atol=1e-5)


def test_reference_sizes_and_config_check():
    from distributedvolunteercomputing_tpu.models import get_model

    assert ref.sizes(CFG) == {"n_layer": 4, "d_model": 2560, "seq_len": 16384, "vocab": 18992}
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    ref.check_config(bundle.config, CFG)
    for key, change in (("moe_num_active_primary_experts", 2), ("moe_num_primary_experts", 16),
                        ("sliding_window_size", 512), ("rope_theta", 10000), ("expert_offset", 8),
                        ("norm_topk_prob", False), ("num_experts_per_tok", 8), ("moe_ffn_hidden_size", 512)):
        with pytest.raises(ValueError, match=key):
            ref.check_config(bundle.config, dict(CFG, **{key: change}))
    with pytest.raises(ValueError, match="n_layers"):
        ref.check_config(dataclasses.replace(bundle.config, n_layers=52), CFG)
    with pytest.raises(ValueError, match="experts_held"):
        ref.check_config(get_model(CFG["registry_model"]).config, CFG)  # the published model, uncut
    with pytest.raises(ValueError, match="rope_layout"):
        ref.check_config(bundle.config, dict(CFG, rope_layout=[1] * 52))
    with pytest.raises(ValueError, match="router_site"):
        ref.check_config(bundle.config, dict(CFG, assumed={**CFG["assumed"], "router": {"site": "post_attention"}}))
    with pytest.raises(ValueError, match="aux_coef"):
        ref.check_config(dataclasses.replace(bundle.config, aux_coef=0.0), CFG)


def test_configuration_file_is_what_the_program_runs_with_its_cut_listed():
    """``test_yardstick_manifest.py``'s check of a configuration, for one whose
    ``reduced`` is not empty (tests/conftest.py marks that case), and every
    number of the catalog row under its own key."""
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    entry = M.config_entry("smallthinker-21b-a3b")
    assert CFG["source"] == entry["source"]
    assert CFG["reduced"] == entry["reduced"] == ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    assert CFG["published"] == {"num_hidden_layers": 52, "moe_num_primary_experts": 64, "vocab_size": 151936}
    assert (CFG["num_hidden_layers"], CFG["moe_num_primary_experts"], CFG["vocab_size"]) == (4, 8, 18992)
    assert CFG["num_experts_per_tok"] == CFG["moe_num_active_primary_experts"] == 6  # moe_trace.routed_rows' key
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    references.load(CFG["family"]).check_config(bundle.config, CFG)
    assert set(CFG["volunteer"]) <= {f.name for f in dataclasses.fields(VolunteerConfig)}
    assert CFG["volunteer"]["batch_size"] == 2
    assert "eight chips share each layer" in CFG["deployment"]
    assert "eight chips share each layer" in CFG["reduced_why"]["moe_num_primary_experts"]
    for key in ("router", "secondary_experts", "position_encoding", "window", "aux_coefficients", "seq_len",
                "batch_size"):
        assert key in CFG["assumed"], key
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key  # every width and both layout lists whole


def test_the_window_lies_inside_an_lr_warmup_stated_with_its_routing_trace():
    """PR 54: at 1e-3 from the first step the softmax router collapses inside
    the five warm-up steps and the step's time follows the seed; the cell times
    the schedule the four other warm-up share cells run in."""
    warm = CFG["assumed"]["lr_warmup"]
    assert CFG["volunteer"]["warmup_steps"] == warm["warmup_steps"] == 2000
    assert TINY["volunteer"]["warmup_steps"] == 2000
    assert "linear warm-up from 0 over warmup_steps" in CFG["assumed"]["optimizer"]
    for word in ("2412.19437", "WITHOUT", "WITH it", "24,576", "3,072", "--warmup-steps 0", "PR 54"):
        assert word in warm["why"], word


# -- FLOPs, pairs and bytes ----------------------------------------------------------


def test_pairs_and_flop_counts_against_a_hand_sum():
    t, w, d, hd, f = 16384, 4096, 2560, 128, 768
    assert flops_smallthinker.kept_pairs(CFG, t, False) == t * (t + 1) // 2 == 134_225_920
    assert flops_smallthinker.kept_pairs(CFG, t, True) == sum(min(i + 1, w) for i in range(t)) == 58_722_304
    attn = d * 28 * hd + 2 * d * 4 * hd + 28 * hd * d
    assert attn == flops_smallthinker.attention_params(CFG) == 20_971_520
    layer = attn + d * 64 + 8 * 3 * d * f + 2 * d
    assert layer == 68_326_400
    assert flops_smallthinker.total_params(CFG) == 4 * layer + 2 * 18992 * d + d == 370_547_200
    active = 4 * (attn + d * 64 + 0.75 * 3 * d * f) + d * 18992
    assert flops_smallthinker.active_params(CFG) == active == 150_855_680
    pairs = 28 * 134_225_920 + 3 * 28 * 58_722_304
    assert (flops_smallthinker.attention_pair_heads(CFG, t, False)
            + flops_smallthinker.attention_pair_heads(CFG, t, True)) == pairs
    assert flops_smallthinker.train_flops_per_token(CFG, t) == 6 * active + 12 * hd * pairs / t
    # one call of each kernel, batch 2
    assert flops_smallthinker.kernel_flops(CFG, t, 2, True, False) == 4 * hd * 2 * 28 * 58_722_304
    assert flops_smallthinker.kernel_flops(CFG, t, 2, False, True) == 10 * hd * 2 * 28 * 134_225_920
    rows = 2 * t * hd * 2
    assert flops_smallthinker.kernel_bytes(CFG, t, 2, True, False) == rows * (2 * 28 + 2 * 4)
    assert flops_smallthinker.kernel_bytes(CFG, t, 2, False, True) == rows * (5 * 28 + 2 * 4)
    least = flops_smallthinker.kernel_least_seconds(CFG, t, 2, False, True, 197e12, 819e9)
    assert least == pytest.approx(10 * hd * 2 * 28 * 134_225_920 / 197e12) == pytest.approx(48.84e-3, rel=1e-3)
    # the issue's arithmetic: kernel FLOPs a step as they run, matrix products over the active parameters
    step_kernels = sum(flops_smallthinker.kernel_flops(CFG, t, 2, s, b) * n
                       for s, n in ((False, 1), (True, 3)) for b in (False, True))
    assert step_kernels / 1e12 == pytest.approx(13.47 + 17.68, abs=0.01)
    assert 6 * active * 2 * t / 1e12 == pytest.approx(29.66, abs=0.01)


def test_the_program_holds_as_many_parameters_as_the_count_says():
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    assert n == flops_smallthinker.total_params(CFG) == CFG["parameters"]["counted_by_the_program"]
    assert CFG["parameters"]["active_on_a_token"] == flops_smallthinker.active_params(CFG)
    full = dict(CFG, num_hidden_layers=52, moe_num_primary_experts=64, vocab_size=151936)
    assert flops_smallthinker.total_params(full) == 21_506_562_560  # the published model: 21 B


def test_family_flops_finds_a_configurations_arithmetic_by_its_family():
    assert family_flops.load(CFG) is flops_smallthinker
    assert family_flops.load(M.load_config("laguna-xs2")).__name__ == "benchmark.flops_laguna"
    assert family_flops.load(M.load_config("gpt2-medium")) is None and family_flops.load({}) is None


# -- the readers -----------------------------------------------------------------------

WIN_FWD = "%dvc_flash_win_fwd.12 = (bf16[2,28,16384,128]{3,2,1,0}) custom-call(%q, %k, %v)"
WIN_BWD = "%dvc_flash_win_bwd.13 = (bf16[2,28,16384,128]{3,2,1,0}) custom-call(%q)"
FULL_FWD = "%dvc_flash_fwd.7 = (bf16[2,28,16384,128]{3,2,1,0}) custom-call(%q)"
FULL_BWD = "%dvc_flash_bwd.2 = (bf16[2,28,16384,128]{3,2,1,0}) custom-call(%q)"
HEAD = "%select_add_fusion.2 = f32[2560,18992]{1,0:T(8,128)} fusion(%x)"
MS = 1_000_000


def make_trace(ops):
    return Trace.from_json({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_step(7)", 1_000_000, 900_000_000],
                ["jit_step(7)", 902_000_000, 900_000_000],
                ["jit_step(7)", 1_803_000_000, 950_000_000],   # ends after the window
            ]},
            {"name": "XLA Ops", "events": ops},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench:trace_begin", 0, 900_000], ["bench:trace_end", 2_500_000_000, 10],
        ]}]},
    ]})


def run_of(ops, spans=(), **more):
    return {"trace": make_trace(ops), "step_program": r"^jit_step\(", "spans": list(spans),
            "stats": {}, "config": CFG, "tokens_per_step": 32768, "chips": 1,
            "peak": flops.PEAKS["TPU v5 lite"], **more}


STEP_OPS = [
    [FULL_FWD, 2 * MS, 27 * MS], [FULL_BWD, 40 * MS, 66 * MS],
    [WIN_FWD, 120 * MS, 15 * MS], [WIN_FWD, 140 * MS, 15 * MS], [WIN_FWD, 160 * MS, 16 * MS],
    [WIN_BWD, 200 * MS, 36 * MS], [HEAD, 300 * MS, 50 * MS],
    [WIN_BWD, 1000 * MS, 38 * MS], [FULL_FWD, 1100 * MS, 29 * MS],
    [WIN_BWD, 2000 * MS, 99 * MS],                             # in the step the window cuts
]


def test_mfu_model_is_the_familys_flops_over_the_steps_median_time():
    path = M.layer_metric_path("step.mfu_model")
    got = readers.compute(path, run_of(STEP_OPS))
    per_token = flops_smallthinker.train_flops_per_token(CFG, 16384)
    assert got == pytest.approx(100 * 32768 * per_token / (0.9 * 197e12)) and 0 < got < 100
    # a family with no module, or one whose module has no such function: nothing, and no error
    assert readers.compute(path, dict(run_of(STEP_OPS), config=M.load_config("gpt2-medium"))) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), config=M.load_config("laguna-xs2"))) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), trace=None)) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), peak=None)) is None


def test_attention_roofline_is_least_time_of_every_call_over_time_taken():
    path = M.layer_metric_path("attention.roofline")
    got = readers.compute(path, run_of(STEP_OPS))
    ms = lambda sliding, bwd: (10 if bwd else 4) * 128 * 2 * 28 * (  # noqa: E731
        58_722_304 if sliding else 134_225_920) / 197e12 * 1e3
    least = 2 * ms(False, False) + ms(False, True) + 3 * ms(True, False) + 2 * ms(True, True)
    took = 27 + 66 + 15 + 15 + 16 + 36 + 38 + 29
    assert got == pytest.approx(100 * least / took) and 0 < got < 100
    # a program that runs no such kernel, no trace, or a family without the arithmetic: nothing
    assert readers.compute(path, run_of([[HEAD, 300 * MS, 50 * MS]])) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), trace=None)) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), config=M.load_config("gpt2-medium"))) is None


def route_span(t0, zero_share=None, held=60000.0, moved=4 * 73728.0):
    attrs = {"step": 10, "moe_load_max": 4000.0, "moe_load_mean": 3072.0, "moe_dropped": 0.0,
             "moe_rows_moved": moved, "moe_rows_held": held, "experts_held": 8, "router_site": "layer_input"}
    if zero_share is not None:
        attrs["moe_act_zero_share"] = zero_share
    return {"trace": "loop", "name": "moe.route", "t0": t0, "dur_s": 1e-5, "attrs": attrs}


def test_act_zero_share_is_the_median_of_the_route_spans_attribute():
    path = M.layer_metric_path("moe.act_zero_share")
    spans = [route_span(1.0, 0.50), route_span(2.0, 0.53), route_span(3.0, 0.61),
             {"trace": "loop", "name": "loop.log_sync", "t0": 1.0, "dur_s": 0.2, "attrs": {"step": 50}}]
    assert readers.compute(path, run_of([], spans)) == pytest.approx(0.53)
    # SiLU experts (Laguna, OLMoE), a dense model, the parent: no such attribute
    assert readers.compute(path, run_of([], [route_span(1.0)])) is None
    assert readers.compute(path, run_of([], spans[-1:])) is None and readers.compute(path, run_of([])) is None


def test_older_readers_read_this_cells_kernels_spans_and_loops():
    run = run_of(STEP_OPS, [route_span(1.0, 0.5), route_span(2.0, 0.5, held=70000.0)])
    assert readers.compute(M.layer_metric_path("attention.window_device_ms"), run) == pytest.approx(
        (15 + 15 + 16 + 36 + 38) / 2)
    assert readers.compute(M.layer_metric_path("attention.full_device_ms"), run) == pytest.approx((27 + 66 + 29) / 2)
    assert readers.compute(M.layer_metric_path("moe.dropped"), run) == 0.0
    assert readers.compute(M.layer_metric_path("moe.load_max_over_mean"), run) == pytest.approx(4000 / 3072)
    assert readers.compute(M.layer_metric_path("moe.rows_moved_over_held"), run) == pytest.approx(
        8 * 73728 / 130000)
    # the share's loops carry a vector over the S x k = 196,608 assignments (padded to 221,184: three chunks)
    fwd = ("%while.31 = (s32[]{:T(128)}, bf16[32768,2560]{1,0:T(8,128)(2,1)}, s32[]{:T(128)}, s32[]{:T(128)}, "
           "s32[221184]{0:T(1024)}, s32[221184]{0:T(1024)}) while(%tuple.7), condition=%c, body=%b")
    bwd = ("%while.39 = (s32[]{:T(128)}, bf16[32768,2560]{1,0:T(8,128)(2,1)}, f32[196608]{0:T(1024)}, "
           "bf16[8,2560,768]{2,1,0}) while(%tuple.9), condition=%c, body=%b")
    layers = ("%while.40 = (s32[]{:T(128)}, bf16[2,16384,2560]{2,1,0}, f32[64]{0}, s32[3,32768,6]{2,1,0}, "
              "f32[3,2560,64]{2,1,0}) while(%tuple.3), condition=%c, body=%b")  # the scan over the sliding layers
    sort_all = "%sort.3 = (s32[196608]{0}, s32[196608]{0}) sort(%keys, %iota), dimensions={0}"
    ops = [[sort_all, 400 * MS, 1 * MS], [layers, 405 * MS, 400 * MS], [fwd, 410 * MS, 20 * MS],
           [bwd, 600 * MS, 30 * MS], [fwd, 1500 * MS, 22 * MS]]
    assert readers.compute(M.layer_metric_path("moe.share_device_ms"), run_of(STEP_OPS + ops)) == pytest.approx(
        (1 + 20 + 30 + 22) / 2)


# -- the manifest ----------------------------------------------------------------------

NEW_METRICS = {"step.mfu_model": ("%", "higher", "device_trace"),
               "attention.roofline": ("%", "higher", "device_trace"),
               "moe.act_zero_share": ("ratio", "higher", "program_span")}
APPENDED = ("tok_s_chip", "loop.step_gap_ms", "step.device_ms", "device.idle_share", "device.peak_hbm_GB",
            "moe.load_max_over_mean", "moe.dropped", "moe.rows_moved_over_held", "moe.share_device_ms",
            "attention.window_device_ms", "attention.full_device_ms")
# Laguna's six (PR 33), and which of them read any such model's kernels, spans and loops
LAGUNA_METRICS = ("attention.window_device_ms", "attention.full_device_ms", "attention.window_roofline",
                  "step.mfu_held", "moe.rows_moved_over_held", "moe.share_device_ms")
LAGUNA_ONLY = ("attention.window_roofline", "step.mfu_held")  # tied to flops_laguna and Laguna's keys
OLMOE_METRICS = ("step.mfu_active", "moe.device_ms", "moe.gmm_roofline", "moe.load_max_over_mean", "moe.dropped")
SPAN_METRICS = ("moe.load_max_over_mean", "moe.dropped")


def test_manifest_holds_the_new_configuration_cell_and_metrics():
    M.check()
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("smallthinker-21b-a3b", "solo", 1)
    assert len(cell["why"]) <= 200 and "3,072 rows" in cell["why"] and "2 x 16,384" in cell["why"]
    assert "more than its share" in cell["why"]
    per_layer = {m["name"]: m for m in M.metrics_for(CELL, "per_layer")}
    for name, (unit, better, source) in NEW_METRICS.items():
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert m["layer"] == "compiled step" and m["moves"] == "tok_s_chip"
        assert m["workloads"] == [CELL]
    every = {m["name"]: m for kind in ("end_to_end", "per_layer") for m in M.doc[kind]}
    for name in APPENDED:
        assert every[name]["workloads"][-2:] == ["laguna-solo-8k", CELL], name
    # their readers count gpt2's, OLMoE's or Laguna's shape: this cell stays out of their lists
    for other in ("attention.device_ms", "step.mfu", "step.mfu_active", "moe.device_ms", "moe.gmm_roofline",
                  *LAGUNA_ONLY):
        assert other not in per_layer and CELL not in every[other]["workloads"], other
    assert {m["name"] for m in M.metrics_for(CELL, "end_to_end")} == {"tok_s_chip", "setup_s"}
    named = lambda names: [every[n] for n in names]  # noqa: E731
    assert M.doc["per_layer"][-3:] == named(NEW_METRICS)
    assert M.doc["workloads"][-1] is cell and M.doc["configs"][-1]["name"] == "smallthinker-21b-a3b"
    assert len(M.doc["workloads"]) == 6 and sum(w["chips"] == 4 for w in M.doc["workloads"]) == 1
    # what test_yardstick_laguna.py asserted of the manifest's tail and of Laguna's lists, one place up
    assert M.doc["per_layer"][-9:-3] == named(LAGUNA_METRICS)
    assert M.doc["per_layer"][-14:-9] == named(OLMOE_METRICS)
    assert M.doc["workloads"][-2]["name"] == "laguna-solo-8k" and M.doc["configs"][-2]["name"] == "laguna-xs2"
    assert M.doc["workloads"][-3]["name"] == "olmoe-solo" and M.doc["configs"][-3]["name"] == "olmoe-1b-7b"
    for m in named(LAGUNA_METRICS):
        assert m["workloads"] == ["laguna-solo-8k"] + [CELL] * (m["name"] not in LAGUNA_ONLY)
        assert m["layer"] == "compiled step" and m["moves"] == "tok_s_chip"
    for m in named(OLMOE_METRICS):
        assert m["workloads"] == ["olmoe-solo"] + ["laguna-solo-8k", CELL] * (m["name"] in SPAN_METRICS)
    laguna = {m["name"] for m in M.metrics_for("laguna-solo-8k", "per_layer")}
    assert not laguna & set(NEW_METRICS) and set(LAGUNA_METRICS) <= laguna


def test_reference_check_limits_are_written_with_their_readings():
    rc = CFG["reference_check"]
    assert rc["sequences"] == 1 and rc["seq_len"] == 16384
    assert 0 < rc["grad_rel_err"] <= 0.15 and 0 < rc["loss_atol"] <= 0.01
    for word in ("flipped", "e4m3", "bfloat16", "left out"):
        assert word in rc["why"], word
    for variant in ref.VARIANTS:
        assert variant in rc["left_out"], variant
    assert "PROVISIONAL" not in rc["why"] + rc["left_out"] + rc["size_why"]


# -- the rehearsal, end to end -----------------------------------------------------------


def test_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``tiny-rehearsal-smallthinker:solo`` through ``benchmark/run.py``:
    volunteer, probe, window, a traced run, the reference check, the result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "benchmark", "run.py"), "--rehearse",
         "tiny-rehearsal-smallthinker:solo", "--seed", "3500000019", "--seconds", "3", "--trace", "1"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert '"reference": true' in out.stderr and '"no_compile_in_window": true' in out.stderr
