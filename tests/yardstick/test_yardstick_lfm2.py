"""What PR 39 added to the yardstick: the LFM2 reference's own consistency,
the FLOP, pair and byte counts against hand sums, each new reader on a
hand-made trace or span list (and the older readers on this cell's kernel
names and loops), the manifest with the new entries (and what
``test_yardstick_lifecycle.py`` and ``test_yardstick_attention_metric.py``
asserted of the manifest's tail and lists, three metrics and one cell up: see
tests/conftest.py), and the rehearsal configuration through the runner's whole
path on the CPU."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import datagen, family_flops, flops, flops_lfm2, lfm2_trace, readers, references
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.references import lfm2 as ref
from benchmark.trace import Trace

M = Manifest(REPO_ROOT)
CFG = M.load_config("lfm2-24b-a2b")
TINY = M.load_config("tiny-rehearsal-lfm2")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "lfm2-solo-8k"
KINDS = ["conv", "full_attention", "conv", "conv", "conv"]


# -- the reference ---------------------------------------------------------------


def tiny_params(seed=3, scale=3.0, bias=0.05):
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(TINY["registry_model"], **TINY["model_overrides"])
    ref.check_config(bundle.config, TINY)
    params = bundle.init(jax.random.PRNGKey(seed))

    def scaled(path, x):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):
            return bias * jax.random.normal(jax.random.PRNGKey(11), x.shape)
        return x if "ln_" in name or "_norm" in name else x * scale

    return bundle, jax.tree_util.tree_map_with_path(scaled, params), datagen.lm_arrays(5, 2, 24, TINY["vocab_size"])


def test_reference_gradient_agrees_with_finite_differences():
    """Its ``jax.grad`` against central differences of its own loss, along a
    seeded direction in every leaf, the routes held at those of the unmoved
    parameters (the top-k is piecewise constant). The selection bias's leaf is
    zeros on both sides: it moves the choice, and the choice is held."""
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
    zeros = 0
    for i, (leaf, g) in enumerate(zip(leaves, g_leaves)):
        direction = rng.standard_normal(leaf.shape).astype(np.float32)
        direction /= np.linalg.norm(direction)
        eps = 1e-3 * float(jnp.linalg.norm(leaf))
        moved = lambda s: jax.tree_util.tree_unflatten(  # noqa: E731
            treedef, leaves[:i] + [leaf + s * eps * direction] + leaves[i + 1:])
        fd = (float(loss(moved(1.0))) - float(loss(moved(-1.0)))) / (2 * eps)
        want = float(jnp.sum(g * direction))
        assert fd == pytest.approx(want, rel=0.05, abs=6e-4), (i, fd, want)  # float32 differences of a loss near 6
        zeros += not np.any(np.asarray(g))
    assert zeros == 2  # the two runs' selection biases, and no other leaf


def test_reference_attention_is_a_causal_softmax_one_head_at_a_time(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(ref, "ATTN_BLOCK", 4)  # three blocks of queries
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 12, 8))
    k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 12, 8)) for i in (1, 2))
    got = ref._attention(q, k, v)
    for h in (0, 3, 4, 7):  # four query heads read one key/value head
        for i in range(12):
            s = jnp.stack([q[0, h, i] @ k[0, h // 4, j] for j in range(i + 1)]) / np.sqrt(8)
            want = jax.nn.softmax(s) @ jnp.stack([v[0, h // 4, j] for j in range(i + 1)])
            np.testing.assert_allclose(np.asarray(got[0, h, i]), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_reference_convolution_is_three_shifted_copies_with_zeros_before_the_start():
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    d = 6
    p = {"w_in": jax.random.normal(ks[0], (d, 3 * d)), "taps": jax.random.normal(ks[1], (3, d)),
         "w_out": jnp.eye(d)}
    n = jax.random.normal(ks[2], (1, 9, d))
    got = np.asarray(ref._conv_mixer(p, n, None))
    b_, c_, u_ = np.split(np.asarray(n @ p["w_in"]), 3, axis=-1)
    g, taps = b_ * u_, np.asarray(p["taps"])
    for t in range(9):
        conv = sum(taps[j] * g[0, t - (2 - j)] for j in range(3) if t - (2 - j) >= 0)
        np.testing.assert_allclose(got[0, t], c_[0, t] * conv, rtol=1e-5, atol=1e-6)
    assert np.array_equal(np.asarray(ref._shift(n, 2))[0, :2], np.zeros((2, d)))
    assert np.array_equal(np.asarray(ref._shift(n, -1))[0, :-1], np.asarray(n)[0, 1:])
    for variant in ("conv_taps_reversed", "gates_swapped", "conv_not_causal"):
        assert not np.allclose(np.asarray(ref._conv_mixer(p, n, variant)), got, atol=1e-3), variant


def test_reference_sizes_and_config_check():
    from distributedvolunteercomputing_tpu.models import get_model

    assert ref.sizes(CFG) == {"n_layer": 5, "d_model": 2048, "seq_len": 8192, "vocab": 8192}
    assert ref.layer_types(CFG) == KINDS and len(ref.layer_types(dict(CFG, layers_run=range(40)))) == 40
    assert ref.hyper(CFG) == {"heads": 32, "n_kv": 8, "head_dim": 64, "theta": 1e6, "eps": 1e-5, "top_k": 4,
                              "offset": 0, "scale": 1.0}
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    ref.check_config(bundle.config, CFG)
    for key, change in (("num_experts_per_tok", 2), ("num_experts", 16), ("moe_intermediate_size", 512),
                        ("intermediate_size", 8192), ("conv_L_cache", 4), ("expert_offset", 8),
                        ("norm_topk_prob", False), ("use_expert_bias", False), ("conv_bias", True),
                        ("num_dense_layers", 2), ("norm_eps", 1e-6), ("num_hidden_layers", 40)):
        with pytest.raises(ValueError, match=key):
            ref.check_config(bundle.config, dict(CFG, **{key: change}))
    with pytest.raises(ValueError, match="layer_types"):
        ref.check_config(bundle.config, dict(CFG, layers_run=[0, 1, 2, 3, 4]))
    with pytest.raises(ValueError, match="experts_held"):
        ref.check_config(get_model(CFG["registry_model"]).config, CFG)  # the published model, uncut
    with pytest.raises(ValueError, match="rope_theta"):
        ref.check_config(bundle.config, dict(CFG, rope_parameters={"rope_theta": 10000, "rope_type": "default"}))
    with pytest.raises(ValueError, match="bias_gamma"):
        ref.check_config(dataclasses.replace(bundle.config, bias_gamma=0.01), CFG)
    assumed = CFG["assumed"]
    with pytest.raises(ValueError, match="ties"):
        ref.check_config(bundle.config, dict(CFG, assumed={**assumed, "tie_word_embeddings": {"value": False}}))
    with pytest.raises(ValueError, match="auxiliary"):
        ref.check_config(bundle.config, dict(CFG, assumed={**assumed, "aux_coefficients": {"load_balancing": 0.01}}))


def test_configuration_file_is_what_the_program_runs_with_its_cut_listed():
    """``test_yardstick_manifest.py``'s check of a configuration, for one whose
    ``reduced`` is not empty (tests/conftest.py marks that case), and every
    number of the catalog row under its own key."""
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    entry = M.config_entry("lfm2-24b-a2b")
    assert CFG["source"] == entry["source"]
    assert CFG["reduced"] == entry["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    assert CFG["published"] == {"num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64, "vocab_size": 65536}
    assert [CFG[k] for k in CFG["reduced"]] == [5, 1, 8, 8192]
    assert CFG["layers_run"] == [0, 2, 3, 4, 5] and len(CFG["layer_types"]) == 40
    assert CFG["model_overrides"]["layer_types"] == KINDS
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    references.load(CFG["family"]).check_config(bundle.config, CFG)
    assert set(CFG["volunteer"]) <= {f.name for f in dataclasses.fields(VolunteerConfig)}
    assert CFG["volunteer"]["batch_size"] == 4
    # the window lies inside an LR warm-up, stated with its readings: without one the router outruns the bias rule
    assert CFG["volunteer"]["warmup_steps"] == CFG["assumed"]["lr_warmup"]["warmup_steps"] == 2000
    assert TINY["volunteer"]["warmup_steps"] == 2000
    assert "eight chips share each layer" in CFG["deployment"]
    assert "eight chips share each layer" in CFG["reduced_why"]["num_experts"]
    for key in ("tie_word_embeddings", "expert_bias", "aux_coefficients", "router", "head_dim", "conv", "seq_len",
                "batch_size", "optimizer", "lr_warmup", "dtypes", "initialisation"):
        assert key in CFG["assumed"], key
    assert CFG["assumed"]["expert_bias"]["gamma"] == 0.001 and "2408.15664" in CFG["assumed"]["expert_bias"]["why"]
    assert CFG["assumed"]["aux_coefficients"]["load_balancing"] == 0
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "LFM2-24B-A2B")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key  # every width, the list of layer kinds and the rotary group whole


# -- FLOPs, pairs and bytes ----------------------------------------------------------


def test_flop_pair_and_byte_counts_against_a_hand_sum():
    t, d, hd, f, ff, v = 8192, 2048, 64, 1536, 11776, 8192
    conv = d * 3 * d + d * d
    attn = 2 * d * 32 * hd + 2 * d * 8 * hd
    assert flops_lfm2.mixer_matrix_params(CFG, "conv") == conv == 16_777_216
    assert flops_lfm2.mixer_matrix_params(CFG, "full_attention") == attn == 10_485_760
    dense_layer = conv + 3 * d + 2 * d + 3 * d * ff
    attn_layer = attn + 2 * hd + 2 * d + d * 64 + 64 + 8 * 3 * d * f
    conv_layer = conv + 3 * d + 2 * d + d * 64 + 64 + 8 * 3 * d * f
    assert (dense_layer, attn_layer, conv_layer) == (89_139_200, 86_118_592, 92_416_064)
    assert flops_lfm2.total_params(CFG) == dense_layer + attn_layer + 3 * conv_layer + v * d + d == 469_285_248
    # experts at their expected rows: 4 x 8 / 64 = half an expert a token
    active = (conv + 3 * d * ff) + (attn + d * 64 + 0.5 * 3 * d * f) + 3 * (conv + d * 64 + 0.5 * 3 * d * f) + d * v
    assert flops_lfm2.active_params(CFG) == active == 186_122_240
    pairs = 32 * (t * (t + 1) // 2)
    assert flops_lfm2.attention_pair_heads(CFG, t) == pairs == 32 * 33_558_528
    assert flops_lfm2.train_flops_per_token(CFG, t) == 6 * active + 12 * hd * pairs / t
    # one call of the attention kernel, batch 4; the model has no windowed layer
    assert flops_lfm2.kernel_flops(CFG, t, 4, False, False) == 4 * hd * 4 * 32 * 33_558_528
    assert flops_lfm2.kernel_flops(CFG, t, 4, False, True) == 10 * hd * 4 * 32 * 33_558_528
    assert flops_lfm2.kernel_flops(CFG, t, 4, True, False) == 0 == flops_lfm2.kernel_bytes(CFG, t, 4, True, True)
    rows = 4 * t * hd * 2
    assert flops_lfm2.kernel_bytes(CFG, t, 4, False, False) == rows * (2 * 32 + 2 * 8)
    assert flops_lfm2.kernel_bytes(CFG, t, 4, False, True) == rows * (5 * 32 + 2 * 8)
    least = flops_lfm2.kernel_least_seconds(CFG, t, 4, False, True, 197e12, 819e9)
    assert least == pytest.approx(10 * hd * 4 * 32 * 33_558_528 / 197e12) == pytest.approx(13.96e-3, rel=1e-3)
    # the convolution: the streams once each way at bf16, the float32 taps beside them
    positions = 4 * t * d * 2
    assert flops_lfm2.short_conv_bytes(CFG, 4, t, False) == 4 * positions + 3 * d * 4 == 536_895_488
    assert flops_lfm2.short_conv_bytes(CFG, 4, t, True) == 7 * positions + 2 * 3 * d * 4 == 939_573_248
    assert flops_lfm2.hbm_bytes_per_s(flops.PEAKS["TPU v5 lite"]) == 819e9
    # the issue's arithmetic, forward matrix FLOPs a step (T = 10^12)
    s = 4 * t
    assert 2 * s * 4 * conv / 1e12 == pytest.approx(4.4, abs=0.05)          # the four conv mixers' projections
    assert 2 * s * 3 * d * ff / 1e12 == pytest.approx(4.7, abs=0.05)        # the dense FFN
    assert 2 * s * 4 * 0.5 * 3 * d * f / 1e12 == pytest.approx(1.2, abs=0.05)  # the experts' rows
    assert 2 * s * attn / 1e12 == pytest.approx(0.7, abs=0.05)              # attention's projections
    assert 4 * hd * 4 * pairs / 1e12 == pytest.approx(1.1, abs=0.05)        # its one layer's pairs
    assert 2 * s * d * v / 1e12 == pytest.approx(1.1, abs=0.05)             # the head


def test_the_program_holds_as_many_parameters_as_the_count_says():
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    assert n == flops_lfm2.total_params(CFG) == CFG["parameters"]["counted_by_the_program"]
    full = {k: v for k, v in dict(CFG, **CFG["published"]).items() if k != "layers_run"}
    assert flops_lfm2.total_params(full) == 23_843_661_440  # the published model: 24 B
    # at work on a token in the published model: 2.3 B ("A2B")
    assert round(flops_lfm2.active_params(full) / 1e9, 1) == 2.3


def test_family_flops_finds_a_configurations_arithmetic_by_its_family():
    assert family_flops.load(CFG) is flops_lfm2 and family_flops.load(TINY) is flops_lfm2
    assert family_flops.load(M.load_config("smallthinker-21b-a3b")).__name__ == "benchmark.flops_smallthinker"


# -- the readers -----------------------------------------------------------------------

CONV_FWD = "%dvc_short_conv_fwd.5 = bf16[4,8192,2048]{2,1,0} custom-call(%dot.3, %dot.3, %convert.9)"
CONV_BWD = "%dvc_short_conv_bwd.2 = (bf16[4,8192,6144]{2,1,0}, f32[3,2048]{1,0}) custom-call(%dot.7)"
FULL_FWD = "%dvc_flash_fwd.7 = (bf16[4,32,8192,64]{3,2,1,0}) custom-call(%q)"
FULL_BWD = "%dvc_flash_bwd.2 = (bf16[4,32,8192,64]{3,2,1,0}) custom-call(%q)"
HEAD = "%select_add_fusion.2 = f32[8192,2048]{1,0:T(8,128)} fusion(%x)"
MS = 1_000_000
US = 1_000


def make_trace(ops):
    return Trace.from_json({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_step(7)", 1_000_000, 700_000_000],
                ["jit_step(7)", 702_000_000, 700_000_000],
                ["jit_step(7)", 1_403_000_000, 1_200_000_000],   # ends after the window
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
    [CONV_FWD, 2 * MS, 800 * US], [FULL_FWD, 10 * MS, 8 * MS], [CONV_FWD, 30 * MS, 900 * US],
    [FULL_BWD, 300 * MS, 20 * MS], [CONV_FWD, 330 * MS, 850 * US], [CONV_BWD, 340 * MS, 1500 * US],
    [HEAD, 400 * MS, 50 * MS],
    [CONV_BWD, 1000 * MS, 1700 * US], [FULL_FWD, 1100 * MS, 9 * MS],
    [CONV_BWD, 2000 * MS, 99 * MS],                             # in the step the window cuts
]


def test_conv_device_ms_is_the_kernels_time_a_whole_step():
    path = M.layer_metric_path("conv.device_ms")
    assert readers.compute(path, run_of(STEP_OPS)) == pytest.approx((0.8 + 0.9 + 0.85 + 1.5 + 1.7) / 2)
    found = lfm2_trace.kernel_events(run_of(STEP_OPS))
    assert found[0] == 2 and [bwd for bwd, _ in found[1]] == [False, False, False, True, True]
    # a program that runs no such kernel (every other model, the parent), or no trace: nothing, and no error
    assert readers.compute(path, run_of([[HEAD, 300 * MS, 50 * MS], [FULL_FWD, 10 * MS, 8 * MS]])) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), trace=None)) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), step_program=r"^jit_other\(")) is None


def test_conv_roofline_is_the_bytes_time_over_the_time_taken_and_cannot_pass_100():
    path = M.layer_metric_path("conv.roofline")
    fwd_s, bwd_s = 536_895_488 / 819e9, 939_573_248 / 819e9
    got = readers.compute(path, run_of(STEP_OPS))
    took = (0.8 + 0.9 + 0.85 + 1.5 + 1.7) / 1e3
    assert got == pytest.approx(100 * (3 * fwd_s + 2 * bwd_s) / took) and 0 < got < 100
    # a call that takes the bytes' time reads 100, a slower one less: the counted bytes are the least it moves
    at = lambda us_f, us_b: readers.compute(path, run_of(  # noqa: E731
        [[CONV_FWD, 2 * MS, us_f * US], [CONV_BWD, 5 * MS, us_b * US]]))
    assert at(fwd_s * 1e6, bwd_s * 1e6) == pytest.approx(100.0)
    assert at(fwd_s * 1e6 * 1.25, bwd_s * 1e6 * 1.25) == pytest.approx(80.0)
    assert at(fwd_s * 1e6 * 2, bwd_s * 1e6) < 100
    # nothing to read: no such kernel, no trace, no peak, a family without the arithmetic
    assert readers.compute(path, run_of([[HEAD, 300 * MS, 50 * MS]])) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), trace=None)) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), peak=None)) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), config=M.load_config("smallthinker-21b-a3b"))) is None
    assert readers.compute(path, dict(run_of(STEP_OPS), config=M.load_config("gpt2-medium"))) is None


def route_span(t0, bias=None, held=60000.0, moved=4 * 49152.0):
    attrs = {"step": 10, "moe_load_max": 2500.0, "moe_load_mean": 2048.0, "moe_dropped": 0.0,
             "moe_rows_moved": moved, "moe_rows_held": held, "experts_held": 8, "router_site": "post_attention",
             "mixers_conv": 4, "mixers_full_attention": 1}
    if bias is not None:
        attrs.update(moe_bias_max=bias[0], moe_bias_min=bias[1], moe_bias_moved=250.0)
    return {"trace": "loop", "name": "moe.route", "t0": t0, "dur_s": 1e-5, "attrs": attrs}


def test_bias_spread_is_the_last_route_spans_max_minus_min():
    path = M.layer_metric_path("moe.bias_spread")
    spans = [route_span(1.0, (0.004, -0.003)), route_span(3.0, (0.021, -0.017)), route_span(2.0, (0.010, -0.009)),
             {"trace": "loop", "name": "loop.log_sync", "t0": 9.0, "dur_s": 0.2, "attrs": {"step": 50}}]
    assert readers.compute(path, run_of([], spans)) == pytest.approx(0.038)  # the latest by its start, not by its place
    # a router without a selection bias (Laguna, SmallThinker, OLMoE), a dense model, the parent: nothing
    assert readers.compute(path, run_of([], [route_span(1.0)])) is None
    assert readers.compute(path, run_of([], spans[-1:])) is None and readers.compute(path, run_of([])) is None


def test_older_readers_read_this_cells_kernels_spans_and_loops():
    run = run_of(STEP_OPS, [route_span(1.0, (0.001, -0.001)), route_span(2.0, (0.002, -0.002), held=70000.0)])
    assert readers.compute(M.layer_metric_path("attention.device_ms"), run) == pytest.approx((8 + 20 + 9) / 2)
    # no windowed kernel in this model: the readers that are Laguna's and SmallThinker's give nothing
    assert readers.compute(M.layer_metric_path("attention.full_device_ms"), run) is None
    assert readers.compute(M.layer_metric_path("attention.window_device_ms"), run) is None
    ms = lambda bwd: (10 if bwd else 4) * 64 * 4 * 32 * 33_558_528 / 197e12 * 1e3  # noqa: E731
    assert readers.compute(M.layer_metric_path("attention.roofline"), run) == pytest.approx(
        100 * (2 * ms(False) + ms(True)) / (8 + 20 + 9))
    per_token = flops_lfm2.train_flops_per_token(CFG, 8192)
    assert readers.compute(M.layer_metric_path("step.mfu_model"), run) == pytest.approx(
        100 * 32768 * per_token / (0.7 * 197e12))
    assert readers.compute(M.layer_metric_path("moe.dropped"), run) == 0.0
    assert readers.compute(M.layer_metric_path("moe.load_max_over_mean"), run) == pytest.approx(2500 / 2048)
    assert readers.compute(M.layer_metric_path("moe.rows_moved_over_held"), run) == pytest.approx(
        8 * 49152 / 130000)
    # the share's loops carry a vector over the S x k = 131,072 assignments (padded to 147,456: three chunks)
    fwd = ("%while.31 = (s32[]{:T(128)}, bf16[32768,2048]{1,0:T(8,128)(2,1)}, s32[]{:T(128)}, s32[]{:T(128)}, "
           "s32[147456]{0:T(1024)}, s32[147456]{0:T(1024)}) while(%tuple.7), condition=%c, body=%b")
    bwd = ("%while.39 = (s32[]{:T(128)}, bf16[32768,2048]{1,0:T(8,128)(2,1)}, f32[131072]{0:T(1024)}, "
           "bf16[8,2048,1536]{2,1,0}) while(%tuple.9), condition=%c, body=%b")
    layers = ("%while.40 = (s32[]{:T(128)}, bf16[4,8192,2048]{2,1,0}, f32[64]{0}, s32[3,32768,4]{2,1,0}, "
              "f32[3,2048,64]{2,1,0}) while(%tuple.3), condition=%c, body=%b")  # the scan over the conv expert layers
    sort_all = "%sort.3 = (s32[131072]{0}, s32[131072]{0}) sort(%keys, %iota), dimensions={0}"
    ops = [[sort_all, 400 * MS, 1 * MS], [layers, 405 * MS, 250 * MS], [fwd, 410 * MS, 20 * MS],
           [bwd, 500 * MS, 30 * MS], [fwd, 1200 * MS, 22 * MS]]
    assert readers.compute(M.layer_metric_path("moe.share_device_ms"), run_of(STEP_OPS + ops)) == pytest.approx(
        (1 + 20 + 30 + 22) / 2)


# -- the manifest ----------------------------------------------------------------------

NEW_METRICS = {"conv.device_ms": ("ms", "lower", "device_trace"),
               "conv.roofline": ("%", "higher", "device_trace"),
               "moe.bias_spread": ("bias", "lower", "program_span")}
APPENDED = ("tok_s_chip", "loop.step_gap_ms", "step.device_ms", "device.idle_share", "device.peak_hbm_GB",
            "moe.load_max_over_mean", "moe.dropped", "moe.rows_moved_over_held", "moe.share_device_ms",
            "attention.device_ms", "step.mfu_model", "attention.roofline")
LIFECYCLE = {"lifecycle.ready_s": "program_span", "lifecycle.net_s": "program_span",
             "lifecycle.init_s": "program_span", "lifecycle.step_build_s": "program_span",
             "lifecycle.first_step_s": "program_span", "lifecycle.trace_lower_s": "program_counter",
             "lifecycle.cache_load_s": "program_counter"}
SMALLTHINKER_METRICS = ("step.mfu_model", "attention.roofline", "moe.act_zero_share")
LAGUNA_METRICS = ("attention.window_device_ms", "attention.full_device_ms", "attention.window_roofline",
                  "step.mfu_held", "moe.rows_moved_over_held", "moe.share_device_ms")
OLMOE_METRICS = ("step.mfu_active", "moe.device_ms", "moe.gmm_roofline", "moe.load_max_over_mean", "moe.dropped")
OLD_CELLS = ["medium-solo", "medium-round", "large-solo-4chip", "olmoe-solo", "laguna-solo-8k", "smallthinker-solo-16k"]
# readers that find nothing in this cell's runs: a windowed kernel, Laguna's or OLMoE's keys, a ReLU's zeros
NOT_THIS_CELLS = ("attention.window_device_ms", "attention.full_device_ms", "attention.window_roofline",
                  "step.mfu", "step.mfu_active", "step.mfu_held", "moe.device_ms", "moe.gmm_roofline",
                  "moe.act_zero_share")


def test_manifest_holds_the_new_configuration_cell_and_metrics():
    M.check()
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lfm2-24b-a2b", "solo", 1)
    assert len(cell["why"]) <= 200 and "2,048 rows" in cell["why"] and "4 x 8,192" in cell["why"]
    assert "short convolutions" in cell["why"] and "see more" in cell["why"] and "1 in 20" in cell["why"]
    per_layer = {m["name"]: m for m in M.metrics_for(CELL, "per_layer")}
    every = {m["name"]: m for kind in ("end_to_end", "per_layer") for m in M.doc[kind]}
    named = lambda names: [every[n] for n in names]  # noqa: E731
    for name, (unit, better, source) in NEW_METRICS.items():
        m = per_layer[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert m["layer"] == "compiled step" and m["moves"] == "tok_s_chip" and m["workloads"] == [CELL]
        path = M.layer_metric_path(name)
        assert path.endswith(".py") and "def compute(run)" in open(path).read()
    assert M.doc["per_layer"][-3:] == named(NEW_METRICS)
    for name in APPENDED + tuple(LIFECYCLE):
        assert every[name]["workloads"][-1] == CELL and every[name]["workloads"].count(CELL) == 1, name
    for other in NOT_THIS_CELLS:
        assert other not in per_layer and CELL not in every[other]["workloads"], other
    assert {m["name"] for m in M.metrics_for(CELL, "end_to_end")} == {"tok_s_chip", "setup_s"}
    # one share of the whole step's peak, and it is the accepted one
    assert [n for n in per_layer if "mfu" in n] == ["step.mfu_model"]
    assert M.doc["workloads"][-1] is cell and M.doc["configs"][-1]["name"] == "lfm2-24b-a2b"
    assert [w["name"] for w in M.doc["workloads"]] == OLD_CELLS + [CELL] and len(M.doc["configs"]) == 6
    assert sum(w["chips"] == 4 for w in M.doc["workloads"]) == 1
    entry = M.config_entry("lfm2-24b-a2b")
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/lfm2-24b-a2b.json" and "469.3 M" in entry["why"]


def test_manifest_tail_as_the_lifecycle_tests_asserted_it_three_metrics_and_a_cell_up():
    """What ``test_yardstick_lifecycle.py`` asserted of the manifest's end and
    ``test_yardstick_attention_metric.py`` of its metric's list, with this
    PR's three metrics, cell and configuration after them (tests/conftest.py
    marks those cases)."""
    every = {m["name"]: m for kind in ("end_to_end", "per_layer") for m in M.doc[kind]}
    named = lambda names: [every[n] for n in names]  # noqa: E731
    assert M.doc["per_layer"][-10:-3] == named(LIFECYCLE)
    for name, source in LIFECYCLE.items():
        m = every[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"]) == ("s", "lower", source)
        assert m["layer"] == "entry / lifecycle" and m["moves"] == "setup_s"
        assert m["workloads"] == OLD_CELLS + [CELL]
    assert [m["name"] for m in M.doc["per_layer"][:3]] == [
        "lifecycle.compile_s", "lifecycle.cache_misses", "lifecycle.backend_init_s"]
    assert all("workloads" not in m for m in M.doc["per_layer"][:3])
    assert every["setup_s"] == {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
                                "source": "host_clock"}
    for cell in OLD_CELLS + [CELL]:
        assert set(LIFECYCLE) <= {m["name"] for m in M.metrics_for(cell, "per_layer")}
    # SmallThinker's, Laguna's and OLMoE's metrics, where they were, ten places from the end
    assert M.doc["per_layer"][-13:-10] == named(SMALLTHINKER_METRICS)
    assert M.doc["per_layer"][-19:-13] == named(LAGUNA_METRICS)
    assert M.doc["per_layer"][-24:-19] == named(OLMOE_METRICS)
    small = "smallthinker-solo-16k"
    assert every["moe.act_zero_share"]["workloads"] == [small]
    for name in ("step.mfu_model", "attention.roofline"):
        assert every[name]["workloads"] == [small, CELL]
    for name in ("moe.rows_moved_over_held", "moe.share_device_ms"):
        assert every[name]["workloads"] == ["laguna-solo-8k", small, CELL]
    for name in ("attention.window_device_ms", "attention.full_device_ms"):
        assert every[name]["workloads"] == ["laguna-solo-8k", small]
    for name in ("moe.load_max_over_mean", "moe.dropped"):
        assert every[name]["workloads"] == ["olmoe-solo", "laguna-solo-8k", small, CELL]
    assert M.doc["workloads"][-2]["name"] == small and M.doc["configs"][-2]["name"] == "smallthinker-21b-a3b"
    assert M.doc["workloads"][-3]["name"] == "laguna-solo-8k" and M.doc["configs"][-3]["name"] == "laguna-xs2"
    # attention.device_ms: the gpt2 cells, and this one, whose only kernels are the full-causal ones it reads
    entry = every["attention.device_ms"]
    assert entry["layer"] == "compiled step" and entry["moves"] == "tok_s_chip"
    assert entry["workloads"] == ["medium-solo", "large-solo-4chip", CELL]


def test_reference_check_limits_are_written_with_their_readings():
    rc = CFG["reference_check"]
    assert rc["sequences"] == 1 and rc["seq_len"] == 8192
    assert 0 < rc["grad_rel_err"] <= 0.15 and 0 < rc["loss_atol"] <= 0.01
    for word in ("flipped", "e4m3", "bfloat16", "left out"):
        assert word in rc["why"], word
    for variant in ref.VARIANTS:
        assert variant in rc["left_out"], variant
    assert "TO BE SET" not in rc["why"] + rc["left_out"] + rc["size_why"]
    assert CFG["loss_band"]["last_minus_first_max"] == 0.5


# -- the rehearsal, end to end -----------------------------------------------------------


def test_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``tiny-rehearsal-lfm2:solo`` through ``benchmark/run.py``: volunteer,
    probe, window, a traced run, the reference check, the result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "benchmark", "run.py"), "--rehearse",
         "tiny-rehearsal-lfm2:solo", "--seed", "3900000019", "--seconds", "3", "--trace", "1"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert '"reference": true' in out.stderr and '"no_compile_in_window": true' in out.stderr
