"""What PR 67 added to the yardstick: the Qwen3-Next reference's own consistency
(its delta rule against a loop over tokens, its partial rotary, which key head a
value head reads), the configuration file against the program and the catalog,
the parameter sum and the FLOPs and bytes against hand sums, the three ``gdn.*``
readers on hand-made trace events and spans, the manifest with the new entries
asserted BY NAME (the next PR's entries move nothing here), and what Ouro's
yardstick tests asserted of the manifest's tail, run as they stand against the
manifest less this PR's entries (see tests/conftest.py)."""

import dataclasses
import importlib
import json
import os

import numpy as np
import pytest

from benchmark import family_flops, flops, flops_qwen3_next as fl, gdn_trace, kda_trace, readers, references
from benchmark.manifest import REPO_ROOT, Manifest, ManifestError
from benchmark.references import qwen3_next as ref
from benchmark.trace import Trace

M = Manifest(REPO_ROOT)
NAME, CELL = "qwen3-next-80b-a3b", "qwen3-next-solo-8k"
CFG = M.load_config(NAME)
TINY = M.load_config("tiny-rehearsal-qwen3-next")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"gdn.device_ms": ("ms", "lower", "device_trace"), "gdn.roofline": ("%", "higher", "device_trace"),
               "gdn.carry_share": ("ratio", "higher", "program_span")}
# the lists the cell's name was appended to (ISSUE 67, Tentpole 7)
APPENDED = (
    "loop.step_gap_ms", "step.device_ms", "step.mfu_model", "device.idle_share", "device.peak_hbm_GB",
    "attention.device_ms", "attention.roofline", "conv.device_ms", "conv.roofline", "moe.load_max_over_mean",
    "moe.dropped", "moe.rows_moved_over_held", "moe.share_device_ms", "moe.chunks_extra", "scope.attention_ms",
    "scope.mixer_ms", "scope.moe_ms", "scope.loss_head_ms", "scope.optimizer_ms", "scope.other_ms",
    "scope.recompute_share", "scope.unresolved_share", "lifecycle.ready_s", "lifecycle.net_s", "lifecycle.init_s",
    "lifecycle.step_build_s", "lifecycle.first_step_s", "lifecycle.trace_lower_s", "lifecycle.cache_load_s")


# -- the reference ---------------------------------------------------------------


def test_reference_delta_rule_is_the_steps_a_token_under_one_decay_a_head():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    z, t, h, dk, dv = 1, 7, 2, 3, 4
    q, k = rng.normal(size=(z, t, h, dk)), rng.normal(size=(z, t, h, dk))
    v, g, beta = rng.normal(size=(z, t, h, dv)), -rng.uniform(0.1, 1.0, (z, t, h)), rng.uniform(0.1, 0.9, (z, t, h))
    want = np.zeros((z, t, h, dv))
    for head in range(h):
        s = np.zeros((dk, dv))
        for i in range(t):
            s = np.exp(g[0, i, head]) * s
            u = beta[0, i, head] * (v[0, i, head] - s.T @ k[0, i, head])
            s = s + np.outer(k[0, i, head], u)
            want[0, i, head] = s.T @ q[0, i, head]
    with jax.default_matmul_precision("highest"):
        got = ref._delta_rule(*(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)), 0, None)
        reset = ref._delta_rule(*(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)), 4, None)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(reset[:, :4]), want[:, :4], rtol=2e-5, atol=2e-6)
    assert np.abs(np.asarray(reset[:, 4:]) - want[:, 4:]).max() > 1e-2      # a state set to zero at token 4


def test_reference_rotates_the_first_quarter_of_a_head_in_half_split_pairs():
    import jax.numpy as jnp

    x = np.random.default_rng(1).normal(size=(1, 5, 16)).astype(np.float32)
    got = np.asarray(ref._rotate(jnp.asarray(x), 4, 1e7))
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])                  # twelve of sixteen untouched
    np.testing.assert_array_equal(got[:, 0], x[:, 0])                        # position 0 turns nothing
    for pos in range(5):
        for i in range(2):                                                   # pairs (i, i + 2), angle pos x theta^(-2i/4)
            angle = pos * 1e7 ** (-2 * i / 4)
            a, b = x[0, pos, i], x[0, pos, i + 2]
            np.testing.assert_allclose(got[0, pos, [i, i + 2]],
                                       [a * np.cos(angle) - b * np.sin(angle), b * np.cos(angle) + a * np.sin(angle)],
                                       rtol=1e-4, atol=1e-5)


def test_reference_sizes_and_config_check():
    from distributedvolunteercomputing_tpu.models import get_model

    assert ref.sizes(CFG) == {"n_layer": 4, "d_model": 2048, "seq_len": 8192, "vocab": 18992}
    assert ref.hyper(CFG) == {"heads": 16, "kv_heads": 2, "head_dim": 256, "rotary_dim": 64, "theta": 1e7,
                              "key_heads": 16, "value_heads": 32, "key_dim": 128, "value_dim": 128, "taps": 4,
                              "chunk": 64, "eps": 1e-6, "top_k": 10, "offset": 0, "aux_coef": 0.001}
    assert len(ref.VARIANTS) == 16 and len(set(ref.VARIANTS)) == 16
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    ref.check_config(bundle.config, CFG)
    for key, change in (("num_experts_per_tok", 8), ("num_experts", 32), ("moe_intermediate_size", 768),
                        ("shared_expert_intermediate_size", 1024), ("head_dim", 128), ("num_attention_heads", 32),
                        ("num_key_value_heads", 4), ("partial_rotary_factor", 0.5), ("rope_theta", 1000000),
                        ("linear_num_key_heads", 32), ("linear_num_value_heads", 16), ("linear_key_head_dim", 64),
                        ("linear_value_head_dim", 64), ("linear_conv_kernel_dim", 3), ("full_attention_interval", 2),
                        ("expert_offset", 16), ("norm_topk_prob", False), ("decoder_sparse_step", 2),
                        ("mlp_only_layers", [0]), ("hidden_act", "gelu"), ("rms_norm_eps", 1e-5),
                        ("num_hidden_layers", 8), ("tie_word_embeddings", True), ("vocab_size", 151936)):
        with pytest.raises(ValueError, match=key):
            ref.check_config(bundle.config, dict(CFG, **{key: change}))
    with pytest.raises(ValueError, match="n_layers"):
        ref.check_config(get_model(CFG["registry_model"]).config, CFG)  # the published model, uncut
    assumed = CFG["assumed"]
    for key, value, word in (("chunk", {"value": 128}, "chunk"), ("aux_coefficients", {"load_balancing": 0.01}, "aux_coef"),
                             ("gdn_init", {"value": "normal(0, 0.02)"}, "initialisation"),
                             ("key_head_of_value_head", {"value": "j % 16"}, "key head"),
                             ("seq_len", {"value": 4096}, "max_len")):
        with pytest.raises(ValueError, match=word):
            ref.check_config(bundle.config, dict(CFG, assumed={**assumed, key: value}))


def test_configuration_file_is_what_the_program_runs_with_its_cut_listed():
    """``test_yardstick_manifest.py``'s check of a configuration, for one whose
    ``reduced`` is not empty (tests/conftest.py marks that case), and every
    number of the catalog row under its own key."""
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    entry = M.config_entry(NAME)
    assert CFG["source"] == entry["source"] and entry["file"] == f"benchmark/configs/{NAME}.json"
    assert CFG["reduced"] == entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert set(CFG["reduced_why"]) == set(CFG["reduced"]) == set(CFG["published"])
    assert CFG["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert (CFG["num_hidden_layers"], CFG["num_experts"], CFG["vocab_size"], CFG["expert_offset"]) == (4, 16, 18992, 0)
    assert (CFG["family"], CFG["registry_model"]) == ("qwen3_next", "qwen3_next_80b_a3b")
    assert CFG["model_overrides"] == {"n_layers": 4, "experts_held": 16, "expert_offset": 0, "vocab": 18992}
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    references.load(CFG["family"]).check_config(bundle.config, CFG)
    assert set(CFG["volunteer"]) <= {f.name for f in dataclasses.fields(VolunteerConfig)}
    assert CFG["volunteer"] == {"batch_size": 2, "optimizer": "adam", "lr": 0.001, "steps": 1000000,
                                "warmup_steps": 2000, "mesh": ""}
    assert "thirty-two chips share each layer" in CFG["deployment"]
    for key in ("norms", "projection_order", "key_head_of_value_head", "chunk", "gdn_init", "positions", "router",
                "aux_coefficients", "mtp", "seq_len", "batch_size", "optimizer", "lr_warmup", "initialisation"):
        assert key in CFG["assumed"], key
    for key in ("norms", "projection_order", "key_head_of_value_head", "chunk", "gdn_init", "seq_len", "lr_warmup"):
        assert CFG["assumed"][key]["why"], key  # none guessed silently
    assert CFG["assumed"]["lr_warmup"]["warmup_steps"] == 2000 and CFG["assumed"]["seq_len"]["value"] == 8192
    assert "TO BE MEASURED" not in json.dumps(CFG)
    # no width, head count, router width or choices a token differs from the published
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CFG["published"][key] == value if key in CFG["reduced"] else CFG[key] == value, key


# -- parameters, FLOPs and bytes -----------------------------------------------------------


def test_flop_byte_and_parameter_counts_against_a_hand_sum():
    t, d, v = 8192, 2048, 18992
    delta_mat = d * 12288 + d * 64 + 4096 * d
    attn_mat = d * 8192 + 2 * d * 512 + 4096 * d
    assert (fl.delta_matrix_params(CFG), fl.attention_matrix_params(CFG)) == (delta_mat, attn_mat)
    assert fl.conv_channels(CFG) == 8192
    expert = 3 * d * 512
    around = 2 * d + d * 512 + expert + d + 16 * expert
    assert fl.total_params(CFG) == 3 * (delta_mat + 4 * 8192 + 64 + 128 + around) + (attn_mat + 512 + around) + 2 * v * d + d
    assert fl.total_params(CFG) == 424_340_544
    # the shared expert whole, the routed ones at their expected rows: 10 x 16 / 512 = 0.3125 of an expert a token
    active = 3 * delta_mat + attn_mat + 4 * (d * 512 + expert + d + 0.3125 * expert) + d * v
    assert fl.active_params(CFG) == active
    pairs = 16 * (t * (t + 1) // 2)
    assert fl.attention_pair_heads(CFG, t) == pairs
    # the scan, a chunk: two in-chunk matrices a KEY head; the solve applied, the in-chunk output, two reads of the
    # state and its update a VALUE head
    a_chunk = 16 * (2 * 2 * 64 * 64 * 128) + 32 * (2 * 2 * 64 * 64 * 128 + 3 * 2 * 64 * 128 * 128)
    assert fl.gdn_flops(CFG, 1, t, False) == 128 * a_chunk and fl.gdn_flops(CFG, 2, t, True) == 4 * 128 * a_chunk
    assert fl.gdn_flops(CFG, 1, 130, False) == 3 * a_chunk                            # a started chunk is a chunk
    # a small size by hand: 2 key heads of 4 under 4 value heads of 8, chunks of 2 over 6 tokens
    small = dict(CFG, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=4, linear_value_head_dim=8,
                 assumed={**CFG["assumed"], "chunk": {"value": 2}})
    by_hand = 3 * (2 * (2 * 2 * 2 * 4 + 2 * 2 * 2 * 4) + 4 * (2 * 2 * 2 * 8 + 2 * 2 * 2 * 8 + 3 * 2 * 2 * 4 * 8))
    assert fl.gdn_flops(small, 1, 6, False) == by_hand == 6528 and fl.gdn_flops(small, 3, 6, True) == 6 * by_hand
    assert fl.gdn_bytes(small, 1, 6, False) == 6 * (2 * 2 * 4 * 2 + 2 * 4 * 8 * 2 + 2 * 4 * 4) + 3 * 4 * 4 * 8 * 4
    assert fl.gdn_bytes(small, 1, 6, True) == 6 * (2 * 2 * 2 * 4 * 2 + 3 * 4 * 8 * 2 + 2 * 2 * 4 * 4) + 3 * 4 * 4 * 8 * 4
    assert fl.train_flops_per_token(CFG, t) == 6 * active + 3 * 1024 * pairs / t + 3 * 3 * 128 * a_chunk / t
    # 1.37 GFLOP a token: ISSUE 67's 221 M forward multiply-adds x 6; its 1.8 counts the recomputed forward too, the metric does not
    assert fl.train_flops_per_token(CFG, t) / 1e9 == pytest.approx(1.371, abs=0.005)
    # bytes of one pass of one mixer: q, k at 16 heads, v and o at 32, g and beta float32 a value head, the states
    states = 2 * 128 * 32 * 128 * 128 * 4
    assert fl.gdn_bytes(CFG, 2, t, False) == 2 * t * (2 * 2048 * 2 + 2 * 4096 * 2 + 2 * 32 * 4) + states
    assert fl.gdn_bytes(CFG, 2, t, True) == 2 * t * (2 * 2 * 2048 * 2 + 3 * 4096 * 2 + 4 * 32 * 4) + states
    least_f = fl.gdn_least_seconds(CFG, 2, t, False, 197e12, 819e9)
    assert least_f == pytest.approx(fl.gdn_bytes(CFG, 2, t, False) / 819e9)           # the bytes bind
    assert least_f > fl.gdn_flops(CFG, 2, t, False) / 197e12
    assert fl.gdn_scan_shapes(CFG, 2, t) == ((2, 32, 128, 128), (2, 8192))
    assert fl.gdn_scan_shapes(CFG, 2, 8200) == ((2, 32, 128, 128), (2, 8256))         # the padded stream's length
    # attention at a head of 256, sixteen query heads over two
    assert fl.kernel_flops(CFG, t, 2, False, False) == (2 * 256 + 2 * 256) * 2 * pairs
    assert fl.kernel_flops(CFG, t, 2, False, True) == (6 * 256 + 4 * 256) * 2 * pairs
    assert fl.kernel_flops(CFG, t, 2, True, True) == 0
    rows = 2 * t * 256 * 2
    assert fl.kernel_bytes(CFG, t, 2, False, False) == rows * (2 * 16 + 2 * 2)
    assert fl.kernel_bytes(CFG, t, 2, False, True) == rows * (5 * 16 + 2 * 2)
    # THIS convolution: ONE stream of 8,192 channels in and out a call, float32 taps and the zeros it takes as a bias
    assert fl.short_conv_bytes(CFG, 2, t, False) == 2 * 2 * t * 8192 * 2 + 5 * 8192 * 4
    assert fl.short_conv_bytes(CFG, 2, t, True) == 3 * 2 * t * 8192 * 2 + 2 * 5 * 8192 * 4


def test_the_program_holds_as_many_parameters_as_the_count_says():
    import jax

    from distributedvolunteercomputing_tpu.models import common, get_model

    count = lambda b: common.count_params(jax.eval_shape(b.init, jax.random.PRNGKey(0)))  # noqa: E731
    assert count(get_model(CFG["registry_model"], **CFG["model_overrides"])) == fl.total_params(CFG) == \
        CFG["parameters"]["counted_by_the_program"] == sum(CFG["parameters"]["by_layer"]) + 2 * 38895616 + 2048
    assert count(get_model(TINY["registry_model"], **TINY["model_overrides"])) == fl.total_params(TINY)
    full = dict(CFG, **CFG["published"])
    assert fl.total_params(full) == CFG["parameters"]["at_the_published_sizes"] == 79_674_391_296
    # at work on a token without the head's 0.31 B: the card's "A3B" (mixers 1.54 B, routers and shared experts 0.20 B,
    # ten experts a layer 1.51 B)
    assert round((fl.active_params(full) - 2048 * 151936) / 1e9, 1) == 3.3
    assert family_flops.load(CFG) is fl and family_flops.load(TINY) is fl and references.load(CFG["family"]) is ref


# -- the readers -----------------------------------------------------------------------

# the scan's loops as the v5e compiler writes them (tests/test_tpu_compile.py compiles them for a described v5e: the
# tuples cut to what the readers look at): a forward pass that keeps nothing, the recomputed forward that stacks the
# chunks' states, the backward, and the loop over the three scanned delta layers, which carries no head's state
_STATE = "s32[]{:T(128)}, f32[2,32,128,128]{3,2,1,0:T(8,128)S(1)}"
_QKV, _O = "bf16[2,8192,8192]{2,1,0:T(8,128)(2,1)}", "bf16[2,8192,4096]{2,1,0:T(8,128)(2,1)}"
_BY_HEAD = "f32[2,8192,32]{2,1,0:T(8,128)}"
_STATES, _SUMS = "f32[128,2,32,128,128]{4,3,2,1,0:T(8,128)}", "f32[128,2,32]{2,1,0:T(2,128)}"
_LOOP = "(%s) while((%s) %%tuple.1964), condition=%%wide.region_42, body=%%wide.region_41.sunk"
GDN_FIRST = "%while.503 = " + _LOOP % ((", ".join([_STATE, _O, _SUMS, _QKV, _BY_HEAD, _BY_HEAD]),) * 2)
GDN_FWD = "%while.505 = " + _LOOP % ((", ".join([_STATE, _O, _SUMS, _STATES, _QKV, _BY_HEAD, _BY_HEAD]),) * 2)
GDN_BWD = "%while.508 = " + _LOOP % ((", ".join([_STATE, _QKV, _BY_HEAD, _BY_HEAD, _STATES, _QKV, _BY_HEAD, _BY_HEAD, _O]),) * 2)
LAYERS = "%while.502 = " + _LOOP % (("s32[]{:T(128)}, bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)S(1)}, f32[512]{0:T(512)}, "
                                     "bf16[3,2,8192,2048]{3,2,1,0:T(8,128)(2,1)}, bf16[2,8192,8192]{2,1,0:T(8,128)(2,1)}",) * 2)
IN_A_LOOP = "%fusion.5803 = bf16[2,16,64,128]{3,2,1,0:T(8,128)(2,1)} fusion(%x)"
FULL_FWD = "%dvc_flash_fwd.7 = (bf16[2,8192,4096]{2,1,0}) custom-call(%q)"
FULL_BWD = "%dvc_flash_bwd.2 = (bf16[2,8192,4096]{2,1,0}) custom-call(%q)"
CONV_FWD = "%dvc_short_conv_fwd.5 = bf16[2,8192,8192]{2,1,0} custom-call(%u)"
CONV_BWD = "%dvc_short_conv_bwd.5 = (bf16[2,8192,8192]{2,1,0}, f32[4,8192]{1,0}) custom-call(%u)"
HEAD = "%select_add_fusion.2 = f32[8192,2048]{1,0:T(8,128)} fusion(%x)"
MS = 1_000_000


def make_trace(ops):
    return Trace.from_json({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_step(7)", 1_000_000, 500_000_000],
                ["jit_step(7)", 502_000_000, 500_000_000],
                ["jit_step(7)", 1_003_000_000, 1_600_000_000],   # ends after the window
            ]},
            {"name": "XLA Ops", "events": ops},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench:trace_begin", 0, 900_000], ["bench:trace_end", 2_500_000_000, 10],
        ]}]},
    ]})


def run_of(ops, spans=(), **more):
    return {"trace": make_trace(ops), "step_program": r"^jit_step\(", "spans": list(spans),
            "stats": {}, "config": CFG, "tokens_per_step": 16384, "chips": 1,
            "peak": flops.PEAKS["TPU v5 lite"], **more}


STEP_OPS = [
    [LAYERS, 5 * MS, 180 * MS],                                # spans the three loops that follow, and is none of them
    [GDN_FIRST, 10 * MS, 9 * MS], [IN_A_LOOP, 11 * MS, 1 * MS], [GDN_FWD, 40 * MS, 10 * MS], [GDN_BWD, 100 * MS, 30 * MS],
    [CONV_FWD, 200 * MS, 2 * MS], [CONV_BWD, 210 * MS, 4 * MS],
    [FULL_FWD, 250 * MS, 12 * MS], [FULL_BWD, 300 * MS, 30 * MS],
    [HEAD, 400 * MS, 50 * MS],
    [GDN_FWD, 700 * MS, 11 * MS], [GDN_BWD, 800 * MS, 31 * MS],
    [GDN_BWD, 2000 * MS, 99 * MS],                             # in the step the window cuts
]


def scan_span(t0, share):
    return {"trace": "loop", "name": "gdn.scan", "t0": t0, "dur_s": 1e-5,
            "attrs": {"step": 10, "gdn_carry_share": share, "gdn_decay_min": -90.0, "gdn_beta_mean": 0.5,
                      "gdn_form": "scalar_decay_xla"}}


def route_span(t0):
    attrs = {"step": 10, "moe_load_max": 400.0, "moe_load_mean": 320.0, "moe_dropped": 0.0, "moe_rows_moved": 4 * 15360.0,
             "moe_rows_held": 20000.0, "experts_held": 16, "router_site": "post_attention", "mixers_linear": 3,
             "mixers_full": 1, "moe_chunks_extra": 0.0, "shared_gate_mean": 0.5}
    return {"trace": "loop", "name": "moe.route", "t0": t0, "dur_s": 1e-5, "attrs": attrs}


def test_the_scan_readers_read_the_loops_and_the_spans():
    run = run_of(STEP_OPS, [scan_span(1.0, 0.50), scan_span(2.0, 0.54), scan_span(3.0, 0.51), route_span(1.5)])
    assert kda_trace.carried(GDN_FWD)[:3] == [(), (2, 32, 128, 128), (2, 8192, 4096)]
    assert kda_trace.carried(IN_A_LOOP) == [] and (2, 32, 128, 128) not in kda_trace.carried(LAYERS)
    # forward loops carry four whole streams, a backward one seven: the mark lies between
    whole = lambda text: sum(len(s) == 3 and s[:2] == (2, 8192) for s in kda_trace.carried(text))  # noqa: E731
    assert (whole(GDN_FIRST), whole(GDN_FWD), whole(GDN_BWD)) == (4, 4, 7)
    assert 4 <= gdn_trace.FORWARD_CARRIES_AT_MOST < 7
    assert gdn_trace.loop_events(run) == (2, [(False, 9 * MS), (False, 10 * MS), (True, 30 * MS),
                                              (False, 11 * MS), (True, 31 * MS)])
    took = 9 + 10 + 30 + 11 + 31
    assert readers.compute(M.layer_metric_path("gdn.device_ms"), run) == pytest.approx(took / 2)
    least = lambda bwd: fl.gdn_least_seconds(CFG, 2, 8192, bwd, 197e12, 819e9) * 1e3  # noqa: E731
    got = readers.compute(M.layer_metric_path("gdn.roofline"), run)
    assert got == pytest.approx(100 * (3 * least(False) + 2 * least(True)) / took) and 0 < got < 100
    assert readers.compute(M.layer_metric_path("gdn.carry_share"), run) == 0.51       # the median of the spans
    assert readers.compute(M.layer_metric_path("gdn.carry_share"), dict(run, trace=None)) == 0.51
    # a program with no such loop gives nothing, and no error; nor does another step's batch (the state's shape differs)
    for name in ("gdn.device_ms", "gdn.roofline"):
        assert readers.compute(M.layer_metric_path(name), run_of([STEP_OPS[0], STEP_OPS[7], STEP_OPS[9]])) is None
        assert readers.compute(M.layer_metric_path(name), dict(run, trace=None)) is None
        assert readers.compute(M.layer_metric_path(name), dict(run, tokens_per_step=8192)) is None
    assert readers.compute(M.layer_metric_path("gdn.carry_share"), run_of(STEP_OPS, [route_span(1.0)])) is None
    # the other scans' readers find nothing under this cell's names, and this cell's nothing under theirs
    for name in ("kda.device_ms", "kda.roofline", "kda.carry_share", "ssm.device_ms", "ssm.carry_share"):
        assert readers.compute(M.layer_metric_path(name), run) is None, name
    # the parent's configuration of another family under the same trace: no shapes to look for, nothing
    other = dict(run, config=M.load_config("kimi-linear-48b-a3b"))
    for name in ("gdn.device_ms", "gdn.roofline"):
        assert readers.compute(M.layer_metric_path(name), other) is None


def test_a_roofline_over_100_is_not_producible_from_the_modules_own_count():
    """The share reads the SAME required work whatever implements it; at the
    speed of the chip's own limits it reads exactly 100, and any loop that the
    chip could run reads under it."""
    least = [fl.gdn_least_seconds(CFG, 2, 8192, bwd, 197e12, 819e9) for bwd in (False, True)]
    at_the_limit = [[GDN_FWD, 10 * MS, round(least[0] * 1e9)], [GDN_BWD, 100 * MS, round(least[1] * 1e9)]]
    assert readers.compute(M.layer_metric_path("gdn.roofline"), run_of(at_the_limit)) == pytest.approx(100.0, rel=1e-4)
    slower = [[GDN_FWD, 10 * MS, 2 * round(least[0] * 1e9)], [GDN_BWD, 100 * MS, 2 * round(least[1] * 1e9)]]
    assert readers.compute(M.layer_metric_path("gdn.roofline"), run_of(slower)) == pytest.approx(50.0, rel=1e-4)
    # a backward loop counted as a forward one would read LOWER, never over: its least time is the smaller
    assert least[0] < least[1]


def test_older_readers_read_this_cells_kernels_and_spans():
    run = run_of(STEP_OPS, [route_span(1.0), route_span(2.0)])
    assert readers.compute(M.layer_metric_path("attention.device_ms"), run) == pytest.approx((12 + 30) / 2)
    ms = lambda bwd: (2560 if bwd else 1024) * 2 * 16 * (8192 * 8193 // 2) / 197e12 * 1e3  # noqa: E731
    got = readers.compute(M.layer_metric_path("attention.roofline"), run)
    assert got == pytest.approx(100 * (ms(False) + ms(True)) / 42) and 0 < got < 100
    assert readers.compute(M.layer_metric_path("conv.device_ms"), run) == pytest.approx(6 / 2)
    conv = readers.compute(M.layer_metric_path("conv.roofline"), run)
    want = (fl.short_conv_bytes(CFG, 2, 8192, False) + fl.short_conv_bytes(CFG, 2, 8192, True)) / 819e9 * 1e3
    assert conv == pytest.approx(100 * want / 6) and 0 < conv < 100
    assert readers.compute(M.layer_metric_path("step.mfu_model"), run) == pytest.approx(
        100 * 16384 * fl.train_flops_per_token(CFG, 8192) / (0.5 * 197e12))
    assert readers.compute(M.layer_metric_path("moe.dropped"), run) == 0.0
    assert readers.compute(M.layer_metric_path("moe.load_max_over_mean"), run) == pytest.approx(400 / 320)
    assert readers.compute(M.layer_metric_path("moe.rows_moved_over_held"), run) == pytest.approx(4 * 15360 / 20000)
    assert readers.compute(M.layer_metric_path("moe.chunks_extra"), run) == 0.0
    assert readers.compute(M.layer_metric_path("moe.bias_spread"), run) is None      # no selection bias exists
    # the share's loops carry a vector over the S x k = 163,840 assignments (found by num_experts_per_tok)
    fwd = ("%while.31 = (s32[]{:T(128)}, bf16[16384,2048]{1,0:T(8,128)(2,1)}, s32[]{:T(128)}, s32[]{:T(128)}, "
           "s32[168960]{0:T(1024)}, s32[168960]{0:T(1024)}) while(%tuple.7), condition=%c, body=%b")
    bwd = ("%while.39 = (s32[]{:T(128)}, bf16[16384,2048]{1,0:T(8,128)(2,1)}, f32[163840]{0:T(1024)}, "
           "bf16[16,2048,512]{2,1,0}) while(%tuple.9), condition=%c, body=%b")
    ops = [[fwd, 410 * MS, 5 * MS], [bwd, 450 * MS, 12 * MS], [fwd, 900 * MS, 6 * MS]]
    assert readers.compute(M.layer_metric_path("moe.share_device_ms"), run_of(STEP_OPS + ops)) == pytest.approx(
        (5 + 12 + 6) / 2)


# -- the manifest, by name ------------------------------------------------------------------


def test_manifest_holds_the_new_configuration_cell_and_metrics_by_name():
    M.check()
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "solo", 1)
    assert len(cell["why"]) <= 200 and "in LR warm-up" in cell["why"] and "scalar-decay" in cell["why"]
    entry = M.config_entry(NAME)
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and len(entry["why"]) <= 200
    assert entry["source"] == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    assert [c["file"] for c in M.doc["configs"]].count(entry["file"]) == 1
    per_layer = {m["name"]: m for m in M.doc["per_layer"]}
    for name, (unit, better, source) in NEW_METRICS.items():
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert m["layer"] == "compiled step" and m["moves"] == "tok_s_chip" and CELL in m["workloads"]
        assert os.path.exists(M.layer_metric_path(name))
    listed = {m["name"] for m in M.metrics_for(CELL, "per_layer")}
    assert listed == set(APPENDED) | set(NEW_METRICS) | {
        "lifecycle.compile_s", "lifecycle.cache_misses", "lifecycle.backend_init_s"}
    for name in APPENDED:   # appended, never inserted: what stood before the cell on a list stands before it still
        names = per_layer[name]["workloads"]
        assert names.count(CELL) == 1 and names.index(CELL) > names.index("sdar-solo-4k" if "sdar-solo-4k" in names
                                                                        else "kimi-linear-solo-8k"), name
    # their readers count another scan, another mask, a dense FFN, a stepped bias or a loop over passes: the cell stays out
    for other in ("kda.device_ms", "kda.roofline", "kda.carry_share", "ssm.device_ms", "scope.mlp_ms", "moe.bias_spread",
                  "moe.act_zero_share", "attention.bd_device_ms", "recur.exit_entropy", "step.mfu", "step.mfu_held",
                  "step.mfu_active", "attention.window_device_ms", "device.collective_share"):
        assert CELL not in per_layer[other].get("workloads", ()), other
    e2e = {m["name"]: m for m in M.metrics_for(CELL, "end_to_end")}
    assert set(e2e) == {"tok_s_chip", "setup_s"} and e2e["tok_s_chip"]["workloads"].count(CELL) == 1
    # one cell in four at most may take four chips; one does
    assert sum(w["chips"] == 4 for w in M.doc["workloads"]) == 1 <= len(M.doc["workloads"]) // 4
    assert [w["name"] for w in M.doc["workloads"]].count(CELL) == 1


def less_this_pr(root=REPO_ROOT):
    """The manifest without this PR's three metrics, its cell (on every list)
    and its configuration, each taken off BY NAME."""
    view = Manifest(root)
    doc = json.loads(json.dumps(view.doc))
    without = lambda m: dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m  # noqa: E731
    doc["per_layer"] = [without(m) for m in doc["per_layer"] if m["name"] not in NEW_METRICS]
    doc["end_to_end"] = [without(m) for m in doc["end_to_end"]]
    doc["workloads"] = [w for w in doc["workloads"] if w["name"] != CELL]
    doc["configs"] = [c for c in doc["configs"] if c["name"] != NAME]
    view.doc = doc
    return view


@pytest.mark.parametrize("test,args", [
    ("test_manifest_holds_the_new_configuration_cell_and_metrics", None),
    ("test_manifest_as_the_sdar_tests_asserted_it_before_this_cell",
     ("test_manifest_holds_the_new_configuration_cell_and_metrics", None)),
    ("test_manifest_as_the_sdar_tests_asserted_it_before_this_cell",
     ("test_manifest_as_the_collective_pairs_tests_asserted_it_before_this_cell",
      ("test_manifest_holds_the_nine_scope_metrics_at_its_end", ()))),
    ("test_manifest_as_the_sdar_tests_asserted_it_before_this_cell",
     ("test_manifest_as_the_collective_pairs_tests_asserted_it_before_this_cell",
      ("test_manifest_as_the_kimi_tests_asserted_it_nine_places_up",
       ("test_manifest_holds_the_new_configuration_cell_and_metrics",)))),
    ("test_manifest_as_the_sdar_tests_asserted_it_before_this_cell",
     ("test_manifest_as_the_collective_pairs_tests_asserted_it_before_this_cell",
      ("test_manifest_as_the_kimi_tests_asserted_it_nine_places_up",
       ("test_manifest_tail_as_the_nemotron_tests_asserted_it_three_metrics_and_a_cell_up",)))),
])
def test_manifest_as_the_ouro_tests_asserted_it_before_this_cell(test, args, monkeypatch):
    """``test_yardstick_ouro.py``'s manifest cases (tests/conftest.py marks them:
    they assert that ouro-solo-4k and Ouro's three metrics END the manifest, and
    run the older tail tests behind them), run as they stand against the manifest
    less this PR's entries; against the manifest as it is each fails on the tail
    alone."""
    ouro = importlib.import_module("test_yardstick_ouro")

    def run():
        getattr(ouro, test)(*(() if args is None else (*args, monkeypatch)))

    monkeypatch.setattr(ouro, "M", less_this_pr())
    monkeypatch.setattr(ouro, "Manifest", less_this_pr)
    run()
    monkeypatch.setattr(ouro, "M", M)
    monkeypatch.setattr(ouro, "Manifest", Manifest)
    with pytest.raises((AssertionError, ManifestError)):   # a list that names a cell the view has taken off, or the tail
        run()


def test_reference_check_limits_are_written_with_their_readings():
    rc = CFG["reference_check"]
    assert rc["sequences"] == 1 and rc["seq_len"] in (8192, 4096)
    assert 0 < rc["grad_rel_err"] <= 0.15 and 0 < rc["loss_atol"] <= 0.05
    for word in ("e4m3", "bfloat16", "my chip run"):
        assert word in rc["why"], word
    for variant in ref.VARIANTS:
        assert variant in rc["left_out"], variant
    assert "tests/test_qwen3_next_variants.py" in rc["left_out"] and rc["size_why"]
    band = CFG["loss_band"]
    assert 0 < band["last_minus_first_max"] <= 1.5
