"""What PR 64 added to the yardstick: the Ouro reference's own consistency (its
gradient against finite differences, its exit distribution), the parameter sum
and the FLOPs against hand sums, the three ``recur.*`` readers on a hand-made
trace, scope map and span list, the manifest with the new entries (and what
SDAR's yardstick tests asserted of the manifest's tail, run as they stand
against the manifest less this PR's entries: see tests/conftest.py), and the
rehearsal configuration through the runner's whole path on the CPU."""

import dataclasses
import importlib
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops, flops_ouro, readers, recur_trace, references, scope_trace
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.references import ouro as ref
from benchmark.trace import Trace

M = Manifest(REPO_ROOT)
CFG = M.load_config("ouro-2.6b")
TINY = M.load_config("tiny-rehearsal-ouro")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "ouro-solo-4k"


# -- the reference ---------------------------------------------------------------


def tiny_params(seed=3, scale=3.0):
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(TINY["registry_model"], **TINY["model_overrides"])
    ref.check_config(bundle.config, TINY)
    params = bundle.init(jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (20.0 if getattr(path[0], "key", None) == "exit_gate" else scale if x.ndim >= 2 else 1.0),
        params)
    tokens = np.random.default_rng(5).integers(0, TINY["vocab_size"], (2, 32))
    return bundle, params, tokens, np.roll(tokens, -1, axis=1)


def test_reference_gradient_agrees_with_finite_differences():
    """Its ``jax.grad`` against central differences of its own loss, along a
    seeded direction in every leaf, the exit gate away from a half."""
    import jax
    import jax.numpy as jnp

    _, params, tokens, targets = tiny_params()
    hp = ref.hyper(TINY)
    grads = jax.jit(jax.grad(lambda p: ref.loss(p, tokens, targets, hp)))(params)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    rng = np.random.default_rng(0)
    loss = jax.jit(lambda p: ref.loss(p, tokens, targets, hp))
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


def test_reference_exit_distribution_gives_the_last_pass_what_is_left():
    lam = np.array([[0.5, 0.9], [0.5, 0.2], [0.5, 0.7], [0.5, 0.01]], np.float32)
    p = np.asarray(ref.exit_distribution(lam))
    np.testing.assert_allclose(p[:, 0], [0.5, 0.25, 0.125, 0.125], rtol=1e-6)
    np.testing.assert_allclose(p[:, 1], [0.9, 0.1 * 0.2, 0.1 * 0.8 * 0.7, 0.1 * 0.8 * 0.3], rtol=1e-5)
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    mistaken = np.asarray(ref.exit_distribution(lam, remainder=False))
    np.testing.assert_allclose(mistaken[:3], p[:3])
    np.testing.assert_allclose(mistaken[3], [0.0625, 0.1 * 0.8 * 0.3 * 0.01], rtol=1e-5)  # sums to less than 1


def test_reference_sizes_and_config_check():
    assert ref.sizes(CFG) == {"n_layer": 6, "d_model": 2048, "seq_len": 4096, "vocab": 49152}
    assert ref.hyper(CFG) == {"heads": 16, "n_kv": 16, "head_dim": 128, "theta": 1e6, "eps": 1e-6, "passes": 4,
                              "beta": 0.1}
    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    ref.check_config(bundle.config, CFG)
    for attr, bad, word in (("passes", 3, "passes"), ("entropy_coef", 0.0, "entropy_coef"), ("max_len", 8192, "max_len"),
                            ("rope_theta", 1e4, "rope_theta"), ("d_ff", 4096, "d_ff"), ("n_layers", 4, "n_layers")):
        with pytest.raises(ValueError, match=word):
            ref.check_config(dataclasses.replace(bundle.config, **{attr: bad}), CFG)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        ref.check_config(bundle.config, dict(CFG, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="layer_types"):
        ref.check_config(bundle.config, dict(CFG, layer_types=["sliding_attention"]))
    with pytest.raises(ValueError, match="unknown variant"):
        ref.loss({}, None, None, ref.hyper(CFG), variant="nothing")


def test_configuration_file_is_what_the_program_runs_with_its_cut_listed():
    """``test_yardstick_manifest.py``'s check of a configuration, for one whose
    ``reduced`` is not empty (tests/conftest.py marks that case), and every
    number of the catalog row under its own key."""
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    entry = M.config_entry("ouro-2.6b")
    assert CFG["source"] == entry["source"] and entry["file"] == "benchmark/configs/ouro-2.6b.json"
    assert CFG["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    assert CFG["published"] == {"num_hidden_layers": 48}
    assert (CFG["num_hidden_layers"], CFG["total_ut_steps"], CFG["vocab_size"]) in ((6, 4, 49152), (7, 4, 49152))
    assert (CFG["family"], CFG["registry_model"]) == ("ouro", "ouro_2_6b")
    assert CFG["model_overrides"] == {"n_layers": CFG["num_hidden_layers"], "max_len": 4096}
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    references.load(CFG["family"]).check_config(bundle.config, CFG)
    assert set(CFG["volunteer"]) <= {f.name for f in dataclasses.fields(VolunteerConfig)}
    assert CFG["volunteer"] == {"batch_size": 2, "optimizer": "adam", "lr": 0.001, "steps": 1000000,
                                "warmup_steps": 2000, "mesh": ""}
    assert "eight such stages in a ring" in CFG["deployment"]
    for key in ("sandwich_norms", "biases_and_qk_norm", "carried_state", "exit_gate", "objective", "seq_len",
                "batch_size", "optimizer", "lr_warmup", "initialisation", "early_exit_threshold"):
        assert key in CFG["assumed"], key
    for key in ("objective", "seq_len"):
        assert CFG["assumed"][key]["why"], key  # none guessed silently
    assert CFG["assumed"]["objective"]["value"]["beta"] == 0.1 and CFG["assumed"]["seq_len"]["value"] == 4096
    assert "PROVISIONAL" not in json.dumps(CFG)
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Ouro-2.6B")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key  # every width, list and nested group whole


# -- parameters and FLOPs ------------------------------------------------------------------


def test_the_program_holds_as_many_parameters_as_the_sum_says():
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    def counted(**overrides):
        shapes = jax.eval_shape(get_model("ouro_2_6b", **overrides).init, jax.random.PRNGKey(0))
        return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))

    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 16_777_216 + 34_603_008 + 8_192 == 51_388_416
    rest = 2 * 49_152 * 2048 + 2048 + 2049
    assert rest == 201_326_592 + 4_097
    total = 6 * layer + rest
    assert total == 509_661_185 == flops_ouro.total_params(CFG) == counted(**CFG["model_overrides"])
    assert CFG["parameters"]["counted_by_the_program"] == total and CFG["parameters"]["a_layer"] == layer
    # the published model: every layer, each held once however often it runs
    full = dict(CFG, num_hidden_layers=48)
    assert flops_ouro.total_params(full) == counted() == 2_667_974_657 == CFG["parameters"]["at_published_sizes"]
    # seven layers: what the check's two halves were sized against (24 bytes a parameter at the check)
    assert counted(n_layers=7) == 561_049_601


def test_flops_a_data_token_against_a_hand_sum():
    d, hd, heads, f, v, layers, passes, t = 2048, 128, 16, 5632, 49152, 6, 4, 4096
    a_pass = layers * (4 * d * d + 3 * d * f) + d * v
    assert flops_ouro.products_a_token_a_pass(CFG) == a_pass == 308_281_344 + 100_663_296
    pairs = t * (t + 1) // 2
    want = passes * (6 * a_pass + 12 * hd * layers * heads * pairs / t)
    assert flops_ouro.train_flops_per_token(CFG, t) == pytest.approx(want)
    assert 11.0e9 < want < 11.1e9  # 90 TFLOP a step of 8,192 data tokens
    as_run = want + passes * (2 * layers * (4 * d * d + 3 * d * f) + 2 * hd * layers * heads * pairs / t)
    assert flops_ouro.train_flops_per_token_as_run(CFG, t) == pytest.approx(as_run) and 13.4e9 < as_run < 13.7e9
    # the head is a quarter of a pass's products here, a twenty-fifth in the 48-layer model
    assert d * v / a_pass == pytest.approx(0.246, abs=0.001)
    assert d * v / flops_ouro.products_a_token_a_pass(dict(CFG, num_hidden_layers=48)) == pytest.approx(0.039, abs=0.001)
    # the kernel of a layer-run, as it runs: 4 D forward and 10 D backward a kept pair a head
    assert flops_ouro.kernel_flops(CFG, t, 2, False) == 4 * hd * 2 * heads * pairs
    assert flops_ouro.kernel_flops(CFG, t, 2, True) == 10 * hd * 2 * heads * pairs
    rows = 2 * t * hd * 2
    assert flops_ouro.kernel_bytes(CFG, t, 2, False) == rows * 4 * heads
    assert flops_ouro.kernel_bytes(CFG, t, 2, True) == rows * 7 * heads
    least = flops_ouro.kernel_least_seconds(CFG, t, 2, False, True, 197e12, 819e9)
    assert least == pytest.approx(10 * hd * 2 * heads * pairs / 197e12)  # the FLOPs, not the bytes
    with pytest.raises(ValueError, match="window"):
        flops_ouro.kernel_least_seconds(CFG, t, 2, True, False, 197e12, 819e9)


# -- the readers -----------------------------------------------------------------------

VOCABULARY = {"attention": "attention", "mlp": "mlp", "loss_head": "loss_head", "optimizer": "optimizer",
              "noising": "other", "recur": "other"}
OPS = {  # name -> the instruction's text as the chip names its event
    "while.9": ("%while.9 = (s32[]{:T(128)}, bf16[2,8]{1,0:T(8,128)(2,1)}) while((s32[]{:T(128)}, bf16[2,8]{1,0}) "
                "%tuple.1), condition=%cond, body=%body"),
    "dvc_flash_fwd.2": "%dvc_flash_fwd.2 = (bf16[2,4096,2048]{2,1,0}, f32[2,16,8,1,512]{4,3,2,1,0}) custom-call(%q, %k, %v)",
    "fusion.3": "%fusion.3 = bf16[2,8]{1,0:T(8,128)(2,1)} fusion(bf16[2,8]{1,0} %c), kind=kLoop, calls=%fc.3",
    "copy.4": "%copy.4 = bf16[2,8]{0,1:T(8,128)(2,1)} copy(bf16[2,8]{1,0} %d)",
    "add_any.5": "%add_any.5 = f32[6,8]{1,0:T(8,128)} fusion(f32[6,8]{1,0} %e), kind=kLoop, calls=%fc.5",
    "head.6": "%head.6 = f32[]{:T(128)} fusion(f32[2,8]{1,0} %g), kind=kLoop, calls=%fc.6",
    "embed.7": "%embed.7 = bf16[2,8]{1,0:T(8,128)(2,1)} fusion(s32[2]{0} %t), kind=kLoop, calls=%fc.7",
}
MAP = {
    "while.9": {"scope": "recur", "pass": "fwd", "result": "(s32[], bf16[2,8]{1,0})", "mixed": False},
    "dvc_flash_fwd.2": {"scope": "attention", "pass": "fwd", "result": "(bf16[2,4096,2048]{2,1,0}, f32[2,16,8,1,512]{4,3,2,1,0})",
                        "mixed": False},
    "fusion.3": {"scope": "mlp", "pass": "refwd", "result": "bf16[2,8]{1,0}", "mixed": False},
    "copy.4": {"scope": "recur", "pass": "fwd", "result": "bf16[2,8]{0,1}", "mixed": False},
    "add_any.5": {"scope": "recur", "pass": "bwd", "result": "f32[6,8]{1,0}", "mixed": False},
    "head.6": {"scope": "loss_head", "pass": "fwd", "result": "f32[]", "mixed": False},
    "embed.7": {"scope": None, "pass": "fwd", "result": "bf16[2,8]{1,0}", "mixed": False},
}
DOC = {"program": "jit(step)", "module": "jit_step", "vocabulary": VOCABULARY, "map": MAP,
       "seconds": {"lower": 0.0, "compile": 0.0, "parse": 0.0}}
MS = 1_000_000
# one step, from its start: (name, start, duration) in ms; the passes' loop spans the kernel, the MLP and the carry's copy
STEP = [("embed.7", 0, 2), ("while.9", 5, 300), ("dvc_flash_fwd.2", 10, 40), ("fusion.3", 60, 200), ("copy.4", 270, 10),
        ("add_any.5", 320, 6), ("head.6", 340, 50)]
STARTS = (1, 402)


def hand_trace():
    ops = [[OPS[name], (s0 + s) * MS, d * MS] for s0 in STARTS for name, s, d in STEP]
    ops.append([OPS["copy.4"], 900 * MS, 99 * MS])  # inside the execution the window cuts
    modules = [["jit_step(7)", s0 * MS, 400 * MS] for s0 in STARTS] + [["jit_step(7)", 803 * MS, 450 * MS]]
    return Trace.from_json({"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": modules},
                                            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench:trace_begin", 0, 900_000], ["bench:trace_end", 900 * MS, 10]]}]},
    ]})


def exit_span(t0, **attrs):
    return {"trace": "loop", "name": "recur.exit", "t0": t0, "dur_s": 1e-5,
            "attrs": {"step": 10, "passes": 4, "layers": 6, **attrs}}


def run_of(spans=(), trace=True, **more):
    return {"trace": hand_trace() if trace else None, "step_program": r"^jit_step\(", "spans": list(spans),
            "stats": {}, "config": CFG, "tokens_per_step": 8192, "chips": 1, "cell": {"name": "no-such-cell"},
            "peak": flops.PEAKS["TPU v5 lite"], **more}


@pytest.fixture
def offered(monkeypatch):
    """A program whose accessor gives the hand-built document."""
    monkeypatch.setattr(scope_trace, "scopes_of", lambda run: DOC)


def read(name, run):
    return readers.compute(M.layer_metric_path(name), run)


def test_outside_blocks_is_the_loops_own_time_without_its_blocks(offered):
    """The ``while`` without its body's kernel, MLP and copy, the carry's copy,
    and the shared weights' gradient sum: what resolves to ``recur`` itself; the
    embedding (no word), the head and the blocks are not the loop's."""
    assert read("recur.outside_blocks_ms", run_of()) == pytest.approx((300 - 40 - 200 - 10) + 10 + 6)


def test_outside_blocks_reports_nothing_without_the_word_the_map_or_the_trace(monkeypatch, offered):
    assert read("recur.outside_blocks_ms", run_of(trace=False)) is None
    # a program whose vocabulary has no such word (every model that is not looped; the parent)
    older = dict(DOC, vocabulary={k: v for k, v in VOCABULARY.items() if k != "recur"})
    monkeypatch.setattr(scope_trace, "scopes_of", lambda run: older)
    assert read("recur.outside_blocks_ms", run_of()) is None
    monkeypatch.setattr(scope_trace, "scopes_of", lambda run: None)  # a program without the accessor
    assert read("recur.outside_blocks_ms", run_of()) is None

    def broken(run):
        raise RuntimeError("no map")

    monkeypatch.setattr(scope_trace, "scopes_of", broken)
    assert read("recur.outside_blocks_ms", run_of()) is None


@pytest.mark.parametrize("metric,key,values,want", [
    ("recur.exit_entropy", "exit_entropy", (1.213, 1.209, 1.211), 1.211),
    ("recur.expected_passes", "expected_passes", (1.875, 1.881), 1.878),
])
def test_the_steps_exit_statistics_are_read_from_the_exit_spans(metric, key, values, want):
    spans = [exit_span(float(i), **{key: v}) for i, v in enumerate(values)]
    spans.append({"trace": "loop", "name": "loop.log_sync", "t0": 1.0, "dur_s": 0.2, "attrs": {key: 9.0}})
    assert read(metric, run_of(spans, trace=False)) == pytest.approx(want)
    # a model that is not looped (or the parent) records no such span
    assert read(metric, run_of([exit_span(1.0)], trace=False)) is None
    assert read(metric, run_of(trace=False)) is None
    assert recur_trace.exit_span_attribute(run_of(spans, trace=False), "passes") == [4.0] * len(values)


def test_mfu_model_and_the_attention_readers_take_this_cell_as_they_stand():
    """``step.mfu_model`` finds ``benchmark.flops_ouro`` by the family and counts
    DATA tokens, each at four passes' FLOPs; ``attention.device_ms`` and
    ``attention.roofline`` read the causal kernel's calls, a layer-run each."""
    per_token = flops_ouro.train_flops_per_token(CFG, 4096)
    got = read("step.mfu_model", run_of())
    assert got == pytest.approx(100 * 8192 * per_token / (0.4 * 197e12)) and 0 < got < 100 * 1.2
    assert read("attention.device_ms", run_of()) == pytest.approx(40.0)
    fwd_ms = 4 * 128 * 2 * 16 * (4096 * 4097 // 2) / 197e12 * 1e3
    assert read("attention.roofline", run_of()) == pytest.approx(100 * fwd_ms / 40.0)


# -- the manifest ----------------------------------------------------------------------

NEW_METRICS = {"recur.exit_entropy": ("nats", "higher", "program_counter"),
               "recur.expected_passes": ("passes", "lower", "program_counter"),
               "recur.outside_blocks_ms": ("ms", "lower", "device_trace")}
# the lists SDAR's cell ended, less what reads experts, the block-diffusion mask or its objective
LISTS_AFTER_SDAR = ("loop.step_gap_ms", "step.device_ms", "device.idle_share", "device.peak_hbm_GB", "step.mfu_model",
                    "lifecycle.ready_s", "lifecycle.net_s", "lifecycle.init_s", "lifecycle.step_build_s",
                    "lifecycle.first_step_s", "lifecycle.trace_lower_s", "lifecycle.cache_load_s",
                    "scope.attention_ms", "scope.loss_head_ms", "scope.optimizer_ms", "scope.other_ms",
                    "scope.recompute_share", "scope.unresolved_share")
# and those of the dense block and of the causal kernels, which Kimi's cell ended
LISTS_AFTER_KIMI = ("scope.mlp_ms", "attention.device_ms", "attention.roofline")


def test_manifest_holds_the_new_configuration_cell_and_metrics():
    M.check()
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ouro-2.6b", "solo", 1)
    assert len(cell["why"]) <= 200 and "4 times" in cell["why"] and "in LR warm-up" in cell["why"]
    assert "head" in cell["why"] and "attention" in cell["why"]
    per_layer = {m["name"]: m for m in M.metrics_for(CELL, "per_layer")}
    for name, (unit, better, source) in NEW_METRICS.items():
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert m["layer"] == "compiled step" and m["moves"] == "tok_s_chip" and m["workloads"] == [CELL]
    for shared in LISTS_AFTER_SDAR:
        assert per_layer[shared]["workloads"][-2:] == ["sdar-solo-4k", CELL], shared
    for shared in LISTS_AFTER_KIMI:
        assert per_layer[shared]["workloads"][-2:] == ["kimi-linear-solo-8k", CELL], shared
    # their readers count experts, another mask or another mixer: the cell stays out
    for other in ("moe.dropped", "moe.load_max_over_mean", "moe.rows_moved_over_held", "moe.share_device_ms",
                  "scope.moe_ms", "scope.mixer_ms", "attention.bd_device_ms", "attention.bd_roofline",
                  "attention.bd_tiles_share", "diffusion.head_rows_share", "step.mfu", "step.mfu_held",
                  "step.mfu_active", "attention.window_device_ms", "device.collective_share"):
        assert other not in per_layer, other
    assert set(per_layer) == set(LISTS_AFTER_SDAR) | set(LISTS_AFTER_KIMI) | set(NEW_METRICS) | {
        "lifecycle.compile_s", "lifecycle.cache_misses", "lifecycle.backend_init_s"}
    e2e = {m["name"]: m for m in M.metrics_for(CELL, "end_to_end")}
    assert set(e2e) == {"tok_s_chip", "setup_s"} and e2e["tok_s_chip"]["workloads"][-1] == CELL
    assert [m["name"] for m in M.doc["per_layer"][-3:]] == list(NEW_METRICS) and len(M.doc["per_layer"]) == 75
    assert M.doc["workloads"][-1] is cell and M.doc["configs"][-1]["name"] == "ouro-2.6b"
    assert len(M.doc["workloads"]) == 12 and sum(w["chips"] == 4 for w in M.doc["workloads"]) == 1
    assert len(M.doc["configs"]) == 11
    entry = M.doc["configs"][-1]
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and len(entry["why"]) <= 200


def less_this_pr(root=REPO_ROOT):
    """The manifest as PR 63 left it: without this PR's three metrics, its cell
    (on every list) and its configuration."""
    view = Manifest(root)
    doc = json.loads(json.dumps(view.doc))
    doc["per_layer"] = [dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m
                        for m in doc["per_layer"][:-3]]
    doc["end_to_end"] = [dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m
                         for m in doc["end_to_end"]]
    doc["workloads"], doc["configs"] = doc["workloads"][:-1], doc["configs"][:-1]
    view.doc = doc
    return view


@pytest.mark.parametrize("test,args", [
    ("test_manifest_holds_the_new_configuration_cell_and_metrics", None),
    ("test_manifest_as_the_collective_pairs_tests_asserted_it_before_this_cell",
     ("test_manifest_holds_the_nine_scope_metrics_at_its_end", ())),
    ("test_manifest_as_the_collective_pairs_tests_asserted_it_before_this_cell",
     ("test_manifest_as_the_kimi_tests_asserted_it_nine_places_up",
      ("test_manifest_holds_the_new_configuration_cell_and_metrics",))),
    ("test_manifest_as_the_collective_pairs_tests_asserted_it_before_this_cell",
     ("test_manifest_as_the_kimi_tests_asserted_it_nine_places_up",
      ("test_manifest_tail_as_the_nemotron_tests_asserted_it_three_metrics_and_a_cell_up",))),
])
def test_manifest_as_the_sdar_tests_asserted_it_before_this_cell(test, args, monkeypatch):
    """``test_yardstick_sdar_moe.py``'s manifest cases (tests/conftest.py marks
    them: they assert that sdar-solo-4k and SDAR's four metrics END the
    manifest, and run the older tail tests four places up), run as they stand
    against the manifest less this PR's entries; against the manifest as it is
    each fails on the tail alone."""
    sdar = importlib.import_module("test_yardstick_sdar_moe")

    def run():
        getattr(sdar, test)(*(() if args is None else (*args, monkeypatch)))

    monkeypatch.setattr(sdar, "M", less_this_pr())
    monkeypatch.setattr(sdar, "Manifest", less_this_pr)
    run()
    monkeypatch.setattr(sdar, "M", M)
    monkeypatch.setattr(sdar, "Manifest", Manifest)
    with pytest.raises(AssertionError):
        run()


def test_reference_check_limits_are_written_with_their_readings():
    rc = CFG["reference_check"]
    assert rc["sequences"] == 1 and rc["seq_len"] == 4096
    assert 0 < rc["grad_rel_err"] <= 0.15 and 0 < rc["loss_atol"] <= 0.05
    for word in ("e4m3", "bfloat16", "left_out", "my chip run"):
        assert word in rc["why"], word
    for variant in ref.VARIANTS:
        assert variant in rc["left_out"], variant
    assert "rotary" in rc["left_out"]  # why positions that run on over the passes cannot be seen
    band = CFG["loss_band"]
    assert 0 < band["last_minus_first_max"] <= 1.5 and "seed" in band["why"]


# -- the rehearsal, end to end -----------------------------------------------------------


def test_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``tiny-rehearsal-ouro:solo`` through ``benchmark/run.py``: volunteer,
    probe, window, a traced run, the reference check, the result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "benchmark", "run.py"), "--rehearse",
         "tiny-rehearsal-ouro:solo", "--seed", "3000000019", "--seconds", "3", "--trace", "1"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert '"reference": true' in out.stderr and '"no_compile_in_window": true' in out.stderr
    assert math.isfinite(float(out.stderr.split("'loss': ")[1].split(",")[0]))
