"""What PR 70 added to the yardstick: the configuration file against the program
and the catalog, the parameter sum, the FLOPs and the residual path's bytes
against hand sums, the three new readers on hand-made trace events and spans (a
program without the ``hc`` word gives nothing; a roofline over 100 is not
producible from the module's own count), the manifest with the new entries
asserted BY NAME (the next PR's entries move nothing here), and what
Qwen3-Next's yardstick tests asserted of the manifest's tail, run as they stand
against the manifest less PR 67's and this PR's entries (see tests/conftest.py)."""

import dataclasses
import importlib
import json
import os

import pytest

from benchmark import family_flops, flops, flops_moe, flops_xing4 as fl, readers, references, scope_trace
from benchmark.manifest import REPO_ROOT, Manifest, ManifestError
from benchmark.references import xing4 as ref

M = Manifest(REPO_ROOT)
NAME, CELL = "xing4.0-29b-a4b", "xing4-solo"
CFG = M.load_config(NAME)
TINY = M.load_config("tiny-rehearsal-xing4")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"scope.residual_ms": ("ms", "lower", "device_trace"), "hc.roofline": ("%", "higher", "device_trace"),
               "hc.res_offdiag": ("ratio", "higher", "program_span")}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
# the lists the cell's name was appended to (ISSUE 70, Tentpole 7): every per-layer list that carries glm47-flash-solo-8k
APPENDED = (
    "loop.step_gap_ms", "step.device_ms", "step.mfu_model", "device.idle_share", "device.peak_hbm_GB",
    "attention.device_ms", "attention.roofline", "moe.load_max_over_mean", "moe.dropped", "moe.rows_moved_over_held",
    "moe.share_device_ms", "moe.bias_spread", "moe.chunks_extra", "scope.attention_ms", "scope.mlp_ms", "scope.moe_ms",
    "scope.loss_head_ms", "scope.optimizer_ms", "scope.other_ms", "scope.recompute_share", "scope.unresolved_share",
    "lifecycle.ready_s", "lifecycle.net_s", "lifecycle.init_s", "lifecycle.step_build_s", "lifecycle.first_step_s",
    "lifecycle.trace_lower_s", "lifecycle.cache_load_s")


# -- the configuration file ------------------------------------------------------------------


def test_configuration_file_is_what_the_program_runs_with_its_cut_listed():
    from distributedvolunteercomputing_tpu.models import common, get_model

    import jax

    entry = M.config_entry(NAME)
    assert CFG["source"] == entry["source"] and CFG["reduced"] == entry["reduced"] == REDUCED
    assert set(CFG["published"]) == set(REDUCED) == set(CFG["reduced_why"])
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    ref.check_config(bundle.config, CFG)
    ref.check_config(get_model(TINY["registry_model"], **TINY["model_overrides"]).config, TINY)
    with pytest.raises(ValueError, match="hc_mult"):
        ref.check_config(dataclasses.replace(bundle.config, hc_mult=2), CFG)
    with pytest.raises(ValueError, match="dense_layers"):
        ref.check_config(dataclasses.replace(bundle.config, dense_layers=2), CFG)
    # every number of the catalog's row under the same key, but for the five that are cut (and say from what)
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Xing4.0-29B-A4B")
    assert row["source_url"] == CFG["source"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CFG["published"][key] == value and CFG[key] != value, key
        else:
            assert CFG[key] == value, key
    # the floors: four expert layers after the one dense layer held, an eighth of the experts and of the vocabulary
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] == 4 and CFG["first_k_dense_replace"] == 1
    assert CFG["n_routed_experts"] * 8 == CFG["published"]["n_routed_experts"]
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    counted = common.count_params(jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    assert counted == CFG["parameters"]["counted_by_the_program"] == fl.total_params(CFG) == 759_489_806
    assert sum(CFG["parameters"]["by_layer"]) + 2 * CFG["parameters"]["head"] + CFG["parameters"]["final_norm"] == counted
    for word in ("28,411,136", "358,427", "128,225,590", "128,455,030", "759,489,806"):
        assert word in CFG["parameters"]["sum_from_the_widths"], word
    assert ref.sizes(CFG) == {"n_layer": 5, "d_model": 3584, "seq_len": 4096, "vocab": 16384}
    assumed = CFG["assumed"]
    for key in ("source", "stream_norm", "x0", "readout", "maps_a_sublayer", "precision", "sinkhorn", "initialisation"):
        assert assumed["residual_path"][key], key
    assert "noise" in assumed["residual_path"]["initialisation"] and "16.4e9" in assumed["seq_len"]["why"]
    assert CFG["volunteer"]["batch_size"] == 1 and CFG["volunteer"]["warmup_steps"] == 2000


def test_reference_check_limits_are_written_with_their_readings():
    rc = CFG["reference_check"]
    assert rc["sequences"] == 1 and rc["seq_len"] == ref.sizes(CFG)["seq_len"]
    assert 0 < rc["grad_rel_err"] <= 0.15 and 0 < rc["loss_atol"] <= 0.05
    for word in ("e4m3", "bfloat16", "my chip run", "flipped"):
        assert word in rc["why"], word
    for variant in ref.VARIANTS:
        assert variant in rc["left_out"], variant
    assert "tests/test_xing4_variants.py" in rc["left_out"] and rc["size_why"]
    assert 0 < CFG["loss_band"]["last_minus_first_max"] <= 1.5


# -- the arithmetic --------------------------------------------------------------------------


def test_flop_byte_and_parameter_counts_against_a_hand_sum():
    small = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 3, "kv_lora_rank": 5, "qk_nope_head_dim": 4,
             "qk_rope_head_dim": 2, "v_head_dim": 3, "intermediate_size": 16, "moe_intermediate_size": 6,
             "n_shared_experts": 1, "n_routed_experts": 2, "published": {"n_routed_experts": 8},
             "num_experts_per_tok": 4, "vocab_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1, "hc_mult": 4}
    attention = 8 * 3 + 3 * 2 * 6 + 8 * (5 + 2) + 5 * 2 * (4 + 3) + 2 * 3 * 8
    assert fl.attention_matrix_params(small) == attention == 234
    phi = 4 * 8 * (4 + 4 + 16)
    assert fl.hc_matrix_params(small) == phi == 768 and fl.hc_params(small) == phi + 24 + 3 + 32
    layer = attention + 3 + 5 + 2 * 8 + 2 * fl.hc_params(small)
    dense, sparse = layer + 3 * 8 * 16, layer + 8 * 8 + 8 + (1 + 2) * 3 * 8 * 6
    assert fl.total_params(small) == 2 * 32 * 8 + 8 + dense + 2 * sparse
    active = 8 * 32 + 3 * (attention + 2 * phi) + 3 * 8 * 16 + 2 * (8 * 8 + (1 + 4 * 2 / 8) * 3 * 8 * 6)
    assert fl.active_params(small) == pytest.approx(active)
    pairs = 3 * 2 * (10 * 11 // 2)
    assert fl.attention_pair_heads(small, 10) == pairs
    assert fl.train_flops_per_token(small, 10) == pytest.approx(6 * active + 3 * (2 * 6 + 2 * 3) * pairs / 10)
    # the residual path, by hand: a sublayer a token forward reads 4 x 8 and writes 8, then reads 4 x 8 + 8 and writes 4 x 8
    forward = (32 + 8) + (32 + 8 + 32)
    assert fl.hc_bytes(small, 10, "fwd") == fl.hc_bytes(small, 10, "refwd") == forward * 6 * 10 * 2 == 13_440
    assert fl.hc_bytes(small, 10, "bwd") == 2 * 13_440 and fl.hc_bytes(small, 10, "fwd", itemsize=4) == 2 * 13_440
    assert fl.hc_least_seconds(small, 10, 1000.0) == pytest.approx(4 * 13_440 / 1000.0)
    with pytest.raises(ValueError, match="unknown pass"):
        fl.hc_bytes(small, 10, "sideways")
    # at the cell: 2.85 GFLOP a token, the path's 16.4 GB a step
    assert family_flops.load(CFG) is fl
    assert fl.train_flops_per_token(CFG, 4096) == pytest.approx(2.851e9, rel=1e-3)
    assert fl.hc_least_seconds(CFG, 4096, 819e9) == pytest.approx(10 * 4096 * (5 + 9) * 3584 * 2 * 4 / 819e9, rel=1e-9)
    # the kernel is counted at the published head, not at the 256 lanes the program pads the key to
    assert fl.kernel_flops(CFG, 4096, 1, False, False) == (2 * 192 + 2 * 128) * 32 * (4096 * 4097 // 2)
    assert fl.kernel_flops(CFG, 4096, 1, False, True) == (6 * 192 + 4 * 128) * 32 * (4096 * 4097 // 2)
    assert fl.kernel_bytes(CFG, 4096, 1, False, False) == 32 * 4096 * 2 * (2 * 192 + 2 * 128)
    assert fl.kernel_flops(CFG, 4096, 1, True, False) == 0.0


# -- the readers, on hand-made events ---------------------------------------------------------

MS = 1_000_000
MIX = "%fusion.11 = bf16[1,4096,14336]{2,1,0:T(8,128)(2,1)} fusion(%x)"
MAPS = "%fusion.12 = f32[4096,24]{1,0:T(8,128)} fusion(%x)"
MIX_BACK = "%fusion.13 = (bf16[1,4096,14336]{2,1,0:T(8,128)(2,1)}, bf16[1,4096,3584]{2,1,0:T(8,128)(2,1)}) fusion(%g)"
PRODUCT = "%fusion.14 = bf16[1,4096,8192]{2,1,0:T(8,128)(2,1)} fusion(%x)"
HEAD = "%fusion.15 = f32[4096,3584]{1,0:T(8,128)} fusion(%x)"


def scope_doc(with_hc: bool):
    def rec(scope, which, result):
        return {"scope": scope, "pass": which, "result": result, "mixed": False}

    vocabulary = {"attention": "attention", "loss_head": "loss_head", **({"hc": "residual"} if with_hc else {})}
    hc = "hc" if with_hc else None
    return {"program": "jit_step", "module": "jit_step", "seconds": 0.01, "vocabulary": vocabulary, "map": {
        "fusion.11": rec(hc, "fwd", "bf16[1,4096,14336]{2,1,0}"), "fusion.12": rec(hc, "refwd", "f32[4096,24]{1,0}"),
        "fusion.13": rec(hc, "bwd", "(bf16[1,4096,14336]{2,1,0}, bf16[1,4096,3584]{2,1,0})"),
        "fusion.14": rec("attention", "fwd", "bf16[1,4096,8192]{2,1,0}"),
        "fusion.15": rec("loss_head", "fwd", "f32[4096,3584]{1,0}")}}


def run_of(ops, spans=(), **more):
    make_trace = importlib.import_module("test_yardstick_qwen3_next").make_trace   # two whole steps and one the window cuts
    return {"trace": make_trace(ops), "step_program": r"^jit_step\(", "spans": list(spans), "cell": {"name": CELL},
            "stats": {}, "config": CFG, "tokens_per_step": 4096, "chips": 1,
            "peak": flops.PEAKS["TPU v5 lite"], **more}


def step_ops(hc_ms: float):
    """Two whole steps, each with ``hc_ms`` under the residual path's three instructions."""
    third = hc_ms / 4
    one = lambda t0: [[MIX, t0 + 5 * MS, third * MS], [MAPS, t0 + 100 * MS, third * MS],  # noqa: E731
                      [MIX_BACK, t0 + 200 * MS, 2 * third * MS], [PRODUCT, t0 + 300 * MS, 20 * MS],
                      [HEAD, t0 + 400 * MS, 30 * MS]]
    return one(1 * MS) + one(502 * MS) + [[MIX, 2000 * MS, 99 * MS]]   # the last in the step the window cuts


def mix_span(t0, offdiag):
    return {"trace": "loop", "name": "hc.mix", "t0": t0, "dur_s": 1e-5,
            "attrs": {"step": 10, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_res_offdiag": offdiag,
                      "hc_sinkhorn_err": 0.01, "hc_pre_max": 0.6, "hc_post_mean": 1.0}}


def read(name, run):
    return readers.compute(M.layer_metric_path(name), run)


def test_the_three_readers_read_the_scope_and_the_span(monkeypatch):
    monkeypatch.setattr(scope_trace, "scopes_of", lambda run: scope_doc(True))
    run = run_of(step_ops(40.0), [mix_span(1.0, 0.050), mix_span(2.0, 0.058), mix_span(3.0, 0.052)])
    assert read("scope.residual_ms", run) == pytest.approx(40.0)
    assert read("scope.attention_ms", run) == pytest.approx(20.0) and read("scope.unresolved_share", run) == 0.0
    least_ms = 1e3 * fl.hc_least_seconds(CFG, 4096, flops_moe.hbm_bytes_per_s(run["peak"]))
    assert least_ms == pytest.approx(20.08, abs=0.01)
    assert read("hc.roofline", run) == pytest.approx(100.0 * least_ms / 40.0)
    assert read("hc.res_offdiag", run) == 0.052
    # a configuration whose family counts no such path: the time is read, the share is not
    glm = M.load_config("glm-4.7-flash")
    assert read("hc.roofline", run_of(step_ops(40.0), config=glm)) is None
    assert read("scope.residual_ms", run_of(step_ops(40.0), config=glm)) == pytest.approx(40.0)


def test_a_program_without_the_word_or_the_span_gives_nothing(monkeypatch):
    monkeypatch.setattr(scope_trace, "scopes_of", lambda run: scope_doc(False))   # the parent's vocabulary
    run = run_of(step_ops(40.0), [])
    assert read("scope.residual_ms", run) is None and read("hc.roofline", run) is None
    assert read("hc.res_offdiag", run) is None
    assert read("scope.other_ms", run) == pytest.approx(40.0)   # what carries no word is the step's own
    monkeypatch.setattr(scope_trace, "scopes_of", lambda run: None)               # no accessor at all
    assert read("scope.residual_ms", run_of(step_ops(40.0))) is None
    assert read("hc.roofline", run_of(step_ops(40.0))) is None
    untraced = {**run_of([]), "trace": None}
    assert read("scope.residual_ms", untraced) is None and read("hc.roofline", untraced) is None


def test_a_roofline_over_100_is_not_producible_from_the_modules_own_count(monkeypatch):
    """The least time counts ONE read of the streams for the maps and the input and one read and one write for
    the mix, in the compute dtype, at the published bandwidth: a pass that moves those bytes at the chip's whole
    bandwidth reads 100, and nothing that computes the equations moves fewer. A time under it is a trace that
    lost part of the path (or a path that skips work): the reader does not hide that under a ``min``."""
    monkeypatch.setattr(scope_trace, "scopes_of", lambda run: scope_doc(True))
    peak = flops.PEAKS["TPU v5 lite"]
    least_ms = 1e3 * fl.hc_least_seconds(CFG, 4096, flops_moe.hbm_bytes_per_s(peak))
    assert read("hc.roofline", run_of(step_ops(least_ms))) == pytest.approx(100.0)
    assert read("hc.roofline", run_of(step_ops(2 * least_ms))) == pytest.approx(50.0)
    assert read("hc.roofline", run_of(step_ops(least_ms / 2))) == pytest.approx(200.0)   # shown, not clipped
    # the count by hand: 10 sublayers x 4,096 tokens x (5 + 9) x 3,584 elements x 2 bytes forward, four times over the passes
    by_hand = 10 * 4096 * (5 + 9) * 3584 * 2 * 4
    assert sum(fl.hc_bytes(CFG, 4096, which) for which in fl.PASSES) == by_hand == 16_441_671_680


# -- the manifest, by name ------------------------------------------------------------------


def test_manifest_holds_the_new_configuration_cell_and_metrics_by_name():
    M.check()
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "solo", 1)
    assert len(cell["why"]) <= 200 and "in LR warm-up" in cell["why"] and "residual path" in cell["why"]
    entry = M.config_entry(NAME)
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and len(entry["why"]) <= 200
    assert entry["source"] == "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json"
    assert entry["reduced"] == REDUCED and [c["file"] for c in M.doc["configs"]].count(entry["file"]) == 1
    per_layer = {m["name"]: m for m in M.doc["per_layer"]}
    for name, (unit, better, source) in NEW_METRICS.items():
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert m["layer"] == "compiled step" and m["moves"] == "tok_s_chip" and m["workloads"] == [CELL]
        assert os.path.exists(M.layer_metric_path(name))
    listed = {m["name"] for m in M.metrics_for(CELL, "per_layer")}
    assert listed == set(APPENDED) | set(NEW_METRICS) | {
        "lifecycle.compile_s", "lifecycle.cache_misses", "lifecycle.backend_init_s"}
    for name in APPENDED:   # appended, never inserted: every list that carries GLM's cell carries this one behind it
        names = per_layer[name]["workloads"]
        assert names.count(CELL) == 1 and names.index(CELL) > names.index("glm47-flash-solo-8k"), name
    assert {m["name"] for m in M.doc["per_layer"] if "glm47-flash-solo-8k" in m.get("workloads", ())} == set(APPENDED)
    # their readers count another mixer, mask, scan or utilisation: the cell stays out
    for other in ("kda.device_ms", "gdn.device_ms", "ssm.device_ms", "conv.device_ms", "scope.mixer_ms",
                  "moe.act_zero_share", "attention.bd_device_ms", "recur.exit_entropy", "step.mfu", "step.mfu_held",
                  "step.mfu_active", "attention.window_device_ms", "device.collective_share"):
        assert CELL not in per_layer[other].get("workloads", ()), other
    e2e = {m["name"]: m for m in M.metrics_for(CELL, "end_to_end")}
    assert set(e2e) == {"tok_s_chip", "setup_s"} and e2e["tok_s_chip"]["workloads"].count(CELL) == 1
    # fourteen cells of 24, one of them on four chips
    assert len(M.doc["workloads"]) >= 14 and sum(w["chips"] == 4 for w in M.doc["workloads"]) == 1
    assert [w["name"] for w in M.doc["workloads"]].count(CELL) == 1
    assert [c["name"] for c in M.doc["configs"]].count(NAME) == 1


def less_these_prs(root=REPO_ROOT):
    """The manifest without PR 67's entries (``test_yardstick_qwen3_next.less_this_pr``)
    and without this PR's three metrics, its cell (on every list) and its
    configuration, each taken off BY NAME."""
    view = importlib.import_module("test_yardstick_qwen3_next").less_this_pr(root)
    doc = view.doc
    without = lambda m: dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m  # noqa: E731
    doc["per_layer"] = [without(m) for m in doc["per_layer"] if m["name"] not in NEW_METRICS]
    doc["end_to_end"] = [without(m) for m in doc["end_to_end"]]
    doc["workloads"] = [w for w in doc["workloads"] if w["name"] != CELL]
    doc["configs"] = [c for c in doc["configs"] if c["name"] != NAME]
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
def test_manifest_as_the_qwen3_next_tests_asserted_it_before_this_cell(test, args, monkeypatch):
    """``test_yardstick_qwen3_next.py`` runs ``test_yardstick_ouro.py``'s manifest
    cases against the manifest less PR 67's entries (tests/conftest.py marks its
    five cases: this PR's entries end the manifest now); the same cases, as they
    stand, against the manifest less BOTH PRs' entries; against the manifest as
    it is each fails on the tail alone."""
    ouro = importlib.import_module("test_yardstick_ouro")

    def run():
        getattr(ouro, test)(*(() if args is None else (*args, monkeypatch)))

    monkeypatch.setattr(ouro, "M", less_these_prs())
    monkeypatch.setattr(ouro, "Manifest", less_these_prs)
    run()
    monkeypatch.setattr(ouro, "M", M)
    monkeypatch.setattr(ouro, "Manifest", Manifest)
    with pytest.raises((AssertionError, ManifestError)):
        run()


def test_the_older_configuration_test_holds_for_this_file_but_for_its_reduced_list(monkeypatch):
    """``test_yardstick_manifest.py::test_configuration_file_is_what_the_program_runs`` asserts ``reduced == []``
    (tests/conftest.py marks this configuration's case); with the list emptied on both sides it passes as it stands."""
    older = importlib.import_module("test_yardstick_manifest")
    view = Manifest(REPO_ROOT)
    load, entry = view.load_config, view.config_entry
    monkeypatch.setattr(view, "load_config", lambda name: dict(load(name), reduced=[]))
    monkeypatch.setattr(view, "config_entry", lambda name: dict(entry(name), reduced=[]))
    monkeypatch.setattr(older, "M", view)
    older.test_configuration_file_is_what_the_program_runs(NAME)
    monkeypatch.setattr(older, "M", M)
    with pytest.raises(AssertionError):
        older.test_configuration_file_is_what_the_program_runs(NAME)
