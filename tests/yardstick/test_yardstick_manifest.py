"""The manifest, the files it names, and that a later PR can add by adding files."""

import dataclasses
import json
import os
import shutil

import pytest

from benchmark import readers, references
from benchmark.manifest import NAME_RE, REPO_ROOT, UNIT_RE, Manifest, ManifestError

M = Manifest(REPO_ROOT)
DOC = M.doc
ALL_METRICS = DOC["end_to_end"] + DOC["per_layer"]


def test_manifest_has_exactly_the_contract_keys_and_checks():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    M.check()
    assert 1 <= DOC["run_seconds"] <= 51
    assert len(json.dumps(DOC)) < 64 * 1024
    for path in DOC["paths"]:
        assert os.path.isdir(os.path.join(REPO_ROOT, path))
    # the command names no file outside `paths`
    assert DOC["command"][1].startswith("benchmark/")


@pytest.mark.parametrize("cell", DOC["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_by_name(cell):
    cfg = M.load_config(cell["config"])
    traffic = M.load_traffic(cell["traffic"])
    assert cfg["name"] == cell["config"] and traffic["name"] == cell["traffic"]
    assert cfg["chips"] == cell["chips"]
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    e2e = {m["name"] for m in M.metrics_for(cell["name"], "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = M.metrics_for(cell["name"], "per_layer")
    assert layer and all(m["moves"] in e2e for m in layer)


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in DOC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(DOC["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("cell", ["smallthinker-solo-16k", "lfm2-solo-8k", "glm47-flash-solo-8k",
                                  "nemotron3-nano-solo-8k", "kimi-linear-solo-8k"])
def test_a_cell_that_times_inside_an_lr_warmup_says_so(cell):
    """The five share cells whose configuration carries ``volunteer.warmup_steps``:
    the window (steps 5 to under 100) lies in the warm-up's first twentieth, so the
    cell's ``why`` says what schedule its number belongs to."""
    entry = M.cell(cell)
    cfg = M.load_config(entry["config"])
    assert cfg["volunteer"]["warmup_steps"] == cfg["assumed"]["lr_warmup"]["warmup_steps"] == 2000
    assert "in LR warm-up" in entry["why"] and len(entry["why"]) <= 200


def test_only_a_cell_whose_configuration_carries_a_warmup_says_it_times_inside_one():
    for w in DOC["workloads"]:
        carries = "warmup_steps" in M.load_config(w["config"])["volunteer"]
        assert ("in LR warm-up" in w["why"]) == carries, w["name"]


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_names_units_and_bounds(metric):
    assert NAME_RE.match(metric["name"]) and UNIT_RE.match(metric["unit"])
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in DOC["end_to_end"]:
        assert set(metric) <= allowed | {"bound"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        assert metric["layer"] == metric["layer"].strip() and len(metric["layer"]) <= 200


@pytest.mark.parametrize("metric", DOC["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_reader_agrees_with_the_manifest(metric):
    path = M.layer_metric_path(metric["name"])
    if path.endswith(".json"):
        with open(path) as fh:
            decl = json.load(fh)
        for key in ("name", "layer", "unit", "moves", "source", "better"):
            assert decl[key] == metric[key], key
    else:
        with open(path) as fh:
            head = fh.read()
        assert metric["name"] in head and "def compute(run)" in head


@pytest.mark.parametrize("name", [c["name"] for c in DOC["configs"]])
def test_configuration_file_is_what_the_program_runs(name):
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    cfg = M.load_config(name)
    entry = M.config_entry(name)
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"] == []
    bundle = get_model(cfg["registry_model"], **cfg["model_overrides"])
    references.load(cfg["family"]).check_config(bundle.config, cfg)
    fields = {f.name for f in dataclasses.fields(VolunteerConfig)}
    assert set(cfg["volunteer"]) <= fields


@pytest.mark.parametrize("name", sorted({w["traffic"] for w in DOC["workloads"]}))
def test_traffic_file_uses_volunteer_config_fields(name):
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    traffic = M.load_traffic(name)
    fields = {f.name for f in dataclasses.fields(VolunteerConfig)}
    assert set(traffic["volunteer"]) <= fields
    # everything not named stays at the volunteer's default: the senses are on
    for sense in ("telemetry", "health_probe", "watchdog"):
        assert sense not in traffic["volunteer"]
    for peer in traffic["peers"]:
        assert peer["peer_id"] < traffic["volunteer"]["peer_id"], "the stub must sort first to lead"


def test_program_config_mismatch_is_refused():
    from distributedvolunteercomputing_tpu.models import get_model

    cfg = dict(M.load_config("gpt2-medium"), n_layer=23)
    with pytest.raises(ValueError, match="n_layer"):
        references.load("gpt2").check_config(get_model("gpt2_medium").config, cfg)


def test_a_later_pr_adds_a_config_a_mix_a_cell_and_a_metric_with_files_only(tmp_path):
    """Copy the benchmark, add one throw-away file of each kind and one
    manifest entry each, edit nothing that exists: it resolves and reads."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(REPO_ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "benchmark"
    cfg = M.load_config("gpt2-medium")
    cfg.update(name="throwaway-model")
    (bench / "configs" / "throwaway-model.json").write_text(json.dumps(cfg))
    traffic = M.load_traffic("solo")
    traffic.update(name="throwaway-mix")
    (bench / "traffic" / "throwaway-mix.json").write_text(json.dumps(traffic))
    (bench / "layer_metrics" / "throwaway.count.json").write_text(json.dumps({
        "name": "throwaway.count", "layer": "train loop", "unit": "count",
        "better": "higher", "moves": "tok_s_chip", "source": "program_counter",
        "read": {"stat": "steps.window", "scale": 2},
    }))
    doc = json.loads(json.dumps(DOC))
    doc["configs"].append({"name": "throwaway-model", "source": cfg["source"],
                           "file": "benchmark/configs/throwaway-model.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "throwaway-cell", "config": "throwaway-model",
                             "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "throwaway.count", "unit": "count", "better": "higher",
                             "source": "program_counter", "layer": "train loop",
                             "moves": "tok_s_chip", "workloads": ["throwaway-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(ManifestError, match="throwaway-cell"):
        Manifest(str(root)).check()  # the cell does not report the metric it would move
    next(e for e in doc["end_to_end"] if e["name"] == "tok_s_chip")["workloads"].append(
        "throwaway-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    m = Manifest(str(root))
    m.check()
    assert m.load_config(m.cell("throwaway-cell")["config"])["name"] == "throwaway-model"
    names = [x["name"] for x in m.metrics_for("throwaway-cell", "per_layer")]
    assert "throwaway.count" in names and "round.wire_s" not in names
    value = readers.compute(m.layer_metric_path("throwaway.count"), {"stats": {"steps.window": 21}})
    assert value == 42.0
    with pytest.raises(ManifestError):
        m.cell("no-such-cell")


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda c: c["name"])
def test_the_reference_check_runs_at_the_timed_length_or_the_file_says_why_not(entry):
    """`correct` is decided at the length the cell times (one sequence of it), since PR 69
    in `kimi-linear-solo-8k` too: the check no longer holds the training state, so memory
    sets no check's length. The three older configurations check half or a quarter of
    their timed length for another reason, and their `size_why` says so."""
    cfg = M.load_config(entry["name"])
    rc = cfg["reference_check"]
    timed = references.load(cfg["family"]).sizes(cfg)["seq_len"]
    if "bytes" in cfg.get("parameters", {}):
        said = cfg["parameters"]["bytes"]
        assert "16 a parameter in the step" in said and "12 at the reference check" in said, said
        assert rc["seq_len"] == timed and rc["sequences"] == 1
        assert "24" in said and "PR 69" in said  # what the cuts were made under stays in the text
    else:
        assert entry["name"] in ("gpt2-medium", "gpt2-large", "olmoe-1b-7b")
        assert rc["seq_len"] < timed and "not set by memory" in rc["size_why"]


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda c: c["name"])
def test_a_configuration_that_names_the_tasks_initial_parameters_says_why(entry):
    """``init_seed`` in a configuration file is the TASK's seed for the initial parameters
    (``benchmark/run.py:init_seed_of``): every run starts from the same router and ``--seed``
    moves the data. Only a configuration that holds a share of its experts has a reason to
    (its step's work follows the rows its router sends them), and it gives the readings."""
    from benchmark import run

    cfg = M.load_config(entry["name"])
    assert run.init_seed_of(cfg, 7) == cfg.get("init_seed", 7)
    if "init_seed" not in cfg:
        assert "init_seed_why" not in cfg
        return
    assert isinstance(cfg["init_seed"], int) and 0 <= cfg["init_seed"] < 2 ** 32
    assert "experts_held" in cfg["model_overrides"], "nothing in a dense step follows the seed"
    why = cfg["init_seed_why"]
    assert "tok_s_chip" in why and "PR 69" in why and "refused" in why and len(why) > 200
