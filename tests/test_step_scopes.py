"""The compiled step's scope map (``utils/step_scopes.py``): the parser on
op_names and a module written by hand and on REAL lowerings (a scanned,
checkpointed gpt2 through a ``Trainer``; every family's step at its cell's size
stands the same checks in ``tests/test_tpu_compile.py``, on the text it already
compiles for a described v5e), what the
trainer remembers of a step call, who may ask for the map and when, and the
file a ``DVC_PROFILE_DIR`` run leaves beside its profile."""

import collections
import glob
import json
import os
import threading
import time

import jax
import pytest

from benchmark.manifest import REPO_ROOT, Manifest
from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.training.optim import make_optimizer
from distributedvolunteercomputing_tpu.training.steps import TrainState, make_train_step
from distributedvolunteercomputing_tpu.training.trainer import Trainer
from distributedvolunteercomputing_tpu.utils import step_scopes as ss
from distributedvolunteercomputing_tpu.utils.jaxenv import compile_log

TINY_GPT2 = dict(vocab=128, max_len=32, d_model=64, n_heads=4, n_layers=2, d_ff=128)  # scanned, remat on
M = Manifest(REPO_ROOT)


def off_thread(fn, *args):
    """``fn(*args)`` on another thread than the caller's."""
    box = {}

    def run():
        try:
            box["out"] = fn(*args)
        except BaseException as e:  # noqa: BLE001 - handed to the caller
            box["err"] = e

    t = threading.Thread(target=run, name="asks-for-scopes")
    t.start()
    t.join()
    if "err" in box:
        raise box["err"]
    return box["out"]


# -- op_names and a module by hand ---------------------------------------------------


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp()/while/body/closed_call/attention/dot_general", ("attention", "fwd")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mlp/tanh", ("mlp", "refwd")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/attention/transpose", ("attention", "bwd")),
    ("jit(step)/jvp(loss_head)/while/body/closed_call/jit(take_along_axis)/gather", ("loss_head", "fwd")),
    ("jit(step)/transpose(jvp(loss_head))/mul;jit(step)/optimizer/add", ("loss_head", "bwd")),
    ("jit(step)/optimizer/mul", ("optimizer", "fwd")),
    # the innermost word counts: a router's scope inside the expert block's
    ("jit(step)/jvp()/moe/moe_route/top_k", ("moe_route", "fwd")),
    ("jit(step)/jvp()/moe_route/dot_general", ("moe_route", "fwd")),
    ("jit(step)/jvp()/while/body/closed_call/kda/while/body/dot_general", ("kda", "fwd")),
    ("jit(step)/jvp()/conv_mixer/jit(_attention)/mul", ("conv_mixer", "fwd")),     # `_attention` is no word
    ("jit(step)/transpose(jvp())/while/body/dynamic_update_slice", (None, "bwd")),
    ("jit(step)/jvp()/while/body/closed_call/mlp_norm/mul", (None, "fwd")),       # nor is `mlp_norm`
    # the second origin of a merged instruction may come without its prefix
    ("jit(step)/transpose(jvp())/checkpoint/attention/transpose;checkpoint/mlp/bhqk/transpose", ("attention", "bwd")),
    ("", (None, "fwd")),
])
def test_scope_and_pass_of_an_op_name(op_name, want):
    assert ss.scope_and_pass(op_name) == want


def test_vocabulary_is_one_table_and_every_word_has_a_group():
    assert set(ss.VOCABULARY.values()) | {ss.OTHER} == set(ss.GROUPS)
    assert ss.GROUPS == ("attention", "mixer", "mlp", "moe", "loss_head", "optimizer", "other", "residual")
    assert {w for w, g in ss.VOCABULARY.items() if g == "residual"} == {"hc"}
    assert {w for w, g in ss.VOCABULARY.items() if g == "mixer"} == {"kda", "gdn", "conv_mixer", "mamba"}
    assert {w for w, g in ss.VOCABULARY.items() if g == "moe"} == {"moe", "moe_route"}
    assert ss.group_of(None) == ss.group_of("embedding") == "other"
    # the words the models' files really use are all in it (none added, renamed or moved by a reader)
    used = set()
    for path in glob.glob(os.path.join(REPO_ROOT, "distributedvolunteercomputing_tpu", "**", "*.py"), recursive=True):
        with open(path) as fh:
            text = fh.read()
        used |= {w for w in ss.VOCABULARY if f'named_scope("{w}")' in text}
    assert used >= set(ss.VOCABULARY) - {"mamba"}  # nemotron_h names its mixers through KIND_NAMES
    from distributedvolunteercomputing_tpu.models import nemotron_h

    assert {"mamba", "attention"} <= set(nemotron_h.KIND_NAMES.values())


HAND = """HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8,64]{1,0})->f32[8,64]{1,0}}

FileNames
1 "x.py"

%fused_computation.1 (param_0: f32[8,64], param_1: f32[8,64]) -> f32[8,64] {
  %param_0 = f32[8,64]{1,0} parameter(0)
  %param_1 = f32[8,64]{1,0} parameter(1)
  %constant.1 = f32[] constant(2), metadata={op_name="jit(step)/jvp()/while/body/closed_call"}
  %add.1 = f32[8,64]{1,0} add(%param_0, %param_1), metadata={op_name="jit(step)/jvp()/while/body/closed_call/attention/add"}
  ROOT %mul.1 = f32[8,64]{1,0} multiply(%add.1, %add.1), metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/mul"}
}

%fused_computation.2 (param_0.1: f32[8,64]) -> f32[8,64] {
  %param_0.1 = f32[8,64]{1,0} parameter(0)
  %constant.2 = f32[] constant(2), metadata={op_name="jit(step)/jvp()/while/body/closed_call"}
  ROOT %tanh.1 = f32[8,64]{1,0} tanh(%param_0.1), metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/tanh"}
}

%body (arg: (s32[], f32[8,64])) -> (s32[], f32[8,64]) {
  %arg = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}) parameter(0)
  %gte.1 = f32[8,64]{1,0} get-tuple-element(%arg), index=1
  %add_mul_fusion = f32[8,64]{1,0:T(8,128)(2,1)S(1)} fusion(%gte.1, %gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/mul" stack_frame_id=3}
  %wrapped_tanh = f32[8,64]{1,0} fusion(%add_mul_fusion), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mlp/tanh"}
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  ROOT %tuple.1 = (s32[], f32[8,64]{1,0}) tuple(%gte.0, %wrapped_tanh)
}

ENTRY %main.1 (x.1: f32[8,64]) -> f32[8,64] {
  %x.1 = f32[8,64]{1,0} parameter(0)
  %while.1 = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)(2,1)}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/kda/while"}
  ROOT %head = f32[8,64]{1,0} custom-call(%x.1), custom_call_target="x", metadata={op_name="jit(step)/transpose(jvp(loss_head))/mul;jit(step)/optimizer/add"}
}
"""


def test_scope_map_of_a_module_written_by_hand():
    got = ss.scope_map(HAND)
    # a fusion's own instructions are inside its event: not in the map
    assert set(got) == {"arg", "gte.1", "add_mul_fusion", "wrapped_tanh", "gte.0", "tuple.1", "x.1", "while.1", "head"}
    assert got["add_mul_fusion"] == {"scope": "mlp", "pass": "fwd", "mixed": True,
                                     "result": "f32[8,64]{1,0:T(8,128)(2,1)S(1)}"}
    # a constant under no word blurs nothing
    assert got["wrapped_tanh"] == {"scope": "mlp", "pass": "refwd", "mixed": False, "result": "f32[8,64]{1,0}"}
    assert got["while.1"] == {"scope": "kda", "pass": "bwd", "mixed": False,
                              "result": "(s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)(2,1)})"}
    assert got["head"]["scope"] == "loss_head" and got["head"]["pass"] == "bwd"
    # what the compiler made in a loop's body and gave no origin takes the loop's
    assert got["gte.0"] == {"scope": "kda", "pass": "bwd", "mixed": False, "result": "s32[]"}
    assert got["tuple.1"] == {"scope": "kda", "pass": "bwd", "mixed": False, "result": "(s32[], f32[8,64]{1,0})"}
    # at the top of the module there is no caller to ask
    assert got["x.1"] == {"scope": None, "pass": "fwd", "mixed": False, "result": "f32[8,64]{1,0}"}


# -- a real lowering, through a trainer ----------------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """ONE scanned, checkpointed gpt2 on THIS thread (the one that trains): two
    steps untraced, then three under ``DVC_PROFILE_DIR``; and its scope document
    asked for from another thread. (trainer, document, what was seen on the way)."""
    seen = {"asked_on": [], "remembered": []}
    real_ask, real_remember = ss.step_scopes, ss.remember
    profile_dir = str(tmp_path_factory.mktemp("profile"))
    patch = pytest.MonkeyPatch()
    patch.setattr(ss, "step_scopes",
                  lambda program: (seen["asked_on"].append(threading.current_thread().name), real_ask(program))[1])
    patch.setattr(ss, "remember", lambda fn, args: (seen["remembered"].append(fn.__name__), real_remember(fn, args)))
    patch.delenv("DVC_PROFILE_DIR", raising=False)
    began = time.time()
    try:
        tr = Trainer(get_model("gpt2_small", **TINY_GPT2), batch_size=2, optimizer="adam", lr=1e-3)
        tr.run(steps=2, log_every=0)
        seen["asked_untraced"] = list(seen["asked_on"])
        patch.setenv("DVC_PROFILE_DIR", profile_dir)
        patch.setenv("DVC_PROFILE_START", "1")
        patch.setenv("DVC_PROFILE_STEPS", "1")
        tr.run(steps=3, log_every=0)
    finally:
        patch.undo()
    seen["profile_dir"] = profile_dir
    doc = off_thread(ss.step_scopes, "jit(step)")
    seen["step_compiles"] = compile_log().summary("jit(step)", since=began)["program_compiles"]
    return tr, doc, seen


def test_real_lowering_gives_every_pass_of_both_block_scopes(trained):
    _, doc, seen = trained
    assert doc["program"] == "jit(step)" and doc["module"] == "jit_step"
    assert doc["vocabulary"] == ss.VOCABULARY and set(doc["seconds"]) == {"lower", "compile", "parse", "cache_load", "cache"}
    # the lowering is the call's own, so jax's in-process caches answer it: nothing compiles a second time
    assert doc["seconds"]["cache"] == "in_process" and seen["step_compiles"] == 1
    seen = collections.Counter((r["scope"], r["pass"]) for r in doc["map"].values())
    for scope in ("attention", "mlp"):
        for which in ss.PASSES:
            assert seen[(scope, which)] > 0, (scope, which)
    # the head makes its gradients in the loop that makes its loss (models/common.lm_xent_chunked, a custom_vjp):
    # all of it is forward, nothing is recomputed, and its backward rule (the two gradients times a cotangent that
    # value_and_grad makes the literal 1.0) leaves no instruction at all
    assert seen[("loss_head", "fwd")] and not seen[("loss_head", "refwd")] and not seen[("loss_head", "bwd")]
    assert seen[("optimizer", "fwd")] and not seen[("optimizer", "bwd")] and not seen[("optimizer", "refwd")]
    # the layer scan's own slices and stack updates are under no word, forward and backward
    assert seen[(None, "fwd")] and seen[(None, "bwd")]
    assert all(set(r) == {"scope", "pass", "result", "mixed"} and r["result"] for r in doc["map"].values())
    json.dumps(doc)  # what a profile run and the benchmark's reader write


def test_real_lowering_holds_merged_origins_and_a_mixed_fusion(trained):
    tr, doc, _ = trained
    text = tr._step_fn.lower(*ss._remembered["jit(step)"][1]).compile().as_text()
    merged = [line for line in text.splitlines() if 'op_name="' in line and ";" in line.split('op_name="')[1].split('"')[0]]
    assert merged, "XLA merged no instructions here: find another model for this case"
    for line in merged:
        name = line.split(" = ")[0].replace("ROOT", "").strip().lstrip("%")
        op_name = line.split('op_name="')[1].split('"')[0]
        if name in doc["map"]:
            first = ss.scope_and_pass(op_name.split(";")[0])
            assert (doc["map"][name]["scope"], doc["map"][name]["pass"]) == first
    mixed = [name for name, r in doc["map"].items() if r["mixed"]]
    assert mixed and all("fusion" in name for name in mixed)


def test_the_trainer_keeps_abstract_arguments_and_no_live_buffer(trained):
    tr, _, _ = trained
    fn, abstract, train_thread = ss._remembered["jit(step)"]
    assert fn is tr._step_fn and train_thread is threading.current_thread()
    leaves = jax.tree_util.tree_leaves(abstract)
    assert leaves and all(type(x) is jax.ShapeDtypeStruct for x in leaves)
    state, batch = abstract
    assert jax.tree_util.tree_structure(state) == jax.tree_util.tree_structure(tr.state)
    live = jax.tree_util.tree_leaves(tr.state)
    assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(state)] == [(x.shape, x.dtype) for x in live]
    # a sharding only where the call specified one (a committed array): an uncommitted array's is where it happened to be
    assert all(a.sharding in (None, x.sharding) for a, x in zip(jax.tree_util.tree_leaves(state), live))
    assert tr._scoped == {"step"}  # once a step function, not once a step


def test_the_map_is_refused_on_the_thread_that_trains_and_kept_by_program(trained):
    _, doc, _ = trained
    with pytest.raises(RuntimeError, match="thread that trains"):
        ss.step_scopes("jit(step)")
    assert off_thread(ss.step_scopes, "jit(step)") is doc  # built once
    assert ss.step_scopes("jit(no_such_step)") is None
    assert "jit(step)" in ss.remembered()


def test_a_newer_step_takes_the_programs_name(trained):
    _, doc, _ = trained

    def step(state, batch):
        return state + batch.sum(), {"loss": batch.sum()}

    newer_fn = jax.jit(step)
    off_thread(ss.remember, newer_fn, (jax.numpy.ones((4,)), jax.numpy.ones((2,))))  # another trainer's thread
    newer = ss.step_scopes("jit(step)")
    assert newer is not doc and 0 < len(newer["map"]) < len(doc["map"])
    assert not any(r["scope"] for r in newer["map"].values())


def test_a_step_over_a_mesh_is_described_by_its_named_shardings(eight_devices):
    from jax.sharding import NamedSharding

    from distributedvolunteercomputing_tpu.parallel.mesh import make_mesh

    tr = Trainer(get_model("gpt2_small", **{**TINY_GPT2, "n_layers": 1, "max_len": 16}), batch_size=4,
                 optimizer="adam", lr=1e-3, mesh=make_mesh(dp=2, tp=2))
    tr.run(steps=1, log_every=0)
    state, batch = ss._remembered["jit(step)"][1]
    assert all(isinstance(a.sharding, NamedSharding) for a in jax.tree_util.tree_leaves((state, batch)))
    doc = off_thread(ss.step_scopes, "jit(step)")
    assert doc["seconds"]["cache"] == "in_process"
    # the sums over `tp` and `dp` are instructions of the blocks whose products they complete
    reduces = {name: r for name, r in doc["map"].items() if name.startswith("all-reduce")}
    assert reduces and {r["scope"] for r in reduces.values()} >= {"attention", "mlp"}


def test_an_untraced_run_remembers_and_never_asks(trained):
    _, _, seen = trained
    assert seen["remembered"] == ["step"]  # once a step function, over five steps of two runs
    assert seen["asked_untraced"] == []


def test_a_profile_run_leaves_the_map_beside_the_profile_from_its_own_thread(trained):
    _, doc, seen = trained
    assert seen["asked_on"] == ["step-scopes"]
    with open(os.path.join(seen["profile_dir"], "step_scopes.json")) as fh:
        written = json.load(fh)
    assert written == json.loads(json.dumps(doc))  # built once: the accessor hands out what the writer made
    assert written["program"] == "jit(step)" and written["vocabulary"] == ss.VOCABULARY
    assert {r["pass"] for r in written["map"].values()} == set(ss.PASSES)
    assert os.path.isdir(os.path.join(seen["profile_dir"], "plugins"))  # the profile it stands beside
