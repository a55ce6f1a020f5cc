"""What the expert families share (models/moe.py, models/common.py, the
registry's one builder of a language-model bundle), at the tiny sizes the
rehearsal configurations build: a layer's statistics against a plain count
from its routes and the dispatch's own results, the metric keys each family's
step returns, the sigmoid router's selection bias in the choice and not in the
weights, the held slice's check, which way the imports point, and which
registry names hand the step leaves of its own."""

import ast
import dataclasses
import functools
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from distributedvolunteercomputing_tpu import models as models_package
from distributedvolunteercomputing_tpu.models import (
    common, get_model, glm4_moe_lite, kimi_linear, laguna, lfm2, list_models, moe, nemotron_h, smallthinker,
)
from distributedvolunteercomputing_tpu.models.registry import _LANGUAGE_MODELS
from distributedvolunteercomputing_tpu.ops import moe_dispatch
from distributedvolunteercomputing_tpu.training.steps import TrainState
from tests import tiny_models

SHARED_KEYS = {"loss", "lm_loss", "aux_loss", "moe_load_max", "moe_load_mean", "moe_rows_held",
               "moe_rows_moved", "moe_dropped"}
STEPPED_KEYS = {"moe_chunks_extra", "moe_bias_max", "moe_bias_min", "moe_bias_moved"}


def first(tree, n=1):
    return jax.tree_util.tree_map(lambda a: a[(0,) * n], tree)


def softmax_mean(router, x, h):
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    return (e / e.sum(-1, keepdims=True)).mean(0)


def sigmoid_share_mean(router, x, h):
    s = 1.0 / (1.0 + np.exp(-(np.asarray(h, np.float64) @ np.asarray(router, np.float64))))
    return (s / s.sum(-1, keepdims=True)).mean(0)


# family -> its module, registry name, rehearsal configuration, one expert layer
# of the tree with ``_layer``'s own arguments, the statistics it starts from, the
# router's distribution its balancing term reads (of the layer's input ``x`` or
# the expert layer's ``h``), and the metric keys its step returns beside SHARED_KEYS
FAMILIES = {
    "laguna": (laguna, "laguna_xs2", "tiny-rehearsal-laguna", lambda b: b[1], (1,),
               dict(balanced=16), sigmoid_share_mean, set()),
    "smallthinker": (smallthinker, "smallthinker_21b_a3b", "tiny-rehearsal-smallthinker",
                     lambda b: first(b["sliding"], 2), ("sliding",),
                     dict(balanced=16, act_zeros=True, chunks_extra=True), softmax_mean,
                     {"moe_act_zero_share", "moe_chunks_extra"}),
    "lfm2": (lfm2, "lfm2_24b_a2b", "tiny-rehearsal-lfm2", lambda b: first(b[1]),
             ("full_attention", "sparse"), dict(chunks_extra=True), None, STEPPED_KEYS),
    "glm": (glm4_moe_lite, "glm4_7_flash", "tiny-rehearsal-glm", lambda b: first(b[1]), ("sparse",),
            dict(chunks_extra=True), None, STEPPED_KEYS),
    # a block is one mixer there: the expert BLOCK stands for the layer (its leaves at the top of a unit's tree)
    "nemotron": (types.SimpleNamespace(_layer=nemotron_h._experts, moe_dispatch=nemotron_h.moe_dispatch,
                                       SHARE_ROWS_SLACK=nemotron_h.SHARE_ROWS_SLACK),
                 "nemotron3_nano_30b_a3b", "tiny-rehearsal-nemotron",
                 lambda b: {k: v for k, v in first(b[0]).items() if k != "before"}, (),
                 dict(act_zeros=True, chunks_extra=True), None,
                 STEPPED_KEYS | {"moe_act_zero_share", "ssm_carry_share"}),
    # its latent-attention layer: a KDA layer's statistics hold its scan's counters beside the share's
    "kimi": (kimi_linear, "kimi_linear_48b_a3b", "tiny-rehearsal-kimi", lambda b: first(b[2]),
             ("latent_attention", "sparse"), dict(chunks_extra=True), None,
             STEPPED_KEYS | {"kda_carry_share", "kda_decay_min", "kda_beta_mean"}),
}


def tiny(family):
    """The family's module and its tiny bundle: the one ``tests/tiny_models.py`` keeps for every file."""
    module, name, rehearsal = FAMILIES[family][:3]
    assert rehearsal == f"tiny-rehearsal-{family}"
    bundle = tiny_models.bundle(family)
    assert bundle.name == name
    return module, bundle


@pytest.mark.parametrize("family", FAMILIES)
def test_a_layers_statistics_are_a_plain_count_of_its_routes_and_the_dispatchs_results(family, monkeypatch):
    module, bundle = tiny(family)
    _, _, _, layer_of, args, zero, probs_of, _ = FAMILIES[family]
    cfg = bundle.config
    seen = {}
    holder = module if hasattr(module, "share_glu_experts") else module.moe_dispatch
    real = moe_dispatch.share_glu_experts

    def recorded(h, *a, **kw):
        out = real(h, *a, **kw)
        seen.update(h=h, dispatch=out[1:], slack=kw.get("slack"))
        return out

    monkeypatch.setattr(holder, "share_glu_experts", recorded)
    params = jax.tree_util.tree_map(lambda a: a * 3.0, jax.jit(bundle.init)(jax.random.PRNGKey(3)))
    p = layer_of(params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, cfg.max_len, cfg.d_model))
    start = moe.zero_share_stats(**zero)

    @jax.jit
    def layer(stats):
        """The layer's statistics and routes, and what the dispatch was handed and returned while it was traced."""
        _, new, out = module._layer(p, x, stats, cfg, *args)
        return new, out, {k: seen[k] for k in ("h", "dispatch")}

    stats, out, told = layer(start)
    seen.update(told)   # the values, where the trace left tracers
    assert set(stats) == set(start)
    top_idx = np.asarray(out[0] if isinstance(out, tuple) else out)
    s = top_idx.shape[0]
    assert top_idx.shape == (2 * cfg.max_len, cfg.top_k)
    chosen = np.bincount(top_idx.ravel(), minlength=cfg.n_experts)
    load = chosen[cfg.expert_offset:cfg.expert_offset + cfg.experts_held]
    group_sizes, dropped, moved, act_zeros = seen["dispatch"]
    assert np.array_equal(np.asarray(group_sizes), load)
    want = {"load_max": load.max(), "rows_held": load.sum(), "rows_moved": int(moved), "dropped": int(dropped)}
    if "choices" in start:
        want["choices"] = chosen / s
        want["probs"] = probs_of(p["router"], x.reshape(s, -1), seen["h"])
    if "act_zeros" in start:
        want["act_zeros"] = int(act_zeros)
        assert 0 < want["act_zeros"] < load.sum() * cfg.d_expert
    if "chunks_extra" in start:
        cap = moe_dispatch.share_rows_bound(s, cfg.top_k, cfg.experts_held, cfg.n_experts, seen["slack"])
        want["chunks_extra"] = -(-int(moved) // cap) - 1
        assert seen["slack"] == module.SHARE_ROWS_SLACK
    if isinstance(out, tuple):
        assert np.array_equal(np.asarray(out[1]), chosen)  # what the stepped bias's rule reads
    assert set(want) == set(stats)
    for key, value in want.items():
        np.testing.assert_allclose(np.asarray(stats[key]), value, rtol=1e-5, err_msg=key)
    # a second layer adds to the sums and keeps the fullest
    twice, _, _ = layer(stats)
    for key in want:
        both = want[key] if key == "load_max" else 2 * np.asarray(want[key])
        np.testing.assert_allclose(np.asarray(twice[key]), both, rtol=1e-5, err_msg=key)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_familys_step_returns_the_metric_keys_it_returned(family):
    module, bundle = tiny(family)
    own = FAMILIES[family][7]
    tx, step = tiny_models.train_step(bundle, "adam", lr=1e-3)   # the step the family's own file takes
    state = TrainState.create(jax.jit(bundle.init)(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(1))
    batch = bundle.make_batch(jax.random.PRNGKey(2), 2)
    _, metrics, _ = tiny_models.programs(bundle).loss_and_routes(state.params, batch)   # bundle.loss_fn's two results
    counts = {moe.COUNTS} if bundle.stepped else set()
    assert set(metrics) == SHARED_KEYS | own | counts
    _, metrics = step(state, batch)
    assert set(metrics) == SHARED_KEYS | own | {"grad_norm"}
    cfg = bundle.config
    assert float(metrics["moe_load_mean"]) == 2 * cfg.max_len * cfg.top_k / cfg.n_experts
    assert float(metrics["moe_dropped"]) == 0.0 and float(metrics["moe_rows_moved"]) >= float(metrics["moe_rows_held"]) > 0
    if bundle.stepped:
        assert float(metrics["aux_loss"]) == 0.0 and float(metrics["loss"]) == float(metrics["lm_loss"])
    else:
        assert float(metrics["aux_loss"]) >= 1.0  # E sum f P is 1 at an even router and more elsewhere
        assert float(metrics["loss"]) == pytest.approx(
            float(metrics["lm_loss"]) + cfg.aux_coef * float(metrics["aux_loss"]), rel=1e-6)


@pytest.mark.parametrize("module,config,scale", [
    (lfm2, lfm2.LFM2Config, 1.0), (glm4_moe_lite, glm4_moe_lite.Glm4MoeLiteConfig, 1.8)], ids=["lfm2", "glm"])
def test_the_bias_changes_the_choice_and_not_the_weights_which_sum_to_the_scaling_factor(module, config, scale):
    eps = module.ROUTE_EPS
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (12,))
    scores = np.asarray(jax.nn.sigmoid(x @ w), np.float64)
    idx0, w0, s0 = moe.route(w, x, 4, scale, jnp.zeros(12), eps)
    idx1, w1, _ = moe.route(w, x, 4, scale, bias, eps)
    np.testing.assert_allclose(np.asarray(s0), scores, rtol=1e-5)
    assert np.array_equal(np.sort(np.asarray(idx0), -1), np.sort(np.argsort(-scores, -1)[:, :4], -1))
    biased = np.argsort(-(scores + np.asarray(bias)), -1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(idx1), -1), np.sort(biased, -1))
    assert not np.array_equal(np.sort(np.asarray(idx0), -1), np.sort(np.asarray(idx1), -1))
    # the weights are the chosen experts' own scores over their sum: the bias is not in them
    chosen = np.take_along_axis(scores, np.asarray(idx1), axis=-1)
    np.testing.assert_allclose(np.asarray(w1), scale * chosen / (chosen.sum(-1, keepdims=True) + eps), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w1).sum(-1), scale, rtol=1e-4)   # routed_scaling_factor
    # where both pick the same four, the weights are the same numbers
    same = np.all(np.sort(np.asarray(idx0), -1) == np.sort(np.asarray(idx1), -1), axis=-1)
    assert same.any() and not same.all()
    np.testing.assert_allclose(np.sort(np.asarray(w0)[same], -1), np.sort(np.asarray(w1)[same], -1), rtol=1e-6)
    # and no gradient reaches it
    g = jax.grad(lambda b: jnp.sum(moe.route(w, x, 4, scale, b, eps)[1] ** 2))(bias)
    assert not np.any(np.asarray(g))
    # with no bias at all the router is the one of a zero bias (Laguna's)
    idx, plain, _ = moe.route(w, x, 4, scale)
    assert np.array_equal(np.asarray(idx), np.asarray(idx0))
    np.testing.assert_allclose(np.asarray(plain), np.asarray(w0), rtol=1e-6)
    assert (lfm2.ROUTE_EPS, glm4_moe_lite.ROUTE_EPS) == (1e-6, 1e-20) and config().routed_scale == scale


@pytest.mark.parametrize("family", FAMILIES)
def test_a_config_whose_held_experts_are_no_slice_of_the_routers_is_refused(family):
    _, bundle = tiny(family)
    cfg = bundle.config  # 4 of 16 from 4 on, top-4 (top-3 SmallThinker)
    for change in (dict(expert_offset=13), dict(experts_held=0), dict(expert_offset=-1), dict(experts_held=17)):
        with pytest.raises(ValueError, match="are not a slice of the 16"):
            dataclasses.replace(cfg, **change)
    for top_k in (0, 17):
        with pytest.raises(ValueError, match="top_k"):
            dataclasses.replace(cfg, top_k=top_k)
    assert dataclasses.replace(cfg, expert_offset=12).expert_offset == 12  # the last slice is one


def imports_of(path: pathlib.Path):
    """Every module a file imports, absolute names, wherever in the file."""
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            found.add(node.module)
            found.update(f"{node.module}.{a.name}" for a in node.names)
    return found


def test_no_model_knows_the_registry_and_the_shared_helpers_know_no_model():
    package = pathlib.Path(models_package.__file__).parent
    registry = "distributedvolunteercomputing_tpu.models.registry"
    files = sorted(package.glob("*.py"))
    assert {"moe.py", "common.py", "gpt2_moe.py", "registry.py"} <= {f.name for f in files}
    for path in files:
        if path.name not in ("registry.py", "__init__.py"):
            assert not any(m.startswith(registry) for m in imports_of(path)), path.name
    models = {f"distributedvolunteercomputing_tpu.models.{f.stem}" for f in files} - {
        "distributedvolunteercomputing_tpu.models.common"}
    assert not imports_of(package / "common.py") & models
    # what the expert families share rests on the helpers and the dispatch alone
    assert not imports_of(package / "moe.py") & (models - {"distributedvolunteercomputing_tpu.models.moe"})


def test_the_loop_and_the_swarm_name_no_models_metric_and_no_op_keeps_an_observer_slot():
    """What a family's step says of itself is declared in ``models/`` (``spans(cfg)``) and what a trace chose is
    noted where it is chosen (``utils/traced.py``): the train loop and the volunteer carry both without a name."""
    package = pathlib.Path(models_package.__file__).parent.parent
    for path in [*sorted((package / "training").glob("*.py")), package / "swarm" / "volunteer.py"]:
        assert not re.findall(r"\b(?:moe|ssm|kda|diffusion|attention_bd)_[a-z]\w*", path.read_text()), path.name
    for path in [*(package / "ops").glob("*.py"), *(package / "models").glob("*.py")]:
        assert not re.findall(r"def set_\w*observer", path.read_text()), path.name
    assert not any(m.startswith("distributedvolunteercomputing_tpu.ops") for m in imports_of(package / "training" / "trainer.py"))


@functools.lru_cache(maxsize=None)
def tiny_language_model(name):
    """The registry's language model ``name`` at a tiny size (an expert family's at its rehearsal's) with the
    shapes of its parameters and of what its loss returns: traced, never compiled."""
    rehearsals = {"olmoe_1b_7b": "tiny-rehearsal-olmoe", "sdar_30b_a3b": "tiny-rehearsal-sdar",
                  "ouro_2_6b": "tiny-rehearsal-ouro", "qwen3_next_80b_a3b": "tiny-rehearsal-qwen3-next",
                  "xing4_29b_a4b": "tiny-rehearsal-xing4",
                  **{n: r for _, n, r, *_ in FAMILIES.values()}}
    if name in rehearsals:
        overrides = Manifest().load_config(rehearsals[name])["model_overrides"]
    else:
        overrides = dict(n_layers=2, d_model=32, n_heads=2, vocab=64, max_len=16)
        if name == "llama_lora":
            overrides["n_kv_heads"] = 2
    bundle = get_model(name, **overrides)
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    batch = jax.eval_shape(lambda: bundle.make_batch(jax.random.PRNGKey(1), 2))
    loss, metrics = jax.eval_shape(bundle.loss_fn, shapes, batch, jax.random.PRNGKey(2))
    return bundle, overrides, shapes, loss, metrics


@pytest.mark.parametrize("name", sorted(_LANGUAGE_MODELS))
def test_a_language_models_bundle_names_stepped_leaves_only_where_the_step_moves_a_bias(name):
    bundle, overrides, shapes, loss, metrics = tiny_language_model(name)
    assert bundle.name == name and name in list_models()
    for key, value in overrides.items():
        got = getattr(bundle.config, key)
        assert (list(got) if isinstance(got, tuple) else got) == value
    assert (bundle.stepped is not None) == (name in ("lfm2_24b_a2b", "glm4_7_flash", "nemotron3_nano_30b_a3b",
                                                     "kimi_linear_48b_a3b", "xing4_29b_a4b"))
    if bundle.stepped is not None:
        assert bundle.stepped.signal == moe.COUNTS
    # the swarm averages the whole tree of every model but the one with adapters
    lora = getattr(bundle.config, "lora_rank", 0) > 0
    assert lora == (name == "llama_lora")
    picked = jax.eval_shape(bundle.avg_select, shapes)
    assert (jax.tree_util.tree_structure(picked) == jax.tree_util.tree_structure(shapes)) == (not lora)
    assert loss.shape == () and "loss" in metrics


SPANS = {"nemotron3_nano_30b_a3b": {"moe.route", "ssm.scan"}, "kimi_linear_48b_a3b": {"moe.route", "kda.scan"},
         **dict.fromkeys(("olmoe_1b_7b", "laguna_xs2", "smallthinker_21b_a3b", "lfm2_24b_a2b", "glm4_7_flash",
                          "sdar_30b_a3b"), {"moe.route"}),
         "ouro_2_6b": {"recur.exit"}, "qwen3_next_80b_a3b": {"moe.route", "gdn.scan", "attention.gate"},
         "xing4_29b_a4b": {"moe.route", "hc.mix"}}


@pytest.mark.parametrize("name", sorted(_LANGUAGE_MODELS))
def test_each_key_a_bundles_spans_declare_is_a_key_its_tiny_step_returns(name):
    bundle, _, _, _, metrics = tiny_language_model(name)
    assert set(bundle.spans) == SPANS.get(name, set())
    returned = {k for k, v in metrics.items() if v.shape == ()}   # the step takes the stepped rule's signal out: no scalar
    declared = [k for span in bundle.spans.values() for k in span.keys]
    assert len(declared) == len(set(declared)) and set(declared) <= returned
    if bundle.spans:  # and nothing a family's step says beyond its loss is left off its spans
        assert returned - set(declared) <= {"loss", "z_loss"}
    for span in bundle.spans.values():
        assert not set(span.attrs) & set(span.keys) and not set(span.noted) & (set(span.attrs) | set(span.keys))


def test_a_bundle_that_declares_a_span_and_returns_no_routing_still_gets_it_recorded():
    """The loop records what the bundle declares, whatever the keys are called: a stub model with a scan and no
    experts gets its span at the log points and every ``ROUTE_EVERY`` steps, with the declaration's own attributes
    and the label a trace noted; a declared span none of whose keys the step returns is not recorded."""
    from distributedvolunteercomputing_tpu.models.registry import ModelBundle
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import ROUTE_EVERY, Trainer
    from distributedvolunteercomputing_tpu.utils import traced

    def loss_fn(params, batch, rng):
        traced.note("stub_scan", form="plain", width=3)
        loss = jnp.sum((params["w"] - batch["x"].mean()) ** 2)
        return loss, {"loss": loss, "stub_carry_share": jnp.float32(0.25), "unnamed": jnp.float32(1.0)}

    bundle = ModelBundle(
        name="stub", config=None, init=lambda rng: {"w": jnp.zeros((3,))}, loss_fn=loss_fn,
        make_batch=lambda rng, bs: {"x": jax.random.normal(rng, (bs, 3))},
        spans={"stub.scan": common.StepSpan(("stub_carry_share", "stub_absent"), {"stub_layers": 2},
                                            {"stub_form": ("stub_scan", "form")}),
               "stub.never": common.StepSpan(("stub_absent",))})
    tel = Telemetry(peer_id="v", enabled=True)
    trainer = Trainer(bundle, batch_size=2, lr=1e-2, optimizer="sgd", tracer=tel.tracer)
    trainer.run(steps=ROUTE_EVERY + 5, log_every=ROUTE_EVERY + 5)
    spans = sorted((s for s in tel.tracer.spans() if s["name"].startswith("stub.")), key=lambda s: s["attrs"]["step"])
    # noted between the log points without a wait (recorded once its scalars were ready), and at the log point
    assert [(s["name"], s["attrs"]["step"]) for s in spans] == [("stub.scan", ROUTE_EVERY), ("stub.scan", ROUTE_EVERY + 5)]
    assert spans[1]["parent"] == "loop.log_sync" != spans[0].get("parent")
    for s in spans:
        assert s["attrs"] == {"step": s["attrs"]["step"], "stub_carry_share": 0.25, "stub_layers": 2, "stub_form": "plain"}
