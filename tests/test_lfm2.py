"""LFM2-24B-A2B on the normal path (models/lfm2.py), at a tiny size on the CPU:
the program against the plain reference (benchmark/references/lfm2.py) on the
loss and every leaf's gradient, with both mixer kinds, the dense layer, a held
share, a run of three stacked layers and a tied head; the reference against
each term left out; the convolution against ``lax.conv_general_dilated`` and a
loop over positions, plain and as a kernel; causality; the selection bias in
the choice and not in the weights; the step's rule moving the bias by exactly
``gamma`` and nothing else touching it; the shares adding up to the uncut
layer; QK-norm over each head through ``attention_core``; the list-of-runs
tree through count_params, the sharding rules, a checkpoint and the train
loop's span."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.manifest import Manifest
from benchmark.references import lfm2 as ref
from distributedvolunteercomputing_tpu.models import common, get_model, lfm2
from distributedvolunteercomputing_tpu.ops import moe_dispatch, short_conv
from distributedvolunteercomputing_tpu.training import steps
from distributedvolunteercomputing_tpu.utils import traced
from tests import tiny_models

TINY = tiny_models.rehearsal("lfm2")
OVERRIDES = TINY["model_overrides"]
HP = ref.hyper(TINY)
KINDS = ["conv", "full_attention", "conv", "conv", "conv"]


@pytest.fixture(autouse=True)
def tight_chunks(monkeypatch):
    """A chunk a quarter over the even share, whatever the model's own bound
    becomes (it is the levelled router's, ``models/lfm2.SHARE_ROWS_SLACK``, and
    no longer the dispatch's default): under matrices scaled up and a seeded
    bias several chunks run at these sizes."""
    monkeypatch.setattr(lfm2, "SHARE_ROWS_SLACK", 1.25)


def seeded(scale: float = 3.0, bias: float = 0.0, **overrides):
    """The tiny model (a dense conv layer, an attention expert layer, a run of
    three conv expert layers; 8 query heads over 2 key/value heads; experts
    4..7 of 16 held, top-4) with matrices scaled up so that every term matters,
    a seeded selection bias of that size where asked, and two seeded sequences."""
    bundle = tiny_models.bundle("lfm2", **overrides)
    params = jax.jit(bundle.init)(jax.random.PRNGKey(3))

    def scaled(path, x):
        name = jax.tree_util.keystr(path)
        if lfm2.moe.is_bias(path):
            return bias * jax.random.normal(jax.random.PRNGKey(11), x.shape)
        return x if "ln_" in name or "_norm" in name else x * scale

    params = jax.tree_util.tree_map_with_path(scaled, params)
    rng = np.random.default_rng(0)
    t, v = bundle.config.max_len, bundle.config.vocab
    batch = {"tokens": jnp.asarray(rng.integers(0, v, (2, t))),
             "targets": jnp.asarray(rng.integers(0, v, (2, t)))}
    return bundle, params, batch


def leaf_errors(got, want):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
            for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                    jax.tree_util.tree_leaves(want))}


def whole_error(got, want):
    num = sum(float(jnp.sum((a.astype(jnp.float32) - b) ** 2)) for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))
    den = sum(float(jnp.sum(b ** 2)) for b in jax.tree_util.tree_leaves(want))
    return math.sqrt(num / den)


def one_layer(params, run, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], params["blocks"][run])


# ``reference(grad=False, **static)``: the plain reference's loss (and gradient) as one program a set of static arguments
reference = tiny_models.reference_programs(ref, HP)


# the reference's own loss-and-gradient as the harness calls it, under one jit
REFERENCE = jax.jit(ref.make_loss_and_grad(TINY))


# -- the program against the reference ---------------------------------------------


@pytest.mark.parametrize("remat", [True, False])
def test_float32_program_equals_the_reference_on_loss_and_every_leaf(remat):
    bundle, params, batch = seeded(bias=0.05, remat=remat)
    cfg = bundle.config
    ref.check_config(dataclasses.replace(cfg, remat=True), TINY)
    # what the comparison covers: both mixers, the dense layer, a stacked run, group 4, a share
    assert cfg.runs == (("conv", "dense", 1), ("full_attention", "sparse", 1), ("conv", "sparse", 3))
    assert cfg.n_heads // cfg.n_kv_heads == 4 and (cfg.experts_held, cfg.expert_offset, cfg.n_experts) == (4, 4, 16)
    lp, gp = tiny_models.programs(bundle).loss_and_grad(params, batch)
    lr, gr = REFERENCE(params, batch["tokens"], batch["targets"])
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    errors = leaf_errors(gp, gr)
    # 8 leaves of the dense conv layer, 13 of the attention expert layer, 10 of the conv expert run, 2 outside
    assert len(errors) == 33
    biases = [k for k in errors if k.endswith("['bias']")]
    assert len(biases) == 2 and all(
        not np.any(np.asarray(g)) for g in (gp["blocks"][1]["bias"], gr["blocks"][2]["bias"]))
    assert max(v for k, v in errors.items() if k not in biases) < 1e-4, max(errors.items(), key=lambda kv: kv[1])


def test_bf16_program_equals_the_reference_given_its_routes(monkeypatch):
    """bf16 compute against float32, with the program's own routes handed to
    the reference so that arithmetic is compared and not near-ties. bf16 keeps
    8 significant bits (2^-8 a rounding); through five layers, forward,
    recomputed forward and backward, this size reads a whole-gradient relative
    error of 0.009 (worst leaf 0.014) and a loss apart by 0.0003; the limits are
    about three times that, as OLMoE's test has them. At the initialisation's
    scale: with matrices three times larger the softmax saturates and bf16
    reads tenths on every leaf, which measures the test and not the model."""
    monkeypatch.setattr(common, "compute_dtype", lambda: jnp.bfloat16)
    bundle, params, batch = seeded(scale=1.0)
    (got_l, routes), got_g = tiny_models.programs(bundle).loss_routes_and_grad(params, batch)
    assert routes.shape == (4, 128, 4)
    want_l, want_g = REFERENCE(params, batch["tokens"], batch["targets"], routes)
    assert abs(float(got_l) - float(want_l)) < 0.002
    assert whole_error(got_g, want_g) < 0.03
    errors = leaf_errors(got_g, want_g)
    assert max(v for k, v in errors.items() if not k.endswith("['bias']")) < 0.045


def test_the_layers_follow_the_published_list():
    cfg = lfm2.LFM2Config()
    want = Manifest().load_config("lfm2-24b-a2b")
    assert list(cfg.layer_types) == want["layer_types"] and cfg.n_layers == 40
    assert cfg.layer_types.count("conv") == 30 and cfg.layer_types.count("full_attention") == 10
    assert [want["layer_types"][i] for i in want["layers_run"]] == KINDS == ref.layer_types(want)
    assert len(cfg.runs) == 21 and len(set(cfg.runs)) == 4 and cfg.runs[0] == ("conv", "dense", 2)
    assert sum(n for _, _, n in cfg.runs) == 40 and cfg.runs[-1] == ("conv", "sparse", 1)
    # a command line's string is the list
    assert lfm2.LFM2Config(layer_types="conv, full_attention,conv", dense_layers=1).layer_types == tuple(KINDS[:3])
    shapes = jax.eval_shape(get_model("lfm2_24b_a2b", **OVERRIDES).init, jax.random.PRNGKey(0))
    dense, attn, convs = shapes["blocks"]
    assert dense["conv"]["w_in"].shape == (1, 64, 192) and dense["mlp"]["w_gate"].shape == (1, 64, 128)
    assert attn["wq"].shape == (1, 64, 64) and attn["wk"].shape == (1, 64, 16) and attn["q_norm"]["g"].shape == (1, 8)
    assert convs["conv"]["taps"].shape == (3, 3, 64) and convs["experts"]["w_gate"].shape == (3, 4, 64, 32)
    assert convs["router"].shape == (3, 64, 16) and convs["bias"].shape == (3, 16)  # the router keeps its width
    assert "lm_head" not in shapes  # the head is the embedding
    for bad in (dict(layer_types=["conv", "mamba"]), dict(layer_types=[]), dict(n_heads=7), dict(top_k=17),
                dict(experts_held=8, expert_offset=12), dict(dense_layers=6)):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, **{**OVERRIDES, **bad})


_AS_WRITTEN = []


def _as_written():
    """The reference as written on the seeded model, once for all variants:
    (its arguments, the program's loss, the routes, the reference's gradient)."""
    if not _AS_WRITTEN:
        bundle, params, batch = seeded(bias=0.1)
        args = (params, batch["tokens"], batch["targets"])
        program = float(tiny_models.programs(bundle).loss(params, batch))
        right, routes = reference(with_routes=True)(*args)
        assert program == pytest.approx(float(right), rel=1e-5)
        _AS_WRITTEN.append((args, program, routes, reference(grad=True)(*args, routes)[1]))
    return _AS_WRITTEN[0]


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_reference_notices_a_term_left_out(variant):
    """Each term of the layer equations computed as a mistaken implementation
    would changes the loss and the gradient; the program agrees with the
    reference as written. The selection bias is seeded away from zero: at zero
    ``bias_in_weights`` is no mistake at all."""
    args, program, routes, g_right = _as_written()
    if variant == "softmax_for_sigmoid":  # other scores pick other experts: its own routes
        wrong, g_wrong = reference(grad=True, variant=variant)(*args)
    else:
        wrong, g_wrong = reference(grad=True, variant=variant)(*args, routes)
    assert abs(float(wrong) - program) > 1e-4, variant
    assert whole_error(g_wrong, g_right) > 0.01, variant
    with pytest.raises(ValueError, match="unknown variant"):
        ref.loss(*args, HP, variant="nothing")


def test_routes_given_equal_routes_computed_and_another_share_is_noticed():
    bundle, params, batch = seeded(bias=0.05)
    loss, routes = reference(with_routes=True)(params, batch["tokens"], batch["targets"])
    assert routes.shape == (4, batch["tokens"].size, 4)  # the four expert layers, in layer order
    _, _, mine = tiny_models.programs(bundle).loss_and_routes(params, batch)
    assert np.array_equal(np.sort(np.asarray(mine), -1), np.sort(np.asarray(routes), -1))
    fn = REFERENCE
    l0, g0 = fn(params, batch["tokens"], batch["targets"])
    l1, g1 = fn(params, batch["tokens"], batch["targets"], routes)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6) == pytest.approx(float(loss), rel=1e-6)
    errors = leaf_errors(g1, g0)
    assert max(v for k, v in errors.items() if not k.endswith("['bias']")) < 1e-5
    other = float(jax.jit(lambda p, tok, tgt: ref.loss(p, tok, tgt, dict(HP, offset=0)))(
        params, batch["tokens"], batch["targets"]))
    assert abs(float(loss) - other) > 1e-4


# -- the convolution ------------------------------------------------------------------


def conv_inputs(batch=2, t=64, d=128, key=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ks[0], (batch, t, 3 * d), dtype), jax.random.normal(ks[1], (3, d)),
            jax.random.normal(ks[2], (batch, t, d), dtype))


def conv_by_lax(bcu, taps):
    """``C * conv(B * u)`` with the depthwise convolution as the library has it:
    a cross-correlation over a sequence padded with K - 1 zeros in front."""
    b_, c_, u_ = jnp.split(bcu, 3, axis=-1)
    d = taps.shape[1]
    conv = jax.lax.conv_general_dilated(
        b_ * u_, taps[:, None, :], window_strides=(1,), padding=[(taps.shape[0] - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=d,
        precision=jax.lax.Precision.HIGHEST)
    return c_ * conv


def conv_by_loop(bcu, taps):
    bcu, taps = np.asarray(bcu, np.float64), np.asarray(taps, np.float64)
    b_, c_, u_ = np.split(bcu, 3, axis=-1)
    g, out = b_ * u_, np.zeros_like(b_)
    for t in range(g.shape[1]):
        for j in range(3):  # w[0] g_{t-2} + w[1] g_{t-1} + w[2] g_t
            if t - (2 - j) >= 0:
                out[:, t] += taps[j] * g[:, t - (2 - j)]
    return c_ * out


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_convolution_is_the_librarys_and_a_loops_with_its_transpose(form):
    """Both forms (the kernel interpreted, three blocks of 32 positions so
    that both edges are crossed) against ``lax.conv_general_dilated`` and a
    Python loop over t; the gradients by ``jax.vjp`` against the library's."""
    bcu, taps, dy = conv_inputs(t=96)
    fn = short_conv.short_conv_xla if form == "xla" else (
        lambda a, w: short_conv.short_conv_kernel(a, w, 32, True))
    y, vjp = jax.vjp(fn, bcu, taps)
    want, want_vjp = jax.vjp(conv_by_lax, bcu, taps)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), conv_by_loop(bcu, taps), rtol=1e-4, atol=1e-4)
    for a, b in zip(vjp(dy), want_vjp(dy)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_the_kernel_takes_whole_blocks_of_whole_lane_tiles_and_the_dispatch_knows():
    assert short_conv.choose_block(8192, 2048, 3) == 256 and short_conv.choose_block(96, 128, 3) == 32
    assert short_conv.choose_block(64, 64, 3) is None     # the tests' width: the plain form
    assert short_conv.choose_block(100, 128, 3) is None and short_conv.choose_block(64, 128, 1) is None
    bcu, taps, _ = conv_inputs()
    # on the CPU the dispatch is the plain form, whatever the shape
    assert np.array_equal(np.asarray(short_conv.short_conv(bcu, taps)),
                          np.asarray(short_conv.short_conv_xla(bcu, taps)))
    # in bfloat16 the kernel rounds once, on the way out: no further from float32 than the plain form
    bcu16 = bcu.astype(jnp.bfloat16)
    exact = np.asarray(short_conv.short_conv_xla(bcu16.astype(jnp.float32), taps))
    kernel = np.asarray(short_conv.short_conv_kernel(bcu16, taps, 32, True), np.float32)
    plain = np.asarray(short_conv.short_conv_xla(bcu16, taps), np.float32)
    assert np.abs(kernel - exact).max() <= np.abs(plain - exact).max()


@pytest.mark.parametrize("run,kind", [(0, "conv"), (1, "full_attention"), (2, "conv")])
def test_a_token_changes_nothing_before_it(run, kind):
    bundle, params, batch = seeded()
    cfg = bundle.config
    x = params["wte"][batch["tokens"]][:1]
    p = one_layer(params, run)
    ffn = "dense" if run == 0 else "sparse"
    at = 40
    other = x.at[0, at].set(x[0, at] + 1.0)
    layer = jax.jit(lambda x: lfm2._layer(p, x, lfm2.moe.zero_share_stats(chunks_extra=True), cfg, kind, ffn)[0])
    a, b = layer(x), layer(other)
    diff = np.abs(np.asarray(a - b)).max(axis=-1)[0]
    assert diff[:at].max() == 0.0 and diff[at] > 0
    # a conv layer reaches two positions on (three taps), an attention layer to the end
    reach = np.nonzero(diff)[0].max()
    assert reach == (at + 2 if kind == "conv" else cfg.max_len - 1)


# -- the selection bias (the router itself: tests/test_expert_families.py) -----------------------------------------------------


def own_update(tx, state, grads):
    """The test's own copy of the update with the rule switched off."""
    updates, opt_state = tx.update(grads, state.opt_state, state.params)
    return optax.apply_updates(state.params, updates), opt_state


@pytest.mark.parametrize("optimizer", ["adam", "adamw"])
def test_a_step_moves_each_bias_by_gamma_by_the_counts_and_nothing_else_differs(optimizer):
    """After one step every selection bias is its old value +gamma, -gamma or
    +0, by the sign of (mean count - its count); the optimizer had no part in
    it (under AdamW's decay it would shrink); every other leaf, and the
    optimizer's state, are what the step without the rule gives, bit for bit."""
    from distributedvolunteercomputing_tpu.training.optim import make_optimizer

    bundle, params, batch = seeded(scale=1.0, bias=0.05)
    tx = make_optimizer(optimizer, lr=1e-2, weight_decay=0.1)
    state = steps.TrainState.create(params, tx, jax.random.PRNGKey(0))
    step = steps.make_train_step(bundle.loss_fn, tx, donate=False, stepped=bundle.stepped)
    new, metrics = step(state, batch)
    assert lfm2.moe.COUNTS not in metrics and float(metrics["aux_loss"]) == 0.0
    (_, m), grads = tiny_models.programs(bundle).loss_metrics_and_grad(params, batch)
    counts = np.asarray(m[lfm2.moe.COUNTS])
    assert counts.shape == (4, 16) and np.all(counts.sum(-1) == 2 * 64 * 4)
    # the rule switched off: the same step over a loss that keeps its counts to itself
    plain = steps.make_train_step(
        lambda p, b, r: (lambda l, m: (l, {k: v for k, v in m.items() if k != lfm2.moe.COUNTS}))(*bundle.loss_fn(p, b, r)),
        tx, donate=False)
    off, _ = plain(state, batch)
    want_params, want_opt = off.params, off.opt_state
    gamma, first = np.float32(bundle.config.bias_gamma), 0
    moved = 0
    for run, (old, got) in enumerate(zip(params["blocks"], new.params["blocks"])):
        if "bias" not in old:
            continue
        n = old["bias"].shape[0]
        sign = np.sign(counts[first:first + n].mean(-1, keepdims=True) - counts[first:first + n])
        assert np.array_equal(np.asarray(got["bias"]), np.asarray(old["bias"]) + gamma * sign.astype(np.float32))
        moved += int(np.count_nonzero(sign))
        first += n
    assert 0 < moved == int(metrics["moe_bias_moved"]) <= 64
    assert float(metrics["moe_bias_max"]) == float(max(np.asarray(p["bias"]).max() for p in params["blocks"] if "bias" in p))
    assert float(metrics["moe_bias_min"]) == float(min(np.asarray(p["bias"]).min() for p in params["blocks"] if "bias" in p))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(new.params), jax.tree_util.tree_leaves(want_params)):
        if lfm2.moe.is_bias(path):
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))
    for a, b in zip(jax.tree_util.tree_leaves(new.opt_state), jax.tree_util.tree_leaves(want_opt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if optimizer == "adamw":  # left to the optimizer, the bias would have decayed
        assert not np.array_equal(np.asarray(off.params["blocks"][2]["bias"]), np.asarray(params["blocks"][2]["bias"]))
    # the bias's gradient is zero, so it has no share of the clip's norm, and its moments stay zero
    assert not np.any(np.asarray(grads["blocks"][2]["bias"]))
    adam = [s for s in jax.tree_util.tree_leaves(new.opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    assert adam and not np.any(np.asarray(adam[0].mu["blocks"][2]["bias"])) and not np.any(
        np.asarray(adam[0].nu["blocks"][2]["bias"]))


def test_the_rule_owns_only_what_it_names_and_a_model_without_one_steps_as_before():
    bundle, params, _ = seeded()
    owned = bundle.stepped.owns(params)
    names = [jax.tree_util.keystr(p) for p, own in jax.tree_util.tree_leaves_with_path(owned) if own]
    assert names == ["['blocks'][1]['bias']", "['blocks'][2]['bias']"]
    # an owned leaf's gradient never reaches the optimizer, whatever it is
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.sgd(1.0))
    state = steps.TrainState.create(params, tx, jax.random.PRNGKey(0))
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    counts = jnp.tile(jnp.arange(16.0), (4, 1))
    new = steps.apply_half(tx, state, grads, state.rng, bundle.stepped, counts)
    plain = jax.tree_util.tree_map_with_path(lambda p, g: jnp.zeros_like(g) if lfm2.moe.is_bias(p) else g, grads)
    want, _ = own_update(tx, state, plain)  # the clip's norm is over the other leaves alone
    np.testing.assert_array_equal(np.asarray(new.params["wte"]), np.asarray(want["wte"]))
    np.testing.assert_allclose(np.asarray(new.params["blocks"][2]["bias"][0]), 0.001 * np.sign(7.5 - np.arange(16.0)))
    # a bundle that names no such leaf: the same program with and without the argument
    gpt2 = get_model("gpt2_small", n_layers=2, d_model=32, n_heads=2, max_len=16, vocab=64)
    assert gpt2.stepped is None
    tx = optax.adam(1e-3)
    shapes = jax.eval_shape(lambda: steps.TrainState.create(gpt2.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(1)))
    b = jax.eval_shape(lambda: gpt2.make_batch(jax.random.PRNGKey(2), 2))
    texts = [steps.make_train_step(gpt2.loss_fn, tx, **kw).lower(shapes, b).as_text() for kw in ({}, {"stepped": None})]
    assert texts[0] == texts[1]


def test_an_lr_warm_up_holds_the_optimizer_back_and_not_the_rule():
    """The cell trains inside a linear warm-up (``volunteer.warmup_steps``). The schedule is 0 at step 0 with or
    without one; at step 1 it is lr / 2000 where the schedule without one is lr. After two steps every leaf the
    optimizer owns has moved by Adam's lr / 2000 at most, and every bias by gamma a step by the counts."""
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    make = lambda warm: Trainer(get_model("lfm2_24b_a2b", **OVERRIDES), batch_size=2, optimizer="adam",  # noqa: E731
                                lr=1e-3, init_seed=1, seed=1, total_steps=10_000, warmup_steps=warm)
    cold, warm = make(0), make(2000)
    start = jax.tree_util.tree_map(np.asarray, warm.state.params)
    cold.run(steps=2)
    warm.run(steps=2)
    moved = lambda path, a: float(np.abs(np.asarray(a) - _at(start, path)).max())  # noqa: E731
    for path, a in jax.tree_util.tree_leaves_with_path(warm.state.params):
        if getattr(path[-1], "key", None) == "bias":
            assert set(np.round(np.abs(np.asarray(a)) / 0.001, 4).ravel()) <= {0.0, 1.0, 2.0} and np.any(np.asarray(a))
        else:
            assert moved(path, a) <= 1.01 * 1e-3 / 2000, path
    assert moved((jax.tree_util.DictKey("wte"),), cold.state.params["wte"]) > 0.9e-3  # without one: lr in a step
    for a, b in zip(cold.state.params["blocks"], warm.state.params["blocks"]):
        if "bias" in a:  # both chose twice with the initial parameters, whose first update is the schedule's 0
            np.testing.assert_array_equal(np.asarray(a["bias"]), np.asarray(b["bias"]))


def _at(tree, path):
    for k in path:
        tree = tree[getattr(k, "key", getattr(k, "idx", None))]
    return tree


def test_the_split_steps_of_gradient_averaging_carry_the_rule():
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    make = lambda **kw: Trainer(get_model("lfm2_24b_a2b", **OVERRIDES), batch_size=2, optimizer="adam",  # noqa: E731
                                lr=1e-3, init_seed=1, seed=1, **kw)
    fused, split = make(), make(averager=lambda grads, step: None, average_what="grads")
    fused.run(steps=3)
    split.run(steps=3)
    for a, b in zip(fused.state.params["blocks"], split.state.params["blocks"]):
        if "bias" in a:
            assert np.any(np.asarray(a["bias"])) and np.array_equal(np.asarray(a["bias"]), np.asarray(b["bias"]))


# -- the share -----------------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test, on an expert layer of each mixer kind: the
    outputs of the four shares of four experts each (at the cell's sizes eight
    of eight), with what every chip computes alike (the mixer, the residual)
    counted once, are the uncut reference's output for the whole layer."""
    uncut = dict(TINY, num_experts=16, expert_offset=0)
    bundle, params, batch = seeded(bias=0.05, experts_held=16, expert_offset=0)
    hp = ref.hyper(uncut)
    x = params["wte"][batch["tokens"]][:1]
    for run, kind in ((1, "full_attention"), (2, "conv")):
        p = one_layer(params, run)
        with jax.default_matmul_precision("highest"):
            block = jax.jit(lambda p: ref._block(p, x, None, hp)[0])
            whole = block(p)
            no_experts = jax.tree_util.tree_map(jnp.zeros_like, p["experts"])
            alike = block(dict(p, experts=no_experts))
        total = alike
        for offset in range(0, 16, 4):
            cfg = dataclasses.replace(bundle.config, experts_held=4, expert_offset=offset)
            held = jax.tree_util.tree_map(lambda a: a[offset:offset + 4], p["experts"])
            y, stats, _ = jax.jit(lambda p: lfm2._layer(  # a program a share: the offset is the trace's
                p, x, lfm2.moe.zero_share_stats(chunks_extra=True), cfg, kind, "sparse"))(dict(p, experts=held))
            assert float(stats["dropped"]) == 0.0
            total = total + (y - alike)  # this share's experts' part alone
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=2e-4, atol=2e-4)
        assert float(jnp.max(jnp.abs(y - whole))) > 1e-2  # one share is not the whole


def lifted(params, cfg, n):
    """``params`` with the selection bias of the first ``n`` held experts of
    every expert layer at 2 (over any sigmoid score: every token chooses them)."""
    first = cfg.expert_offset
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x.at[..., first:first + n].set(2.0) if lfm2.moe.is_bias(path) else x, params)


@pytest.mark.parametrize("n_lifted,chunks_a_layer", [(0, 1), (2, 2), (3, 3)])
def test_the_levelled_bound_computes_what_three_even_shares_compute(monkeypatch, n_lifted, chunks_a_layer):
    """The chunk of a levelled router (1.25 even shares: ``moe_dispatch.
    SHARE_ROWS_SLACK_LEVELLED``, which the model passes) against the dispatch's
    default of three: the loss, every leaf's gradient, the experts' counts and
    ``moe_dropped`` = 0 agree, under an even load (one chunk a layer,
    ``moe_chunks_extra`` 0) and under a load that a seeded selection bias
    pushes past the smaller bound, so that every layer runs two, then three
    chunks where three even shares hold it in one, then two."""
    assert lfm2.SHARE_ROWS_SLACK == moe_dispatch.SHARE_ROWS_SLACK_LEVELLED == 1.25  # the fixture's is the program's
    bundle, params, batch = seeded()
    c = bundle.config
    params = lifted(params, c, n_lifted)
    read = {}
    for slack in (moe_dispatch.SHARE_ROWS_SLACK_LEVELLED, moe_dispatch.SHARE_ROWS_SLACK):
        monkeypatch.setattr(lfm2, "SHARE_ROWS_SLACK", slack)
        (loss, m), grads = tiny_models.programs(bundle).loss_metrics_and_grad(params, batch)  # keyed by the slack in force
        cap = moe_dispatch.share_rows_bound(batch["tokens"].size, c.top_k, c.experts_held, c.n_experts, slack)
        held = np.asarray(m[lfm2.moe.COUNTS])[:, c.expert_offset:c.expert_offset + c.experts_held].sum(axis=1)
        chunks = np.ceil(held / cap)
        assert float(m["moe_dropped"]) == 0.0 and float(m["moe_rows_held"]) == held.sum()
        assert float(m["moe_chunks_extra"]) == (chunks - 1).sum()  # what the load says
        assert float(m["moe_rows_moved"]) == chunks.sum() * cap
        read[slack] = (float(loss), grads, np.asarray(m[lfm2.moe.COUNTS]), (cap, list(chunks)))
    (loss_a, grads_a, counts_a, ran_a), (loss_b, grads_b, counts_b, ran_b) = read.values()
    assert ran_a == (160, [chunks_a_layer] * 4)           # the levelled bound over an even 128 rows
    assert ran_b == (384, [max(1, chunks_a_layer - 1)] * 4)  # three even shares
    assert loss_a == pytest.approx(loss_b, rel=1e-6)
    np.testing.assert_array_equal(counts_a, counts_b)
    assert max(leaf_errors(grads_a, grads_b).values()) < 1e-5


# -- attention at head dim 64, four query heads a key/value head, each head normed ---------


def test_per_head_qk_norm_at_64_with_groups_of_four_through_the_core():
    """32 query heads over 8 key/value heads of 64, q and k normed over each
    head's 64 with learned scales before the rotary embedding, through
    ``attention_core`` (the XLA core on the CPU) against the reference's block."""
    cfg = lfm2.LFM2Config(layer_types=["full_attention"], dense_layers=0, d_model=256, n_heads=32, n_kv_heads=8,
                          head_dim=64, max_len=48)
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    d, hd = 256, 64
    p = {"ln_mixer": {"g": 1.0 + 0.1 * jax.random.normal(ks[0], (d,))},
         "wq": 0.1 * jax.random.normal(ks[1], (d, 32 * hd)), "wk": 0.1 * jax.random.normal(ks[2], (d, 8 * hd)),
         "wv": 0.1 * jax.random.normal(ks[3], (d, 8 * hd)), "wo": 0.1 * jax.random.normal(ks[4], (32 * hd, d)),
         "q_norm": {"g": 1.0 + 0.3 * jax.random.normal(ks[5], (hd,))},
         "k_norm": {"g": 1.0 + 0.3 * jax.random.normal(ks[6], (hd,))}}
    x = jax.random.normal(ks[7], (2, 48, d))
    seen = []
    with traced.subscribe(lambda kind, labels: seen.append((kind, labels))):
        got = lfm2._attention(p, x, cfg)
    assert seen == [("attention_core", dict(impl="xla", T=48, D=64, dtype="float32", window="none", kv_heads=8,
                                            layout="heads", rotary="none", computed_over_band="none"))]
    hp = {"heads": 32, "n_kv": 8, "head_dim": 64, "theta": cfg.rope_theta, "eps": cfg.rms_eps}
    with jax.default_matmul_precision("highest"):
        n = ref._rmsnorm(p["ln_mixer"]["g"], x, cfg.rms_eps)
        want = x + ref._attention_mixer(p, n, hp, None)
        unnormed = x + ref._attention_mixer(p, n, hp, "no_qk_norm")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    assert float(jnp.max(jnp.abs(got - unnormed))) > 1e-2
    # the norm is over a head's 64, not the projection's 2,048: scaling one head of q leaves the output where it was
    scaled = dict(p, wq=p["wq"].at[:, :hd].multiply(5.0))
    np.testing.assert_allclose(np.asarray(lfm2._attention(scaled, x, cfg)), np.asarray(got), rtol=2e-4, atol=2e-4)


# -- the list-of-runs tree through the rest of the system ----------------------------------


def test_published_sizes_and_parameter_counts():
    from benchmark import flops_lfm2

    full = jax.eval_shape(get_model("lfm2_24b_a2b").init, jax.random.PRNGKey(0))
    n = common.count_params(full)
    cell = Manifest().load_config("lfm2-24b-a2b")
    published = dict(cell, **cell["published"])
    del published["layers_run"]
    assert n == flops_lfm2.total_params(published) == 23_843_661_440 and round(n / 1e9) == 24  # "24B"
    cut = jax.eval_shape(get_model("lfm2_24b_a2b", **cell["model_overrides"]).init, jax.random.PRNGKey(0))
    assert common.count_params(cut) == 469_285_248 == cell["parameters"]["counted_by_the_program"]
    assert common.count_params(cut) == flops_lfm2.total_params(cell)
    by_layer = [common.count_params(cut["blocks"][0]), common.count_params(cut["blocks"][1])] + [
        common.count_params(cut["blocks"][2]) // 3] * 3
    assert by_layer == cell["parameters"]["by_layer"]
    assert common.count_params(cut["wte"]) == cell["parameters"]["embedding_and_tied_head"]


def test_stacked_runs_take_the_sharding_rules(eight_devices):
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from distributedvolunteercomputing_tpu.parallel import sharding
    from distributedvolunteercomputing_tpu.parallel.mesh import AXES

    mesh = Mesh(np.array(eight_devices).reshape(1, 1, 1, 4, 2), AXES)  # ep=4, tp=2
    shapes = jax.eval_shape(get_model("lfm2_24b_a2b", **OVERRIDES).init, jax.random.PRNGKey(0))
    specs = jax.tree_util.tree_map(lambda s: s.spec, sharding.make_param_shardings(mesh, shapes))
    dense, attn, convs = specs["blocks"]
    # right-aligned: a run's layer axis stays whole
    assert convs["experts"]["w_gate"] == P(None, "ep", None, "tp") and convs["experts"]["w_down"] == P(None, "ep", "tp", None)
    assert attn["wq"] == attn["wk"] == P(None, None, "tp") and attn["wo"] == P(None, "tp", None)
    assert dense["mlp"]["w_up"] == P(None, None, "tp") and dense["mlp"]["w_down"] == P(None, "tp", None)
    for whole in (convs["router"], convs["bias"], convs["conv"]["w_in"], convs["conv"]["taps"], attn["q_norm"]["g"],
                  specs["wte"]):
        assert whole == P()


def test_save_and_restore_carry_the_bias(tmp_path):
    from distributedvolunteercomputing_tpu.training import checkpoint
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    make = lambda seed: Trainer(  # noqa: E731
        get_model("lfm2_24b_a2b", **OVERRIDES), batch_size=2, optimizer="adam", lr=1e-3, init_seed=seed)
    tr = make(1)
    tr.run(steps=3)
    assert np.any(np.asarray(tr.state.params["blocks"][2]["bias"]))
    checkpoint.save(tr, str(tmp_path))
    fresh = make(2)
    assert checkpoint.maybe_restore(fresh, str(tmp_path)) and int(fresh.state.step) == 3
    assert jax.tree_util.tree_structure(fresh.state.params) == jax.tree_util.tree_structure(tr.state.params)
    for a, b in zip(jax.tree_util.tree_leaves(fresh.state.params), jax.tree_util.tree_leaves(tr.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    before = float(tr.run(steps=2)["final_loss"])
    assert float(fresh.run(steps=2)["final_loss"]) == pytest.approx(before, rel=1e-5)


def test_train_loop_records_the_bias_and_the_mixers_on_the_route_span():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    bundle = get_model("lfm2_24b_a2b", **OVERRIDES)
    assert {"moe_bias_max", "moe_bias_min", "moe_bias_moved", "moe_chunks_extra"} <= set(bundle.spans["moe.route"].keys)
    tel = Telemetry(peer_id="v", enabled=True)
    tr = Trainer(bundle, batch_size=2, optimizer="adam", lr=1e-3, tracer=tel.tracer)
    summary = tr.run(steps=11, log_every=5)
    assert math.isfinite(summary["final_loss"])
    routes = [s for s in tel.tracer.spans() if s["name"] == "moe.route"]
    assert [s["attrs"]["step"] for s in routes] == [5, 10]
    for s in routes:
        a = s["attrs"]
        assert a["router_site"] == "post_attention" and a["experts_held"] == 4 and a["moe_dropped"] == 0.0
        assert a["mixers_conv"] == 4 and a["mixers_full_attention"] == 1
        assert a["aux_loss"] == 0.0 and a["lm_loss"] > 0
        assert a["moe_load_mean"] == 2 * 64 * 4 / 16 and 0 < a["moe_rows_held"] <= 4 * 2 * 64 * 4
        assert 0 < a["moe_bias_moved"] <= 4 * 16
        # an untrained router under the rule is level: one chunk of 1.25 even shares a layer
        assert a["moe_chunks_extra"] == 0.0 and a["moe_rows_moved"] == 4 * 160
        # the biases the step CHOSE with: after n - 1 steps none is further than (n - 1) gamma from zero
        reach = (a["step"] - 1) * 0.001
        assert -reach - 1e-7 <= a["moe_bias_min"] < 0 < a["moe_bias_max"] <= reach + 1e-7
    assert tel.summary()["moe"]["dropped_total"] == 0.0
    assert tel.summary()["moe"]["chunks_extra"] == 0.0
    assert tel.registry.gauge("swarm.moe_chunks_extra").value() == 0.0


def test_a_load_over_the_bound_shows_on_the_route_span_and_the_gauge():
    """The same loop with three held experts' biases lifted over every score:
    each of the four expert layers runs three chunks, and the span's
    ``moe_chunks_extra`` and the volunteer's gauge say 8."""
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    tr = Trainer(get_model("lfm2_24b_a2b", **OVERRIDES), batch_size=2, optimizer="adam", lr=1e-3, tracer=tel.tracer)
    tr.state = dataclasses.replace(tr.state, params=lifted(tr.state.params, tr.bundle.config, 3))
    tr.run(steps=5, log_every=5)
    (route,) = [s for s in tel.tracer.spans() if s["name"] == "moe.route"]
    assert route["attrs"]["moe_chunks_extra"] == 8.0 and route["attrs"]["moe_dropped"] == 0.0
    assert route["attrs"]["moe_rows_moved"] == 12 * 160
    assert tel.summary()["moe"]["chunks_extra"] == 8.0


@pytest.mark.parametrize("family,carries", [("laguna", False), ("smallthinker", True), ("lfm2", True)])
def test_only_a_levelled_routers_metrics_count_extra_chunks(family, carries):
    """Laguna's step is the program it was: its chunk is the dispatch's
    default, sized for a router that collapses, and its metrics do not carry
    the counter of second chunks. LFM2's do (the levelled bound), and since PR
    63 SmallThinker's (a chunk sized from its own router's load in training)."""
    cfg = Manifest().load_config(f"tiny-rehearsal-{family}")
    bundle = get_model(cfg["registry_model"], **cfg["model_overrides"])
    batch = bundle.make_batch(jax.random.PRNGKey(0), 2)
    _, metrics = jax.eval_shape(bundle.loss_fn, bundle.init(jax.random.PRNGKey(1)), batch, None)
    assert ("moe_chunks_extra" in metrics) == carries and "moe_rows_moved" in metrics
