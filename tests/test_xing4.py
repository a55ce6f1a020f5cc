"""Xing4.0-29B-A4B on the normal path (models/xing4.py), at a tiny size on the
CPU: the program against the plain reference (benchmark/references/xing4.py)
on the loss and every leaf's gradient, float32 against float32, on seeded
NON-initial parameters and on the initial ones; the Sinkhorn steps (what 20 of
them reach and what they do not, their gradient against finite differences);
the model with one-hot / ones / identity maps against GLM's one-stream block;
the shares adding up to the uncut layer; the parameter counts; the train loop's
``hc.mix`` span. The reference's mistaken terms are in
tests/test_xing4_variants.py (a compile each: a file, and a worker, of their own)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from benchmark.references import xing4 as ref
from distributedvolunteercomputing_tpu.models import common, get_model, glm4_moe_lite as glm, moe, xing4
from tests import tiny_models

TINY = tiny_models.rehearsal("xing4")
OVERRIDES = TINY["model_overrides"]
MODEL = "xing4_29b_a4b"
HP = ref.hyper(TINY)


def seeded(initial: bool = False, res: float = 0.0, **overrides):
    """The tiny model (a dense layer and a run of two expert layers; 4 heads of
    12 + 4 over a value head of 8; experts 4..7 of 16 held, top-4, a shared
    expert; four streams of 64) and two seeded sequences. Unless ``initial``,
    at seeded NON-initial parameters where every term matters: the maps'
    ``a`` about 1, ``b`` and ``Phi`` drawn wider, every norm weight drawn about
    1, ``W_qb`` times 4, the head times 10, a drawn selection bias; ``res``: added to every
    ``b_res`` entry's draw, to drive ``Hres~`` past the clip."""
    bundle = tiny_models.bundle("xing4", **overrides)
    params = jax.jit(bundle.init)(jax.random.PRNGKey(3))

    def drawn(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.PRNGKey(11), sum(name.encode()))
        noise = jax.random.normal(key, x.shape)
        if moe.is_bias(path):
            return 0.5 * noise
        if name.endswith("['a']"):
            return 1.0 + 0.2 * noise
        if name.endswith("['b']"):
            n = bundle.config.hc_mult
            return x + (0.7 + res * (jnp.arange(x.shape[-1]) >= 2 * n)) * noise
        if name.endswith("['phi']"):
            return 3.0 * x
        if name.endswith("['g']"):
            return 1.0 + 0.3 * noise
        if name == "['lm_head']":   # logits of a spread of about 1, so that what the layers make moves the loss
            return 10.0 * x
        return 4.0 * x if name.endswith("['wq_b']") else x

    if not initial:
        params = jax.tree_util.tree_map_with_path(drawn, params)
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


REFERENCE = jax.jit(ref.make_loss_and_grad(TINY))


# -- the program against the reference ---------------------------------------------------------


@pytest.mark.parametrize("initial", [False, True], ids=["seeded", "initial"])
def test_float32_program_equals_the_reference_on_loss_and_every_leaf(initial):
    """The first case compiles the tiny model's loss-and-gradient program and the
    reference's (30 s on the CPU: three traced layer bodies, each rematerialised,
    with three hand-written backward rules a sublayer); every later test of the
    file reads the same two programs."""
    bundle, params, batch = seeded(initial)
    (loss, metrics), grads = tiny_models.programs(bundle).loss_metrics_and_grad(params, batch)
    want, want_grads = REFERENCE(params, batch["tokens"], batch["targets"])
    assert float(loss) == pytest.approx(float(want), abs=1e-4)
    errors = leaf_errors(grads, want_grads)
    biases = [k for k in errors if k.endswith("['bias']")]
    assert len(biases) == 1 and all(float(jnp.max(jnp.abs(g))) == 0.0 for g in (  # no gradient reaches the bias
        grads["blocks"][1]["bias"], want_grads["blocks"][1]["bias"]))
    worst = max(v for k, v in errors.items() if k not in biases)
    assert worst < 1e-4, sorted(errors.items(), key=lambda kv: -kv[1])[:5]
    assert len(errors) == len(jax.tree_util.tree_leaves(params)) and float(metrics["moe_dropped"]) == 0.0
    # the maps as the step notes them: the streams mix (more at drawn maps), the 20 steps leave the rows off by
    # what they leave, the input map is a sigmoid's, the output map about 1
    assert 0.0 < float(metrics["hc_res_offdiag"]) < 0.75 and 0.0 < float(metrics["hc_sinkhorn_err"]) < 0.1
    assert 0.0 < float(metrics["hc_pre_max"]) < 1.0 and 0.5 < float(metrics["hc_post_mean"]) < 1.5


def test_rematerialised_and_plain_layers_compute_the_same():
    bundle, params, batch = seeded()
    plain = tiny_models.bundle("xing4", remat=False)
    (loss_a, _), grads_a = tiny_models.programs(bundle).loss_metrics_and_grad(params, batch)
    (loss_b, _), grads_b = tiny_models.programs(plain).loss_metrics_and_grad(params, batch)
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6) and max(leaf_errors(grads_a, grads_b).values()) < 1e-5


def test_the_configuration_builds_what_the_file_says_and_refuses_what_is_not_built():
    bundle = tiny_models.bundle("xing4")
    ref.check_config(bundle.config, TINY)
    with pytest.raises(ValueError, match="yarn_factor"):
        ref.check_config(dataclasses.replace(bundle.config, yarn_factor=32.0), TINY)
    with pytest.raises(ValueError, match="hc_sinkhorn_iters"):
        ref.check_config(dataclasses.replace(bundle.config, hc_sinkhorn_iters=3), TINY)
    with pytest.raises(ValueError, match="mscale"):
        xing4.Xing4Config(yarn_mscale=0.707)
    c = xing4.Xing4Config()
    assert (c.head_dim, c.head_pad, c.hc_maps) == (192, 64, 24)
    assert c.softmax_scale * math.sqrt(192) == pytest.approx(2.0047, abs=1e-4)   # (0.1 ln 64 + 1)^2
    assert bundle.config.head_pad == 112 and bundle.config.hc_maps == 24


# -- the Sinkhorn steps --------------------------------------------------------------------------


def test_twenty_sinkhorn_steps_give_columns_of_one_and_rows_as_near_as_the_entries_allow():
    """Each step ends on the columns, so they sum to 1 to rounding from entries
    anywhere in the clip's [-30, 30]; every entry is in [0, 1]; the rows come
    nearer with every step, and reach 1e-5 after 20 steps from entries within
    [-1, 1]. From entries anywhere in [-30, 30] they do NOT: a matrix that is
    nearly a permutation of blocks converges as slowly as it is nearly so
    (median 0.03 here, what ``hc_sinkhorn_err`` reports of a step), which 20
    steps are the published count for and no tolerance of this test's."""
    rng = np.random.default_rng(5)
    wide = jnp.asarray(rng.uniform(-30, 30, (4, 4, 4096)), jnp.float32)
    mild = jnp.asarray(rng.uniform(-1, 1, (4, 4, 4096)), jnp.float32)

    def row_error(m):
        return np.abs(np.asarray(jnp.sum(m, axis=1)) - 1.0).max(axis=0)

    got = xing4.sinkhorn(jnp.exp(wide), 20)
    assert float(jnp.max(jnp.abs(jnp.sum(got, axis=0) - 1.0))) < 1e-5
    assert float(jnp.min(got)) >= 0.0 and float(jnp.max(got)) <= 1.0 + 1e-6
    errors = [row_error(xing4.sinkhorn(jnp.exp(wide), n)) for n in (1, 5, 20)]
    assert np.median(errors[0]) > np.median(errors[1]) > np.median(errors[2]) > 1e-3
    got = xing4.sinkhorn(jnp.exp(mild), 20)
    assert row_error(got).max() < 1e-5 and float(jnp.max(jnp.abs(jnp.sum(got, axis=0) - 1.0))) < 1e-5
    # the reference's steps are the same steps (its matrix is the last two axes)
    np.testing.assert_allclose(np.asarray(ref.stochastic(jnp.exp(jnp.moveaxis(wide, -1, 0)), 20)),
                               np.asarray(jnp.moveaxis(xing4.sinkhorn(jnp.exp(wide), 20), -1, 0)), rtol=2e-5, atol=1e-7)


def test_the_gradient_through_the_sinkhorn_steps_is_the_finite_difference():
    """Autodiff through the 20 written-out steps (the backward the step runs)
    against central differences, in float64: every entry of the gradient of a
    seeded linear reading of ``Hres`` with respect to ``Hres~``."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(7)
        z = jnp.asarray(rng.normal(0, 2, (4, 4, 3)) + 3 * np.eye(4)[:, :, None])
        w = jnp.asarray(rng.normal(size=(4, 4, 3)))
        read = lambda z: jnp.sum(w * xing4.sinkhorn(jnp.exp(jnp.clip(z, -30.0, 30.0)), 20))
        grad = np.asarray(jax.grad(read)(z))
        eps = 1e-6
        for i, j, s in np.ndindex(4, 4, 3):
            step = jnp.zeros_like(z).at[i, j, s].set(eps)
            assert grad[i, j, s] == pytest.approx(float(read(z + step) - read(z - step)) / (2 * eps), abs=1e-7, rel=1e-5)
        assert np.abs(grad).max() > 1e-2
        # past the clip nothing moves
        assert float(jnp.max(jnp.abs(jax.grad(read)(jnp.full((4, 4, 3), 31.0))))) == 0.0


# -- the one-stream block ------------------------------------------------------------------------


def test_with_one_hot_ones_and_identity_maps_the_model_is_glms_one_stream_block():
    """``Hpre`` one-hot on stream 0, ``Hpost`` ones and ``Hres`` the identity
    keep the four streams copies of one, and that one is ``x + f(norm(x))``:
    the model is then ``models/glm4_moe_lite.py``'s at the same sizes (a value
    head as wide as the key, which is all GLM's config takes; YaRN at factor 1,
    which is plain rotary at scale 1), on the loss and on every leaf the two
    trees share. What ties the new path to the one-stream models."""
    same = dict(v_head_dim=16, yarn_factor=1.0)
    bundle, params, batch = seeded(**same)
    n = bundle.config.hc_mult

    def maps(path, x):
        name = jax.tree_util.keystr(path)
        if name.endswith("['a']"):
            return jnp.zeros_like(x)
        if name.endswith("['b']"):
            b = jnp.concatenate([jnp.asarray([40.0] + [-40.0] * (n - 1)), jnp.zeros((n,)),
                                 (60.0 * jnp.eye(n) - 30.0).reshape(-1)])
            return jnp.broadcast_to(b, x.shape)
        # the head reads 4 x where GLM's reads x: the final norm takes the 4 out but for its epsilon, which an
        # embedding of RMS 1 (0.02 at initialisation) makes 1e-6 of the mean square on either side
        return 50.0 * x if name == "['wte']" else x

    params = jax.tree_util.tree_map_with_path(maps, params)
    (loss, metrics), grads = tiny_models.programs(bundle).loss_metrics_and_grad(params, batch)
    assert float(metrics["hc_res_offdiag"]) < 1e-6 and float(metrics["hc_post_mean"]) == pytest.approx(1.0)

    c = bundle.config
    one = get_model("glm4_7_flash", **{**tiny_models.rehearsal("glm")["model_overrides"], "routed_scale": c.routed_scale,
                                       "rms_eps": c.rms_eps, "rope_theta": c.rope_theta, "max_len": c.max_len})
    strip = lambda tree: [{k: v for k, v in run.items() if not k.startswith("hc_")} for run in tree["blocks"]]
    (want, _), want_grads = jax.jit(jax.value_and_grad(
        lambda p: glm.loss_and_routes(p, batch, one.config)[:2], has_aux=True))(dict(params, blocks=strip(params)))
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    errors = leaf_errors(dict(grads, blocks=strip(grads)), want_grads)
    worst = max(v for k, v in errors.items() if not k.endswith("['bias']"))
    assert worst < 2e-4, sorted(errors.items(), key=lambda kv: -kv[1])[:5]


# -- the shares ----------------------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_with_what_every_chip_computes_counted_once():
    """The guide's share test: the routed parts of the four shares of four
    experts each (at the cell's sizes eight of eight), with what every chip
    computes alike (the residual maps, latent attention, the SHARED expert)
    counted once, are the uncut reference's output for the whole uncut layer.
    A share's routed part enters the streams through ``Hpost``, linearly."""
    uncut = dict(TINY, n_routed_experts=16, expert_offset=0)
    bundle, params, batch = seeded(experts_held=16, expert_offset=0)
    hp = ref.hyper(uncut)
    n, d = bundle.config.hc_mult, bundle.config.d_model
    emb = params["wte"][batch["tokens"]][:1]
    x = jnp.tile(emb, (1, 1, n)) + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (*emb.shape[:2], n * d))
    p = one_layer(params, 1)
    flat = lambda y: y.reshape(*y.shape[:2], n * d)
    with jax.default_matmul_precision("highest"):
        block = jax.jit(lambda p: flat(ref._block(p, x.reshape(*x.shape[:2], n, d), None, hp)[0]))
        whole = block(p)
        no_experts = jax.tree_util.tree_map(jnp.zeros_like, p["experts"])
        alike = block(dict(p, experts=no_experts))   # maps, mixer, shared expert
        no_shared = block(dict(p, experts=no_experts, shared=jax.tree_util.tree_map(jnp.zeros_like, p["shared"])))
    assert float(jnp.max(jnp.abs(alike - no_shared))) > 1e-3   # the shared expert is in what is counted once
    total = alike
    stats = {**moe.zero_share_stats(chunks_extra=True), **{k: jnp.zeros(()) for k in xing4._HC_STATS}}
    for offset in range(0, 16, 4):
        cfg = dataclasses.replace(bundle.config, experts_held=4, expert_offset=offset)
        held = jax.tree_util.tree_map(lambda a: a[offset:offset + 4], p["experts"])
        y, out, _ = jax.jit(lambda p: xing4._layer(p, x, stats, cfg, "sparse"))(dict(p, experts=held))
        assert float(out["dropped"]) == 0.0
        total = total + (y - alike)  # this share's routed experts' part alone
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=2e-4, atol=2e-4)
    assert float(jnp.max(jnp.abs(y - whole))) > 1e-3  # one share is not the whole


# -- counts, the loop, the entry points -----------------------------------------------------------


def test_published_sizes_and_parameter_counts():
    from benchmark import flops_xing4 as fl

    cell = Manifest().load_config("xing4.0-29b-a4b")
    cut = jax.eval_shape(get_model(MODEL, **cell["model_overrides"]).init, jax.random.PRNGKey(0))
    assert common.count_params(cut) == 759_489_806 == cell["parameters"]["counted_by_the_program"] == fl.total_params(cell)
    by_layer = [common.count_params(cut["blocks"][0])] + [common.count_params(cut["blocks"][1]) // 4] * 4
    assert by_layer == cell["parameters"]["by_layer"] == [128_225_590] + [128_455_030] * 4
    assert common.count_params(cut["wte"]) == common.count_params(cut["lm_head"]) == cell["parameters"]["head"] == 58_720_256
    assert fl.attention_matrix_params(cell) + 768 + 512 == 28_411_136
    assert common.count_params(cut["blocks"][0]["hc_mixer"]) == fl.hc_params(cell) == 14336 * 24 + 24 + 3 + 14336 == 358_427
    full = jax.eval_shape(get_model(MODEL).init, jax.random.PRNGKey(0))
    published = dict(cell, **cell["published"])
    n = common.count_params(full)
    # 2 dense layers, 38 expert layers of 64 + 1 experts, embedding and head of 131,072: "29B"; the MTP module not built
    assert n == fl.total_params(published) == cell["parameters"]["at_the_published_sizes"] and n // 10 ** 9 == 29


def test_train_loop_records_the_maps_on_their_span_and_the_bias_on_the_routes():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    tr = Trainer(get_model(MODEL, **OVERRIDES), batch_size=2, optimizer="adam", lr=1e-3, tracer=tel.tracer)
    summary = tr.run(steps=11, log_every=5)
    assert math.isfinite(summary["final_loss"])
    mixes = [s for s in tel.tracer.spans() if s["name"] == "hc.mix"]
    assert [s["attrs"]["step"] for s in mixes] == [5, 10]
    for s in mixes:
        a = s["attrs"]
        assert a["hc_mult"] == 4 and a["hc_sinkhorn_iters"] == 20
        assert 0.0 < a["hc_res_offdiag"] < 0.5 and 0.0 < a["hc_sinkhorn_err"] < 0.1
        assert 0.0 < a["hc_pre_max"] < 1.0 and 0.5 < a["hc_post_mean"] < 1.5
    routes = [s for s in tel.tracer.spans() if s["name"] == "moe.route"]
    assert [s["attrs"]["step"] for s in routes] == [5, 10]
    for s in routes:
        a = s["attrs"]
        assert a["router_site"] == "post_attention" and a["experts_held"] == 4 and a["moe_dropped"] == 0.0
        assert a["mixers_latent_attention"] == 3 and 0 < a["moe_bias_moved"] <= 2 * 16
        reach = (a["step"] - 1) * 0.001
        assert -reach - 1e-7 <= a["moe_bias_min"] < 0 < a["moe_bias_max"] <= reach + 1e-7


def test_run_volunteer_knows_the_model_and_no_training_code_names_it():
    import os
    import subprocess

    from distributedvolunteercomputing_tpu.models import registry

    assert MODEL in registry.list_models()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkg = os.path.join(repo, "distributedvolunteercomputing_tpu")
    hits = subprocess.run(["grep", "-rliE", "xing4|sinkhorn|hc_mult", os.path.join(pkg, "training"),
                           os.path.join(pkg, "swarm"), os.path.join(pkg, "parallel")],
                          capture_output=True, text=True).stdout.split()
    assert hits == []
