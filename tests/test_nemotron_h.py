"""models/nemotron_h.py (NVIDIA-Nemotron-3-Nano-30B-A3B: one-mixer blocks of
three kinds, Mamba-2 mixers through ops/ssd.py, gate-less squared-ReLU experts
through the share path's new kind) at a tiny size on the CPU: the program
against the benchmark's plain reference, the terms a mistaken implementation
would compute, the parameter counts, the share's sum, the gate-less share path
as a dataflow, the step's bias rule and the loop's spans."""

import dataclasses
import functools
import json
import os
import subprocess
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import datagen
from benchmark.manifest import Manifest
from benchmark.references import nemotron_h as ref
from distributedvolunteercomputing_tpu.models import common, get_model, moe, nemotron_h
from distributedvolunteercomputing_tpu.ops import moe_dispatch, ssd
from distributedvolunteercomputing_tpu.utils import traced
from tests import tiny_models

TINY = tiny_models.rehearsal("nemotron")
CFG = Manifest().load_config("nemotron-3-nano-30b-a3b")
HP = ref.hyper(TINY)


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def tiny(seed=3, scale=3.0, bias=0.05, **overrides):
    """The tiny bundle, its parameters moved off their initial values (every
    matrix times ``scale``, seeded selection biases, the convolution's bias and
    the norms' scales and D spread out) and two seeded sequences of 40. One
    tree a set of arguments: no test writes into it or donates it."""
    return _tiny(seed, scale, bias, tuple(sorted(overrides.items())))


@functools.lru_cache(maxsize=None)
def _tiny(seed, scale, bias, overrides):
    bundle = tiny_models.bundle("nemotron", **dict(overrides))
    params = jax.jit(bundle.init)(jax.random.PRNGKey(seed))

    def moved(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.PRNGKey(11), zlib.crc32(name.encode()) % (2 ** 31))  # no PYTHONHASHSEED moves it
        if name.endswith("['bias']"):
            return bias * jax.random.normal(key, x.shape)
        if name.endswith("['g']") or name.endswith("['d_skip']"):
            return x + 0.3 * jax.random.normal(key, x.shape)
        if name.endswith("['conv_b']"):
            return 0.5 * jax.random.normal(key, x.shape)
        if name.endswith(("['a_log']", "['dt_bias']")):
            return x
        return x * scale

    params = jax.tree_util.tree_map_with_path(moved, params) if scale else params
    return bundle, params, datagen.lm_arrays(5, 2, 40, TINY["vocab_size"])


# ``reference(grad=False, **static)``: the plain reference's loss (and gradient) as one program a set of static arguments
reference = tiny_models.reference_programs(ref, HP)


def both_sides(bundle, params, batch, variant=None, routes=None):
    tokens, targets = batch["tokens"], batch["targets"]
    program = tiny_models.programs(bundle).loss_and_grad(params, {"tokens": tokens, "targets": targets})
    return program, reference(grad=True, variant=variant)(params, tokens, targets, routes)


def flat(g):
    return jnp.concatenate([x.ravel() for x in jax.tree_util.tree_leaves(g)])


@functools.lru_cache(maxsize=None)
def as_written(scale):
    """(parameters, tokens, targets, the routes the reference chose, its loss
    and its gradient as one vector given them) at the tiny size, once for all variants."""
    _, params, batch = tiny(scale=scale)
    tokens, targets = batch["tokens"], batch["targets"]
    _, routes = reference(with_routes=True)(params, tokens, targets)
    loss, grads = reference(grad=True, variant=None)(params, tokens, targets, routes)
    return params, tokens, targets, routes, float(loss), flat(grads)


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


# -- program against reference ---------------------------------------------------------


@pytest.mark.parametrize("state", ["initial", "moved", "moved_no_remat"])
def test_float32_program_equals_the_reference_on_loss_and_every_leaf(state):
    """The chunked scan, the shifted-sum convolution, the sort-and-group share
    path and the units' scan against the recurrence position by position and
    every held expert on every token: loss and each gradient leaf at 1e-4."""
    bundle, params, batch = tiny(scale=0.0 if state == "initial" else 3.0,
                                 **({"remat": False} if state.endswith("no_remat") else {}))
    ref.check_config(bundle.config, TINY)
    (lp, gp), (lr, gr) = both_sides(bundle, params, batch)
    assert abs(float(lp) - float(lr)) < 1e-4
    leaves = jax.tree_util.tree_leaves_with_path(gr)
    for (path, want), got in zip(leaves, jax.tree_util.tree_leaves(gp)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):
            assert not np.any(np.asarray(want)) and not np.any(np.asarray(got)), name   # the choice has no gradient
        else:
            assert rel(got, want) < 1e-4, (name, rel(got, want))
    assert len(leaves) == len(jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_reference_notices_a_term_left_out(variant):
    """Every mistaken term changes the loss and the gradient at seeded
    non-initial parameters, against the reference itself with the same routes."""
    params, tokens, targets, routes, lr, gr = as_written(3.0)
    given = None if variant == "softmax_for_sigmoid" else routes
    lv, gv = reference(grad=True, variant=variant)(params, tokens, targets, given)
    # the bias is small beside a score: 8e-5 on the loss, and the gradient reads it
    assert abs(float(lv) - lr) > (5e-5 if variant == "bias_in_weights" else 1e-4), (variant, float(lv), lr)
    assert rel(flat(gv), gr) > 1e-2, (variant, rel(flat(gv), gr))


def test_what_the_check_on_the_initial_parameters_can_and_cannot_see():
    """On ``init``'s own parameters the carried state shows (that is what the
    state-space leaves' initialisation is for), and a selection bias of zero
    hides ``bias_in_weights``, as the configuration file's ``left_out`` says."""
    params, tokens, targets, routes, _, base = as_written(0.0)
    grad_of = lambda v, p=params: flat(reference(grad=True, variant=v)(p, tokens, targets, routes)[1])  # noqa: E731
    # 0.0076 here (40 positions, chunks of 16, d 64); with taps at normal(0, 0.02) it read 4e-5
    assert rel(grad_of("no_state_between_chunks"), base) > 2e-3
    assert rel(grad_of("bias_in_weights"), base) == 0.0
    # with the repo's normal(0, 0.02) for A_log and dt_bias the state is gone within a chunk and the same check is blind
    blind = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) + 4.0 if jax.tree_util.keystr(path).endswith("['dt_bias']") else x, params)
    base_blind, gone = grad_of(None, blind), grad_of("no_state_between_chunks", blind)
    assert rel(gone, base_blind) < 0.5 * rel(grad_of("no_state_between_chunks"), base)


def test_routes_given_equal_routes_computed_and_another_share_is_noticed():
    bundle, params, batch = tiny()
    tokens, targets = batch["tokens"], batch["targets"]
    own, routes = reference(with_routes=True)(params, tokens, targets)
    assert routes.shape == (3, 80, 3) and float(reference()(params, tokens, targets, routes)) == float(own)
    _, _, program_routes = tiny_models.programs(bundle).loss_and_routes(params, batch)
    assert np.array_equal(np.asarray(program_routes), np.asarray(routes))
    other = jax.jit(lambda p, r: ref.loss(p, tokens, targets, dict(HP, offset=8), r))
    assert abs(float(other(params, routes)) - float(own)) > 1e-4


def test_a_token_changes_nothing_before_it():
    bundle, params, batch = tiny()
    cfg = bundle.config
    tokens = jnp.asarray(batch["tokens"][:1])

    @jax.jit
    def hidden(tok):
        x = params["wte"][tok]
        runs = [(lambda p, x, s, u=unit, n=n: nemotron_h._unit(p, x, s, cfg, u, n), n, unit[-1] == "E")
                for unit, n in cfg.runs]
        stats = {**moe.zero_share_stats(act_zeros=True, chunks_extra=True), "ssm_carried": jnp.zeros(())}
        return moe.run_layers(runs, params["blocks"], x, stats, False, tok.size, cfg)[0]

    base, moved = hidden(tokens), hidden(tokens.at[0, 25].set((tokens[0, 25] + 1) % 512))
    assert float(jnp.max(jnp.abs(base[0, :25] - moved[0, :25]))) == 0.0
    assert float(jnp.max(jnp.abs(base[0, 25:] - moved[0, 25:]))) > 1e-3


# -- shapes, counts, units ----------------------------------------------------------------


def test_published_sizes_parameter_counts_and_units():
    """The program's tree, shapes only: the cut's 528,093,120 by block and the
    published model's 31,578 M; the published order as 13 runs of 2 shapes."""
    count = lambda b: sum(int(x.size) for x in jax.tree_util.tree_leaves(jax.eval_shape(b.init, jax.random.PRNGKey(0))))  # noqa: E731
    cut = get_model(CFG["registry_model"], **CFG["model_overrides"])
    m, a, e = 38_744_896, 23_399_040, 100_125_440
    assert count(cut) == 3 * m + 3 * e + a + 2 * 44_040_192 + 2688 == 528_093_120 == CFG["parameters"]["counted_by_the_program"]
    assert CFG["parameters"]["by_block"] == [m, e, m, e, m, a, e] and cut.config.blocks == "MEMEM*E"
    assert cut.config.runs == (("ME", 2), ("M*E", 1))
    shapes = jax.eval_shape(cut.init, jax.random.PRNGKey(0))
    run0, run1 = shapes["blocks"]
    assert run0["bias"].shape == (2, 128) and run0["experts"]["w_up"].shape == (2, 8, 2688, 1856)
    assert run0["before"][0]["w_in"].shape == (2, 2688, 10304) and run0["before"][0]["conv_w"].shape == (2, 4, 6144)
    assert run1["before"][1]["wk"].shape == (1, 2688, 256) and run1["shared"]["w_down"].shape == (1, 3712, 2688)
    full = get_model(CFG["registry_model"])
    assert count(full) == 23 * m + 6 * a + 23 * 1_297_468_160 + 2 * 352_321_536 + 2688 == 31_577_940_288
    cfg = full.config
    assert (cfg.depth, cfg.blocks.count("M"), cfg.blocks.count("E"), cfg.blocks.count("*")) == (52, 23, 23, 6)
    assert cfg.d_inner == 4096 and cfg.conv_dim == 6144
    assert len(cfg.runs) == 13 and {u for u, _ in cfg.runs} == {"ME", "M*E"} and sum(n * len(u) for u, n in cfg.runs) == 52
    assert cfg.layer_types.count("mamba") == 23 and set(cfg.layer_types) == {"mamba", "experts", "attention"}
    # a cut that ends in mixers: a unit of its own, no routes
    tail = dataclasses.replace(cfg, n_layers=5)
    assert tail.blocks == "MEMEM" and tail.runs == (("ME", 2), ("M", 1))
    for bad in ({"pattern": "MEX"}, {"n_layers": 53}, {"n_groups": 7}, {"n_kv_heads": 3}, {"experts_held": 200}):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, **bad)


def test_a_cut_that_ends_in_mixers_trains_and_routes_less():
    bundle, params, batch = tiny(scale=0.0, n_layers=5)
    loss, metrics, routes = tiny_models.programs(bundle).loss_and_routes(params, batch)
    assert routes.shape == (2, 80, 3) and np.isfinite(float(loss)) and 0.0 <= float(metrics["ssm_carry_share"]) <= 1.0


def test_the_state_space_leaves_are_initialised_as_the_family_does():
    bundle, params, _ = tiny(scale=0.0)
    m = params["blocks"][0]["before"][0]
    np.testing.assert_allclose(np.asarray(m["a_log"][0]), np.log(np.arange(1, 5)), rtol=1e-6)
    assert np.all(np.asarray(m["d_skip"]) == 1.0) and np.all(np.asarray(m["norm"]["g"]) == 1.0)
    full = nemotron_h._dt_bias_init(jax.random.PRNGKey(0), nemotron_h.NemotronHConfig())
    dt = np.asarray(jax.nn.softplus(full))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001 and np.median(dt) == pytest.approx(0.01, rel=0.5)
    assert not np.array_equal(np.asarray(params["blocks"][0]["before"][0]["dt_bias"][0]),
                              np.asarray(params["blocks"][0]["before"][0]["dt_bias"][1]))   # from the seed, a block
    again = bundle.init(jax.random.PRNGKey(3))
    assert np.array_equal(np.asarray(again["blocks"][0]["before"][0]["dt_bias"]), np.asarray(m["dt_bias"]))
    assert not np.any(np.asarray(params["blocks"][0]["bias"]))
    # the metric: with this initialisation some head carries a state across a chunk of 16
    _, metrics, _ = tiny_models.programs(bundle).loss_and_routes(params, datagen.lm_arrays(5, 2, 40, 512))
    assert 0.0 < float(metrics["ssm_carry_share"]) <= 1.0


def test_the_grouped_norm_gates_first_and_norms_each_group():
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 32))
    g = jax.random.normal(jax.random.PRNGKey(1), (32,))
    got = np.asarray(nemotron_h.group_rmsnorm(g, y, 4, 1e-5))
    want = np.asarray(y).reshape(2, 5, 4, 8)
    want = (want / np.sqrt((want ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(2, 5, 32) * np.asarray(g)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# -- the share ------------------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once():
    """The guide's share test: the four shares' routed parts (16 experts, four
    held each, same router, biases and routes) plus the shared expert once are
    the expert block with every expert held."""
    bundle, _, _ = tiny(scale=0.0, experts_held=16, expert_offset=0)
    cfg = bundle.config
    params = bundle.init(jax.random.PRNGKey(5))
    p = jax.tree_util.tree_map(lambda a: a[0] * 3.0, {k: v for k, v in params["blocks"][0].items() if k != "before"})
    p["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 40, 64))
    stats = {**moe.zero_share_stats(act_zeros=True, chunks_extra=True), "ssm_carried": jnp.zeros(())}
    experts = lambda p, c: jax.jit(lambda p: nemotron_h._experts(p, x, stats, c))(p)  # noqa: E731 — a program a share
    whole, _, (routes, chosen) = experts(p, cfg)
    h = common.rmsnorm(p["ln"], x, cfg.rms_eps).reshape(80, 64)
    shared = nemotron_h._relu2_mlp(p["shared"], h).reshape(2, 40, 64)
    routed = jnp.zeros_like(x)
    for offset in range(0, 16, 4):
        part_cfg = dataclasses.replace(cfg, experts_held=4, expert_offset=offset)
        part = {**p, "experts": jax.tree_util.tree_map(lambda a: a[offset:offset + 4], p["experts"])}
        out, part_stats, (part_routes, _) = experts(part, part_cfg)
        assert np.array_equal(np.asarray(part_routes), np.asarray(routes)) and float(part_stats["dropped"]) == 0.0
        routed = routed + (out - x - shared)
    np.testing.assert_allclose(np.asarray(x + shared + routed), np.asarray(whole), rtol=1e-4, atol=1e-5)
    assert float(jnp.sum(chosen)) == 80 * 3


S, D, F, E, K, OFFSET, HELD = 48, 16, 8, 16, 4, 4, 4


def share_inputs():
    ks = jax.random.split(jax.random.PRNGKey(48), 5)
    x = jax.random.normal(ks[0], (S, D))
    w_up, w_down = jax.random.normal(ks[1], (HELD, D, F)) * 0.3, jax.random.normal(ks[2], (HELD, F, D)) * 0.3
    idx = jnp.argsort(jax.random.uniform(ks[3], (S, E)), axis=1)[:, :K].astype(jnp.int32)
    idx = idx.at[0].set(jnp.asarray([0, 1, 9, 13])).at[1].set(jnp.asarray([7, 4, 6, 5]))
    return x, idx, jax.nn.softmax(jax.random.normal(ks[4], (S, K)), axis=1), w_up, w_down


def per_token_sum(x, idx, gates, w_up, w_down, offset=OFFSET, held=HELD):
    """``sum_i gates[s, i] W_down_e relu(W_up_e x[s])^2`` over the held choices, nothing sorted or grouped."""
    local = idx - offset
    mine = (local >= 0) & (local < held)
    e = jnp.clip(local, 0, held - 1)
    hidden = jnp.square(jax.nn.relu(jnp.einsum("sd,skdf->skf", x, w_up[e])))
    return jnp.einsum("skd,sk->sd", jnp.einsum("skf,skfd->skd", hidden, w_down[e]), jnp.where(mine, gates, 0.0))


def nan_past_the_sizes(lhs, rhs, sizes):
    """A grouped product that, as megablox, leaves every row past the sizes' sum unwritten (NaN here)."""
    inside = lambda a: (jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None]  # noqa: E731
    product = lambda a, b: jax.lax.ragged_dot(jnp.where(inside(a), a, 0.0), b, sizes)  # noqa: E731

    @jax.custom_vjp
    def unwritten(a, b):
        return jnp.where(inside(a), product(a, b), jnp.nan)

    def backward(res, cot):
        d_a, d_b = jax.vjp(product, *res)[1](jnp.where(inside(cot), cot, 0.0))
        return jnp.where(inside(d_a), d_a, jnp.nan), d_b

    unwritten.defvjp(lambda a, b: (unwritten(a, b), (a, b)), backward)
    return unwritten(lhs, rhs)


@pytest.mark.parametrize("slack", [0.5, 3.0], ids=["several_chunks", "one_chunk"])
@pytest.mark.parametrize("product", ["ragged_dot", "unwritten_past_the_sizes"])
def test_the_gate_less_share_path_is_the_per_token_sum_and_its_products_end_at_the_held_rows(product, slack, monkeypatch):
    """PR 46's property for two matrices an expert: with grouped products that
    write NaN past the sizes' sum, ``y``, the zero count and every gradient are
    finite and the per-token sums'; two products a chunk forward and five backward
    (up again, the cotangent of ``hidden``, the two stacks', the rows'): the
    count of a gated chunk, whose gate and up are one product."""
    import collections

    from jax._src.interpreters import partial_eval as pe

    x, idx, gates, w_up, w_down = share_inputs()
    if product != "ragged_dot":
        monkeypatch.setattr(moe_dispatch, "grouped_matmul", nan_past_the_sizes)

    def share(x, gates, w_up, w_down):
        return moe_dispatch.share_glu_experts(x, idx, gates, None, w_up, w_down, OFFSET, E, act="relu2", slack=slack)

    y, sizes, dropped, moved, zeros = share(x, gates, w_up, w_down)
    rows = moe_dispatch.share_rows_bound(S, K, HELD, E, slack)
    mine = np.asarray((idx >= OFFSET) & (idx < OFFSET + HELD))
    assert int(dropped) == 0 and int(jnp.sum(sizes)) == mine.sum() and int(moved) == rows * -(-int(mine.sum()) // rows)
    np.testing.assert_allclose(np.asarray(y), np.asarray(per_token_sum(x, idx, gates, w_up, w_down)), rtol=2e-5, atol=2e-5)
    up = jnp.einsum("sd,skdf->skf", x, w_up[jnp.clip(idx - OFFSET, 0, HELD - 1)])
    assert int(zeros) == int(jnp.sum((up <= 0) & mine[:, :, None]))     # what a ReLU zeroes, over the held rows
    probe = jax.random.normal(jax.random.PRNGKey(7), (S, D))
    got = jax.jit(jax.grad(lambda *a: jnp.sum(share(*a)[0] * probe), argnums=(0, 1, 2, 3)))(x, gates, w_up, w_down)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(per_token_sum(a[0], idx, *a[1:]) * probe), argnums=(0, 1, 2, 3)))(
        x, gates, w_up, w_down)
    for name, a, b in zip(("x", "top_gates", "w_up", "w_down"), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5, err_msg=name)
    if product == "ragged_dot":
        def live(jaxpr, path=()):
            jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
            for eqn in jaxpr.eqns:
                yield path, eqn
                for value in eqn.params.values():
                    for sub in value if isinstance(value, (list, tuple)) else [value]:
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            yield from live(sub, path + (eqn.primitive.name,))

        closed = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(jnp.sin(share(*a)[0])), argnums=(0, 1, 2, 3)))(x, gates, w_up, w_down)
        products = collections.Counter(
            "forward" if "custom_vjp_call" in path[:path.index("while")] else "backward"
            for path, eqn in live(closed.jaxpr) if "while" in path and eqn.primitive.name.startswith("ragged_dot"))
        assert products == {"forward": 2, "backward": 5}, products


def test_the_gate_less_kind_with_every_expert_held_is_dropless_and_the_dispatch_is_noted():
    x, idx, gates, _, _ = share_inputs()
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    w_up, w_down = jax.random.normal(ks[0], (E, D, F)) * 0.3, jax.random.normal(ks[1], (E, F, D)) * 0.3
    seen = []
    with traced.subscribe(lambda kind, labels: seen.append({"kind": kind, **labels})):
        whole = moe_dispatch.share_glu_experts(x, idx, gates, None, w_up, w_down, 0, E, act="relu2")
        part = moe_dispatch.share_glu_experts(x, idx, gates, None, w_up[4:8], w_down[4:8], 4, E, act="relu2")
    direct = moe_dispatch.dropless_glu_experts(x, idx, gates, None, w_up, w_down, "relu2")
    assert np.array_equal(np.asarray(whole[0]), np.asarray(direct[0])) and int(whole[3]) == S * K
    np.testing.assert_allclose(np.asarray(whole[0]), np.asarray(per_token_sum(x, idx, gates, w_up, w_down, 0, E)),
                               rtol=2e-5, atol=2e-5)
    assert int(whole[4]) == int(jnp.sum(jnp.einsum("sd,skdf->skf", x, w_up[idx]) <= 0))
    assert [s["kind"] for s in seen] == ["moe_dispatch"] * 2
    assert [s["act"] for s in seen] == ["relu2", "relu2"] and [s["held"] for s in seen] == [E, HELD]
    assert moe_dispatch.expert_kind("silu") == "swiglu" and moe_dispatch.expert_kind("relu") == "reglu"
    assert np.isfinite(np.asarray(part[0])).all()


@pytest.mark.parametrize("k,n", [(192, 80), (128, 80), (192, 128)], ids=["both_padded", "n_padded", "k_padded_with_it"])
def test_a_width_no_tile_divides_runs_on_megablox_padded_and_is_the_ragged_product(k, n, monkeypatch):
    """1,856 is 14.5 of megablox's tiles: ``grouped_matmul`` pads k and n with
    zeros to whole tiles, cuts the result back, and values and both gradients
    are ``ragged_dot``'s on the rows inside the groups (the kernel interpreted)."""
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, kk, nn: "megablox")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs, rhs = jax.random.normal(ks[0], (512, k)), jax.random.normal(ks[1], (4, k, n))
    sizes = jnp.asarray([100, 0, 250, 50], jnp.int32)
    probe = jax.random.normal(ks[2], (512, n))
    inside = (jnp.arange(512) < 400)[:, None]
    assert (moe_dispatch._megablox_tiling(512, k, n) is None) == (k != 128 or n != 128)
    assert (moe_dispatch._padded(192), moe_dispatch._padded(80), moe_dispatch._padded(1856), moe_dispatch._padded(2688)) == (
        256, 128, 2048, 3072)
    got = lambda a, b: jnp.sum(jnp.where(inside, moe_dispatch.grouped_matmul(a, b, sizes), 0.0) * probe)  # noqa: E731
    want = lambda a, b: jnp.sum(jnp.where(inside, jax.lax.ragged_dot(a, b, sizes), 0.0) * probe)  # noqa: E731
    assert moe_dispatch.grouped_matmul(lhs, rhs, sizes).shape == (512, n)
    assert float(got(lhs, rhs)) == pytest.approx(float(want(lhs, rhs)), rel=1e-5)
    for a, b in zip(jax.jit(jax.grad(got, (0, 1)))(lhs, rhs), jax.jit(jax.grad(want, (0, 1)))(lhs, rhs)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.where(np.isfinite(np.asarray(a)), np.asarray(a), 0.0), np.asarray(b), rtol=1e-4, atol=1e-4)


# -- the step and the loop ------------------------------------------------------------------


def test_a_step_moves_each_bias_by_gamma_by_the_counts_and_the_state_space_leaves_by_the_optimizer():
    from distributedvolunteercomputing_tpu.training.steps import TrainState

    bundle, params, batch = tiny(scale=0.0)
    tx, step = tiny_models.train_step(bundle, "adam", lr=1e-3)
    state = TrainState.create(params, tx, jax.random.PRNGKey(1))
    before = jax.tree_util.tree_map(np.asarray, params)
    _, metrics, _ = tiny_models.programs(bundle).loss_and_routes(params, batch)
    counts = np.asarray(metrics[moe.COUNTS])
    assert counts.shape == (3, 16) and counts.sum() == 3 * 80 * 3
    state, out = step(state, batch)
    assert moe.COUNTS not in out and "ssm_carry_share" in out and "moe_act_zero_share" in out
    want = 0.001 * np.sign(counts.mean(-1, keepdims=True) - counts)
    got = np.concatenate([np.asarray(p["bias"]) for p in state.params["blocks"]])
    np.testing.assert_allclose(got, want, atol=1e-9)
    m0, m1 = before["blocks"][0]["before"][0], state.params["blocks"][0]["before"][0]
    for leaf in ("a_log", "dt_bias", "d_skip", "conv_b", "conv_w", "w_in"):
        assert np.any(np.asarray(m1[leaf]) != m0[leaf]), leaf


def test_stacked_runs_take_the_sharding_rules(eight_devices):
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from distributedvolunteercomputing_tpu.parallel import sharding
    from distributedvolunteercomputing_tpu.parallel.mesh import AXES

    bundle, _, _ = tiny(scale=0.0)
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    mesh = Mesh(np.asarray(eight_devices[:4]).reshape(1, 1, 1, 4, 1), AXES)   # ep = 4
    specs = jax.tree_util.tree_map(lambda s: s.spec, sharding.make_param_shardings(mesh, shapes))
    run = specs["blocks"][0]
    assert run["experts"]["w_up"] == P(None, "ep", None, None) or "ep" in tuple(run["experts"]["w_up"])
    assert "ep" not in tuple(run["before"][0]["w_in"]) and "ep" not in tuple(run["bias"])


def test_train_loop_records_the_scan_span_beside_the_route_span():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    bundle = get_model(TINY["registry_model"], **TINY["model_overrides"])
    trainer = Trainer(bundle, batch_size=2, lr=1e-3, optimizer="adam", tracer=tel.tracer)
    trainer.run(steps=11, log_every=5)
    routes = [s for s in tel.tracer.spans() if s["name"] == "moe.route"]
    scans = [s for s in tel.tracer.spans() if s["name"] == "ssm.scan"]
    assert len(routes) == len(scans) >= 2
    for s in scans:
        assert 0.0 < s["attrs"]["ssm_carry_share"] <= 1.0 and set(s["attrs"]) == {"step", "ssm_carry_share", "ssm_form"}
        assert s["attrs"]["ssm_form"] == ssd.PLAIN        # no TPU here: what a traced scan's note said (ops/ssd.py)
    attrs = routes[-1]["attrs"]
    assert attrs["mixers_mamba"] == 3 and attrs["mixers_experts"] == 3 and attrs["mixers_attention"] == 1
    assert attrs["experts_held"] == 4 and "moe_act_zero_share" in attrs and "moe_chunks_extra" in attrs
    assert "ssm_carry_share" not in attrs


def test_a_traced_scan_notes_its_form_and_shape():
    """``ops/ssd.ssd``: one note ("ssd_scan") a TRACED scan with form, heads,
    groups, head_dim, state and chunk, as attention's core notes its calls;
    the tiny model's three state-space blocks are two traces (``ME`` scanned
    twice and ``M*E``), each traced again by its checkpoint's backward."""
    bundle, params, batch = tiny()
    seen = []
    with traced.subscribe(lambda kind, labels: kind == "ssd_scan" and seen.append((kind, tuple(labels.items())))):
        jax.make_jaxpr(lambda p: bundle.loss_fn(p, batch, None)[0])(params)
        forward = len(seen)
        jax.make_jaxpr(jax.grad(lambda p: bundle.loss_fn(p, batch, None)[0]))(params)
    cfg = bundle.config
    assert forward == 2 and len(seen) > 2 * forward - 1
    assert set(seen) == {("ssd_scan", tuple(dict(
        form=ssd.PLAIN, heads=cfg.mamba_heads, groups=cfg.n_groups, head_dim=cfg.mamba_head_dim, state=cfg.d_state,
        chunk=cfg.chunk).items()))}
    told = len(seen)
    jax.make_jaxpr(lambda p: bundle.loss_fn(p, batch, None)[0])(params)      # nobody subscribed: nothing is told, nothing fails
    assert len(seen) == told


def test_run_volunteer_knows_the_model_and_no_training_code_names_it():
    from distributedvolunteercomputing_tpu.models import registry
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    assert "nemotron3_nano_30b_a3b" in registry.list_models()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = subprocess.run(["git", "grep", "-n", "-i", "nemotron", "--", "distributedvolunteercomputing_tpu/training",
                            "distributedvolunteercomputing_tpu/swarm"], cwd=root, capture_output=True, text=True)
    assert found.stdout == ""
    assert not [f.name for f in dataclasses.fields(VolunteerConfig) if "ssm" in f.name or "mamba" in f.name]


def test_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``tiny-rehearsal-nemotron:solo`` through ``benchmark/run.py``: volunteer,
    probe, window, a traced run, the reference check, the result line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--rehearse",
         "tiny-rehearsal-nemotron:solo", "--seed", "4200000048", "--seconds", "3", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert '"reference": true' in out.stderr and '"no_compile_in_window": true' in out.stderr
