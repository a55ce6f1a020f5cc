"""SmallThinker-21BA3B-Instruct on the normal path (models/smallthinker.py), at
a tiny size on the CPU: the program against the plain reference
(benchmark/references/smallthinker.py) on the loss and every leaf's gradient,
with T over the window, seven query heads a key/value head, both layer kinds
and a held share; the reference against each term left out; each mechanism
alone (the router on the layer's input, its softmax over the chosen, ReGLU
experts and their zero count, the plan made before attention and spent after,
no position encoding on a global layer); the eight shares adding up to the
uncut layer; the activation argument leaving Laguna's and OLMoE's programs as
they were; the scanned two-kind tree through count_params, the sharding rules,
a checkpoint and the train loop's spans, gauge and counter label."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from benchmark.references import smallthinker as ref
from distributedvolunteercomputing_tpu.models import common, get_model, moe as share, smallthinker
from distributedvolunteercomputing_tpu.ops import moe_dispatch
from distributedvolunteercomputing_tpu.utils import traced
from tests import tiny_models

TINY = tiny_models.rehearsal("smallthinker")
OVERRIDES = TINY["model_overrides"]
HP = ref.hyper(TINY)
# the reference's own loss-and-gradient as the harness calls it, under one jit
REFERENCE = jax.jit(ref.make_loss_and_grad(TINY))


# ``reference(grad=False, **static)``: the plain reference's loss (and gradient) as one program a set of static arguments
reference = tiny_models.reference_programs(ref, HP)


def zero_stats(cfg):
    return share.zero_share_stats(balanced=cfg.n_experts, act_zeros=True)


@pytest.fixture(autouse=True)
def tight_chunks(monkeypatch):
    """A chunk a quarter over the even share (the program's is three times
    it): at these sizes several chunks run."""
    monkeypatch.setattr(moe_dispatch, "SHARE_ROWS_SLACK", 1.25)
    monkeypatch.setattr(smallthinker, "SHARE_ROWS_SLACK", 1.25)  # the model brings its own


def seeded(scale: float = 3.0, **overrides):
    """The tiny model (one period: a global layer and three sliding ones, 14
    query heads over 2 key/value heads, window 8 under 64 positions, experts
    4..7 of 16 held) with weights scaled up so that every term matters, and
    two seeded sequences."""
    bundle = tiny_models.bundle("smallthinker", **overrides)
    params = jax.jit(bundle.init)(jax.random.PRNGKey(3))
    params = jax.tree_util.tree_map_with_path(  # every matrix; the norms' gains stay at 1
        lambda path, x: x if "ln_" in jax.tree_util.keystr(path) else x * scale, params)
    rng = np.random.default_rng(0)
    t, v = bundle.config.max_len, bundle.config.vocab
    batch = {"tokens": jnp.asarray(rng.integers(0, v, (2, t))),
             "targets": jnp.asarray(rng.integers(0, v, (2, t)))}
    return bundle, params, batch


def leaf_errors(got, want):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
            for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                    jax.tree_util.tree_leaves(want))}


def one_layer(params, layer):
    """Layer ``layer``'s own tree out of the scanned ``blocks`` (periods of four)."""
    p, i = divmod(layer, 4)
    stack = params["blocks"]["global" if i == 0 else "sliding"]
    return jax.tree_util.tree_map(lambda a: a[p] if i == 0 else a[p, i - 1], stack)


# -- the program against the reference ---------------------------------------------


@pytest.mark.parametrize("remat", [True, False])
def test_float32_program_equals_the_reference_on_loss_and_every_leaf(remat):
    bundle, params, batch = seeded(remat=remat)
    cfg = bundle.config
    ref.check_config(dataclasses.replace(cfg, remat=True), TINY)
    # what the comparison covers: T over the window, group 7, both kinds, a share
    assert cfg.max_len > cfg.window and cfg.n_heads // cfg.n_kv_heads == 7
    assert [cfg.attention_kind(l) for l in range(4)] == ["global", "sliding", "sliding", "sliding"]
    assert (cfg.experts_held, cfg.expert_offset, cfg.n_experts) == (4, 4, 16)
    lp, gp = tiny_models.programs(bundle).loss_and_grad(params, batch)
    lr, gr = REFERENCE(params, batch["tokens"], batch["targets"])
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    errors = leaf_errors(gp, gr)
    assert len(errors) == 23  # ten leaves a layer kind, embedding, head, final norm
    assert max(errors.values()) < 1e-4, max(errors.items(), key=lambda kv: kv[1])


def test_the_layers_follow_the_published_lists():
    cfg = smallthinker.SmallThinkerConfig()
    want = Manifest().load_config("smallthinker-21b-a3b")
    sliding = [int(cfg.attention_kind(l) == "sliding") for l in range(52)]
    assert sliding == want["sliding_window_layout"] == want["rope_layout"]
    assert (cfg.periods, cfg.router_site) == (13, "layer_input")
    shapes = jax.eval_shape(get_model("smallthinker_21b_a3b", **OVERRIDES).init, jax.random.PRNGKey(0))
    assert shapes["blocks"]["global"]["wq"].shape == (1, 64, 14 * 16)
    assert shapes["blocks"]["sliding"]["wq"].shape == (1, 3, 64, 14 * 16)
    assert shapes["blocks"]["sliding"]["experts"]["w_gate"].shape == (1, 3, 4, 64, 32)  # the share held
    assert shapes["blocks"]["sliding"]["router"].shape == (1, 3, 64, 16)               # the router keeps its width
    for bad in (dict(n_layers=6), dict(n_heads=15), dict(top_k=17), dict(experts_held=8, expert_offset=12)):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, **{**OVERRIDES, **bad})


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_reference_notices_a_term_left_out(variant):
    """Each term of the layer equations computed as a mistaken implementation
    would (the router after attention, SiLU for ReLU, rotary on the global
    layer, no window, top-6 weights not renormalised) changes the loss and the
    gradient; the program agrees with the reference as written."""
    bundle, params, batch = seeded()
    args = (params, batch["tokens"], batch["targets"])
    program = float(tiny_models.programs(bundle).loss(params, batch))
    right, routes = reference(with_routes=True)(*args)
    assert program == pytest.approx(float(right), rel=1e-5)
    g_right = reference(grad=True)(*args, routes)[1]
    if variant == "router_after_attention":  # another router input picks other experts: its own routes
        wrong, g_wrong = reference(grad=True, variant=variant)(*args)
    else:
        wrong, g_wrong = reference(grad=True, variant=variant)(*args, routes)
    assert abs(float(wrong) - program) > 1e-4, variant
    num = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(*map(jax.tree_util.tree_leaves, (g_wrong, g_right))))
    den = sum(float(jnp.sum(b ** 2)) for b in jax.tree_util.tree_leaves(g_right))
    assert math.sqrt(num / den) > 0.05, variant
    with pytest.raises(ValueError, match="unknown variant"):
        ref.loss(*args, HP, variant="nothing")


def test_reference_notices_another_share():
    bundle, params, batch = seeded()
    mine = float(reference()(params, batch["tokens"], batch["targets"]))
    other = float(jax.jit(lambda p, tok, tgt: ref.loss(p, tok, tgt, dict(HP, offset=0)))(
        params, batch["tokens"], batch["targets"]))
    assert abs(mine - other) > 1e-4


def test_routes_given_equal_routes_computed():
    bundle, params, batch = seeded()
    loss, routes = reference(with_routes=True)(params, batch["tokens"], batch["targets"])
    assert routes.shape == (4, batch["tokens"].size, 3)  # every layer routes; layer order
    _, _, mine = tiny_models.programs(bundle).loss_and_routes(params, batch)
    assert np.array_equal(np.sort(np.asarray(mine), -1), np.sort(np.asarray(routes), -1))
    fn = REFERENCE
    l0, g0 = fn(params, batch["tokens"], batch["targets"])
    l1, g1 = fn(params, batch["tokens"], batch["targets"], routes)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6) == pytest.approx(float(loss), rel=1e-6)
    assert max(leaf_errors(g1, g0).values()) < 1e-5


def test_two_periods_scan_in_layer_order():
    """Eight layers (two periods): the program's scan over periods with its
    inner scan over the sliding layers is the reference's plain loop over
    layers 0..7, and the routes come back in that order."""
    two = dict(TINY, num_hidden_layers=8, rope_layout=[0, 1, 1, 1] * 2, sliding_window_layout=[0, 1, 1, 1] * 2)
    bundle, params, batch = seeded(n_layers=8)
    ref.check_config(bundle.config, two)
    (lp, mine), gp = tiny_models.programs(bundle).loss_routes_and_grad(params, batch)
    lr, gr = jax.jit(ref.make_loss_and_grad(two))(params, batch["tokens"], batch["targets"])
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    assert max(leaf_errors(gp, gr).values()) < 1e-4
    _, routes = jax.jit(lambda p, tok, tgt: ref.loss(p, tok, tgt, ref.hyper(two), with_routes=True))(
        params, batch["tokens"], batch["targets"])
    assert mine.shape == routes.shape == (8, 128, 3)
    assert np.array_equal(np.sort(np.asarray(mine), -1), np.sort(np.asarray(routes), -1))
    # layer l's tree: blocks["global"][l // 4] or blocks["sliding"][l // 4, l % 4 - 1], on both sides
    for layer in (0, 3, 4, 6):
        for a, b in zip(jax.tree_util.tree_leaves(one_layer(params, layer)),
                        jax.tree_util.tree_leaves(ref.layer_tree(params, layer))):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# -- each mechanism against its plain form -------------------------------------------


def test_router_reads_the_layers_input_and_weighs_by_the_softmax_over_the_chosen():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (40, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    idx, weights, probs = smallthinker.route(w, x, 4)
    logits = np.asarray(x @ w, np.float64)
    full = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(probs), full, rtol=1e-5)
    want_idx = np.argsort(-logits, axis=-1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want_idx, -1))
    chosen = np.take_along_axis(full, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(np.asarray(weights), chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    # the layer hands the router its input: changing what attention adds moves no route
    bundle, params, batch = seeded()
    cfg, p = bundle.config, one_layer(params, 1)
    xin = params["wte"][batch["tokens"]][:1]
    _, _, routes = smallthinker._layer(p, xin, zero_stats(cfg), cfg, "sliding")
    _, _, again = smallthinker._layer(dict(p, wo=p["wo"] * 7.0, wv=-p["wv"]), xin,
                                      zero_stats(cfg), cfg, "sliding")
    direct, _, _ = smallthinker.route(p["router"], xin.reshape(-1, 64), cfg.top_k)
    assert np.array_equal(np.asarray(routes), np.asarray(again))
    assert np.array_equal(np.asarray(routes), np.asarray(direct))


def test_a_global_layer_encodes_no_position_and_a_sliding_layer_does():
    """Without a position encoding, causal attention over a sequence whose
    EARLIER tokens are permuted gives the last token the same output; rotary
    embedding (a sliding layer) does not."""
    bundle, params, batch = seeded(window=64)  # a window as wide as the sequence: only rotary differs
    cfg = bundle.config
    x = params["wte"][batch["tokens"]][:1]
    perm = np.r_[np.random.default_rng(1).permutation(63), 63]
    for kind, same in (("global", True), ("sliding", False)):
        p = one_layer(params, 0 if kind == "global" else 1)
        a = smallthinker._attention(p, x, cfg, kind)[0, -1]
        b = smallthinker._attention(p, x[:, perm], cfg, kind)[0, -1]
        assert bool(jnp.allclose(a, b, rtol=1e-4, atol=1e-5)) == same, kind


def test_the_step_notes_every_layer_kind_of_attention():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry

    tel = Telemetry(peer_id="v", enabled=True)
    with traced.subscribe(tel.count_traced):
        bundle, params, batch = seeded()
        jax.jit(lambda p: bundle.loss_fn(p, batch, None)[0]).lower(params)
    calls = tel.registry.counter("swarm.attention_core")._scrape()["values"]
    seen = {(r["labels"]["window"], r["labels"]["kv_heads"], r["labels"]["T"]): r["value"] for r in calls}
    assert seen == {("none", "2", "64"): 1, ("8", "2", "64"): 1}  # one trace a kind, whatever the depth
    dispatch = tel.registry.counter("swarm.moe_dispatch")._scrape()["values"]
    assert {r["labels"]["act"] for r in dispatch} == {"reglu"}
    assert {(r["labels"]["E"], r["labels"]["k"], r["labels"]["held"]) for r in dispatch} == {("16", "3", "4")}


# -- ReGLU experts and the share --------------------------------------------------------


def expert_layer_inputs(s=48, d=16, f=8, e=16, k=3, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    x = jax.random.normal(ks[0], (s, d))
    stacks = [jax.random.normal(kk, shape) * 0.3 for kk, shape in zip(ks[1:4], ((e, d, f), (e, d, f), (e, f, d)))]
    idx, weights, _ = smallthinker.route(jax.random.normal(ks[4], (d, e)), x, k)
    return x, idx, weights, stacks


def dense_experts(x, idx, weights, stacks, experts, act=jax.nn.relu):
    """Every named expert on every token, masked by the choices; and how many
    gate entries of the chosen (token, expert) pairs are not above zero."""
    y, zeros = jnp.zeros_like(x), 0
    for e in experts:
        chose = jnp.any(idx == e, axis=1)
        w = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=1)
        gate = x @ stacks[0][e]
        y = y + w[:, None] * ((act(gate) * (x @ stacks[1][e])) @ stacks[2][e])
        zeros += int(jnp.sum((gate <= 0) & chose[:, None]))
    return y, zeros


@pytest.mark.parametrize("offset,held", [(0, 4), (4, 4), (8, 8), (0, 16)])
def test_a_reglu_share_is_its_experts_part_with_gradients_and_counts_its_zeros(offset, held):
    x, idx, weights, stacks = expert_layer_inputs()
    mine = [w[offset:offset + held] for w in stacks]

    def share(x, weights, *held_stacks):
        return moe_dispatch.share_glu_experts(x, idx, weights, *held_stacks, offset, 16, act="relu")[0]

    def dense(x, weights, *held_stacks):
        full = [jnp.zeros_like(w).at[offset:offset + held].set(h) for w, h in zip(stacks, held_stacks)]
        return dense_experts(x, idx, weights, full, range(offset, offset + held))[0]

    y, sizes, dropped, moved, zeros = moe_dispatch.share_glu_experts(
        x, idx, weights, *mine, offset, 16, act="relu")
    want, want_zeros = dense_experts(x, idx, weights, stacks, range(offset, offset + held))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert int(dropped) == 0 and int(zeros) == want_zeros and 0 < want_zeros < int(jnp.sum(sizes)) * 8
    assert [int(n) for n in sizes] == [int(jnp.sum(idx == e)) for e in range(offset, offset + held)]
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    g1 = jax.jit(jax.grad(lambda *a: jnp.sum(share(*a) * probe), argnums=(0, 1, 2, 3, 4)))(x, weights, *mine)
    g2 = jax.grad(lambda *a: jnp.sum(dense(*a) * probe), argnums=(0, 1, 2, 3, 4))(x, weights, *mine)  # counts in Python
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)
    # SiLU experts: the same function, no zero count
    silu = moe_dispatch.share_glu_experts(x, idx, weights, *mine, offset, 16)
    assert silu[4] is None
    np.testing.assert_allclose(
        np.asarray(silu[0]),
        np.asarray(dense_experts(x, idx, weights, stacks, range(offset, offset + held), jax.nn.silu)[0]),
        rtol=2e-5, atol=2e-5)
    with pytest.raises(KeyError):
        moe_dispatch.share_glu_experts(x, idx, weights, *mine, offset, 16, act="gelu")


def test_a_plan_made_before_attention_is_the_plan_made_in_place():
    x, idx, weights, stacks = expert_layer_inputs()
    mine = [w[4:8] for w in stacks]
    plan = moe_dispatch.plan_share(idx, 4, 4, 16)
    cap = moe_dispatch.share_rows_bound(48, 3, 4, 16)
    assert plan.order.shape == plan.keys.shape and plan.order.shape[0] % cap == 0
    assert int(plan.n_held) == int(jnp.sum(plan.group_sizes)) == int(jnp.sum((idx >= 4) & (idx < 8)))
    a = moe_dispatch.share_glu_experts(x, idx, weights, *mine, 4, 16, act="relu", plan=plan)
    b = moe_dispatch.share_glu_experts(x, idx, weights, *mine, 4, 16, act="relu")
    for u, v in zip(a, b):
        assert np.array_equal(np.asarray(u), np.asarray(v))
    with pytest.raises(ValueError, match="another slack"):  # a plan padded to chunks of another size
        moe_dispatch.share_glu_experts(x, idx, weights, *mine, 4, 16, act="relu", plan=plan, slack=3.0)
    wide = moe_dispatch.plan_share(idx, 4, 4, 16, slack=3.0)
    c = moe_dispatch.share_glu_experts(x, idx, weights, *mine, 4, 16, act="relu", plan=wide, slack=3.0)
    np.testing.assert_allclose(np.asarray(c[0]), np.asarray(a[0]), rtol=2e-5, atol=2e-5)  # the same sum in wider chunks
    assert int(c[2]) == 0 and int(c[4]) == int(a[4]) and int(c[3]) == moe_dispatch.share_rows_bound(48, 3, 4, 16, 3.0)  # one chunk
    # the layer makes it first: in the jaxpr the share's sort precedes even the input norm, the loop follows attention
    bundle, params, batch = seeded()
    cfg, p = bundle.config, one_layer(params, 1)
    xin = params["wte"][batch["tokens"]][:1]
    text = str(jax.make_jaxpr(lambda p, x: smallthinker._layer(p, x, zero_stats(cfg), cfg, "sliding")[0])(p, xin))
    assert 0 < text.index("top_k") < text.index(" sort[") < text.index("rsqrt") < text.index("while[")


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test, on one layer of each kind of the tiny model:
    the outputs of the four shares of four experts each (at the cell's sizes
    eight of eight), with what every chip computes alike (attention, the
    residual) counted once, are the uncut reference's output for the whole
    layer."""
    uncut = dict(TINY, moe_num_primary_experts=16, expert_offset=0)
    bundle, params, batch = seeded(experts_held=16, expert_offset=0)
    hp = ref.hyper(uncut)
    x = params["wte"][batch["tokens"]][:1]
    for layer, kind in ((0, "global"), (2, "sliding")):
        p = one_layer(params, layer)
        with jax.default_matmul_precision("highest"):
            sliding = kind == "sliding"  # both published lists' entry for the layer
            block = jax.jit(lambda p: ref._block(p, x, sliding, sliding, None, hp)[0])
            whole = block(p)
            no_experts = jax.tree_util.tree_map(jnp.zeros_like, p["experts"])
            alike = block(dict(p, experts=no_experts))
        total = alike
        for offset in range(0, 16, 4):
            cfg = dataclasses.replace(bundle.config, experts_held=4, expert_offset=offset)
            held = jax.tree_util.tree_map(lambda a: a[offset:offset + 4], p["experts"])
            y, stats, _ = jax.jit(lambda p: smallthinker._layer(  # a program a share: the offset is the trace's
                p, x, zero_stats(cfg), cfg, kind))(dict(p, experts=held))
            assert float(stats["dropped"]) == 0.0
            total = total + (y - alike)  # this share's experts' part alone
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=2e-4, atol=2e-4)
        assert float(jnp.max(jnp.abs(y - whole))) > 1e-2  # one share is not the whole


def test_no_held_assignment_is_dropped_when_every_token_picks_the_same_experts():
    x, _, weights, stacks = expert_layer_inputs()
    idx = jnp.tile(jnp.asarray([[5, 6, 9]], jnp.int32), (48, 1))  # two held, one not: 96 rows on a share of 4
    y, sizes, dropped, moved, zeros = moe_dispatch.share_glu_experts(
        x, idx, weights, *[w[4:8] for w in stacks], 4, 16, act="relu")
    want, want_zeros = dense_experts(x, idx, weights, stacks, (5, 6))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert [int(n) for n in sizes] == [0, 48, 48, 0] and int(dropped) == 0 and int(zeros) == want_zeros
    assert int(moved) > moe_dispatch.share_rows_bound(48, 3, 4, 16)  # more than one chunk ran


# -- the activation argument leaves the SiLU models' programs as they were ------------

LAGUNA_TINY = dict(n_layers=5, d_model=64, head_dim=16, n_kv_heads=2, heads_full=6, heads_sliding=8, d_ff=128,
                   d_expert=32, d_shared=32, n_experts=16, top_k=4, experts_held=4, expert_offset=4, window=8,
                   max_len=64, vocab=512, xent_chunk=32, rotary_dim_full=8, yarn_original_len=16)
OLMOE_TINY = dict(n_layers=2, d_model=64, n_heads=4, n_experts=8, top_k=2, d_expert=32, max_len=32, vocab=256)


@pytest.mark.parametrize("model,overrides,lines,ops", [
    ("laguna_xs2", LAGUNA_TINY, 7383, 6960), ("olmoe_1b_7b", OLMOE_TINY, 1686, 1631)])
def test_a_silu_models_lowered_step_is_unchanged_and_gains_no_output(monkeypatch, model, overrides, lines, ops):
    """The loss and gradient program of Laguna and of OLMoE, lowered at a tiny
    size: OLMoE's as many lines and operations as at the parent of PR 35 (where
    the whole text was compared and equal), which says that the dropless path
    is untouched since; no count of zeros, no new metric. Laguna's step changed
    at PR 36, (7,549, 7,092) -> (7,323, 6,871), and for SmallThinker alike: the
    share's chunk is two grouped matmuls forward and five backward where it was
    three and nine (gate and up one product, the router's weight on ``hidden``
    in front of the down product, no padded copy of the rows before a token
    takes its run's sum): tests/test_moe_share_dispatch.py. And at PR 38,
    (7,323, 6,871) -> (7,566, 7,156): a token's run is summed by one batched
    0/1 product a call of ``_combine`` (with the 0/1 matrices, the carry over
    a tile's edge and its select written out: 36 more operations a call in
    the text, eight calls) where three rounds of slice, select, pad and add
    were; the compiled step holds one fusion for them (tests/test_tpu_compile.py).
    And at PR 46, (7,566, 7,156) -> (7,553, 7,122): the sizes handed to the
    grouped matmuls are no longer topped up to the chunk's rows, ``_spread``
    zeroes nothing (three selects a layer gone), and ``_combine``'s gather
    index and the router weights take a select each, over ``[rows]``, not
    ``[rows, d]`` (tests/test_moe_share_dispatch.py). And at PR 59, (7,553,
    7,122) -> (7,455, 7,018): Laguna's gate multiplies the attention's merged
    ``[B, T, H * D]`` result through a 0/1 product (no transpose of the gate,
    none of the product back, no sum by head); at this size the core itself is ``attention_merged``'s by-head
    fallback, the operations it was, which is why OLMoE's count stood. And at
    PR 61 both, for the first time OLMoE's: the loss head makes its gradients in
    the loop that makes its loss (``common.lm_xent_chunked``, a ``custom_vjp``).
    Laguna's two chunks of 32, (7,455, 7,018) -> (7,383, 6,960): the backward's
    second loop with its recomputed logits is gone. OLMoE's T of 32 is one
    chunk, which ran whole logits under plain autodiff and now runs the same
    rule as a scan of length one, (1,654, 1,601) -> (1,686, 1,631): the loop's
    own slices and the ``dlogits`` written out where autodiff's transpose rules
    wrote less text for the same three products."""
    monkeypatch.setattr(moe_dispatch, "SHARE_ROWS_SLACK", 3.0)  # the program's own
    bundle = get_model(model, **overrides)
    params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    t = overrides["max_len"]
    batch = {k: jax.ShapeDtypeStruct((2, t), jnp.int32) for k in ("tokens", "targets")}
    step = jax.jit(lambda p, b: jax.value_and_grad(lambda p: bundle.loss_fn(p, b, None), has_aux=True)(p))
    text = step.lower(params, batch).as_text()
    assert (len(text.splitlines()), len(re.findall(r"stablehlo\.\w+", text))) == (lines, ops)
    (_, metrics), _ = jax.eval_shape(step, params, batch)
    assert "moe_act_zero_share" not in metrics
    assert getattr(bundle.config, "router_site", "post_attention") == "post_attention"


# -- the scanned two-kind tree through the rest of the system ---------------------------


def test_published_sizes_and_parameter_counts():
    from benchmark import flops_smallthinker

    full = jax.eval_shape(get_model("smallthinker_21b_a3b").init, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_leaves(full["blocks"]["sliding"])[0].shape[:2] == (13, 3)
    n = common.count_params(full)
    cell = Manifest().load_config("smallthinker-21b-a3b")
    published = dict(cell, num_hidden_layers=52, moe_num_primary_experts=64, vocab_size=151936)
    assert n == flops_smallthinker.total_params(published) == 21_506_562_560 and round(n / 1e9, 1) == 21.5  # "21B"
    cut = jax.eval_shape(get_model("smallthinker_21b_a3b", **cell["model_overrides"]).init, jax.random.PRNGKey(0))
    assert common.count_params(cut) == 370_547_200 == cell["parameters"]["counted_by_the_program"]
    assert common.count_params(cut) == flops_smallthinker.total_params(cell)
    one_period = [common.count_params(cut["blocks"]["global"])] + [common.count_params(cut["blocks"]["sliding"]) // 3] * 3
    assert one_period == cell["parameters"]["by_layer"]


def test_scanned_leaves_of_both_kinds_take_the_sharding_rules(eight_devices):
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from distributedvolunteercomputing_tpu.parallel import sharding
    from distributedvolunteercomputing_tpu.parallel.mesh import AXES

    mesh = Mesh(np.array(eight_devices).reshape(1, 1, 1, 4, 2), AXES)  # ep=4, tp=2
    shapes = jax.eval_shape(get_model("smallthinker_21b_a3b", **OVERRIDES).init, jax.random.PRNGKey(0))
    specs = jax.tree_util.tree_map(lambda s: s.spec, sharding.make_param_shardings(mesh, shapes))
    g, s = specs["blocks"]["global"], specs["blocks"]["sliding"]
    # right-aligned: the period axis (and the sliding layers' own) stay whole
    assert g["experts"]["w_gate"] == P(None, "ep", None, "tp") and g["experts"]["w_down"] == P(None, "ep", "tp", None)
    assert s["experts"]["w_up"] == P(None, None, "ep", None, "tp")
    assert g["wq"] == g["wk"] == P(None, None, "tp") and g["wo"] == P(None, "tp", None)
    assert s["wq"] == s["wv"] == P(None, None, None, "tp") and s["wo"] == P(None, None, "tp", None)
    assert g["router"] == P() and s["router"] == P() and s["ln_mlp"]["g"] == P()
    assert specs["lm_head"] == P(None, "tp")


def test_save_and_restore_with_the_scanned_tree(tmp_path):
    from distributedvolunteercomputing_tpu.training import checkpoint
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    make = lambda seed: Trainer(  # noqa: E731
        get_model("smallthinker_21b_a3b", **OVERRIDES), batch_size=2, optimizer="adam", lr=1e-3, init_seed=seed)
    tr = make(1)
    tr.run(steps=3)
    checkpoint.save(tr, str(tmp_path))
    fresh = make(2)
    assert checkpoint.maybe_restore(fresh, str(tmp_path)) and int(fresh.state.step) == 3
    assert jax.tree_util.tree_structure(fresh.state.params) == jax.tree_util.tree_structure(tr.state.params)
    for a, b in zip(jax.tree_util.tree_leaves(fresh.state.params), jax.tree_util.tree_leaves(tr.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    before = float(tr.run(steps=2)["final_loss"])
    assert float(fresh.run(steps=2)["final_loss"]) == pytest.approx(before, rel=1e-5)


def test_train_loop_records_router_site_and_zero_share_on_the_route_span_and_as_a_gauge():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    tr = Trainer(get_model("smallthinker_21b_a3b", **OVERRIDES), batch_size=2, optimizer="adam", lr=1e-3,
                 tracer=tel.tracer)
    summary = tr.run(steps=11, log_every=5)
    assert math.isfinite(summary["final_loss"])
    routes = [s for s in tel.tracer.spans() if s["name"] == "moe.route"]
    assert [s["attrs"]["step"] for s in routes] == [5, 10]
    bound = moe_dispatch.share_rows_bound(2 * 64, 3, 4, 16)
    for s in routes:
        a = s["attrs"]
        assert a["router_site"] == "layer_input" and a["experts_held"] == 4 and a["moe_dropped"] == 0.0
        assert 0.2 < a["moe_act_zero_share"] < 0.8
        assert a["moe_load_mean"] == 2 * 64 * 3 / 16 and 0 < a["moe_rows_held"] <= 4 * 2 * 64 * 3
        assert a["moe_rows_moved"] % bound == 0 and a["moe_rows_moved"] >= a["moe_rows_held"]
    moe = tel.summary()["moe"]
    assert moe["act_zero_share"] == pytest.approx(routes[-1]["attrs"]["moe_act_zero_share"])
    assert moe["experts_held"] == 4.0 and moe["dropped_total"] == 0.0


def test_a_silu_models_route_span_says_post_attention_and_carries_no_zero_share():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    tr = Trainer(get_model("olmoe_1b_7b", **OLMOE_TINY), batch_size=2, optimizer="adam", lr=1e-3, tracer=tel.tracer)
    tr.run(steps=6, log_every=5)
    (route,) = [s for s in tel.tracer.spans() if s["name"] == "moe.route"]
    assert route["attrs"]["router_site"] == "post_attention" and "moe_act_zero_share" not in route["attrs"]
    assert "act_zero_share" not in tel.summary()["moe"]
    off = Telemetry(peer_id="v", enabled=False)
    off.count_traced("moe_dispatch", dict(impl="megablox", E=64, k=6, rows=73728, held=8, act="reglu"))  # off: not counted
    assert off.registry.counter("swarm.moe_dispatch")._scrape()["values"] == []
