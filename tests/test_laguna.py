"""Laguna-XS.2 on the normal path (models/laguna.py), at a tiny size on the
CPU: the program against the plain reference (benchmark/references/laguna.py)
on the loss and every leaf's gradient; each mechanism alone against its plain
form (grouped key/value heads, the window in the XLA core and in the kernel,
partial and YaRN rotary, the per-head gate, sigmoid routing with its scale);
the share of the experts a chip holds (all shares and the shared expert once
add up to the uncut layer; nothing held is dropped under total imbalance;
holding every expert is the dispatch as it was); the unstacked parameter tree
through a round, a checkpoint, the sharding rules and the train loop's spans."""

import asyncio
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from benchmark.references import laguna as ref
from distributedvolunteercomputing_tpu.models import get_model, laguna, moe
from distributedvolunteercomputing_tpu.ops import attention, moe_dispatch
from distributedvolunteercomputing_tpu.ops.pallas_attention import choose_blocks, flash_attention
from distributedvolunteercomputing_tpu.utils import traced
from tests import tiny_models

TINY = tiny_models.rehearsal("laguna")
OVERRIDES = TINY["model_overrides"]
HP = ref.hyper(TINY)
# the reference's own loss-and-gradient as the harness calls it, under one jit
REFERENCE = jax.jit(ref.make_loss_and_grad(TINY))


def reference_loss(hp=HP, **static):
    """``ref.loss`` of ``(params, tokens, targets)`` at ``hp`` as one program."""
    return jax.jit(lambda params, tokens, targets: ref.loss(params, tokens, targets, hp, **static))


@pytest.fixture(autouse=True)
def tight_chunks(monkeypatch):
    """A chunk a quarter over the even share (the program's is three times
    it): at these sizes several chunks run."""
    monkeypatch.setattr(moe_dispatch, "SHARE_ROWS_SLACK", 1.25)


def seeded(scale: float = 3.0, **overrides):
    """The tiny model with weights scaled up so that every term matters, and
    two seeded sequences."""
    bundle = tiny_models.bundle("laguna", **overrides)
    params = jax.jit(bundle.init)(jax.random.PRNGKey(3))
    params = jax.tree_util.tree_map(lambda x: x * scale if x.ndim > 1 else x, params)
    rng = np.random.default_rng(0)
    t, v = bundle.config.max_len, bundle.config.vocab
    batch = {"tokens": jnp.asarray(rng.integers(0, v, (2, t))),
             "targets": jnp.asarray(rng.integers(0, v, (2, t)))}
    return bundle, params, batch


def leaf_errors(got, want):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
            for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                    jax.tree_util.tree_leaves(want))}


# -- the program against the reference ---------------------------------------------


@pytest.mark.parametrize("remat", [True, False])
def test_float32_program_equals_the_reference_on_loss_and_every_leaf(remat):
    bundle, params, batch = seeded(remat=remat)
    ref.check_config(dataclasses.replace(bundle.config, remat=True), TINY)
    lp, gp = tiny_models.programs(bundle).loss_and_grad(params, batch)
    lr, gr = REFERENCE(params, batch["tokens"], batch["targets"])
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    errors = leaf_errors(gp, gr)
    assert len(errors) == 69  # five layers' leaves, embedding, head, final norm
    assert max(errors.values()) < 1e-4, max(errors.items(), key=lambda kv: kv[1])


def test_the_layers_follow_the_published_pattern():
    cfg = laguna.LagunaConfig()
    want = Manifest().load_config("laguna-xs2")
    assert [cfg.attention_kind(l) for l in range(40)] == want["layer_types"]
    assert [cfg.ffn_kind(l) for l in range(40)] == want["mlp_layer_types"]
    assert [cfg.heads(l) for l in range(40)] == want["num_attention_heads_per_layer"]
    shapes = jax.eval_shape(get_model("laguna_xs2", **OVERRIDES).init, jax.random.PRNGKey(0))
    assert [("mlp" in b, "experts" in b, b["wq"].shape[1] // 16) for b in shapes["blocks"]] == [
        (True, False, 6), (False, True, 8), (False, True, 8), (False, True, 8), (False, True, 6)]
    assert shapes["blocks"][1]["experts"]["w_gate"].shape == (4, 64, 32)  # the share held
    assert shapes["blocks"][1]["router"].shape == (64, 16)                # the router keeps its width


AS_WRITTEN = reference_loss()


@pytest.mark.parametrize("name,change", [
    ("the routed scale", lambda hp: dict(hp, routed_scale=1.0)),
    ("the window", lambda hp: dict(hp, window=1 << 20)),
    ("YaRN's attention factor", lambda hp: dict(hp, rope={
        **hp["rope"], "full_attention": {**hp["rope"]["full_attention"], "attention_factor": 1.0}})),
    ("the held share", lambda hp: dict(hp, offset=0)),
])
def test_reference_notices_a_term_left_out(name, change):
    bundle, params, batch = seeded()
    program = float(tiny_models.programs(bundle).loss(params, batch))
    assert program == pytest.approx(float(AS_WRITTEN(params, batch["tokens"], batch["targets"])), rel=1e-5)
    other = float(reference_loss(change(HP))(params, batch["tokens"], batch["targets"]))
    assert abs(other - program) > 1e-4, name


def test_reference_notices_a_missing_gate():
    bundle, params, batch = seeded()
    ungated = dict(params, blocks=[dict(b, wg=b["wg"] + 1.0) for b in params["blocks"]])
    a = float(AS_WRITTEN(params, batch["tokens"], batch["targets"]))
    b = float(AS_WRITTEN(ungated, batch["tokens"], batch["targets"]))
    assert abs(a - b) > 1e-4 and b == pytest.approx(float(tiny_models.programs(bundle).loss(ungated, batch)), rel=1e-5)


def test_routes_given_equal_routes_computed():
    bundle, params, batch = seeded()
    loss, routes = reference_loss(with_routes=True)(params, batch["tokens"], batch["targets"])
    assert routes.shape == (4, batch["tokens"].size, 4)  # four expert layers of five
    _, _, mine = tiny_models.programs(bundle).loss_and_routes(params, batch)
    assert np.array_equal(np.sort(np.asarray(mine), -1), np.sort(np.asarray(routes), -1))
    fn = REFERENCE
    l0, g0 = fn(params, batch["tokens"], batch["targets"])
    l1, g1 = fn(params, batch["tokens"], batch["targets"], routes)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6) == pytest.approx(float(loss), rel=1e-6)
    assert max(leaf_errors(g1, g0).values()) < 1e-5


# -- each mechanism against its plain form -------------------------------------------


def plain_attention(q, k, v, window=None):
    """Softmax attention with the key/value heads repeated and an explicit mask."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    t = q.shape[2]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = (j <= i) if window is None else (j <= i) & (j > i - window)
    s = jnp.where(keep, jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1]), -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def qkv(key, heads, kv_heads, t, d=16, b=2):
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (b, heads, t, d)), jax.random.normal(ks[1], (b, kv_heads, t, d)),
            jax.random.normal(ks[2], (b, kv_heads, t, d)), jax.random.normal(ks[3], (b, heads, t, d)))


@pytest.mark.parametrize("heads,kv_heads,window", [(6, 2, None), (8, 2, 8), (4, 4, 8), (8, 1, 5)])
def test_xla_core_with_grouped_heads_and_a_window(heads, kv_heads, window):
    q, k, v, cot = qkv(jax.random.PRNGKey(1), heads, kv_heads, t=20)
    attention.set_attention_impl("xla")
    try:
        f = lambda core: jax.jit(jax.value_and_grad(  # noqa: E731
            lambda q, k, v: jnp.sum(core(q, k, v) * cot), argnums=(0, 1, 2)))(q, k, v)
        got = f(lambda q, k, v: attention.attention_core(q, k, v, causal=True, window=window))
        want = f(lambda q, k, v: plain_attention(q, k, v, window))
    finally:
        attention.set_attention_impl("auto")
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,window,bq,bk,heads,kv_heads", [
    (52, 8, 16, 16, 4, 2),    # T no multiple of the window or the blocks: padded rows and keys
    (64, 8, 16, 16, 6, 2),    # blocks of two windows: skipped, edge-crossed and inner blocks
    (96, 24, 16, 32, 4, 4),   # the far edge crosses two q-blocks of a k-block
    (96, 24, 32, 16, 4, 1),   # ... and two k-blocks of a q-block; one key/value head
    (64, None, 16, 16, 8, 2),  # grouped heads, no window
    (64, 100, 16, 16, 2, 1),  # a window wider than the sequence is full attention
])
def test_kernel_with_grouped_heads_and_a_window_forward_and_backward(t, window, bq, bk, heads, kv_heads):
    """The Pallas kernel, interpreted, against the plain form: the loops that
    start at the window's first block and end at the diagonal, the masked and
    unmasked bodies, dk and dv summed over a group's query heads."""
    q, k, v, cot = qkv(jax.random.PRNGKey(t), heads, kv_heads, t)
    f = lambda core: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda q, k, v: jnp.sum(core(q, k, v) * cot), argnums=(0, 1, 2)))(q, k, v)
    got = f(lambda q, k, v: flash_attention(q, k, v, True, bq, bk, None, window))
    want = f(lambda q, k, v: plain_attention(q, k, v, window))
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


def test_kernel_and_router_refuse_what_they_cannot_compute():
    q, k, v, _ = qkv(jax.random.PRNGKey(0), 6, 4, 16)
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q, k, v, True)
    with pytest.raises(ValueError, match="do not divide"):
        attention.attention_core(q, k, v, causal=True)
    q, k, v, _ = qkv(jax.random.PRNGKey(0), 4, 2, 16)
    with pytest.raises(ValueError, match="window needs causal"):
        flash_attention(q, k, v, False, window=4)
    with pytest.raises(ValueError, match="window needs causal"):
        attention.attention_core(q, k, v, causal=False, window=4)


def test_blocks_of_a_windowed_call_are_no_wider_than_the_window():
    assert choose_blocks(8192, 8192, 128, jnp.bfloat16) == (1024, 1024)
    assert choose_blocks(8192, 8192, 128, jnp.bfloat16, 512) == (512, 512)
    assert choose_blocks(8192, 8192, 128, jnp.bfloat16, 100) == (128, 128)
    assert choose_blocks(1024, 1024, 64, jnp.bfloat16, None) == (1024, 1024)  # the gpt2 cells' choice


def test_the_step_announces_window_and_key_value_heads():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry

    tel = Telemetry(peer_id="t", enabled=True)
    bundle, params, batch = seeded()
    with traced.subscribe(tel.count_traced):
        jax.jit(lambda p: bundle.loss_fn(p, batch, None)[0])(params)
    cores = {(r["labels"]["window"], r["labels"]["kv_heads"], r["labels"]["impl"]): r["value"]
             for r in tel.registry.counter("swarm.attention_core")._scrape()["values"]}
    assert cores == {("none", "2", "xla"): 2, ("8", "2", "xla"): 3}
    rec = tel.registry.counter("swarm.moe_dispatch")._scrape()["values"][0]
    rows = moe_dispatch.share_rows_bound(2 * 64, 4, 4, 16)
    assert rec["labels"] == {"impl": "ragged_dot", "E": "16", "k": "4", "rows": str(rows), "held": "4",
                             "act": "swiglu"}
    assert rec["value"] == 4


def complex_rotation(x, inv_freq, scale=1.0):
    """Half-split rotary as a complex product: coordinates (i, i + D/2) are
    one complex number turned by ``t * inv_freq[i]``, then scaled."""
    half = x.shape[-1] // 2
    z = (x[..., :half] + 1j * x[..., half:]) * jnp.exp(
        1j * jnp.arange(x.shape[-2])[:, None] * inv_freq[None, :]) * scale
    return jnp.concatenate([z.real, z.imag], axis=-1)


def test_partial_yarn_rotary_against_a_complex_number_formula():
    cfg = laguna.LagunaConfig()
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 40, cfg.head_dim))
    inv_freq = attention.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    # the published settings: dimensions that turn more than 64 times in 4,096
    # positions keep their frequency, those that turn less than once are
    # slowed 64-fold, and the ramp lies between
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    low = math.floor(64 * math.log(4096 / (64 * 2 * math.pi)) / (2 * math.log(500000.0)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(500000.0)))
    assert (low, high) == (5, 16)
    np.testing.assert_allclose(inv_freq[: low + 1], plain[: low + 1], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[high:], plain[high:] / 64, rtol=1e-6)
    assert np.all(np.diff(np.asarray(inv_freq)) < 0)
    got = laguna.rotary(x, cfg, laguna.FULL)
    want = jnp.concatenate(
        [complex_rotation(x[..., :64], inv_freq, cfg.yarn_attention_factor), x[..., 64:]], axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
    assert cfg.yarn_attention_factor == pytest.approx(0.1 * math.log(64.0) + 1.0)
    sliding = laguna.rotary(x, cfg, laguna.SLIDING)
    want = complex_rotation(x, 10000.0 ** (-jnp.arange(0, 128, 2) / 128))
    np.testing.assert_allclose(np.asarray(sliding), np.asarray(want), rtol=1e-4, atol=1e-5)
    # and both are the reference's own
    hp = ref.hyper(Manifest().load_config("laguna-xs2"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref._rope(x, laguna.FULL, hp)), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sliding), np.asarray(ref._rope(x, laguna.SLIDING, hp)), rtol=1e-4, atol=1e-5)


def test_the_gate_scales_each_head_before_the_output_projection():
    bundle, params, _ = seeded()
    cfg, p = bundle.config, params["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, cfg.d_model))
    n = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_eps)
    heads = lambda w, h: (n @ w).reshape(1, 24, h, cfg.head_dim).transpose(0, 2, 1, 3)  # noqa: E731
    q, k = (laguna.rotary(heads(p[w], h), cfg, laguna.SLIDING) for w, h in (("wq", 8), ("wk", 2)))
    a = plain_attention(q, k, heads(p["wv"], 2), cfg.window)                # [1, 8, T, 16]
    gate = jax.nn.sigmoid(n @ p["wg"])                                      # [1, T, 8]
    want = x + (a.transpose(0, 2, 1, 3) * gate[..., None]).reshape(1, 24, -1) @ p["wo"]
    got = laguna._attention(p, x, cfg, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    half = laguna._attention(dict(p, wg=p["wg"] * 0), x, cfg, 1)  # every gate at sigmoid(0)
    ungated = a.transpose(0, 2, 1, 3).reshape(1, 24, -1) @ p["wo"]
    np.testing.assert_allclose(np.asarray(half - x), np.asarray(0.5 * ungated), rtol=1e-4, atol=1e-4)


def test_router_is_sigmoid_top_k_normalised_and_scaled():
    h = jax.random.normal(jax.random.PRNGKey(7), (12, 64))
    w = jax.random.normal(jax.random.PRNGKey(8), (64, 16))
    idx, weights, scores = moe.route(w, h, 4, 2.5)
    s = 1.0 / (1.0 + np.exp(-np.asarray(h @ w, np.float64)))
    np.testing.assert_allclose(np.asarray(scores), s, rtol=1e-5)
    want_idx = np.argsort(-s, axis=1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(idx), 1), np.sort(want_idx, 1))
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(np.asarray(weights), 2.5 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(1), 2.5, rtol=1e-5)


# -- the share of the experts ----------------------------------------------------------


def expert_layer_inputs(s=48, d=16, f=8, e=16, k=4, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    x = jax.random.normal(ks[0], (s, d))
    stacks = [jax.random.normal(kk, shape) * 0.3 for kk, shape in zip(ks[1:4], ((e, d, f), (e, d, f), (e, f, d)))]
    idx, weights, _ = moe.route(jax.random.normal(ks[4], (d, e)), x, k, 2.5)
    return x, idx, weights, stacks


def dense_experts(x, idx, weights, stacks, experts):
    """Every named expert on every token, masked by the choices."""
    y = jnp.zeros_like(x)
    for e in experts:
        w = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=1)
        y = y + w[:, None] * ((jax.nn.silu(x @ stacks[0][e]) * (x @ stacks[1][e])) @ stacks[2][e])
    return y


def test_all_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The guide's share test, on one expert layer of the tiny model: the
    outputs of the four shares of four experts each, with what every chip
    computes alike (the residual, the shared expert) counted once, are the
    uncut reference's output for the whole layer."""
    uncut = dict(TINY, num_experts=16, expert_offset=0)
    bundle, params, batch = seeded(experts_held=16, expert_offset=0)
    hp, p = ref.hyper(uncut), params["blocks"][1]
    x = params["wte"][batch["tokens"]][:1]
    with jax.default_matmul_precision("highest"):
        block = jax.jit(lambda p: ref._block(p, x, 1, None, hp)[0])
        whole = block(p)
        # what every chip computes alike: attention, the residual, the shared expert
        no_experts = jax.tree_util.tree_map(jnp.zeros_like, p["experts"])
        alike = block(dict(p, experts=no_experts))
    total = alike
    for offset in range(0, 16, 4):
        cfg = dataclasses.replace(bundle.config, experts_held=4, expert_offset=offset)
        held = jax.tree_util.tree_map(lambda a: a[offset:offset + 4], p["experts"])
        y, stats, _ = jax.jit(lambda p: laguna._layer(  # a program a share: the offset is the trace's
            p, x, moe.zero_share_stats(balanced=cfg.n_experts), cfg, 1))(dict(p, experts=held))
        assert float(stats["dropped"]) == 0.0
        total = total + (y - alike)  # this share's experts' part alone
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=2e-4, atol=2e-4)
    # and one share is not the whole: the others' part is a real part of it
    assert float(jnp.max(jnp.abs(y - whole))) > 1e-2


@pytest.mark.parametrize("offset", [0, 4, 12])
def test_a_share_is_its_experts_part_with_gradients(offset):
    x, idx, weights, stacks = expert_layer_inputs()
    held = [w[offset:offset + 4] for w in stacks]

    def share(x, weights, *held):
        y, sizes, dropped, moved, _ = moe_dispatch.share_glu_experts(x, idx, weights, *held, offset, 16)
        return jnp.sum(jnp.sin(y)), (y, sizes, dropped, moved)

    def dense(x, weights, *held):
        full = [w.at[offset:offset + 4].set(h) for w, h in zip(stacks, held)]
        return jnp.sum(jnp.sin(dense_experts(x, idx, weights, full, range(offset, offset + 4))))

    (_, (y, sizes, dropped, moved)), got = jax.jit(jax.value_and_grad(share, (0, 1, 2, 3, 4), has_aux=True))(
        x, weights, *held)
    want = jax.jit(jax.grad(dense, (0, 1, 2, 3, 4)))(x, weights, *held)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=16)[offset:offset + 4]
    assert np.array_equal(np.asarray(sizes), counts) and int(dropped) == 0
    bound = moe_dispatch.share_rows_bound(48, 4, 4, 16)
    assert int(moved) == -(-counts.sum() // bound) * bound  # whole chunks, as many as the step needs


def test_no_held_assignment_is_dropped_when_every_token_picks_the_same_expert():
    """Total imbalance: all 48 tokens choose held expert 5 (and three experts
    that are not held): one chunk; then every choice on held experts, which
    takes as many bounded chunks as that needs."""
    x, _, weights, stacks = expert_layer_inputs()
    idx = jnp.tile(jnp.array([[5, 0, 9, 13]], jnp.int32), (48, 1))
    held = [w[4:8] for w in stacks]
    y, sizes, dropped, moved, _ = moe_dispatch.share_glu_experts(x, idx, weights, *held, 4, 16)
    bound = moe_dispatch.share_rows_bound(48, 4, 4, 16)
    assert np.array_equal(np.asarray(sizes), [0, 48, 0, 0]) and int(dropped) == 0
    assert int(moved) == bound == 64  # one chunk holds them
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense_experts(x, idx, weights, stacks, [5])),
                               rtol=1e-4, atol=1e-5)
    # every choice held: all S x k assignments on this share, still none dropped
    idx = jnp.tile(jnp.array([[4, 5, 6, 7]], jnp.int32), (48, 1))
    y, sizes, dropped, moved, _ = moe_dispatch.share_glu_experts(x, idx, weights, *held, 4, 16)
    assert np.asarray(sizes).sum() == 192 and int(dropped) == 0 and int(moved) == 3 * bound
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense_experts(x, idx, weights, stacks, range(4, 8))),
                               rtol=1e-4, atol=1e-5)
    # no choice held (a router collapsed onto other chips' experts): zeros, and
    # still one chunk, so that a step's cost does not follow the router
    idx = jnp.tile(jnp.array([[0, 1, 9, 13]], jnp.int32), (48, 1))
    y, sizes, dropped, moved, _ = moe_dispatch.share_glu_experts(x, idx, weights, *held, 4, 16)
    assert np.asarray(sizes).sum() == 0 and int(dropped) == 0 and int(moved) == bound
    assert not np.asarray(y).any()


@pytest.mark.parametrize("k", [3, 8])
def test_rows_return_to_their_tokens_as_a_segment_sum_and_its_transpose(k):
    """``_combine`` (sort by token, the runs summed by one 0/1 product a tile
    of rows since PR 38, one gather) against a plain segment sum, with runs of
    every length up to k; ``_spread`` is its transpose. The rows are whole
    sublane tiles, as a chunk's always are (``share_rows_bound``): the last
    few are not held."""
    rng = np.random.default_rng(k)
    n_tokens = 20  # eight tokens with k rows, twelve with 0 .. k: a token has at most k choices
    tok = np.concatenate([np.repeat(np.arange(8), k), np.repeat(np.arange(8, n_tokens), rng.integers(0, k + 1, 12))])
    filler = -tok.shape[0] % 8
    tok = np.concatenate([rng.permutation(tok), rng.integers(8, n_tokens, filler)])
    tok, r = jnp.asarray(tok, jnp.int32), tok.shape[0]
    valid = jnp.asarray((rng.random(r) < 0.8) & (np.arange(r) < r - filler))
    valid = valid.at[jnp.nonzero(tok < 8)[0]].set(True)  # eight whole runs of k rows
    rows = jnp.asarray(rng.normal(size=(r, 5)), jnp.float32)
    where = (tok, valid, *moe_dispatch._token_runs(tok, valid, n_tokens))
    want = jax.ops.segment_sum(jnp.where(valid[:, None], rows, 0.0), tok, n_tokens)
    np.testing.assert_allclose(np.asarray(moe_dispatch._combine(rows, where, k)), np.asarray(want), rtol=1e-5, atol=1e-6)
    x = jnp.asarray(rng.normal(size=(n_tokens, 5)), jnp.float32)
    got = jax.grad(lambda x: jnp.sum(moe_dispatch._spread(x, where, k) * rows))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_the_dropped_count_reads_the_sizes_the_kernels_are_handed(monkeypatch):
    """Sizes that lied about an expert's rows would show: two rows short for
    the first expert leave its last two in the next expert's group."""
    x, idx, weights, stacks = expert_layer_inputs()
    real = moe_dispatch._handed_sizes
    monkeypatch.setattr(moe_dispatch, "_handed_sizes",
                        lambda sizes, lo, rows: real(sizes, lo, rows).at[0].add(-2).at[-1].add(2))
    *_, dropped, _, _ = moe_dispatch.share_glu_experts(x, idx, weights, *[w[:4] for w in stacks], 0, 16)
    assert int(dropped) > 0


def test_holding_every_expert_is_the_dispatch_as_it_was():
    x, idx, weights, stacks = expert_layer_inputs()
    y, sizes, dropped, moved, _ = moe_dispatch.share_glu_experts(x, idx, weights, *stacks, 0, 16)
    y0, sizes0, dropped0, _ = moe_dispatch.dropless_glu_experts(x, idx, weights, *stacks)
    assert np.array_equal(np.asarray(y), np.asarray(y0)) and np.array_equal(np.asarray(sizes), np.asarray(sizes0))
    assert int(dropped) == int(dropped0) == 0 and int(moved) == 48 * 4
    # the same program: no chunk loop, no second sort
    text = jax.jit(lambda *a: moe_dispatch.share_glu_experts(*a, 0, 16)[0]).lower(
        x, idx, weights, *stacks).as_text()
    assert "while" not in text
    want = jax.jit(lambda *a: moe_dispatch.dropless_glu_experts(*a)[0]).lower(
        x, idx, weights, *stacks).as_text()
    assert text.count("stablehlo.sort") == want.count("stablehlo.sort") == 1


def test_a_share_moves_a_bounded_chunk_of_rows_and_differentiates_into_gathers():
    assert moe_dispatch.share_rows_bound(32768, 8, 16, 256) == 20480   # a quarter over 16,384 (this file's slack)
    assert moe_dispatch.share_rows_bound(48, 4, 4, 16) == 64
    assert moe_dispatch.share_rows_bound(8, 2, 4, 4) == 16             # never more than S x k
    x, idx, weights, stacks = expert_layer_inputs()
    held = [w[:4] for w in stacks]
    text = jax.jit(jax.grad(lambda x: jnp.sum(
        moe_dispatch.share_glu_experts(x, idx, weights, *held, 0, 16)[0]))).lower(x).as_text()
    assert "192x16x" not in text  # no [S k, d] tensor: the chunk's 64 rows are what moves
    # the only scatters are of row indices (int32) and of the S k gates'
    # cotangents (scalars), never of rows
    import re
    scattered = re.findall(r'"stablehlo.scatter".*?-> tensor<([^>]+)>', text, flags=re.S)
    assert scattered and all(t.endswith("xi32") or t == "192xf32" for t in scattered), scattered


# -- the unstacked tree through the rest of the system ---------------------------------


def test_published_sizes_and_parameter_counts():
    from benchmark import flops_laguna

    full = jax.eval_shape(get_model("laguna_xs2").init, jax.random.PRNGKey(0))
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(full))
    assert n == 33_442_596_864 and round(n / 1e9, 1) == 33.4  # what the model's card states
    cell = Manifest().load_config("laguna-xs2")
    cut = jax.eval_shape(get_model("laguna_xs2", **cell["model_overrides"]).init, jax.random.PRNGKey(0))
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(cut))
    assert n == 490_297_344 == cell["parameters"]["counted_by_the_program"] == flops_laguna.total_params(cell)
    assert [sum(int(x.size) for x in jax.tree_util.tree_leaves(b)) for b in cut["blocks"]] == cell[
        "parameters"]["by_layer"]


def test_unstacked_leaves_take_the_sharding_rules(eight_devices):
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from distributedvolunteercomputing_tpu.parallel import sharding
    from distributedvolunteercomputing_tpu.parallel.mesh import AXES

    mesh = Mesh(np.array(eight_devices).reshape(1, 1, 1, 4, 2), AXES)  # ep=4, tp=2
    shapes = jax.eval_shape(get_model("laguna_xs2", **OVERRIDES).init, jax.random.PRNGKey(0))
    specs = jax.tree_util.tree_map(lambda s: s.spec, sharding.make_param_shardings(mesh, shapes))
    layer = specs["blocks"][1]
    assert layer["experts"]["w_gate"] == P("ep", None, "tp") and layer["experts"]["w_down"] == P("ep", "tp", None)
    assert layer["shared"]["w_up"] == P(None, "tp") and layer["shared"]["w_down"] == P("tp", None)
    assert layer["wq"] == layer["wk"] == layer["wg"] == P(None, "tp") and layer["wo"] == P("tp", None)
    assert specs["blocks"][0]["mlp"]["w_gate"] == P(None, "tp") and layer["router"] == P()


def test_one_two_peer_round_averages_every_leaf_of_the_unstacked_tree():
    from distributedvolunteercomputing_tpu.swarm.averager import SyncAverager
    from distributedvolunteercomputing_tpu.swarm.dht import DHTNode
    from distributedvolunteercomputing_tpu.swarm.membership import SwarmMembership
    from distributedvolunteercomputing_tpu.swarm.transport import Transport

    bundle = get_model("laguna_xs2", **OVERRIDES)
    trees = [jax.tree_util.tree_map(np.asarray, bundle.init(jax.random.PRNGKey(s))) for s in (1, 2)]

    async def main():
        vols, boot = [], None
        for i in range(2):
            t = Transport()
            dht = DHTNode(t)
            await dht.start(bootstrap=[boot] if boot else None)
            boot = boot or t.addr
            mem = SwarmMembership(dht, f"vol{i}", ttl=10.0)
            await mem.join()
            vols.append((t, mem, SyncAverager(t, dht, mem, join_timeout=40.0, gather_timeout=60.0, min_group=2)))
        try:
            return await asyncio.gather(*(v[2].average(tree, round_no=1) for v, tree in zip(vols, trees)))
        finally:
            for t, mem, _ in vols:
                await mem.leave()
                await t.close()

    results = asyncio.run(asyncio.wait_for(main(), timeout=150))  # generous: six test workers share the cores
    want = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, *trees)
    for got in results:
        assert got is not None, "the round formed no group"
        assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), b, rtol=1e-5, atol=1e-7)


def test_save_and_restore_with_the_unstacked_tree(tmp_path):
    from distributedvolunteercomputing_tpu.training import checkpoint
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    make = lambda seed: Trainer(  # noqa: E731
        get_model("laguna_xs2", **OVERRIDES), batch_size=2, optimizer="adam", lr=1e-3, init_seed=seed)
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


def test_train_loop_records_the_share_on_the_route_span_and_as_gauges():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    tr = Trainer(get_model("laguna_xs2", **OVERRIDES), batch_size=2, optimizer="adam", lr=1e-3,
                 tracer=tel.tracer)
    summary = tr.run(steps=11, log_every=5)
    assert math.isfinite(summary["final_loss"])
    routes = [s for s in tel.tracer.spans() if s["name"] == "moe.route"]
    assert [s["attrs"]["step"] for s in routes] == [5, 10]
    bound = moe_dispatch.share_rows_bound(2 * 64, 4, 4, 16)
    for s in routes:
        a = s["attrs"]
        assert s["parent"] == "loop.log_sync" and a["experts_held"] == 4 and a["moe_dropped"] == 0.0
        assert a["moe_load_mean"] == 2 * 64 * 4 / 16 and 0 < a["moe_rows_held"] <= 4 * 2 * 64 * 4
        assert a["moe_rows_moved"] % bound == 0 and a["moe_rows_moved"] >= a["moe_rows_held"]
    moe = tel.summary()["moe"]
    last = routes[-1]["attrs"]
    assert moe["rows_moved_over_held"] == pytest.approx(last["moe_rows_moved"] / last["moe_rows_held"])
    # what the grouped products ran of it: the held rows, not the chunk (1.0 before PR 46)
    assert moe["rows_multiplied_over_moved"] == pytest.approx(last["moe_rows_held"] / last["moe_rows_moved"])
    assert 0 < moe["rows_multiplied_over_moved"] <= 1
    assert moe["experts_held"] == 4.0 and moe["dropped_total"] == 0.0
