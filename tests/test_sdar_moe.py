"""SDAR-30B-A3B-Chat on the normal path (models/sdar_moe.py), at a tiny size
on the CPU: the program against the plain reference
(benchmark/references/sdar_moe.py) on the loss and every leaf's gradient, the
same noise from the same key; the noise rule (one rate a block, the mask id
only where masked, positions repeated); the mask's MEANING (the clean half is
a block-causal pass over x_0 alone and does not move when x_t changes; a noised
block does not move when a later block or another noised block changes); the
softmax router; the per-head QK-norm; the share of the experts a chip holds;
the loss head's weights and divisor; the train loop's spans.

One compile for what the tests share (PR 58's rule): the tiny bundle's
gradient program, its hidden states and the reference, each under one jit."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import sdar_moe as ref
from distributedvolunteercomputing_tpu.models import common, get_model, moe, sdar_moe
from distributedvolunteercomputing_tpu.ops import attention
from tests import tiny_models

TINY = tiny_models.rehearsal("sdar")
HP = ref.hyper(TINY)
BUNDLE = tiny_models.bundle("sdar")
CFG = BUNDLE.config
L, BD = CFG.max_len, CFG.block_length
KEY = jax.random.PRNGKey(0)  # the key the harness's reference check hands the program

REFERENCE = jax.jit(ref.make_loss_and_grad(TINY))
PROGRAM = jax.jit(jax.value_and_grad(
    lambda params, batch, key: sdar_moe.loss_and_routes(params, batch, key, CFG)[:2], has_aux=True))
# hidden states [B, 2L, d] after the last layer, of rows the test builds itself
HIDDEN = jax.jit(lambda params, tokens: sdar_moe.trunk(params, tokens, CFG)[0])


def seeded(scale: float = 3.0):
    """The tiny model with weights scaled up so that every term matters (norm
    vectors drawn away from 1), and two seeded sequences."""
    params = jax.jit(BUNDLE.init)(jax.random.PRNGKey(3))
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    params = jax.tree_util.tree_map(
        lambda x: x * scale if x.ndim > 2 or (x.ndim == 2 and x.shape[0] != CFG.n_layers)
        else x * (1.0 + 0.3 * jax.random.normal(next(keys), x.shape)), params)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab - 1, (2, L)))
    return params, {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}


def leaf_errors(got, want):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
            for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                    jax.tree_util.tree_leaves(want))}


# -- the program against the reference ---------------------------------------------


def test_float32_program_equals_the_reference_on_loss_and_every_leaf():
    params, batch = seeded()
    ref.check_config(CFG, TINY)
    (lp, metrics), gp = PROGRAM(params, batch, KEY)
    lr, gr = REFERENCE(params, batch["tokens"], batch["targets"])
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    errors = leaf_errors(gp, gr)
    assert len(errors) == 15  # a stacked layer's twelve leaves, embedding, head, final norm
    assert max(errors.values()) < 1e-4, max(errors.items(), key=lambda kv: kv[1])
    assert all(float(jnp.linalg.norm(g)) > 0 for g in jax.tree_util.tree_leaves(gr))
    assert float(metrics["diffusion_head_rows_share"]) == 0.5
    assert float(metrics["moe_dropped"]) == 0.0
    # the XLA core computes every pair of the 2L x 2L: twice a causal mask's, and a little
    assert float(metrics["attention_bd_tiles_share"]) == pytest.approx(2 * (2 * L) / (2 * L + 1))


def test_another_key_is_another_loss_and_the_targets_go_unread():
    params, batch = seeded()
    (l0, _), _ = PROGRAM(params, batch, KEY)
    (l1, _), _ = PROGRAM(params, batch, jax.random.PRNGKey(1))
    (l2, _), _ = PROGRAM(params, dict(batch, targets=jnp.zeros_like(batch["targets"])), KEY)
    assert float(l0) != float(l1) and float(l0) == float(l2)


@pytest.mark.parametrize("name,change", [
    ("no_renormalisation", lambda p, hp: (p, dict(hp, norm_topk=False))),
    *[(v, (lambda v: lambda p, hp: (p, dict(hp, _variant=v)))(v)) for v in ref.VARIANTS],
    ("no_qk_norm", lambda p, hp: (jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.ones_like(a) if any(getattr(k, "key", "") in ("q_norm", "k_norm") for k in path)
        else a, p), hp)),
    ("another_mask_id", lambda p, hp: (p, dict(hp, mask_id=hp["mask_id"] - 1))),
    ("another_block_length", lambda p, hp: (p, dict(hp, bd=2 * hp["bd"]))),
    ("another_theta", lambda p, hp: (p, dict(hp, theta=1e4))),
])
def test_reference_notices_a_term_changed(name, change):
    """What the chip's tolerances must tell apart moves the reference's own
    loss at this size (norm vectors away from 1, so a norm left out shows)."""
    params, batch = seeded()
    changed, hp = change(params, HP)
    variant = hp.pop("_variant", None) if "_variant" in hp else None
    got = float(jax.jit(lambda p: ref.loss(p, batch["tokens"], batch["targets"], hp, variant=variant))(changed))
    want = float(REFERENCE(params, batch["tokens"], batch["targets"])[0])
    assert abs(got - want) > 1e-3, name


# -- the noise ---------------------------------------------------------------------------


def test_noise_draws_one_rate_a_block_and_masks_by_it():
    masked, rate = jax.jit(lambda k: sdar_moe.noise(k, 256, 1024, 4, 1e-3))(jax.random.PRNGKey(5))
    rate, masked = np.asarray(rate), np.asarray(masked)
    by_block = rate.reshape(256, 256, 4)
    assert np.all(by_block == by_block[..., :1])  # one rate a block
    t = by_block[..., 0]
    assert t.min() >= 1e-3 and t.max() <= 1.0 and 0.45 < t.mean() < 0.55 and len(np.unique(t)) > 60000
    # a token is masked with its block's probability: by decile of t, the masked share follows t
    for lo in np.arange(0.0, 1.0, 0.1):
        pick = (rate >= lo) & (rate < lo + 0.1)
        assert abs(masked[pick].mean() - rate[pick].mean()) < 0.012  # 26,000 tokens a decile
    # the reference's copy of the rule is the same draw (both compiled, as the harness runs them: op by op
    # the rate's multiply-add rounds twice and differs in the last bit)
    m2, r2 = jax.jit(lambda k: ref.noise(k, 256, 1024, 4, 1e-3))(jax.random.PRNGKey(5))
    assert np.array_equal(np.asarray(m2), masked) and np.array_equal(np.asarray(r2), rate)


def test_rows_hold_the_mask_id_only_where_masked_and_the_weights_are_m_over_t():
    _, batch = seeded()
    clean = batch["tokens"]  # drawn below the mask id
    tokens, weights, masked = sdar_moe.rows(clean, KEY, CFG)
    m, rate = sdar_moe.noise(KEY, 2, L, BD, CFG.eps_t)
    assert tokens.shape == (2, 2 * L) and np.array_equal(np.asarray(tokens[:, :L]), np.asarray(clean))
    noised = np.asarray(tokens[:, L:])
    assert np.array_equal(noised == CFG.mask_id, np.asarray(m)) and np.array_equal(np.asarray(masked), np.asarray(m))
    assert np.array_equal(noised[~np.asarray(m)], np.asarray(clean)[~np.asarray(m)])
    np.testing.assert_allclose(np.asarray(weights), np.asarray(m) / np.asarray(rate), rtol=1e-6)
    assert 0 < np.asarray(m).mean() < 1


def test_positions_repeat_a_rows_place_in_its_half_decides_its_turn():
    """Rotary by position, not by row: with positions 0..L-1 twice, a noised row
    and the clean row of the same place are turned alike, so the merged entry
    over rows [a ; a] gives the tables' halves equal."""
    from distributedvolunteercomputing_tpu.ops.pallas_attention import rotary_tables

    positions = jnp.tile(jnp.arange(L), 2)
    cos, sin = rotary_tables(2 * L, 16, CFG.rope_theta, positions=positions)
    assert np.array_equal(np.asarray(cos[:L]), np.asarray(cos[L:])) and np.array_equal(
        np.asarray(sin[:L]), np.asarray(sin[L:]))
    plain = rotary_tables(L, 16, CFG.rope_theta)
    assert np.array_equal(np.asarray(cos[:L]), np.asarray(plain[0]))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 2 * L, 16))
    np.testing.assert_allclose(
        np.asarray(attention.rope(x, positions, CFG.rope_theta, "half")[:, :, L:]),
        np.asarray(attention.rope(x[:, :, L:], None, CFG.rope_theta, "half")), rtol=1e-6)


# -- the mask's meaning --------------------------------------------------------------------


def block_causal_pass(params, clean):
    """x_0 ALONE through the reference's layers under a block-causal mask
    (``blk(j) <= blk(i)``), positions 0..L-1: what the clean half must equal."""
    blk = jnp.arange(L) // BD
    mask = blk[None, :] <= blk[:, None]
    with jax.default_matmul_precision("highest"):
        x = params["wte"][clean]
        (stack,) = params["blocks"]
        for layer in range(CFG.n_layers):
            p = jax.tree_util.tree_map(lambda a: a[layer], stack)
            x, _, _ = ref._block(p, x, mask, jnp.arange(L), None, HP)
    return x


def test_the_clean_half_is_a_block_causal_pass_over_x0_and_does_not_see_xt():
    params, batch = seeded()
    clean = batch["tokens"]
    tokens, _, _ = sdar_moe.rows(clean, KEY, CFG)
    hidden = HIDDEN(params, tokens)
    want = jax.jit(block_causal_pass)(params, clean)
    np.testing.assert_allclose(np.asarray(hidden[:, :L]), np.asarray(want), rtol=2e-4, atol=2e-4)
    # another x_t altogether: the clean half does not move
    other = jnp.concatenate([clean, jnp.flip(tokens[:, L:], axis=1)], axis=1)
    np.testing.assert_allclose(np.asarray(HIDDEN(params, other)[:, :L]), np.asarray(hidden[:, :L]),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(HIDDEN(params, other)[:, L:] - hidden[:, L:]))) > 1e-2


def test_a_noised_block_sees_the_clean_past_and_itself_and_nothing_else():
    params, batch = seeded()
    tokens, _, _ = sdar_moe.rows(batch["tokens"], KEY, CFG)
    hidden = np.asarray(HIDDEN(params, tokens))
    b = 3  # the noised block under watch: rows L + 12 .. L + 15
    mine = slice(L + b * BD, L + (b + 1) * BD)

    def changed(rows):
        t = np.asarray(tokens).copy()
        t[:, rows] = (t[:, rows] + 7) % (CFG.vocab - 1)
        return np.asarray(HIDDEN(params, jnp.asarray(t)))

    # a later clean block, its own clean block, another noised block (earlier and later): unmoved
    for rows in (slice((b + 1) * BD, L), slice(b * BD, (b + 1) * BD), slice(L, L + b * BD),
                 slice(L + (b + 1) * BD, 2 * L)):
        np.testing.assert_allclose(changed(rows)[:, mine], hidden[:, mine], rtol=1e-5, atol=1e-5)
    # an earlier clean block and a token of its own block (both directions): moved
    assert np.abs(changed(slice(0, BD))[:, mine] - hidden[:, mine]).max() > 1e-3
    last = changed(slice(L + (b + 1) * BD - 1, L + (b + 1) * BD))[:, mine]
    assert np.abs(last[:, 0] - hidden[:, mine][:, 0]).max() > 1e-3  # the block's first row sees its last


def test_the_mask_keeps_l_squared_plus_l_bd_pairs():
    for l, bd in ((16, 4), (32, 4), (64, 32), (24, 12)):
        mask = np.asarray(attention.block_diffusion_mask(2 * l, bd))
        assert mask.sum() == l * l + l * bd == ref.kept_pairs(l, bd)
        assert np.array_equal(mask, np.asarray(ref.three_part_mask(l, bd)))
        assert not mask[:l, l:].any()  # nothing clean sees anything noised


# -- the layer's parts --------------------------------------------------------------------------


def test_router_is_a_softmax_over_all_top_k_renormalised():
    h = jax.random.normal(jax.random.PRNGKey(7), (12, 64))
    w = jax.random.normal(jax.random.PRNGKey(8), (64, 16))
    idx, weights, probs = moe.route(w, h, 4, 1.0, score="softmax")
    logits = np.asarray(h @ w, np.float64)
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    np.testing.assert_allclose(np.asarray(probs), p, rtol=1e-4)
    assert np.array_equal(np.sort(np.asarray(idx), 1), np.sort(np.argsort(-p, axis=1)[:, :4], 1))
    chosen = np.take_along_axis(p, np.asarray(idx), 1)
    np.testing.assert_allclose(np.asarray(weights), chosen / chosen.sum(1, keepdims=True), rtol=1e-4)
    # the sigmoid router is what it was: the default
    a, b = moe.route(w, h, 4, 2.5), moe.route(w, h, 4, 2.5, score="sigmoid")
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b))
    with pytest.raises(KeyError):
        moe.route(w, h, 4, 1.0, score="tanh")


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_qk_norm_is_an_rmsnorm_over_each_heads_own_lanes(dtype, tol):
    x = (3.0 * jax.random.normal(jax.random.PRNGKey(1), (2, 8, 4 * 16))).astype(dtype)
    g = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    got = sdar_moe.head_rmsnorm(g, x, 4, 1e-6)
    xf = np.asarray(x.astype(jnp.float32)).reshape(2, 8, 4, 16)
    want = (xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + 1e-6) * np.asarray(g)).reshape(2, 8, 64)
    assert got.dtype == dtype and got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want, rtol=tol, atol=tol)


def test_all_shares_add_up_to_the_uncut_layer():
    """The guide's share test on one layer of the tiny model: the four shares of
    four experts each, with what every chip computes alike (attention, the
    residual) counted once, are the uncut reference's output for the layer."""
    params, batch = seeded()
    tokens, _, _ = sdar_moe.rows(batch["tokens"][:1], KEY, CFG)
    x = params["wte"][tokens]
    p = jax.tree_util.tree_map(lambda a: a[1], params["blocks"][0])
    full_experts = {k: jax.random.normal(jax.random.PRNGKey(i), (16, *v.shape[1:])) * 0.06 * 3
                    for i, (k, v) in enumerate(sorted(p["experts"].items()))}
    hp = ref.hyper(dict(TINY, num_experts=16, expert_offset=0))
    mask, positions = ref.three_part_mask(L, BD), jnp.tile(jnp.arange(L), 2)
    with jax.default_matmul_precision("highest"):
        block = jax.jit(lambda p: ref._block(p, x, mask, positions, None, hp)[0])
        whole = block(dict(p, experts=full_experts))
        alike = block(dict(p, experts=jax.tree_util.tree_map(jnp.zeros_like, full_experts)))
    total = alike
    for offset in range(0, 16, 4):
        cfg = dataclasses.replace(CFG, experts_held=4, expert_offset=offset)
        held = jax.tree_util.tree_map(lambda a: a[offset:offset + 4], full_experts)
        y, stats, _ = jax.jit(lambda p: sdar_moe._layer(  # a program a share: the offset is the trace's
            p, x, moe.zero_share_stats(balanced=cfg.n_experts, chunks_extra=True), cfg))(dict(p, experts=held))
        assert float(stats["dropped"]) == 0.0
        total = total + (y - alike)  # this share's experts' part alone
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=2e-4, atol=2e-4)
    assert float(jnp.max(jnp.abs(y - whole))) > 1e-2  # one share is not the whole


def test_the_head_weighs_tokens_and_divides_by_the_callers_count():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 8))
    head = jax.random.normal(jax.random.PRNGKey(2), (8, 50))
    labels = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, 50)
    w = jax.random.uniform(jax.random.PRNGKey(4), (2, 32)) * 5
    logp = jax.nn.log_softmax(x @ head, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    for chunk in (8, 32):  # scanned in chunks, and as one
        got = common.lm_xent_chunked(x, head, labels, mask=w, chunk=chunk, head_layout="dv", denominator=64.0)
        assert float(got) == pytest.approx(float(jnp.sum(w * nll) / 64.0), rel=1e-5)
    # a 0/1 mask with no divisor of the caller's is the mean over the mask, as it was
    m = (w > 2.5).astype(jnp.float32)
    got = common.lm_xent_chunked(x, head, labels, mask=m, chunk=8, head_layout="dv")
    assert float(got) == pytest.approx(float(jnp.sum(m * nll) / jnp.sum(m)), rel=1e-5)


def test_published_sizes_and_parameter_counts():
    cfg = sdar_moe.SdarMoeConfig()
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (48, 2048, 32, 4, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.d_expert, cfg.vocab, cfg.rope_theta) == (128, 8, 768, 151936, 1e6)

    def count(**overrides):
        shapes = jax.eval_shape(get_model("sdar_30b_a3b", **overrides).init, jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))

    assert count() == 30_532_122_624
    assert count(n_layers=5, experts_held=16, vocab=18992, mask_id=18991) == 550_984_960
    for bad in (dict(block_length=3), dict(mask_id=151936), dict(eps_t=0.0), dict(n_kv_heads=5)):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, **bad)


def test_train_loop_trains_and_records_the_diffusion_counters_on_the_route_span():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    tr = Trainer(BUNDLE, batch_size=2, optimizer="adam", lr=1e-3, tracer=tel.tracer)
    summary = tr.run(steps=11, log_every=5)
    assert math.isfinite(summary["final_loss"])
    routes = [s for s in tel.tracer.spans() if s["name"] == "moe.route"]
    assert [s["attrs"]["step"] for s in routes] == [5, 10]
    shares = set()
    for s in routes:
        a = s["attrs"]
        assert a["experts_held"] == 4 and a["moe_dropped"] == 0.0
        assert a["moe_load_mean"] == 2 * 2 * L * 4 / 16  # over the layers' 2L rows a sequence
        assert a["diffusion_head_rows_share"] == 0.5 and 0 < a["diffusion_masked_share"] < 1
        assert a["attention_bd_tiles_share"] == pytest.approx(2 * (2 * L) / (2 * L + 1))
        shares.add(a["diffusion_masked_share"])
    assert len(shares) == 2  # a fresh draw a step: the step's rng reaches the loss
