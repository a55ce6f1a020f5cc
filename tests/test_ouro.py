"""Ouro-2.6B on the normal path (models/ouro.py), at a tiny size on the CPU:
the program against the plain reference (benchmark/references/ouro.py) on the
loss and every leaf's gradient at seeded NON-initial weights; the loop's
MEANING (it equals an unshared stack of L x R layers whose weights are the
shared ones tiled, and a shared weight's gradient is the sum of its R copies');
the exit distribution; the gate's gradient through the loss head's
``dweights``; every term the reference can compute wrongly; the loss head
differentiated in its weights, and lowering to the text it lowered to where
they are not; the train loop's span and ``remat_kept`` over the passes.

One compile for what the tests share (PR 58's rule): the tiny bundle's
gradient program and the reference, each under one jit."""

import dataclasses
import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import ouro as ref
from distributedvolunteercomputing_tpu.models import common, get_model, ouro
from tests import tiny_models

TINY = tiny_models.rehearsal("ouro")
HP = ref.hyper(TINY)
BUNDLE = tiny_models.bundle("ouro")
CFG = BUNDLE.config
R, L, T = CFG.passes, CFG.n_layers, CFG.max_len
KEY = jax.random.PRNGKey(0)

PROGRAM = jax.jit(jax.value_and_grad(lambda params, batch: ouro.loss_fn(params, batch, KEY, CFG), has_aux=True))


@functools.lru_cache(maxsize=None)
def reference(variant=None):
    return jax.jit(jax.value_and_grad(lambda p, t, y: ref.loss(p, t, y, HP, variant)))


@functools.lru_cache(maxsize=None)
def seeded(scale: float = 3.0):
    """The tiny model away from its initial state: matrices scaled up so that
    every term matters, norm vectors drawn about 1, the exit gate's vector times
    20 with a bias (at the initial parameters every gate reads 0.5 whatever it
    is handed); and two seeded sequences."""
    params = BUNDLE.init(jax.random.PRNGKey(3))
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))

    def leaf(path, x):
        names = [getattr(k, "key", None) for k in path]
        if "exit_gate" in names:
            return x * 20.0 if names[-1] == "w" else jnp.asarray(0.3, jnp.float32)
        if names[-1] == "g":
            return x * (1.0 + 0.3 * jax.random.normal(next(keys), x.shape))
        return x * scale

    params = jax.tree_util.tree_map_with_path(leaf, params)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, CFG.vocab, (2, T)))
    return params, {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}


def leaf_errors(got, want):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
            for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want))}


# -- the program against the reference ---------------------------------------------


def test_float32_program_equals_the_reference_on_loss_and_every_leaf():
    params, batch = seeded()
    ref.check_config(CFG, TINY)
    (lp, metrics), gp = PROGRAM(params, batch)
    lr, gr = reference()(params, batch["tokens"], batch["targets"])
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    errors = leaf_errors(gp, gr)
    assert len(errors) == 16  # a stacked layer's eleven leaves, embedding, head, final norm, the gate's two
    assert max(errors.values()) < 2e-4, max(errors.items(), key=lambda kv: kv[1])
    assert all(float(jnp.linalg.norm(g)) > 0 for g in jax.tree_util.tree_leaves(gr))
    # the gate is away from a half: the distribution is not the initial (1/2, 1/4, 1/8, 1/8)
    exit_p = [float(metrics[ouro.exit_p_key(i, R)]) for i in range(R)]
    assert sum(exit_p) == pytest.approx(1.0, abs=1e-5) and max(abs(p - q) for p, q in zip(exit_p, (.5, .25, .125, .125))) > 0.05
    assert 0.0 < float(metrics["exit_entropy"]) < math.log(R)
    assert float(metrics["expected_passes"]) == pytest.approx(sum((i + 1) * p for i, p in enumerate(exit_p)), rel=1e-5)
    assert float(metrics["loss"]) == pytest.approx(
        float(metrics["lm_loss"]) - CFG.entropy_coef * float(metrics["exit_entropy"]), rel=1e-6)


def test_initial_gates_read_a_half_and_the_last_pass_takes_the_rest():
    params = BUNDLE.init(jax.random.PRNGKey(5))
    _, batch = seeded()
    (_, metrics), _ = PROGRAM(params, batch)
    exit_p = [float(metrics[ouro.exit_p_key(i, R)]) for i in range(R)]
    np.testing.assert_allclose(exit_p, [0.5, 0.25, 0.125, 0.125], atol=0.02)
    assert float(metrics["exit_entropy"]) == pytest.approx(1.2130, abs=0.03)  # the tiny gate reads 0.5 +- 0.04 a token
    assert float(metrics["expected_passes"]) == pytest.approx(1.875, abs=0.03)


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_reference_notices_a_term_left_out(variant):
    """Each mistaken term changes the reference's loss or a gradient leaf at the
    seeded non-initial weights, but one: rotary attention reads DIFFERENCES of
    positions, so positions that run on over the passes (every pass shifted by
    a multiple of T) compute what positions 0..T-1 compute. The variant is kept
    to say so: no comparison of losses and gradients can hold that line of the
    description, and none is claimed to."""
    params, batch = seeded()
    lr, gr = reference()(params, batch["tokens"], batch["targets"])
    lv, gv = reference(variant)(params, batch["tokens"], batch["targets"])
    moved = max(leaf_errors(gv, gr).values())
    if variant == "positions_run_on_over_passes":
        assert abs(float(lv) - float(lr)) < 1e-5 and moved < 1e-3
    else:
        assert abs(float(lv) - float(lr)) > 1e-3 or moved > 0.02, (variant, float(lv) - float(lr), moved)


def test_exit_distribution_is_the_products_and_sums_to_one():
    g = jax.random.normal(jax.random.PRNGKey(1), (R - 1, 3, 5)) * 4.0
    p, h = ouro.exit_distribution(g)
    lam = np.asarray(jax.nn.sigmoid(g), np.float64)
    want = np.empty((R, 3, 5))
    stayed = np.ones((3, 5))
    for r in range(R - 1):
        want[r] = lam[r] * stayed
        stayed = stayed * (1 - lam[r])
    want[R - 1] = stayed  # the remainder, whatever the last gate would say
    np.testing.assert_allclose(np.asarray(p), want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(p).sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(h), -(want * np.log(want)).sum(0), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.exit_distribution(jnp.concatenate([jax.nn.sigmoid(g), g[:1] * 0 + 0.3]))),
                               want, rtol=1e-5, atol=1e-7)
    # a saturated gate: finite, and all of the mass where the gate puts it
    p, h = ouro.exit_distribution(jnp.full((R - 1, 1), 80.0))
    assert np.isfinite(np.asarray(h)).all() and float(p[0, 0]) == pytest.approx(1.0)


# -- what the loop means -------------------------------------------------------------


def _unshared_loss(stack, rest, batch):
    """The same model as a plain stack of ``L x R`` layers with weights of their
    own (``stack``: every leaf ``[R * L, ...]``), the final norm after every L."""
    h = rest["wte"][batch["tokens"]]
    zs = []
    for i in range(R * L):
        h = ouro._layer(jax.tree_util.tree_map(lambda a: a[i], stack), h, CFG)
        if (i + 1) % L == 0:
            h = common.rmsnorm(rest["ln_f"], h, CFG.rms_eps)
            zs.append(h)
    zs = jnp.stack(zs)
    gate = rest["exit_gate"]
    p, entropy = ouro.exit_distribution(jnp.sum(zs[:-1] * gate["w"], axis=-1) + gate["b"])
    logits = zs @ rest["lm_head"]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), batch["targets"][None, ..., None], axis=-1)[..., 0]
    return jnp.mean(jnp.sum(p * nll, axis=0)) - CFG.entropy_coef * jnp.mean(entropy)


def test_the_loop_is_an_unshared_stack_with_tiled_weights_and_sums_its_copies_gradients():
    params, batch = seeded()
    (loss, _), grads = PROGRAM(params, batch)
    rest = {k: v for k, v in params.items() if k != "blocks"}
    tiled = jax.tree_util.tree_map(lambda a: jnp.tile(a, (R,) + (1,) * (a.ndim - 1)), params["blocks"])
    plain, (g_stack, g_rest) = jax.jit(jax.value_and_grad(_unshared_loss, argnums=(0, 1)))(tiled, rest, batch)
    assert float(loss) == pytest.approx(float(plain), rel=1e-5)
    summed = jax.tree_util.tree_map(lambda g: g.reshape(R, L, *g.shape[1:]).sum(0), g_stack)
    assert max(leaf_errors(grads["blocks"], summed).values()) < 2e-4
    assert max(leaf_errors({k: grads[k] for k in rest}, g_rest).values()) < 2e-4
    # every copy carries some of it: no pass's gradient is dropped or counted twice
    per_copy = np.asarray(jnp.linalg.norm(g_stack["wq"].reshape(R, -1), axis=1))
    assert (per_copy > 1e-3 * per_copy.max()).all()


def test_the_gates_gradient_comes_through_the_heads_dweights():
    """The gate learns through ``p`` alone: its gradient from the program (``p``
    handed to ``lm_xent_chunked`` as weights) equals autodiff's through a plain
    ``softmax_xent`` over whole logits; with the weights held constant it is
    the entropy term's alone, which is not it."""
    params, batch = seeded()
    b = batch["tokens"].shape[0]
    zs = jax.jit(lambda p: ouro.trunk(p, batch["tokens"], CFG))(params)
    labels = jnp.tile(batch["targets"], (R, 1))

    def with_head(head_loss, hold=False):
        def f(gate):
            p, entropy = ouro.exit_distribution(jnp.sum(zs[:-1] * gate["w"], axis=-1) + gate["b"])
            p = jax.lax.stop_gradient(p) if hold else p
            return head_loss(p.reshape(R * b, T)) - CFG.entropy_coef * jnp.mean(entropy)
        return jax.grad(f)(params["exit_gate"])

    x = zs.reshape(R * b, T, -1)
    chunked = with_head(lambda w: common.lm_xent_chunked(
        x, params["lm_head"], labels, mask=w, chunk=CFG.xent_chunk, head_layout="dv", denominator=float(b * T)))
    plain = with_head(lambda w: common.softmax_xent(x @ params["lm_head"], labels, mask=w, denominator=float(b * T)))
    held = with_head(lambda w: common.lm_xent_chunked(
        x, params["lm_head"], labels, mask=w, chunk=CFG.xent_chunk, head_layout="dv", denominator=float(b * T)), hold=True)
    (_, _), grads = PROGRAM(params, batch)
    for got in (chunked, grads["exit_gate"]):
        assert max(leaf_errors(got, plain).values()) < 2e-4
    assert max(leaf_errors(held, plain).values()) > 0.1


# -- the loss head, differentiated in its weights ------------------------------------------


def _head_case(layout="dv", b=3, t=16, d=8, v=11):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(7), 4)
    head = jax.random.normal(k2, (v, d))
    return (jax.random.normal(k1, (b, t, d)), head if layout == "vd" else head.T,
            jax.random.randint(k3, (b, t), 0, v), jax.random.uniform(k4, (b, t)) * 3)


@pytest.mark.parametrize("layout", ["vd", "dv"])
@pytest.mark.parametrize("divisor", ["denominator", "the_weights_sum"])
def test_chunked_xent_with_perturbed_weights_agrees_with_autodiff(layout, divisor):
    """x, head AND weights differentiated at once; over a divisor of the caller's,
    and over the weights' own sum (the divisor's gradient, a mask that is learnt through)."""
    x, head, labels, weights = _head_case(layout)
    denominator = 48.0 if divisor == "denominator" else None

    def chunked(x, head, w):
        return common.lm_xent_chunked(x, head, labels, mask=w, chunk=4, head_layout=layout, denominator=denominator)

    def plain(x, head, w):
        logits = x @ (head.T if layout == "vd" else head)
        return common.softmax_xent(logits, labels, mask=w, denominator=denominator)

    got = jax.jit(jax.value_and_grad(chunked, argnums=(0, 1, 2)))(x, head, weights)
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1, 2)))(x, head, weights)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    # the weights alone: no dx, no dhead, no product beyond the logits'
    only_w = jax.make_jaxpr(jax.grad(lambda w: chunked(x, head, w)))(weights)
    assert str(only_w).count("dot_general") == 1
    np.testing.assert_allclose(jax.grad(lambda w: chunked(x, head, w))(weights), want[1][2], rtol=2e-5, atol=1e-6)


# sha256 of the lowered text of ``jax.value_and_grad(..., argnums=(0, 1))`` of the call below, read at the parent
# of PR 64 (the weights not differentiated): the rule that makes ``dweights`` adds nothing where nobody asks
_LOWERED_AS_BEFORE = {
    ("dv", "weights_over_denominator"): "b95cb6d668516e915bac14692baa34dbe8c7d500c8aacd653929529ab2ebb1e6",
    ("vd", "mask01"): "aa21fb1536cee41ff2658e9357af9440752af583278fe94055260d300c91db35",
    ("vd", "plain"): "b3d9b523db5d72e53d520080d3abfa2d015aeb20c19681d08d9b2aa95bb02c45",
}


def _lowered_head(layout: str, kind: str) -> str:
    x, head, labels, weights = _head_case(layout)
    mask, denominator = {"weights_over_denominator": (weights, 48.0), "mask01": ((weights > 1.5).astype(jnp.float32), None),
                         "plain": (None, None)}[kind]

    def loss(x, head):
        return common.lm_xent_chunked(x, head, labels, mask=mask, chunk=4, head_layout=layout, denominator=denominator)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, head).as_text()


@pytest.mark.parametrize("layout,kind", list(_LOWERED_AS_BEFORE))
def test_chunked_xent_with_unperturbed_weights_lowers_to_the_text_it_lowered_to(layout, kind):
    text = _lowered_head(layout, kind)
    assert hashlib.sha256(text.encode()).hexdigest() == _LOWERED_AS_BEFORE[(layout, kind)]


# -- the loop's tracing ------------------------------------------------------------------


def test_bundle_declares_the_exit_span_and_trains_through_the_loop():
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    span = BUNDLE.spans["recur.exit"]
    assert span.keys == ("exit_entropy", "expected_passes", "exit_p_first", "exit_p_2", "exit_p_3", "exit_p_last", "lm_loss")
    assert dict(span.attrs) == {"passes": R, "layers": L}
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry

    tel = Telemetry(peer_id="v", enabled=True)
    summary = Trainer(BUNDLE, batch_size=2, optimizer="adam", lr=1e-3, tracer=tel.tracer).run(steps=11, log_every=5)
    assert math.isfinite(summary["final_loss"])
    exits = [s["attrs"] for s in tel.tracer.spans() if s["name"] == "recur.exit"]
    assert [a["step"] for a in exits] == [5, 10]
    for a in exits:
        assert (a["passes"], a["layers"]) == (R, L)
        assert 0.0 < a["exit_entropy"] <= math.log(R) and 1.0 <= a["expected_passes"] <= R
        assert sum(a[ouro.exit_p_key(i, R)] for i in range(R)) == pytest.approx(1.0, abs=1e-5)
    # no rule of the step's own, the whole tree averaged: a shared weight is one leaf
    assert BUNDLE.stepped is None and BUNDLE.avg_select(1) == 1


def test_published_sizes_and_the_overrides_that_cut_them():
    cfg = ouro.OuroConfig()
    assert (cfg.n_layers, cfg.passes, cfg.max_len, cfg.d_model, cfg.d_ff, cfg.vocab) == (48, 4, 65536, 2048, 5632, 49152)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rope_theta, cfg.rms_eps) == (16, 16, 128, 1e6, 1e-6)
    cut = get_model("ouro_2_6b", n_layers=6, max_len=4096).config
    assert dataclasses.replace(cut, n_layers=48, max_len=65536) == cfg
    with pytest.raises(ValueError, match="passes"):
        ouro.OuroConfig(passes=1)
