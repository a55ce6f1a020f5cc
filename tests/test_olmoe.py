"""OLMoE on the normal path, at a tiny size on the CPU: the program against the
plain float32 reference (``benchmark/references/olmoe.py``), the sorted
dropless dispatch against a dense computation, and the properties the
published model has (nothing dropped, experts interchangeable, gates not
renormalised, half-split rotary, QK-norm over the whole projection)."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import datagen
from benchmark.references import olmoe as ref
from distributedvolunteercomputing_tpu.models import common, get_model, olmoe
from distributedvolunteercomputing_tpu.ops import attention, moe_dispatch
from distributedvolunteercomputing_tpu.utils import traced

OVERRIDES = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_experts": 8, "top_k": 2,
             "d_expert": 32, "max_len": 32, "vocab": 256, "xent_chunk": 16}
FILE = {
    "name": "tiny", "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_experts": 8, "num_experts_per_tok": 2, "intermediate_size": 32, "vocab_size": 256,
    "max_position_embeddings": 32, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "num_hidden_layers": 2, "norm_topk_prob": False, "tie_word_embeddings": False,
    "assumed": {"aux_coefficients": {"load_balancing": 0.01, "router_z": 0.001}},
}
RNG = jax.random.PRNGKey(0)


def seeded(scale: float = 3.0, **overrides):
    """Bundle, parameters (matrices scaled up so that routing, attention and
    the auxiliary terms all matter at this width) and a batch."""
    bundle = get_model("olmoe_1b_7b", **{**OVERRIDES, **overrides})
    params = bundle.init(jax.random.PRNGKey(3))
    params = jax.tree_util.tree_map(lambda x: x * scale if x.ndim > 1 else x, params)
    return bundle, params, datagen.lm_arrays(5, 2, 32, 256)


def program_loss_and_grad(bundle, params, batch):
    return jax.jit(jax.value_and_grad(lambda p: bundle.loss_fn(p, batch, RNG)[0]))(params)


# the reference's own loss-and-gradient as the harness calls it, under one jit
REFERENCE = jax.jit(ref.make_loss_and_grad(FILE))


def leaf_errors(got, want):
    return {
        jax.tree_util.keystr(path): float(
            jnp.linalg.norm(a.astype(jnp.float32) - b) / (jnp.linalg.norm(b) + 1e-30))
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree_util.tree_leaves(want))
    }


@pytest.mark.parametrize("remat", [True, False])
def test_float32_program_equals_the_reference_on_loss_and_every_leaf(remat):
    """(a) Routing agrees exactly in float32, so nothing may be left out."""
    bundle, params, batch = seeded(remat=remat)
    ref.check_config(bundle.config, FILE)
    got_l, got_g = program_loss_and_grad(bundle, params, batch)
    want_l, want_g = REFERENCE(params, batch["tokens"], batch["targets"])
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-4)
    errs = leaf_errors(got_g, want_g)
    assert len(errs) == 15 and max(errs.values()) < 1e-4, errs


def test_bf16_program_equals_the_reference_given_its_routes(monkeypatch):
    """(b) bf16 compute against float32, with the program's own routes handed
    to the reference so that arithmetic is compared and not near-ties. bf16
    keeps 8 significant bits (2^-8 a rounding); through two layers, forward,
    recomputed forward and backward, this size reads a whole-gradient relative
    error of 0.008 (worst leaf 0.012) and a loss apart by 0.0001, where
    gpt2's 24 layers read 0.014 on the chip. The limits are about three times
    that, as gpt2's are; an 8-bit float lands above 0.07 (``fp8_params`` of
    experiments/olmoe_reference_check.py on the tiny rehearsal), and without
    the routes the expert leaves alone are 0.10 apart. At the initialisation's
    scale: with matrices three times larger the softmaxes saturate and bf16
    reads 0.2-0.3 on every leaf, which measures the test and not the model."""
    monkeypatch.setattr(common, "compute_dtype", lambda: jnp.bfloat16)
    bundle, params, batch = seeded(scale=1.0)
    (got_l, routes), got_g = jax.jit(jax.value_and_grad(
        lambda p: olmoe.loss_and_routes(p, batch, bundle.config)[::2], has_aux=True))(params)
    assert routes.shape == (2, 64, 2)
    want_l, want_g = REFERENCE(params, batch["tokens"], batch["targets"], routes)
    assert abs(float(got_l) - float(want_l)) < 0.005
    num = sum(float(jnp.sum((a.astype(jnp.float32) - b) ** 2)) for a, b in zip(
        jax.tree_util.tree_leaves(got_g), jax.tree_util.tree_leaves(want_g)))
    den = sum(float(jnp.sum(b ** 2)) for b in jax.tree_util.tree_leaves(want_g))
    assert (num / den) ** 0.5 < 0.03
    assert max(leaf_errors(got_g, want_g).values()) < 0.04


def test_reference_notices_a_renormalised_gate():
    """The check must fail when the mathematics differs: here the reference
    against itself with the chosen gates renormalised, as Mixtral does."""
    _, params, batch = seeded()
    hp = ref.hyper(FILE)
    base = float(ref.loss(params, batch["tokens"], batch["targets"], hp))

    def renormalised(p, h, weight):
        return ref_experts(p, h, weight / jnp.sum(weight, axis=-1, keepdims=True))

    ref_experts = ref._experts
    try:
        ref._experts = renormalised
        other = float(ref.loss(params, batch["tokens"], batch["targets"], hp))
    finally:
        ref._experts = ref_experts
    assert abs(other - base) > 1e-3


def test_no_token_is_dropped_when_one_expert_receives_every_token():
    """(c) A zero router ties every expert: top-k takes the lowest indices for
    every token, so experts 0 and 1 get all S rows each, four times the even
    share, and a capacity-limited dispatch would drop most of them."""
    bundle, params, batch = seeded()
    params["blocks"]["router"] = jnp.zeros_like(params["blocks"]["router"])
    loss, m = bundle.loss_fn(params, batch, RNG)
    s = batch["tokens"].size
    assert float(m["moe_load_max"]) == s and float(m["moe_load_mean"]) == s * 2 / 8
    assert float(m["moe_dropped"]) == 0.0
    want = ref.loss(params, batch["tokens"], batch["targets"], ref.hyper(FILE))
    assert float(loss) == pytest.approx(float(want), rel=1e-5)


def test_dispatch_equals_a_dense_computation_with_all_rows_on_one_expert():
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    s, d, f, e = 24, 16, 8, 4
    x = jax.random.normal(k[0], (s, d))
    w = [jax.random.normal(k[i + 1], shape) * 0.3
         for i, shape in enumerate([(e, d, f), (e, d, f), (e, f, d)])]
    gates = jax.random.uniform(k[4], (s, 1))
    idx = jnp.full((s, 1), 2, jnp.int32)
    y, sizes, dropped, _ = moe_dispatch.dropless_glu_experts(x, idx, gates, *w)
    want = gates * ((jax.nn.silu(x @ w[0][2]) * (x @ w[1][2])) @ w[2][2])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-6)
    assert sizes.tolist() == [0, 0, s, 0] and int(dropped) == 0


def test_the_dropped_count_reads_what_the_kernel_is_handed():
    """A capacity that clips the groups, or groups out of step with the sort,
    leaves rows that no expert, or the wrong one, multiplies: counted."""
    idx = jnp.asarray([[0, 1], [0, 2], [0, 3], [0, 1], [2, 3], [0, 1]], jnp.int32)
    order, _, sizes, experts = moe_dispatch.sort_by_expert(idx, 4)
    assert sizes.tolist() == [5, 3, 2, 2] and experts.tolist() == idx.reshape(-1)[order].tolist()
    assert int(moe_dispatch.rows_not_computed(experts, sizes)) == 0
    clipped = jnp.minimum(sizes, 3)  # capacity 3: expert 0 loses two rows, and every later group shifts
    assert int(moe_dispatch.rows_not_computed(experts, clipped)) == 8
    assert int(moe_dispatch.rows_not_computed(experts, sizes.at[3].set(0))) == 2
    assert int(moe_dispatch.rows_not_computed(experts[::-1], sizes)) > 0


def test_permuting_the_experts_with_their_router_columns_leaves_the_loss_unchanged():
    """(d)"""
    bundle, params, batch = seeded()
    perm = jnp.asarray([3, 0, 7, 1, 6, 2, 5, 4])
    b = params["blocks"]
    moved = dict(params, blocks=dict(
        b, router=b["router"][:, :, perm],
        experts={name: w[:, perm] for name, w in b["experts"].items()}))
    l0 = float(bundle.loss_fn(params, batch, RNG)[0])
    l1 = float(bundle.loss_fn(moved, batch, RNG)[0])
    assert l1 == pytest.approx(l0, rel=1e-6)


def test_gates_are_not_renormalised():
    """(e) The chosen gates are the router's probabilities as they are: a
    token's gates sum to less than 1 (``norm_topk_prob`` false)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    h = jax.random.normal(k1, (40, 64))
    w = jax.random.normal(k2, (64, 8))
    idx, gates, probs, logits = olmoe.route(w, h, 2)
    assert gates.shape == (40, 2) and float(jnp.max(jnp.sum(gates, axis=-1))) < 1.0
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(jnp.take_along_axis(probs, idx, axis=-1)))
    np.testing.assert_allclose(np.asarray(jnp.sum(probs, axis=-1)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(h) @ np.asarray(w), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("layout", ["half", "interleaved"])
def test_rotary_against_a_complex_number_formula(layout):
    """(f) Half-split: coordinate i and i + D/2 are the real and imaginary
    part of one complex number that position t turns by t * theta^(-2i/D);
    interleaved: coordinates 2i and 2i + 1 are."""
    b, h, t, d = 2, 3, 7, 16
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (b, h, t, d)), np.float64)
    theta = 10000.0
    turn = np.exp(1j * np.arange(t)[:, None] * theta ** (-np.arange(0, d, 2) / d)[None, :])
    if layout == "half":
        z = (x[..., : d // 2] + 1j * x[..., d // 2:]) * turn
        want = np.concatenate([z.real, z.imag], axis=-1)
    else:
        z = (x[..., 0::2] + 1j * x[..., 1::2]) * turn
        want = np.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    got = attention.rope(jnp.asarray(x, jnp.float32), base=theta, layout=layout)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    if layout == "half":
        np.testing.assert_allclose(
            np.asarray(ref._rope(jnp.asarray(x, jnp.float32), theta)), want, rtol=1e-5, atol=1e-5)


def test_rope_refuses_an_unknown_layout():
    with pytest.raises(ValueError, match="layout"):
        attention.rope(jnp.zeros((1, 1, 2, 4)), layout="neox")


def test_qk_norm_is_over_the_whole_projection(monkeypatch):
    """(g) With head 0's query columns ten times larger, an RMSNorm over the
    whole 64-wide projection shrinks every other head's queries; a norm per
    head would not see it. The program agrees with the reference (whole
    projection) and not with a per-head variant of it."""
    bundle, params, batch = seeded()
    wq = params["blocks"]["wq"]
    params["blocks"]["wq"] = wq.at[:, :, :16].multiply(10.0)
    hp = ref.hyper(FILE)
    got = float(bundle.loss_fn(params, batch, RNG)[0])
    whole = float(ref.loss(params, batch["tokens"], batch["targets"], hp))
    assert got == pytest.approx(whole, rel=1e-5)
    plain = ref._rmsnorm

    def per_head(g, x, eps):
        if x.shape[-1] != 64 or g.shape != (64,) or not per_head.on:
            return plain(g, x, eps)
        xh = x.reshape(x.shape[:-1] + (4, 16))
        return (xh / jnp.sqrt(jnp.mean(xh * xh, axis=-1, keepdims=True) + eps)).reshape(x.shape) * g

    # only the q and k norms: they are the 2nd and 3rd norm calls of a layer
    calls = {"n": 0}

    def counting(g, x, eps):
        calls["n"] += 1
        per_head.on = calls["n"] % 4 in (2, 3)
        return per_head(g, x, eps)

    monkeypatch.setattr(ref, "_rmsnorm", counting)
    # python loop over layers so that the call counter sees every norm in order
    x = params["wte"][batch["tokens"]]
    for i in range(2):
        p = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
        x, _, _ = ref._block(p, x, None, hp)
    monkeypatch.setattr(ref, "_rmsnorm", plain)
    x2 = params["wte"][batch["tokens"]]
    for i in range(2):
        p = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
        x2, _, _ = ref._block(p, x2, None, hp)
    assert float(jnp.max(jnp.abs(x - x2))) > 1e-2


def test_gathers_differentiate_as_gathers_and_agree_with_autodiff():
    """The two custom_vjp row movers against plain indexing under autodiff."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    s, kk, d = 12, 3, 5
    x = jax.random.normal(k1, (s, d))
    idx = jax.random.randint(k2, (s, kk), 0, 4)
    order, inv, sizes, experts = moe_dispatch.sort_by_expert(idx, 4)
    assert int(sizes.sum()) == s * kk
    assert np.array_equal(np.asarray(idx.reshape(-1)[order]), np.asarray(experts))
    assert np.all(np.diff(np.asarray(experts)) >= 0)
    assert np.array_equal(np.asarray(order), np.argsort(np.asarray(idx.reshape(-1)), kind="stable"))
    assert np.array_equal(np.asarray(order[inv]), np.arange(s * kk))
    cot = jax.random.normal(k1, (s * kk, d))
    f_custom = lambda x: jnp.sum(moe_dispatch._rows_of_tokens(x, order, inv, kk) * cot)  # noqa: E731
    f_plain = lambda x: jnp.sum(x[order // kk] * cot)  # noqa: E731
    np.testing.assert_allclose(np.asarray(jax.grad(f_custom)(x)), np.asarray(jax.grad(f_plain)(x)),
                               rtol=1e-5, atol=1e-6)
    a = jax.random.normal(k2, (s * kk, d))
    g_custom = jax.grad(lambda a: jnp.sum(moe_dispatch._permute_rows(a, inv, order) * cot))(a)
    g_plain = jax.grad(lambda a: jnp.sum(a[inv] * cot))(a)
    np.testing.assert_allclose(np.asarray(g_custom), np.asarray(g_plain), rtol=1e-5, atol=1e-6)
    text = jax.jit(jax.grad(f_custom)).lower(x).as_text()
    assert "scatter" not in text


def test_megablox_interpreted_equals_ragged_dot(monkeypatch):
    """Both grouped matmuls give the layer's result and gradients (the kernel
    interpreted here; compiled for the chip in tests/test_tpu_compile.py)."""
    k = jax.random.split(jax.random.PRNGKey(6), 6)
    s, d, f, e, kk = 256, 128, 128, 4, 2
    x = jax.random.normal(k[0], (s, d))
    idx = jax.random.randint(k[1], (s, kk), 0, e)
    gates = jax.random.uniform(k[2], (s, kk))
    w = [jax.random.normal(k[3 + i], shape) * 0.1
         for i, shape in enumerate([(e, d, f), (e, d, f), (e, f, d)])]

    def grads():
        return jax.grad(
            lambda x, *w: jnp.sum(moe_dispatch.dropless_glu_experts(x, idx, gates, *w)[0] ** 2),
            argnums=(0, 1, 3))(x, *w)

    assert moe_dispatch.grouped_matmul_impl(s * kk, d, f) == "ragged_dot"  # the CPU
    want = grads()
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    assert moe_dispatch._megablox_tiling(s * kk, d, f) == (512, 128, 128)
    assert moe_dispatch._megablox_tiling(s * kk, 96, f) is None  # no tile divides 96
    for a, b in zip(grads(), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_the_choice_of_grouped_matmul_follows_the_platform(monkeypatch):
    assert moe_dispatch.grouped_matmul_impl(131072, 2048, 1024) == "ragged_dot"  # the CPU
    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert moe_dispatch.grouped_matmul_impl(131072, 2048, 1024) == "ragged_dot"  # GSPMD partitions it
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert moe_dispatch.grouped_matmul_impl(131072, 2048, 1024) == "megablox"
    # no tile divides 96: since PR 48 padded to whole tiles (ops/moe_dispatch._padded), not handed to ragged_dot
    assert moe_dispatch.grouped_matmul_impl(131072, 2048, 96) == "megablox"
    assert moe_dispatch.grouped_matmul_impl(256, 2048, 1024) == "ragged_dot"  # fewer rows than one row tile


def test_the_step_holds_no_token_by_expert_by_capacity_tensor():
    """The dense dispatch of gpt2_moe builds [S, E, C] one-hot tensors; the
    sorted dispatch's largest arrays are the S k routed rows."""
    bundle, params, batch = seeded()
    text = jax.jit(lambda p: program_loss_and_grad(bundle, p, batch)).lower(params).as_text()
    s, e = batch["tokens"].size, 8
    assert not re.search(rf"tensor<{s}x{e}x\d+x", text)
    assert f"tensor<{s * 2}x64x" in text  # the routed rows [S k, d]


def test_published_sizes_and_parameter_count():
    cfg = olmoe.OlmoeConfig()
    assert (cfg.d_model, cfg.n_heads, cfg.n_layers, cfg.n_experts, cfg.top_k, cfg.d_expert,
            cfg.vocab, cfg.max_len) == (2048, 16, 16, 64, 8, 1024, 50304, 4096)
    one = dataclasses.replace(cfg, n_layers=1)
    shapes = jax.eval_shape(lambda: olmoe.init(jax.random.PRNGKey(0), one))
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes)) == 625_616_896
    full = jax.eval_shape(lambda: olmoe.init(jax.random.PRNGKey(0), cfg))
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(full)) == 6_919_161_856
    with pytest.raises(ValueError, match="top_k"):
        olmoe.OlmoeConfig(top_k=65)


def test_expert_leaves_shard_over_ep_and_tp(eight_devices):
    from jax.sharding import PartitionSpec as P

    from distributedvolunteercomputing_tpu.parallel import sharding
    from distributedvolunteercomputing_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(ep=4, tp=2, devices=eight_devices)
    specs = {name: sharding.partition_spec_for_path(f"blocks/experts/{name}", shape, mesh)
             for name, shape in [("w_gate", (2, 8, 64, 32)), ("w_up", (2, 8, 64, 32)),
                                 ("w_down", (2, 8, 32, 64))]}
    assert specs["w_gate"] == specs["w_up"] == P(None, "ep", None, "tp")
    assert specs["w_down"] == P(None, "ep", "tp", None)
    # the dense Llama leaves of the same names keep their rule
    assert sharding.partition_spec_for_path("blocks/w_gate", (2, 64, 32), mesh) == P(None, None, "tp")
    assert sharding.partition_spec_for_path("blocks/router", (2, 64, 8), mesh) == P()


def test_ep_sharded_step_matches_single_device(eight_devices):
    from distributedvolunteercomputing_tpu.parallel.mesh import make_mesh
    from distributedvolunteercomputing_tpu.parallel.train_step import (
        make_sharded_train_step, put_batch, shard_train_state,
    )
    from distributedvolunteercomputing_tpu.training.optim import make_optimizer
    from distributedvolunteercomputing_tpu.training.steps import TrainState, make_train_step

    bundle, _, _ = seeded()
    tx = make_optimizer("adam", lr=1e-3)
    batch = bundle.make_batch(jax.random.PRNGKey(7), 4)

    def fresh():
        return TrainState.create(bundle.init(jax.random.PRNGKey(8)), tx, jax.random.PRNGKey(9))

    _, m1 = make_train_step(bundle.loss_fn, tx, donate=False)(fresh(), batch)
    mesh = make_mesh(ep=4, devices=eight_devices[:4])
    state, _ = shard_train_state(fresh(), mesh, tx)
    assert "ep" in state.params["blocks"]["experts"]["w_gate"].sharding.spec
    step = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False)
    with mesh:
        _, m4 = step(state, put_batch(batch, mesh))
    assert float(m4["loss"]) == pytest.approx(float(m1["loss"]), rel=1e-5)
    assert float(m4["moe_dropped"]) == 0.0


# -- spans, counters and gauges ----------------------------------------------------


def test_train_loop_records_routing_as_a_span_under_the_log_sync_and_as_gauges():
    """At each log point the loop has just read the loss; the step's routing
    statistics ride on a ``moe.route`` span whose parent is ``loop.log_sync``,
    and the telemetry turns the span into the two gauges of the summary."""
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    recorded = []
    with traced.subscribe(tel.count_traced):
        tr = Trainer(get_model("olmoe_1b_7b", **OVERRIDES), batch_size=2, optimizer="adam",
                     lr=1e-3, tracer=tel.tracer)
        inner = tr.metrics.record
        tr.metrics.record = lambda step, m, n_samples=0: (
            recorded.append((step, dict(m))), inner(step, m, n_samples=n_samples))
        tr.run(steps=11, log_every=5)
    spans = tel.tracer.spans()
    routes = [s for s in spans if s["name"] == "moe.route"]
    assert [s["attrs"]["step"] for s in routes] == [5, 10]
    for s in routes:
        assert s["parent"] == "loop.log_sync" and s["trace"] == "loop"
        a = s["attrs"]
        assert a["moe_dropped"] == 0.0 and a["moe_load_mean"] == 2 * 32 * 2 / 8
        assert a["moe_load_max"] >= a["moe_load_mean"]
        assert a["aux_loss"] > 0 and a["lm_loss"] > 0
    # the same numbers reach Trainer.metrics.record with the loss
    assert [step for step, _ in recorded] == [5, 10]
    assert {"loss", "lm_loss", "aux_loss", "z_loss", "moe_load_max", "moe_load_mean",
            "moe_dropped"} <= set(recorded[0][1])
    moe = tel.summary()["moe"]
    assert moe["dropped_total"] == 0.0
    assert moe["load_max_over_mean"] == pytest.approx(
        routes[-1]["attrs"]["moe_load_max"] / routes[-1]["attrs"]["moe_load_mean"])
    assert moe["dispatch"] == {"ragged_dot": sum(moe["dispatch"].values())}
    rec = tel.registry.counter("swarm.moe_dispatch")._scrape()["values"][0]
    assert rec["labels"] == {"impl": "ragged_dot", "E": "8", "k": "2", "rows": "128", "held": "8",
                             "act": "swiglu"}


def test_a_dense_model_reports_no_routing():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    Trainer(get_model("mnist_mlp"), batch_size=8, optimizer="sgd", lr=1e-2,
            tracer=tel.tracer).run(steps=6, log_every=5)
    assert tel.summary()["moe"] == {}
    assert not [s for s in tel.tracer.spans() if s["name"] == "moe.route"]
    assert [s["name"] for s in tel.tracer.spans()].count("loop.log_sync") == 1


def test_dropped_rows_accumulate_in_the_gauge():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry

    tel = Telemetry(peer_id="v", enabled=True)
    for dropped in (3.0, 4.0):
        with tel.tracer.phase("moe.route", "loop", moe_load_max=30.0, moe_load_mean=20.0,
                              moe_dropped=dropped):
            pass
    assert tel.moe() == {"load_max_over_mean": 1.5, "dropped_total": 7.0}
    off = Telemetry(peer_id="v", enabled=False)
    off.count_traced("moe_dispatch", dict(impl="megablox", E=64, k=8, rows=131072, held=64, act="swiglu"))
    assert off.moe() == {}
