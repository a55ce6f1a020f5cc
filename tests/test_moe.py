"""Mixture-of-Experts: routing math, gradient flow, expert parallelism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.models.gpt2_moe import GPT2MoEConfig, moe_ffn, moe_init
from distributedvolunteercomputing_tpu.parallel import make_mesh
from distributedvolunteercomputing_tpu.parallel.sharding import make_param_shardings
from distributedvolunteercomputing_tpu.parallel.train_step import (
    make_sharded_train_step,
    put_batch,
    shard_train_state,
)
from distributedvolunteercomputing_tpu.training.optim import make_optimizer
from distributedvolunteercomputing_tpu.training.steps import TrainState, make_train_step

TINY = dict(vocab=128, max_len=16, d_model=32, n_heads=2, n_layers=2, d_ff=64,
            n_experts=4, remat=False)


def test_single_expert_equals_dense_ffn():
    """E=1 with ample capacity must reduce exactly to the dense FFN (the
    router has one choice, softmax gate == 1, nothing overflows)."""
    cfg = GPT2MoEConfig(**{**TINY, "n_experts": 1, "capacity_factor": 2.0})
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    y, aux = moe_ffn(p, x, cfg)
    dense = jax.nn.gelu(x @ p["moe_in"][0]) @ p["moe_out"][0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-6)  # E * 1 * 1


def test_capacity_overflow_drops_not_crashes():
    cfg = GPT2MoEConfig(**{**TINY, "capacity_factor": 0.1})  # brutal cap
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, aux = moe_ffn(p, x, cfg)
    assert np.isfinite(np.asarray(y)).all()
    # with most tokens dropped the MoE output is mostly zeros
    zero_rows = np.mean(np.abs(np.asarray(y)).sum(-1) < 1e-6)
    assert zero_rows > 0.5


class TestTop2Routing:
    def test_top2_equals_convex_mixture_with_ample_capacity(self):
        """GShard top-2 with capacity for everyone: each token's output is
        the renormalized-gate convex mixture of its two experts' FFNs."""
        cfg = GPT2MoEConfig(
            **{**TINY, "n_experts": 4, "capacity_factor": 8.0, "router_top_k": 2}
        )
        p = moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
        y, aux = moe_ffn(p, x, cfg)

        xs = np.asarray(x.reshape(-1, cfg.d_model))
        logits = xs.astype(np.float32) @ np.asarray(p["router"])
        gates = jax.nn.softmax(jnp.asarray(logits), axis=-1)
        tg, ti = jax.lax.top_k(gates, 2)
        tg = np.asarray(tg / jnp.sum(tg, -1, keepdims=True))
        ti = np.asarray(ti)
        ref = np.zeros_like(xs)
        for s in range(xs.shape[0]):
            for j in range(2):
                e_idx = ti[s, j]
                h = np.asarray(
                    jax.nn.gelu(jnp.asarray(xs[s] @ np.asarray(p["moe_in"][e_idx])))
                )
                ref[s] += tg[s, j] * (h @ np.asarray(p["moe_out"][e_idx]))
        np.testing.assert_allclose(
            np.asarray(y).reshape(-1, cfg.d_model), ref, rtol=2e-4, atol=2e-5
        )
        assert np.isfinite(float(aux))

    def test_top2_capacity_second_choice_yields(self):
        """Second choices queue AFTER all first choices: under a brutal cap
        the output matches an independent numpy reference that fills every
        expert's slots with first choices before any second choice."""
        import math

        cfg = GPT2MoEConfig(
            **{**TINY, "capacity_factor": 0.15, "router_top_k": 2}
        )
        p = moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
        y, aux = moe_ffn(p, x, cfg)
        assert np.isfinite(np.asarray(y)).all()

        # Independent reference: sequential slot assignment, choice-major
        # (ALL first choices queue before ANY second choice).
        s, e = 32, cfg.n_experts
        cap = max(math.ceil(cfg.capacity_factor * cfg.router_top_k * s / e), 1)
        xs = np.asarray(x.reshape(s, -1))
        gates = np.asarray(
            jax.nn.softmax(
                jnp.asarray(xs.astype(np.float32) @ np.asarray(p["router"])), axis=-1
            )
        )
        ti = np.argsort(-gates, axis=-1)[:, :2]
        tg = np.take_along_axis(gates, ti, axis=-1)
        tg = tg / tg.sum(-1, keepdims=True)
        used = np.zeros(e, np.int64)
        ref = np.zeros_like(xs)
        for j in range(2):  # choice-major order is the invariant under test
            for tok in range(s):
                e_idx = ti[tok, j]
                if used[e_idx] < cap:
                    used[e_idx] += 1
                    h = np.asarray(
                        jax.nn.gelu(jnp.asarray(xs[tok] @ np.asarray(p["moe_in"][e_idx])))
                    )
                    ref[tok] += tg[tok, j] * (h @ np.asarray(p["moe_out"][e_idx]))
        np.testing.assert_allclose(
            np.asarray(y).reshape(s, -1), ref, rtol=2e-4, atol=2e-5
        )

    def test_router_top_k_validation(self):
        with pytest.raises(ValueError, match="router_top_k"):
            GPT2MoEConfig(**{**TINY, "router_top_k": 5})  # > n_experts=4
        with pytest.raises(ValueError, match="router_top_k"):
            GPT2MoEConfig(**{**TINY, "router_top_k": 0})

    def test_top2_trains_and_matches_ep_sharded(self, eight_devices):
        bundle = get_model("gpt2_moe", **{**TINY, "router_top_k": 2})
        tx = make_optimizer("adam", lr=1e-3)
        params = bundle.init(jax.random.PRNGKey(0))
        batch = bundle.make_batch(jax.random.PRNGKey(1), 8)

        ref_state = TrainState.create(params, tx, jax.random.PRNGKey(2))
        ref_step = make_train_step(bundle.loss_fn, tx, donate=False)
        ref_state, ref_m = ref_step(ref_state, batch)

        mesh = make_mesh(dp=2, ep=2, tp=2)
        state = TrainState.create(params, tx, jax.random.PRNGKey(2))
        state, _ = shard_train_state(state, mesh, tx)
        step = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False)
        state, m = step(state, put_batch(batch, mesh))
        np.testing.assert_allclose(
            float(m["loss"]), float(ref_m["loss"]), rtol=2e-4
        )


def test_gpt2_moe_grads_reach_experts_and_router():
    bundle = get_model("gpt2_moe", **TINY)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = bundle.make_batch(jax.random.PRNGKey(1), 4)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(bundle.loss_fn, has_aux=True))(
        params, batch, jax.random.PRNGKey(2)
    )
    assert np.isfinite(float(loss))
    assert float(metrics["aux_loss"]) >= 0.99  # Switch aux lower bound is 1
    for leaf in ("router", "moe_in", "moe_out"):
        g = grads["blocks"]["moe"][leaf]
        assert float(jnp.sum(jnp.abs(g))) > 0, f"no gradient into {leaf}"


def test_gpt2_moe_trains():
    bundle = get_model("gpt2_moe", **TINY)
    tx = make_optimizer("adam", lr=3e-3)
    step = make_train_step(bundle.loss_fn, tx)
    batch = bundle.make_batch(jax.random.PRNGKey(1), 8)
    state = TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(3))
    losses = []
    for _ in range(25):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses[:3] + losses[-3:]


def test_ep_sharded_step_matches_single_device(eight_devices):
    from jax.sharding import PartitionSpec as P

    bundle = get_model("gpt2_moe", **TINY)
    tx = make_optimizer("adam", lr=1e-3)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = bundle.make_batch(jax.random.PRNGKey(1), 8)

    ref_state = TrainState.create(params, tx, jax.random.PRNGKey(2))
    ref_step = make_train_step(bundle.loss_fn, tx, donate=False)
    ref_state, ref_metrics = ref_step(ref_state, batch)

    mesh = make_mesh(dp=2, ep=2, tp=2)
    shardings = make_param_shardings(mesh, params)
    # experts over ep, per-expert hidden over tp, layer axis replicated
    assert shardings["blocks"]["moe"]["moe_in"].spec == P(None, "ep", None, "tp")
    assert shardings["blocks"]["moe"]["moe_out"].spec == P(None, "ep", "tp", None)

    state = TrainState.create(params, tx, jax.random.PRNGKey(2))
    state, _ = shard_train_state(state, mesh, tx)
    step = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False)
    with mesh:
        state, metrics = step(state, put_batch(batch, mesh))

    np.testing.assert_allclose(
        float(metrics["loss"]), float(ref_metrics["loss"]), rtol=2e-4
    )
    got = jax.device_get(state.params["blocks"]["moe"]["moe_in"])
    np.testing.assert_allclose(
        got, np.asarray(ref_state.params["blocks"]["moe"]["moe_in"]),
        rtol=1e-3, atol=1e-5,
    )
