"""The compiled per-volunteer train step.

Reference parity: the per-worker CUDA ``train_step`` (BASELINE.json:5) —
forward + backward + local optimizer update, entirely on-device. Here it is
one ``jax.jit`` computation with donated state, so XLA fuses fwd/bwd/update
and the params never round-trip to host between steps. The multi-chip variant
(psum over ICI inside the same compiled step) lives in
``parallel/train_step.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from distributedvolunteercomputing_tpu.models.common import SteppedLeaves

Batch = Dict[str, jax.Array]
Metrics = Dict[str, jax.Array]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Everything the volunteer owns on-device: params, opt state, step, rng."""

    params: Any
    opt_state: Any
    step: jax.Array
    rng: jax.Array

    @classmethod
    def create(cls, params: Any, tx: optax.GradientTransformation, rng: jax.Array) -> "TrainState":
        return cls(
            params=params,
            opt_state=tx.init(params),
            step=jnp.zeros((), jnp.int32),
            rng=rng,
        )


def grad_half(
    loss_fn: Callable[[Any, Batch, jax.Array], Tuple[jax.Array, Metrics]],
    state: TrainState,
    batch: Batch,
    accum_steps: int = 1,
) -> Tuple[Any, Metrics, jax.Array]:
    """fwd/bwd half of the step: (grads, metrics, next_rng).

    ``accum_steps > 1`` runs gradient accumulation INSIDE the compiled step:
    the batch's leading dim is split into ``accum_steps`` microbatches and
    scanned (``lax.scan`` — one microbatch's HLO in the program, activation
    memory of ONE microbatch), grads averaged across them. The optimizer
    semantics are identical to one big batch; only peak activation memory
    changes — the TPU-idiomatic way to train effective batch sizes that
    don't fit HBM."""
    rng, step_rng = jax.random.split(state.rng)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    if accum_steps <= 1:
        (_, metrics), grads = grad_fn(state.params, batch, step_rng)
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        return grads, metrics, rng

    micro = jax.tree_util.tree_map(
        lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps) + x.shape[1:]),
        batch,
    )

    def body(carry, mb_and_rng):
        g_acc, m_acc = carry
        mb, r = mb_and_rng
        (_, m), g = grad_fn(state.params, mb, r)
        g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
        m_acc = jax.tree_util.tree_map(jnp.add, m_acc, m)
        return (g_acc, m_acc), None

    g0 = jax.tree_util.tree_map(jnp.zeros_like, state.params)
    # One traced microbatch probe would double compile time; metrics trees in
    # the zoo are scalar-valued, so zeros of scalars is the right init.
    m0 = jax.eval_shape(
        lambda p, b, r: loss_fn(p, b, r)[1],
        state.params,
        jax.tree_util.tree_map(lambda x: x[0], micro),
        step_rng,
    )
    m0 = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), m0)
    rngs = jax.random.split(step_rng, accum_steps)
    (g_sum, m_sum), _ = jax.lax.scan(body, (g0, m0), (micro, rngs))
    inv = 1.0 / accum_steps
    grads = jax.tree_util.tree_map(lambda g: g * inv, g_sum)
    metrics = dict(jax.tree_util.tree_map(lambda m: m * inv, m_sum))
    metrics["grad_norm"] = optax.global_norm(grads)
    return grads, metrics, rng


@jax.named_scope("optimizer")  # names the update's ops in a profiler trace
def apply_half(
    tx: optax.GradientTransformation,
    state: TrainState,
    grads: Any,
    rng: jax.Array,
    stepped: Optional[SteppedLeaves] = None,
    signal: Any = None,
) -> TrainState:
    """Optimizer-update half of the step. With ``stepped``, the leaves it owns
    are its rule's (from ``signal``, the step's metric of that name) and the
    optimizer's update of every other leaf is what it would be without them."""
    if stepped is not None:
        owned = stepped.owns(state.params)
        grads = jax.tree_util.tree_map(
            lambda own, g: jnp.zeros_like(g) if own else g, owned, grads)
    updates, opt_state = tx.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    if stepped is not None:
        params = jax.tree_util.tree_map(
            lambda own, ruled, updated: ruled if own else updated,
            owned, stepped.rule(state.params, signal), params)
    return TrainState(params=params, opt_state=opt_state, step=state.step + 1, rng=rng)


def train_step_body(
    loss_fn: Callable[[Any, Batch, jax.Array], Tuple[jax.Array, Metrics]],
    tx: optax.GradientTransformation,
    state: TrainState,
    batch: Batch,
    accum_steps: int = 1,
    stepped: Optional[SteppedLeaves] = None,
) -> Tuple[TrainState, Metrics]:
    """The traced step math, shared by the single-device step, the sharded
    step (parallel/train_step.py), and — via its two halves — the split
    grad/apply steps of gradient-averaging mode, so no path can diverge."""
    grads, metrics, rng = grad_half(loss_fn, state, batch, accum_steps)
    signal = metrics.pop(stepped.signal) if stepped is not None else None
    return apply_half(tx, state, grads, rng, stepped, signal), metrics


def make_train_step(
    loss_fn: Callable[[Any, Batch, jax.Array], Tuple[jax.Array, Metrics]],
    tx: optax.GradientTransformation,
    donate: bool = True,
    accum_steps: int = 1,
    stepped: Optional[SteppedLeaves] = None,
) -> Callable[[TrainState, Batch], Tuple[TrainState, Metrics]]:
    """Build the jitted ``(state, batch) -> (state, metrics)`` step."""

    def step(state: TrainState, batch: Batch) -> Tuple[TrainState, Metrics]:
        return train_step_body(loss_fn, tx, state, batch, accum_steps, stepped)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_multi_step(
    loss_fn: Callable[[Any, Batch, jax.Array], Tuple[jax.Array, Metrics]],
    tx: optax.GradientTransformation,
    accum_steps: int = 1,
    stepped: Optional[SteppedLeaves] = None,
) -> Callable[[TrainState, Batch], Tuple[TrainState, jax.Array]]:
    """N train steps in ONE compiled call: ``(state, stacked_batches) ->
    (state, per_step_losses)``.

    Host-loop amortization (Trainer ``steps_per_call``): a Python loop
    dispatches one program per step, so per-dispatch overhead sits on the
    step's critical path. ``lax.scan`` over the SAME traced body
    (``train_step_body`` — identical math to the single step, by
    construction) moves the loop on-device: one dispatch per N steps, and
    XLA can overlap the next step's prologue with the previous epilogue.
    The leading axis of every batch leaf is the step index."""

    def multi(state: TrainState, batches: Batch) -> Tuple[TrainState, jax.Array]:
        def body(s: TrainState, b: Batch):
            s2, metrics = train_step_body(loss_fn, tx, s, b, accum_steps, stepped)
            return s2, metrics["loss"]

        return jax.lax.scan(body, state, batches)

    return jax.jit(multi, donate_argnums=(0,))


def make_grad_step(
    loss_fn: Callable[[Any, Batch, jax.Array], Tuple[jax.Array, Metrics]],
    accum_steps: int = 1,
) -> Callable[[TrainState, Batch], Tuple[Any, Metrics, jax.Array]]:
    """Gradient-averaging mode, half 1: fwd/bwd WITHOUT the update.

    The reference's synchronous GradientAverager semantics (BASELINE.json:5)
    average GRADIENTS across volunteers before any optimizer sees them; that
    forces the grads out to host between bwd and update, so the fused step
    splits into (grad_step, apply_step). State is NOT donated here — the
    same state is consumed again by apply_step."""
    return jax.jit(lambda state, batch: grad_half(loss_fn, state, batch, accum_steps))


def make_apply_step(
    tx: optax.GradientTransformation,
    donate: bool = True,
    stepped: Optional[SteppedLeaves] = None,
) -> Callable[..., TrainState]:
    """Gradient-averaging mode, half 2: optimizer update from (possibly
    swarm-averaged) grads. With ``stepped`` the caller takes the rule's signal
    out of the grad step's metrics (its own, not averaged) and passes it as
    the fourth argument."""
    return jax.jit(
        lambda state, grads, rng, signal=None: apply_half(tx, state, grads, rng, stepped, signal),
        donate_argnums=(0,) if donate else (),
    )


def make_eval_step(
    loss_fn: Callable[[Any, Batch, jax.Array], Tuple[jax.Array, Metrics]],
) -> Callable[[Any, Batch, jax.Array], Metrics]:
    def ev(params: Any, batch: Batch, rng: jax.Array) -> Metrics:
        _, metrics = loss_fn(params, batch, rng)
        return metrics

    return jax.jit(ev)
