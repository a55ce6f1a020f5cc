"""The share path of ops/moe_dispatch.py as a dataflow (PR 36), at a tiny size
on the CPU and whatever model calls it: how many grouped matmuls a chunk is
(two forward: gate-up and down; five backward), that the router's weight meets
``hidden`` [rows, f] and never a [rows, d] buffer, and every gradient,
the weight's own among them, against a plain per-token float32 sum over the
held experts. tests/test_laguna.py and tests/test_smallthinker.py hold the
path to each model's dense form; this file holds it to its shape."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.interpreters import partial_eval as pe

from distributedvolunteercomputing_tpu.ops import moe_dispatch

S, D, F, E, K, OFFSET, HELD = 48, 16, 8, 16, 4, 4, 4
ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
# the chunk's rows over the even share (48): chunks of 24 rows, of which the
# routes below need three, and one of 144
SLACKS = {"several": 0.5, "one": 3.0}


def inputs():
    """Tokens, routes and held stacks: token 0 chooses no held expert, token 1
    all four of them (a run of k rows), the others as the draw has it (52 held
    assignments of 192)."""
    ks = jax.random.split(jax.random.PRNGKey(36), 6)
    x = jax.random.normal(ks[0], (S, D))
    stacks = [jax.random.normal(kk, shape) * 0.3
              for kk, shape in zip(ks[1:4], ((HELD, D, F), (HELD, D, F), (HELD, F, D)))]
    idx = jnp.argsort(jax.random.uniform(ks[4], (S, E)), axis=1)[:, :K].astype(jnp.int32)
    idx = idx.at[0].set(jnp.asarray([0, 1, 9, 13])).at[1].set(jnp.asarray([7, 4, 6, 5]))
    gates = jax.nn.softmax(jax.random.normal(ks[5], (S, K)), axis=1)
    return x, idx, gates, stacks


def per_token_sum(x, idx, gates, w_gate, w_up, w_down, act):
    """``y[s] = sum_i gates[s, i] * down_e(act(gate_e x[s]) * up_e x[s])`` over
    the choices whose expert ``e = idx[s, i]`` is held: every (token, choice)
    pair with its own expert's matrices, nothing sorted, nothing grouped."""
    local = idx - OFFSET
    held = (local >= 0) & (local < HELD)
    e = jnp.clip(local, 0, HELD - 1)
    with jax.default_matmul_precision("highest"):
        hidden = ACTS[act](jnp.einsum("sd,skdf->skf", x, w_gate[e])) * jnp.einsum("sd,skdf->skf", x, w_up[e])
        out = jnp.einsum("skf,skfd->skd", hidden, w_down[e])
    return jnp.einsum("skd,sk->sd", out, jnp.where(held, gates, 0.0))


def live_equations(jaxpr, path=()):
    """(enclosing primitives, equation) for every equation of ``jaxpr`` that
    something reads, the bodies of its loops and calls cleaned likewise: what
    a compiler keeps of it."""
    jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    for eqn in jaxpr.eqns:
        yield path, eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from live_equations(sub, path + (eqn.primitive.name,))


@pytest.mark.parametrize("chunks", list(SLACKS))
@pytest.mark.parametrize("act", list(ACTS))
def test_a_chunk_is_two_grouped_products_forward_and_five_backward(act, chunks):
    """In the gradient's jaxpr with dead code removed: the forward chunk loop
    (inside the ``custom_vjp`` call) holds the gate-up and the down product;
    the backward loop gate-up again, the cotangent of ``hidden``, and the
    three by the weights and the rows: the down product forward is dead there,
    since the router's weight is applied in front of it and nothing reads its
    result. Twelve before PR 36 (3 + 9). No multiply in either loop has a
    [rows, d] result: the gathered gates scale ``hidden`` [rows, f]."""
    x, idx, gates, stacks = inputs()
    slack = SLACKS[chunks]
    rows = moe_dispatch.share_rows_bound(S, K, HELD, E, slack)

    def loss(x, gates, *stacks):
        return jnp.sum(jnp.sin(moe_dispatch.share_glu_experts(
            x, idx, gates, *stacks, OFFSET, E, act=act, slack=slack)[0]))

    moved = moe_dispatch.share_glu_experts(x, idx, gates, *stacks, OFFSET, E, act=act, slack=slack)[3]
    assert int(moved) // rows == {"several": 3, "one": 1}[chunks]
    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(x, gates, *stacks)
    in_loops = [(path, eqn) for path, eqn in live_equations(closed.jaxpr) if "while" in path]
    products = collections.Counter(
        "forward" if "custom_vjp_call" in path[:path.index("while")] else "backward"
        for path, eqn in in_loops if eqn.primitive.name.startswith("ragged_dot"))
    assert products == {"forward": 2, "backward": 5}, products
    multiplies = [eqn for _, eqn in in_loops if eqn.primitive.name == "mul"]
    assert any(eqn.outvars[0].aval.shape == (rows, F) for eqn in multiplies)
    assert not any(eqn.outvars[0].aval.shape == (rows, D) for eqn in multiplies)
    # and nothing copies a [rows, d] buffer to pad it: the only concatenate in a loop is of row indices
    assert all(eqn.outvars[0].aval.ndim == 1 for _, eqn in in_loops if eqn.primitive.name == "concatenate")


@pytest.mark.parametrize("chunks", list(SLACKS))
@pytest.mark.parametrize("act", list(ACTS))
def test_every_gradient_is_the_per_token_sums(act, chunks):
    """The gradient with respect to the tokens, the router's weights and the
    three stacks against ``per_token_sum``'s, in float32. The weight's
    cotangent, computed from ``hidden`` and its cotangent since PR 36, on its
    own: for a held choice the probe's product with that expert's output for
    the token, for any other choice exactly zero."""
    x, idx, gates, stacks = inputs()
    probe = jax.random.normal(jax.random.PRNGKey(7), (S, D))

    def share(x, gates, *stacks):
        return moe_dispatch.share_glu_experts(
            x, idx, gates, *stacks, OFFSET, E, act=act, slack=SLACKS[chunks])[0]

    want_y = per_token_sum(x, idx, gates, *stacks, act)
    np.testing.assert_allclose(np.asarray(share(x, gates, *stacks)), np.asarray(want_y), rtol=2e-5, atol=2e-5)
    assert not np.asarray(want_y[0]).any() and np.asarray(want_y[1]).any()
    got = jax.grad(lambda *a: jnp.sum(share(*a) * probe), argnums=(0, 1, 2, 3, 4))(x, gates, *stacks)
    want = jax.grad(lambda x, gates, *w: jnp.sum(per_token_sum(x, idx, gates, *w, act) * probe),
                    argnums=(0, 1, 2, 3, 4))(x, gates, *stacks)
    for name, a, b in zip(("x", "top_gates", "w_gate", "w_up", "w_down"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5, err_msg=name)
    held = np.asarray((idx >= OFFSET) & (idx < OFFSET + HELD))
    assert not held[0].any() and held[1].all()
    d_gates = np.asarray(got[1])
    assert not d_gates[~held].any() and np.all(d_gates[held] != 0)
    # each choice's expert output alone, at weight 1: the weight's cotangent is its product with the probe
    alone = jnp.stack([jnp.sum(per_token_sum(x, idx, jnp.tile(jnp.eye(K)[i], (S, 1)), *stacks, act) * probe, axis=1)
                       for i in range(K)], axis=1)
    np.testing.assert_allclose(d_gates, np.asarray(alone), rtol=2e-4, atol=2e-5)
    assert not np.asarray(got[0][0]).any()  # a token with no held row takes no gradient from this share
