"""The plain reference of Kimi-Linear-48B-A3B-Instruct
(benchmark/references/kimi_linear.py) against itself with one term of the layer
equations computed as a mistaken implementation would, at a tiny size on the
CPU: every one of its ``VARIANTS`` is noticed at seeded non-initial parameters,
and what a check on the initial parameters can and cannot see. Beside
tests/test_kimi_linear.py, whose ``tiny`` it uses, in a file of its own so that
the two run side by side. The reference's gradients are jitted, one program a
variant: evaluated eagerly its nested checkpoints and scans are thousands of
small dispatches from a deep stack."""

import functools

import jax
import jax.numpy as jnp
import pytest

from benchmark.references import kimi_linear as ref
from tests.test_kimi_linear import flat, reference, rel, tiny


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def state(scale):
    """(parameters, tokens, targets, the routes the reference chose) at the tiny size."""
    _, params, batch = tiny(scale=scale)
    tokens, targets = batch["tokens"], batch["targets"]
    _, routes = reference(with_routes=True)(params, tokens, targets)
    return params, tokens, targets, routes


def loss_and_grad(variant, params, tokens, targets, routes):
    """The reference's loss and its gradient as one flat vector, ``variant`` in place of one term."""
    loss, grads = reference(grad=True, variant=variant)(params, tokens, targets, routes)
    return float(loss), flat(grads)


@functools.lru_cache(maxsize=None)
def unmistaken(scale):
    return loss_and_grad(None, *state(scale))


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_reference_notices_a_term_left_out(variant):
    """Every mistaken term changes the loss and the gradient at seeded
    non-initial parameters, against the reference itself with the same routes."""
    (lr, gr), (lv, gv) = unmistaken(3.0), loss_and_grad(variant, *state(3.0))
    # one layer of the five is latent attention, over 40 positions whose scores are nearly flat: its two
    # quietest terms read 4.3e-4 / 5.0e-3 (the scale) and 3.2e-5 / 2.9e-2 (the shared key part) here
    loss_floor, grad_floor = {"scale_128_for_192": (1e-4, 3e-3), "shared_key_per_head": (2e-5, 1e-2)}.get(
        variant, (5e-5, 1e-2))
    assert abs(lv - lr) > loss_floor, (variant, lv, lr)
    assert rel(gv, gr) > grad_floor, (variant, rel(gv, gr))


def test_what_the_check_on_the_initial_parameters_can_and_cannot_see():
    """On ``init``'s own parameters the carried state and the delta term show
    (that is what the decay leaves' initialisation is for), and a selection bias
    of zero hides ``bias_in_weights``, as the configuration file's ``left_out``
    says."""
    params, tokens, targets, routes = state(0.0)
    base = unmistaken(0.0)[1]
    grad_of = lambda v: loss_and_grad(v, params, tokens, targets, routes)[1]  # noqa: E731
    assert rel(grad_of("no_state_between_chunks"), base) > 2e-3
    assert rel(grad_of("no_delta_term"), base) > 2e-3
    assert rel(grad_of("bias_in_weights"), base) == 0.0
    # with a dt_bias that makes every channel forget within a few tokens the same check is blind to the carried state
    blind = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) + 4.0 if jax.tree_util.keystr(path).endswith("['dt_bias']") else x, params)
    base_blind = loss_and_grad(None, blind, tokens, targets, routes)[1]
    gone = loss_and_grad("no_state_between_chunks", blind, tokens, targets, routes)[1]
    assert rel(gone, base_blind) < 0.5 * rel(grad_of("no_state_between_chunks"), base)
