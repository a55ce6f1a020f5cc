"""The plain reference of Qwen3-Next (benchmark/references/qwen3_next.py) with
one term of the layer equations computed as a mistaken implementation would:
each of the sixteen changes loss and gradient at seeded non-initial parameters,
and what the harness's check on the INITIAL parameters can and cannot see."""

import functools

import jax
import jax.numpy as jnp
import pytest

from benchmark.references import qwen3_next as ref
from tests.test_qwen3_next import flat, reference, rel, tiny


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
    """The reference's loss and its gradient tree, ``variant`` in place of one term."""
    loss, grads = reference(grad=True, variant=variant)(params, tokens, targets, routes)
    return float(loss), grads


@functools.lru_cache(maxsize=None)
def unmistaken(scale):
    return loss_and_grad(None, *state(scale))


def routers(grads):
    return {kind: grads["blocks"][kind]["router"] for kind in ("linear", "full")}


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_reference_notices_a_term_left_out(variant):
    """Every mistaken term changes the loss and the gradient at seeded
    non-initial parameters, against the reference itself with the same routes
    (readings here: the loss by 7.6e-4 (``no_attention_gate``) to 0.11, the whole
    gradient by 0.24 to 1.37 of its norm). The balancing term, at 0.001, moves
    the loss by 4.0e-3 and reaches the routers alone: it is read on their leaves (2.1e-4 of their norm; a variant
    that computed the same function would read 0 exactly: same routes, float32, one summation order)."""
    (lr, gr), (lv, gv) = unmistaken(3.0), loss_and_grad(variant, *state(3.0))
    assert abs(lv - lr) > 2e-4, (variant, lv, lr)
    if variant == "no_aux_loss":
        assert rel(flat(routers(gv)), flat(routers(gr))) > 1e-4 and rel(flat(gv), flat(gr)) > 2e-5
    else:
        assert rel(flat(gv), flat(gr)) > 5e-2, (variant, rel(flat(gv), flat(gr)))


def test_what_the_check_on_the_initial_parameters_can_and_cannot_see():
    """On ``init``'s own parameters the carried state and the delta term show
    (that is what the decay leaves' initialisation is for), and a zero-centred
    norm read as ``* w`` silences the model outright; with a ``dt_bias`` under
    which every head forgets within a few tokens the same check is blind to the
    carried state, as the configuration file's ``assumed.gdn_init`` says."""
    params, tokens, targets, routes = state(0.0)
    base = flat(unmistaken(0.0)[1])
    grad_of = lambda v, p=params: flat(loss_and_grad(v, p, tokens, targets, routes)[1])  # noqa: E731
    assert rel(grad_of("no_state_between_chunks"), base) > 2e-3
    assert rel(grad_of("no_delta_term"), base) > 2e-3
    assert rel(grad_of("norm_weight_not_offset"), base) > 0.5
    blind = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) + 4.0 if jax.tree_util.keystr(path).endswith("['dt_bias']") else x, params)
    assert rel(grad_of("no_state_between_chunks", blind), grad_of(None, blind)) \
        < 0.5 * rel(grad_of("no_state_between_chunks"), base)
