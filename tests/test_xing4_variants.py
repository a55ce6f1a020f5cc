"""The plain reference of Xing4.0-29B-A4B (benchmark/references/xing4.py)
against each term of its equations computed as a mistaken implementation would
(``VARIANTS``), at the tiny size on the CPU: every one changes the loss and the
gradient on seeded NON-initial parameters, and the test says which of them the
initial parameters hide and why. A compile a variant: a file of its own, so that
a worker of its own runs it beside tests/test_xing4.py."""

import math

import jax
import pytest

from benchmark.references import xing4 as ref
from tests.test_xing4 import HP, seeded, whole_error

# What the INITIAL parameters cannot show (the harness checks there, and only there): a selection bias of zero
# added to the weights is no mistake; no ``Hres~`` entry is past the clip (|4 + noise| against 30); and with
# ``a`` = 0.01 the token's own part of every map is a hundredth of its bias, so leaving it out (or making it of
# an un-normed state) moves the gradient by 5e-4 of itself, far inside any limit a bf16 program can be held to.
BLIND_AT_INITIALISATION = {"bias_in_weights": 1e-6, "no_clip": 1e-6, "static_maps": 1e-3, "no_stream_norm": 1e-3}


def _read(params, batch, variant=None):
    return jax.jit(lambda p: jax.value_and_grad(ref.loss)(p, batch["tokens"], batch["targets"], HP, None, False, variant))(params)


@pytest.fixture(scope="module")
def states():
    """(parameters, batch, the reference's own loss and gradient) at the seeded
    state, at one that drives ``Hres~`` past the clip, and at initialisation."""
    out = {}
    for name, kwargs in (("seeded", {}), ("past_the_clip", {"res": 12.0}), ("initial", {"initial": True})):
        _, params, batch = seeded(n_layers=2, dense_layers=0, max_len=32, **kwargs)   # two expert layers, ONE scanned body: a compile a variant
        out[name] = (params, batch, *_read(params, batch))
    return out


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_reference_notices_a_term_left_out(variant, states):
    params, batch, loss, grads = states["past_the_clip" if variant == "no_clip" else "seeded"]
    got, got_grads = _read(params, batch, variant)
    assert math.isfinite(float(got)) and abs(float(got) - float(loss)) > 2e-6, variant           # float32 against float32: 5e-7 is rounding
    assert whole_error(got_grads, grads) > 1e-3, variant


@pytest.mark.parametrize("variant", sorted(BLIND_AT_INITIALISATION))
def test_what_the_initial_parameters_hide(variant, states):
    params, batch, loss, grads = states["initial"]
    got, got_grads = _read(params, batch, variant)
    error = whole_error(got_grads, grads)
    assert error <= BLIND_AT_INITIALISATION[variant], (variant, error)


def test_the_variants_are_the_ones_the_issue_lists_and_an_unknown_one_is_refused(states):
    assert set(ref.VARIANTS) >= {
        "no_sinkhorn", "rows_only", "sinkhorn_1_iter", "res_transposed", "post_not_doubled", "pre_softmax",
        "static_maps", "no_stream_norm", "no_clip", "streams_mean_at_the_end", "one_map_a_layer", "no_yarn_scale",
        "yarn_scale_on_rope_only", "plain_rope_frequencies", "rope_key_per_head", "rope_on_whole_head", "scale_by_128",
        "weights_not_renormalised", "no_routed_scaling", "bias_in_weights", "shared_expert_weighted"}
    assert len(set(ref.VARIANTS)) == len(ref.VARIANTS) == 21
    params, batch, *_ = states["initial"]
    with pytest.raises(ValueError, match="unknown variant"):
        ref.loss(params, batch["tokens"], batch["targets"], HP, variant="no_such_term")
