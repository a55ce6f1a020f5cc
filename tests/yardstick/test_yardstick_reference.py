"""The plain reference against the program's model, at a tiny width on the CPU."""

import numpy as np
import pytest

from benchmark import datagen
from benchmark.references import gpt2 as ref

OVERRIDES = {"n_layers": 2, "d_model": 64, "n_heads": 4, "max_len": 32, "vocab": 256,
             "d_ff": 256, "xent_chunk": 16}
FILE = {"name": "tiny", "n_layer": 2, "n_embd": 64, "n_head": 4, "n_positions": 32,
        "vocab_size": 256, "n_inner": 256, "layer_norm_epsilon": 1e-5}


@pytest.mark.parametrize("remat", [True, False])
def test_reference_equals_the_programs_loss_and_gradients(remat):
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model("gpt2_small", remat=remat, **OVERRIDES)
    ref.check_config(bundle.config, FILE)
    params = bundle.init(jax.random.PRNGKey(3))
    batch = datagen.lm_arrays(5, 2, 32, 256)
    rng = jax.random.PRNGKey(0)
    want_l, want_g = jax.value_and_grad(lambda p: bundle.loss_fn(p, batch, rng)[0])(params)
    got_l, got_g = ref.make_loss_and_grad(FILE)(params, batch["tokens"], batch["targets"])
    assert float(got_l) == pytest.approx(float(want_l), abs=1e-5)
    # float32 on both sides: only the order of summation differs
    for a, b in zip(jax.tree_util.tree_leaves(got_g), jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6)


def test_reference_notices_a_dropped_term():
    """The check must fail when part of the mathematics is left out: here the
    program's model without its position embeddings."""
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model("gpt2_small", **OVERRIDES)
    params = bundle.init(jax.random.PRNGKey(3))
    batch = datagen.lm_arrays(5, 2, 32, 256)
    broken = dict(params, wpe=params["wpe"] * 0.0)
    want = float(bundle.loss_fn(broken, batch, jax.random.PRNGKey(0))[0])
    got = float(ref.loss(params, batch["tokens"], batch["targets"], 4))
    assert abs(got - want) > 1e-4
