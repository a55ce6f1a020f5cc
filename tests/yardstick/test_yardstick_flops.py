"""The FLOP arithmetic and the peak table."""

import pytest

from benchmark import flops, references
from benchmark.manifest import REPO_ROOT, Manifest

M = Manifest(REPO_ROOT)


@pytest.mark.parametrize("name,n_expected", [("gpt2-medium", 354_823_168), ("gpt2-large", 774_030_080)])
def test_flops_per_token_is_6n_plus_12ltd(name, n_expected):
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    cfg = M.load_config(name)
    bundle = get_model(cfg["registry_model"], **cfg["model_overrides"])
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    assert n == n_expected
    s = references.load(cfg["family"]).sizes(cfg)
    got = flops.train_flops_per_token(n, s["n_layer"], s["seq_len"], s["d_model"])
    assert got == 6 * n + 12 * cfg["n_layer"] * cfg["n_positions"] * cfg["n_embd"]


def test_mfu_is_model_flops_over_peak():
    peak = flops.peak_for("tpu", "TPU v5 lite")
    assert peak["bf16_flops"] == 197e12
    # 16,384 tokens at 2.4 GFLOP each in 0.5 s on one chip
    assert flops.mfu_percent(16384, 2.4e9, 0.5, 1, 197e12) == pytest.approx(39.92, abs=0.01)
    assert flops.mfu_percent(16384, 2.4e9, 0.5, 4, 197e12) == pytest.approx(9.98, abs=0.01)


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v9 imaginary"), ("cpu", "cpu"), ("gpu", "TPU v5 lite")])
def test_peak_table_refuses_what_it_does_not_know(platform, kind):
    with pytest.raises(flops.UnknownDevice):
        flops.peak_for(platform, kind)
