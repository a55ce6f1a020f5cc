"""What PR 52 added to the yardstick: the Kimi-Linear reference's own
consistency (its delta rule against the three steps written out in numpy, its
gradient against finite differences, its latent attention against a loop over
heads), the FLOP, byte and parameter counts against hand sums, the three new
readers (``kda.device_ms``, ``kda.roofline``, ``kda.carry_share``) on a hand-made
trace and span list and the older readers on this cell's kernel names, the
configuration file against the catalog row, and the manifest with the new
entries (and what ``test_yardstick_nemotron_h.py`` asserted of the manifest's
tail and lists, three metrics, one cell and one configuration up: see
tests/conftest.py)."""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmark import datagen, family_flops, flops, flops_kimi_linear as fl, kda_trace, readers, references
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.references import kimi_linear as ref
from benchmark.trace import Trace

M = Manifest(REPO_ROOT)
CFG = M.load_config("kimi-linear-48b-a3b")
TINY = M.load_config("tiny-rehearsal-kimi")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "kimi-linear-solo-8k"
NEMOTRON, GLM, LFM2, SMALL = "nemotron3-nano-solo-8k", "glm47-flash-solo-8k", "lfm2-solo-8k", "smallthinker-solo-16k"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


# -- the reference ---------------------------------------------------------------


def tiny_params(seed=3, scale=3.0, bias=0.05):
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(TINY["registry_model"], **TINY["model_overrides"])
    ref.check_config(bundle.config, TINY)
    params = bundle.init(jax.random.PRNGKey(seed))

    def scaled(path, x):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):
            return bias * jax.random.normal(jax.random.PRNGKey(11), x.shape)
        keep = ("['g']", "['a_log']", "['dt_bias']", "['conv_w']", "['gate_b']")
        return x if name.endswith(keep) else x * scale

    return bundle, jax.tree_util.tree_map_with_path(scaled, params), datagen.lm_arrays(5, 2, 24, TINY["vocab_size"])


def test_reference_gradient_agrees_with_finite_differences():
    """Its ``jax.grad`` against central differences of its own loss, along a
    seeded direction in every leaf, the routes held at those of the unmoved
    parameters. The selection biases' leaves are zeros on both sides."""
    import jax
    import jax.numpy as jnp

    _, params, batch = tiny_params()
    hp = ref.hyper(TINY)
    tokens, targets = batch["tokens"], batch["targets"]
    _, routes = ref.loss(params, tokens, targets, hp, with_routes=True)
    grads = jax.grad(ref.loss)(params, tokens, targets, hp, routes)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    g_leaves = jax.tree_util.tree_leaves(grads)
    rng = np.random.default_rng(0)
    loss = jax.jit(lambda p: ref.loss(p, tokens, targets, hp, routes))
    zeros = 0
    for i, (leaf, g) in enumerate(zip(leaves, g_leaves)):
        direction = rng.standard_normal(leaf.shape).astype(np.float32)
        direction /= np.linalg.norm(direction)
        eps = 1e-3 * max(float(jnp.linalg.norm(leaf)), 1.0)
        moved = lambda s: jax.tree_util.tree_unflatten(  # noqa: E731
            treedef, leaves[:i] + [leaf + s * eps * direction] + leaves[i + 1:])
        fd = (float(loss(moved(1.0))) - float(loss(moved(-1.0)))) / (2 * eps)
        want = float(jnp.sum(g * direction))
        # float32's own noise on a loss of 6.3 is 6e-7, over 2 eps = 2e-3 and more: 6e-4 and less of the difference quotient
        assert fd == pytest.approx(want, rel=0.05, abs=1.5e-3), (i, fd, want)
        zeros += not np.any(np.asarray(g))
    assert zeros == 3  # the three expert runs' selection biases, and no other leaf


def test_reference_delta_rule_is_the_three_steps_a_token():
    """``_delta_rule`` against the steps written out in numpy, float64: decay by
    channel, the delta along the key, the rank-one update, the read; the
    stretches change nothing and the variants change what they name."""
    import jax
    import jax.numpy as jnp

    z, t, h, dk, dv = 1, 10, 2, 4, 3
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    q, key = jax.random.normal(k[0], (z, t, h, dk)), jax.random.normal(k[1], (z, t, h, dk))
    key = key / jnp.linalg.norm(key, axis=-1, keepdims=True)
    v = jax.random.normal(k[2], (z, t, h, dv))
    g = -jnp.exp(jax.random.normal(k[3], (z, t, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (z, t, h)))

    def by_hand(reset=0, delta=True, after=False):
        qn, kn, vn, gn, bn = (np.asarray(a, np.float64) for a in (q, key, v, g, beta))
        out = np.zeros((z, t, h, dv))
        for head in range(h):
            s = np.zeros((dk, dv))
            for pos in range(t):
                if reset and pos % reset == 0:
                    s[:] = 0
                alpha = np.exp(gn[0, pos, head])[:, None]
                if not after:
                    s = alpha * s
                held = s.T @ kn[0, pos, head] if delta else 0.0
                s = s + np.outer(kn[0, pos, head], bn[0, pos, head] * (vn[0, pos, head] - held))
                if after:
                    s = alpha * s
                out[0, pos, head] = s.T @ qn[0, pos, head]
        return out

    got = ref._delta_rule(q, key, v, g, beta, 0, None)
    np.testing.assert_allclose(np.asarray(got), by_hand(), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref._delta_rule(q, key, v, g, beta, 4, None)), by_hand(reset=4), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref._delta_rule(q, key, v, g, beta, 0, "no_delta_term")), by_hand(delta=False),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref._delta_rule(q, key, v, g, beta, 0, "decay_after_update")), by_hand(after=True),
                               rtol=2e-5, atol=2e-5)
    assert np.abs(by_hand(delta=False) - by_hand()).max() > 1e-2 and np.abs(by_hand(after=True) - by_hand()).max() > 1e-2
    # 128 tokens are two stretches of 64: the same numbers as one stretch of 128
    long = tuple(jnp.tile(a, (1, 13) + (1,) * (a.ndim - 2))[:, :128] for a in (q, key, v, g, beta))
    whole = ref._delta_rule(*long, 0, None)
    stretch, ref.SCAN_STRETCH = ref.SCAN_STRETCH, 128
    try:
        np.testing.assert_allclose(np.asarray(ref._delta_rule(*long, 0, None)), np.asarray(whole), rtol=1e-6, atol=1e-7)
    finally:
        ref.SCAN_STRETCH = stretch


def test_reference_convolution_is_causal_and_reads_the_last_tap_at_the_position():
    import jax

    u = jax.random.normal(jax.random.PRNGKey(0), (1, 7, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
    got = np.asarray(ref._causal_conv(u, w))
    un, wn = np.asarray(u), np.asarray(w)
    for t in range(7):
        want = sum(wn[j] * un[0, t - 3 + j] for j in range(4) if t - 3 + j >= 0)
        np.testing.assert_allclose(got[0, t], want, rtol=1e-5, atol=1e-6)


def test_reference_latent_attention_shares_one_key_part_and_its_values_are_narrower():
    """``_latent`` against a loop over heads in plain softmax: keys of 6 + 2, the
    2 the same for every head, values of 3, the scale 1/sqrt(8), nothing rotated."""
    import jax
    import jax.numpy as jnp

    hp = dict(ref.hyper(TINY), heads=4, latent=5, nope=6, shared_key=2, v_dim=3)
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    d = 8
    p = {"wq": jax.random.normal(k[0], (d, 4 * 8)), "wkv_a": jax.random.normal(k[1], (d, 5 + 2)),
         "kv_a_norm": {"g": 1.0 + 0.1 * jax.random.normal(k[2], (5,))},
         "wkv_b": jax.random.normal(k[3], (5, 4 * 9)), "wo": jax.random.normal(k[4], (4 * 3, d))}
    n = jax.random.normal(k[5], (1, 6, d))
    got = ref._latent(p, n, hp, None)
    joint = n @ p["wkv_a"]
    c = joint[..., :5]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + hp["eps"]) * p["kv_a_norm"]["g"]
    kv = (c @ p["wkv_b"]).reshape(1, 6, 4, 9)
    q = (n @ p["wq"]).reshape(1, 6, 4, 8)
    heads = []
    for h in range(4):
        key = jnp.concatenate([kv[:, :, h, :6], joint[..., 5:]], axis=-1)
        s = jnp.einsum("btd,bsd->bts", q[:, :, h], key) / np.sqrt(8.0)
        s = jnp.where(jnp.tril(jnp.ones((6, 6), bool)), s, -jnp.inf)
        heads.append(jax.nn.softmax(s, -1) @ kv[:, :, h, 6:])
    want = jnp.concatenate(heads, axis=-1) @ p["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    for variant in ("value_head_192", "shared_key_per_head", "scale_128_for_192"):
        assert float(jnp.max(jnp.abs(ref._latent(p, n, hp, variant) - got))) > 1e-3, variant


def test_reference_sizes_and_config_check():
    from distributedvolunteercomputing_tpu.models import get_model

    assert ref.sizes(CFG) == {"n_layer": 5, "d_model": 2304, "seq_len": 8192, "vocab": 20480}
    assert ref.hyper(CFG) == {"heads": 32, "latent": 512, "nope": 128, "shared_key": 64, "v_dim": 128, "kda_heads": 32,
                              "kda_head": 128, "taps": 4, "chunk": 64, "eps": 1e-5, "top_k": 8, "offset": 0, "scale": 2.446}
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    ref.check_config(bundle.config, CFG)
    for key, change in (("num_experts_per_token", 4), ("num_experts", 16), ("moe_intermediate_size", 512),
                        ("intermediate_size", 4608), ("kv_lora_rank", 256), ("qk_nope_head_dim", 64),
                        ("qk_rope_head_dim", 32), ("v_head_dim", 192), ("num_attention_heads", 16),
                        ("num_key_value_heads", 8), ("expert_offset", 8), ("moe_renormalize", False),
                        ("num_expert_group", 8), ("mla_use_nope", False), ("q_lora_rank", 768), ("hidden_act", "gelu"),
                        ("rms_norm_eps", 1e-6), ("num_hidden_layers", 6), ("routed_scaling_factor", 1.0),
                        ("num_shared_experts", 2), ("tie_word_embeddings", True), ("first_k_dense_replace", 2),
                        ("moe_router_activation_func", "softmax"), ("vocab_size", 163840)):
        with pytest.raises(ValueError, match=key):
            ref.check_config(bundle.config, dict(CFG, **{key: change}))
    linear = CFG["linear_attn_config"]
    for key, change, word in (("num_heads", 16, "kda_heads"), ("head_dim", 64, "kda_head_dim"),
                              ("short_conv_kernel_size", 3, "conv_taps"), ("kda_layers", [1, 2, 3], "kda_layers"),
                              ("full_attn_layers", [4], "full_attn_layers")):
        with pytest.raises(ValueError, match=word):
            ref.check_config(bundle.config, dict(CFG, linear_attn_config={**linear, key: change}))
    with pytest.raises(ValueError, match="n_layers"):
        ref.check_config(get_model(CFG["registry_model"]).config, CFG)  # the published model, uncut
    with pytest.raises(ValueError, match="bias_gamma"):
        ref.check_config(dataclasses.replace(bundle.config, bias_gamma=0.01), CFG)
    assumed = CFG["assumed"]
    for key, value, word in (("gate_rank", {"value": 64}, "gate_rank"), ("chunk", {"value": 128}, "chunk"),
                             ("aux_coefficients", {"load_balancing": 0.01}, "auxiliary"),
                             ("kda_init", {"value": "normal(0, 0.02)"}, "initialisation"),
                             ("biases", {"value": "none"}, "bias"), ("seq_len", {"value": 4096}, "max_len")):
        with pytest.raises(ValueError, match=word):
            ref.check_config(bundle.config, dict(CFG, assumed={**assumed, key: value}))


def test_configuration_file_is_what_the_program_runs_with_its_cut_listed():
    """``test_yardstick_manifest.py``'s check of a configuration, for one whose
    ``reduced`` is not empty (tests/conftest.py marks that case), and every
    number of the catalog row under its own key, its nested group whole."""
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    entry = M.config_entry("kimi-linear-48b-a3b")
    assert CFG["source"] == entry["source"] and CFG["reduced"] == entry["reduced"] == REDUCED
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    assert CFG["published"] == {"num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    assert [CFG[k] for k in CFG["reduced"]] == [5, 8, 20480]
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    assert CFG["model_overrides"] == {"n_layers": 5, "experts_held": 8, "expert_offset": 0, "vocab": 20480}
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    references.load(CFG["family"]).check_config(bundle.config, CFG)
    assert bundle.config.layer_types == ("kda", "kda", "kda", "latent_attention", "kda")
    assert CFG["assumed"]["layers_run"]["value"].count("kda") == 4
    assert set(CFG["volunteer"]) <= {f.name for f in dataclasses.fields(VolunteerConfig)}
    assert CFG["volunteer"]["batch_size"] == 2
    assert CFG["volunteer"]["warmup_steps"] == CFG["assumed"]["lr_warmup"]["warmup_steps"] == 2000
    assert TINY["volunteer"]["warmup_steps"] == 2000 and TINY["assumed"]["seq_len"]["value"] % TINY["assumed"]["chunk"]["value"]
    assert "thirty-two chips share each layer" in CFG["deployment"] and "pipeline stages" in CFG["deployment"]
    assert "thirty-two chips share each layer" in CFG["reduced_why"]["num_experts"]
    for key in ("layers_run", "gate_rank", "biases", "chunk", "head_dim", "positions", "l2norm", "kda_init", "expert_bias",
                "aux_coefficients", "router", "seq_len", "batch_size", "optimizer", "lr_warmup", "dtypes",
                "initialisation", "unused_keys", "alias_keys"):
        assert key in CFG["assumed"], key
    assert CFG["assumed"]["expert_bias"]["gamma"] == 0.001 and "2408.15664" in CFG["assumed"]["expert_bias"]["why"]
    assert CFG["assumed"]["kda_init"]["value"] == ref.KDA_INIT == TINY["assumed"]["kda_init"]["value"]
    assert CFG["num_experts_per_tok"] == CFG["num_experts_per_token"] == 8        # the alias the share's reader knows
    params = CFG["parameters"]
    assert params["counted_by_the_program"] == sum(params["by_layer"]) + params["embedding"] + params["head"] + params["final_norm"]
    assert params["counted_by_the_program"] == 602_450_816 and params["at_the_published_sizes"] == 49_122_763_648
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key  # every width and head size under its own key, the nested group whole


# -- FLOPs, bytes and parameters ---------------------------------------------------------


def test_flop_byte_and_parameter_counts_against_a_hand_sum():
    t, d, v, inner = 8192, 2304, 20480, 4096
    kda_mat = d * 3 * inner + 2 * (d * 128 + 128 * inner) + d * 32 + inner * d
    latent_mat = d * 32 * 192 + d * 576 + 512 * 32 * 256 + inner * d
    assert (fl.kda_matrix_params(CFG), fl.latent_matrix_params(CFG)) == (kda_mat, latent_mat) == (39_460_864, 29_114_368)
    kda_vectors = 4 * 3 * inner + 32 + 2 * inner + 128
    expert = 3 * d * 1024
    first = kda_mat + kda_vectors + 2 * d + 3 * d * 9216
    sparse = d * 256 + 256 + 9 * expert + 2 * d
    assert fl.total_params(CFG) == first + 3 * (kda_mat + kda_vectors + sparse) + (latent_mat + 512 + sparse) + 2 * v * d + d
    assert fl.total_params(CFG) == 602_450_816
    # the shared expert whole, the routed ones at their expected rows: 8 x 8 / 256 = 0.25 of an expert a token
    active = 4 * kda_mat + latent_mat + 3 * d * 9216 + 4 * (d * 256 + 1.25 * expert) + d * v
    assert fl.active_params(CFG) == active
    pairs = 32 * (t * (t + 1) // 2)
    assert fl.attention_pair_heads(CFG, t) == pairs
    # the scan, a chunk of a head: two in-chunk matrices, the solve applied and the in-chunk output, two reads of the
    # state and its update
    a_chunk = 32 * (2 * 2 * 64 * 64 * 128 + 2 * 2 * 64 * 64 * 128 + 3 * 2 * 64 * 128 * 128)
    assert fl.kda_flops(CFG, 1, t, False) == 128 * a_chunk and fl.kda_flops(CFG, 2, t, True) == 4 * 128 * a_chunk
    assert fl.kda_flops(CFG, 1, 130, False) == 3 * a_chunk                            # a started chunk is a chunk
    assert fl.kda_flops(CFG, 1, t, False) / t == pytest.approx(5.24e6, rel=0.01)
    assert fl.train_flops_per_token(CFG, t) == 6 * active + 3 * (2 * 192 + 2 * 128) * pairs / t + 3 * 4 * 128 * a_chunk / t
    assert fl.train_flops_per_token(CFG, t) / 1e9 == pytest.approx(2.328, abs=0.002)
    # bytes of one pass of one mixer: q, k, v, o at 4,096 channels in bf16, the decay float32 a channel, beta a head,
    # the states float32
    states = 2 * 128 * 32 * 128 * 128 * 4
    assert fl.kda_bytes(CFG, 2, t, False) == 2 * t * (4 * inner * 2 + inner * 4 + 32 * 4) + states
    assert fl.kda_bytes(CFG, 2, t, True) == 2 * t * (7 * inner * 2 + 2 * inner * 4 + 2 * 32 * 4) + states
    least_f = fl.kda_least_seconds(CFG, 2, t, False, 197e12, 819e9)
    assert least_f == pytest.approx(fl.kda_bytes(CFG, 2, t, False) / 819e9)           # the bytes bind: 1.64 ms of 1.34 GB
    assert least_f > fl.kda_flops(CFG, 2, t, False) / 197e12 and least_f == pytest.approx(1.641e-3, rel=0.001)
    # attention at the widths the equations have: keys of 192, values of 128
    assert fl.kernel_flops(CFG, t, 2, False, False) == (2 * 192 + 2 * 128) * 2 * pairs
    assert fl.kernel_flops(CFG, t, 2, False, True) == (6 * 192 + 4 * 128) * 2 * pairs
    assert fl.kernel_flops(CFG, t, 2, True, True) == 0
    rows = 2 * 32 * t * 2
    assert fl.kernel_bytes(CFG, t, 2, False, False) == rows * (2 * 192 + 2 * 128)
    assert fl.kernel_bytes(CFG, t, 2, False, True) == rows * (4 * 192 + 3 * 128)
    # THIS convolution: one stream of 4,096 channels in and out a call, float32 taps and the zeros it takes as a bias
    assert fl.short_conv_bytes(CFG, 2, t, False) == 2 * 2 * t * inner * 2 + 5 * inner * 4
    assert fl.short_conv_bytes(CFG, 2, t, True) == 3 * 2 * t * inner * 2 + 2 * 5 * inner * 4


def test_the_program_holds_as_many_parameters_as_the_count_says():
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    count = lambda b: sum(int(x.size) for x in jax.tree_util.tree_leaves(jax.eval_shape(b.init, jax.random.PRNGKey(0))))  # noqa: E731
    assert count(get_model(CFG["registry_model"], **CFG["model_overrides"])) == fl.total_params(CFG) == \
        CFG["parameters"]["counted_by_the_program"]
    assert count(get_model(TINY["registry_model"], **TINY["model_overrides"])) == fl.total_params(TINY)
    full = dict(CFG, **CFG["published"])
    assert fl.total_params(full) == CFG["parameters"]["at_the_published_sizes"] == 49_122_763_648
    # at work on a token, the head's 0.38 B among them: the card's "A3B"
    assert round(fl.active_params(full) / 1e9, 1) == 3.1 and round((fl.active_params(full) - 2304 * 163840) / 1e9, 1) == 2.7


def test_family_flops_finds_a_configurations_arithmetic_by_its_family():
    assert family_flops.load(CFG) is fl and family_flops.load(TINY) is fl
    assert references.load(CFG["family"]) is ref
    assert family_flops.load(M.load_config("nemotron-3-nano-30b-a3b")).__name__ == "benchmark.flops_nemotron_h"


# -- the readers -----------------------------------------------------------------------

FULL_FWD = "%dvc_flash_fwd.7 = (bf16[2,32,8192,128]{3,2,1,0}) custom-call(%q)"
FULL_BWD = "%dvc_flash_bwd.2 = (bf16[2,32,8192,192]{3,2,1,0}) custom-call(%q)"
# the scan's loops as a v5e trace names them (my chip run, PR 52, call 9; the tuples cut after the arrays held by chunk):
# a forward pass that keeps nothing, the recomputed forward that keeps the chunks' states, the backward, and the
# loop over the two scanned layers, which spans its layers' loops and carries no head's state
_STATE = "s32[]{:T(128)}, f32[2,32,128,128]{3,2,1,0:T(8,128)S(1)}"
_OUT = "bf16[128,2,32,64,128]{4,3,2,1,0:T(8,128)(2,1)}"
_IN = "bf16[128,2,64,32,128]{4,2,3,1,0:T(8,128)(2,1)}"
_STATES = "f32[128,2,32,128,128]{4,3,2,1,0:T(8,128)}"
_MASKS = "bf16[768,64]{0,1:T(8,128)(2,1)S(1)}, pred[64,64]{0,1:T(8,128)(4,1)S(1)}"
_LOOP = "(%s) while((%s) %%tuple.1964), condition=%%wide.region_42, body=%%wide.region_41.sunk"
KDA_FIRST = "%while.503 = " + _LOOP % ((", ".join([_STATE, _OUT, *[_IN] * 4, "f32[128,2,64,32,128]{4,3,2,0,1:T(8,128)}", _MASKS]),) * 2)
KDA_FWD = "%while.505 = " + _LOOP % ((", ".join([_STATE, _OUT, _STATES, *[_IN] * 4, "f32[128,2,64,32,128]{4,2,3,1,0:T(8,128)}", _MASKS]),) * 2)
KDA_BWD = "%while.508 = " + _LOOP % ((", ".join([_STATE, *[_OUT] * 4, "f32[128,2,32,64,128]{4,3,2,1,0:T(8,128)}", _STATES, *[_IN] * 4,
                                                 "f32[128,2,64,32,128]{4,2,3,1,0:T(8,128)}", _IN, "s32[]{:T(128)}"]),) * 2)
LAYERS = "%while.502 = " + _LOOP % (("s32[]{:T(128)}, bf16[2,8192,2304]{2,1,0:T(8,128)(2,1)S(1)}, f32[2,256]{1,0:T(2,128)}, "
                                     "bf16[2,2,8192,2304]{3,2,1,0:T(8,128)(2,1)}, bf16[2,8,1024,2304]{3,2,1,0:T(8,128)(2,1)}",) * 2)
IN_A_LOOP = "%fusion.5803 = bf16[2,32,64,128]{3,2,1,0:T(8,128)(2,1)} fusion(%x)"
CONV_FWD = "%dvc_short_conv_fwd.5 = bf16[2,8192,4096]{2,1,0} custom-call(%u)"
CONV_BWD = "%dvc_short_conv_bwd.5 = (bf16[2,8192,4096]{2,1,0}, f32[4,4096]{1,0}) custom-call(%u)"
HEAD = "%select_add_fusion.2 = f32[8192,2304]{1,0:T(8,128)} fusion(%x)"
MS = 1_000_000


def make_trace(ops):
    return Trace.from_json({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_step(7)", 1_000_000, 700_000_000],
                ["jit_step(7)", 702_000_000, 700_000_000],
                ["jit_step(7)", 1_403_000_000, 1_200_000_000],   # ends after the window
            ]},
            {"name": "XLA Ops", "events": ops},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench:trace_begin", 0, 900_000], ["bench:trace_end", 2_500_000_000, 10],
        ]}]},
    ]})


def run_of(ops, spans=(), **more):
    return {"trace": make_trace(ops), "step_program": r"^jit_step\(", "spans": list(spans),
            "stats": {}, "config": CFG, "tokens_per_step": 16384, "chips": 1,
            "peak": flops.PEAKS["TPU v5 lite"], **more}


STEP_OPS = [
    [LAYERS, 5 * MS, 180 * MS],                                # spans the three loops that follow, and is none of them
    [KDA_FIRST, 10 * MS, 20 * MS], [IN_A_LOOP, 11 * MS, 1 * MS], [KDA_FWD, 40 * MS, 21 * MS], [KDA_BWD, 100 * MS, 60 * MS],
    [CONV_FWD, 200 * MS, 1 * MS], [CONV_BWD, 210 * MS, 2 * MS],
    [FULL_FWD, 250 * MS, 12 * MS], [FULL_BWD, 300 * MS, 30 * MS],
    [HEAD, 400 * MS, 50 * MS],
    [KDA_FWD, 1100 * MS, 19 * MS], [KDA_BWD, 1200 * MS, 61 * MS],
    [KDA_BWD, 2000 * MS, 99 * MS],                             # in the step the window cuts
]


def scan_span(t0, share):
    return {"trace": "loop", "name": "kda.scan", "t0": t0, "dur_s": 1e-5,
            "attrs": {"step": 10, "kda_carry_share": share, "kda_decay_min": -90.0, "kda_beta_mean": 0.5}}


def route_span(t0, bias=(0.01, -0.01)):
    attrs = {"step": 10, "moe_load_max": 640.0, "moe_load_mean": 512.0, "moe_dropped": 0.0, "moe_rows_moved": 4 * 12288.0,
             "moe_rows_held": 16000.0, "experts_held": 8, "router_site": "post_attention", "mixers_kda": 4,
             "mixers_latent_attention": 1, "moe_bias_max": bias[0], "moe_bias_min": bias[1],
             "moe_bias_moved": 900.0, "moe_chunks_extra": 0.0}
    return {"trace": "loop", "name": "moe.route", "t0": t0, "dur_s": 1e-5, "attrs": attrs}


def test_the_scan_readers_read_the_loops_and_the_spans():
    run = run_of(STEP_OPS, [scan_span(1.0, 0.90), scan_span(2.0, 0.94), scan_span(3.0, 0.91), route_span(1.5)])
    assert fl.kda_scan_shapes(CFG, 2, 8192) == ((2, 32, 128, 128), (128, 2))
    assert kda_trace.carried(KDA_FWD)[:4] == [(), (2, 32, 128, 128), (128, 2, 32, 64, 128), (128, 2, 32, 128, 128)]
    assert kda_trace.carried(IN_A_LOOP) == [] and (2, 32, 128, 128) not in kda_trace.carried(LAYERS)
    assert kda_trace.loop_events(run) == (2, [(False, 20 * MS), (False, 21 * MS), (True, 60 * MS),
                                              (False, 19 * MS), (True, 61 * MS)])
    took = 20 + 21 + 60 + 19 + 61
    assert readers.compute(M.layer_metric_path("kda.device_ms"), run) == pytest.approx(took / 2)
    least = lambda bwd: fl.kda_least_seconds(CFG, 2, 8192, bwd, 197e12, 819e9) * 1e3  # noqa: E731
    got = readers.compute(M.layer_metric_path("kda.roofline"), run)
    assert got == pytest.approx(100 * (3 * least(False) + 2 * least(True)) / took) and 0 < got < 100
    assert readers.compute(M.layer_metric_path("kda.carry_share"), run) == 0.91       # the median of the spans
    assert readers.compute(M.layer_metric_path("kda.carry_share"), dict(run, trace=None)) == 0.91
    # a program with no such loop gives nothing, and no error; nor does another step's batch (the state's shape differs)
    for name in ("kda.device_ms", "kda.roofline"):
        assert readers.compute(M.layer_metric_path(name), run_of([STEP_OPS[0], STEP_OPS[7], STEP_OPS[9]])) is None
        assert readers.compute(M.layer_metric_path(name), dict(run, trace=None)) is None
        assert readers.compute(M.layer_metric_path(name), dict(run, tokens_per_step=8192)) is None
    assert readers.compute(M.layer_metric_path("kda.carry_share"), run_of(STEP_OPS, [route_span(1.0)])) is None
    # the state-space scan's readers find nothing under this cell's names, and this cell's nothing under theirs
    assert readers.compute(M.layer_metric_path("ssm.device_ms"), run) is None
    assert readers.compute(M.layer_metric_path("ssm.carry_share"), run) is None
    # the parent's configuration of another family under the same trace: no shapes to look for, nothing
    other = dict(run, config=M.load_config("nemotron-3-nano-30b-a3b"))
    for name in ("kda.device_ms", "kda.roofline"):
        assert readers.compute(M.layer_metric_path(name), other) is None


def test_older_readers_read_this_cells_kernels_and_spans():
    run = run_of(STEP_OPS, [route_span(1.0, (0.001, -0.001)), route_span(2.0, (0.012, -0.009))])
    assert readers.compute(M.layer_metric_path("attention.device_ms"), run) == pytest.approx((12 + 30) / 2)
    ms = lambda bwd: (6 * 192 + 4 * 128 if bwd else 2 * 192 + 2 * 128) * 2 * 32 * (8192 * 8193 // 2) / 197e12 * 1e3  # noqa: E731
    got = readers.compute(M.layer_metric_path("attention.roofline"), run)
    assert got == pytest.approx(100 * (ms(False) + ms(True)) / 42) and 0 < got < 100
    assert readers.compute(M.layer_metric_path("conv.device_ms"), run) == pytest.approx(3 / 2)
    conv = readers.compute(M.layer_metric_path("conv.roofline"), run)
    want = (fl.short_conv_bytes(CFG, 2, 8192, False) + fl.short_conv_bytes(CFG, 2, 8192, True)) / 819e9 * 1e3
    assert conv == pytest.approx(100 * want / 3) and 0 < conv < 100
    assert readers.compute(M.layer_metric_path("step.mfu_model"), run) == pytest.approx(
        100 * 16384 * fl.train_flops_per_token(CFG, 8192) / (0.7 * 197e12))
    assert readers.compute(M.layer_metric_path("moe.dropped"), run) == 0.0
    assert readers.compute(M.layer_metric_path("moe.load_max_over_mean"), run) == pytest.approx(640 / 512)
    assert readers.compute(M.layer_metric_path("moe.rows_moved_over_held"), run) == pytest.approx(4 * 12288 / 16000)
    assert readers.compute(M.layer_metric_path("moe.bias_spread"), run) == pytest.approx(0.021)
    assert readers.compute(M.layer_metric_path("moe.chunks_extra"), run) == 0.0
    # the share's loops carry a vector over the S x k = 131,072 assignments (found by the alias key num_experts_per_tok)
    fwd = ("%while.31 = (s32[]{:T(128)}, bf16[16384,2304]{1,0:T(8,128)(2,1)}, s32[]{:T(128)}, s32[]{:T(128)}, "
           "s32[135168]{0:T(1024)}, s32[135168]{0:T(1024)}) while(%tuple.7), condition=%c, body=%b")
    bwd = ("%while.39 = (s32[]{:T(128)}, bf16[16384,2304]{1,0:T(8,128)(2,1)}, f32[131072]{0:T(1024)}, "
           "bf16[8,2304,1024]{2,1,0}) while(%tuple.9), condition=%c, body=%b")
    ops = [[fwd, 510 * MS, 5 * MS], [bwd, 600 * MS, 12 * MS], [fwd, 1300 * MS, 6 * MS]]
    assert readers.compute(M.layer_metric_path("moe.share_device_ms"), run_of(STEP_OPS + ops)) == pytest.approx(
        (5 + 12 + 6) / 2)


# -- the manifest ----------------------------------------------------------------------

APPENDED = ("tok_s_chip", "loop.step_gap_ms", "step.device_ms", "device.idle_share", "device.peak_hbm_GB",
            "moe.load_max_over_mean", "moe.dropped", "moe.rows_moved_over_held", "moe.share_device_ms",
            "attention.device_ms", "step.mfu_model", "attention.roofline", "moe.bias_spread", "moe.chunks_extra",
            "conv.device_ms", "conv.roofline")
LIFECYCLE = {"lifecycle.ready_s": "program_span", "lifecycle.net_s": "program_span",
             "lifecycle.init_s": "program_span", "lifecycle.step_build_s": "program_span",
             "lifecycle.first_step_s": "program_span", "lifecycle.trace_lower_s": "program_counter",
             "lifecycle.cache_load_s": "program_counter"}
KDA_METRICS = {"kda.device_ms": ("ms", "lower", "device_trace"), "kda.roofline": ("%", "higher", "device_trace"),
               "kda.carry_share": ("ratio", "higher", "program_span")}
SSM_METRICS = ("ssm.device_ms", "ssm.roofline", "ssm.carry_share")
LFM2_METRICS = ("conv.device_ms", "conv.roofline", "moe.bias_spread")
OLD_CELLS = ["medium-solo", "medium-round", "large-solo-4chip", "olmoe-solo", "laguna-solo-8k", SMALL, LFM2, GLM, NEMOTRON]
# readers that find nothing in this cell's runs: a windowed kernel, Laguna's or OLMoE's keys, ReLU experts, another scan
NOT_THIS_CELLS = ("attention.window_device_ms", "attention.full_device_ms", "attention.window_roofline",
                  "step.mfu", "step.mfu_active", "step.mfu_held", "moe.device_ms", "moe.gmm_roofline",
                  "moe.act_zero_share") + SSM_METRICS


def test_manifest_holds_the_new_configuration_cell_and_metrics():
    M.check()
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("kimi-linear-48b-a3b", "solo", 1)
    assert len(cell["why"]) <= 200 and "512 rows" in cell["why"] and "2 x 8,192" in cell["why"]
    assert "KDA" in cell["why"] and "more than their share" in cell["why"] and "latent attention" in cell["why"]
    per_layer = {m["name"]: m for m in M.metrics_for(CELL, "per_layer")}
    every = {m["name"]: m for kind in ("end_to_end", "per_layer") for m in M.doc[kind]}
    assert [m["name"] for m in M.doc["per_layer"][-3:]] == list(KDA_METRICS)
    for name, (unit, better, source) in KDA_METRICS.items():
        assert per_layer[name] == {"name": name, "unit": unit, "better": better, "source": source,
                                   "layer": "compiled step", "moves": "tok_s_chip", "workloads": [CELL]}
        path = M.layer_metric_path(name)
        assert path.endswith(".py") and "def compute(run)" in open(path).read()
    for name in APPENDED + tuple(LIFECYCLE):
        assert every[name]["workloads"][-1] == CELL and every[name]["workloads"].count(CELL) == 1, name
    for other in NOT_THIS_CELLS:
        assert other not in per_layer and CELL not in every[other]["workloads"], other
    assert {m["name"] for m in M.metrics_for(CELL, "end_to_end")} == {"tok_s_chip", "setup_s"}
    # one share of the whole step's peak, and it is the accepted one; the roofline shares of its three kernel families
    assert [n for n in per_layer if "mfu" in n] == ["step.mfu_model"]
    assert [n for n in per_layer if "roofline" in n] == ["attention.roofline", "conv.roofline", "kda.roofline"]
    assert M.doc["workloads"][-1] is cell and M.doc["configs"][-1]["name"] == "kimi-linear-48b-a3b"
    assert [w["name"] for w in M.doc["workloads"]] == OLD_CELLS + [CELL] and len(M.doc["configs"]) == 9
    # ten cells: a quarter of them, two, may take four chips; one does
    assert len(M.doc["workloads"]) == 10 and sum(w["chips"] == 4 for w in M.doc["workloads"]) == 1
    entry = M.config_entry("kimi-linear-48b-a3b")
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/kimi-linear-48b-a3b.json" and "602 M" in entry["why"]
    assert len(open(os.path.join(REPO_ROOT, "BENCHMARK.json")).read()) < 64 * 1024
    # a full check: 2 + 14 x cells runs of run_seconds + 60, 2 x 90 more a cell, 1,200 spare, within 43,200 s
    assert (2 + 14 * 10) * (M.run_seconds + 60) + 2 * 90 * 10 + 1200 <= 43200


def test_manifest_tail_as_the_nemotron_tests_asserted_it_three_metrics_and_a_cell_up():
    """What ``test_yardstick_nemotron_h.py`` asserted of the manifest's end and
    of its lists (and, through it, the GLM, LFM2, lifecycle and attention-metric
    tests), with this PR's metrics, cell and configuration after them
    (tests/conftest.py marks those cases)."""
    every = {m["name"]: m for kind in ("end_to_end", "per_layer") for m in M.doc[kind]}
    named = lambda names: [every[n] for n in names]  # noqa: E731
    # what test_manifest_holds_the_new_configuration_cell_and_metrics asserted there of Nemotron's own entries
    nemotron = M.cell(NEMOTRON)
    assert (nemotron["config"], nemotron["traffic"], nemotron["chips"]) == ("nemotron-3-nano-30b-a3b", "solo", 1)
    assert "768 rows" in nemotron["why"] and "2 x 8,192" in nemotron["why"] and "4 do not fit" in nemotron["why"]
    assert M.doc["per_layer"][-6:-3] == named(SSM_METRICS)
    units = {"ssm.device_ms": ("ms", "lower", "device_trace"), "ssm.roofline": ("%", "higher", "device_trace"),
             "ssm.carry_share": ("ratio", "higher", "program_span")}
    for name, (unit, better, source) in units.items():
        assert every[name] == {"name": name, "unit": unit, "better": better, "source": source,
                               "layer": "compiled step", "moves": "tok_s_chip", "workloads": [NEMOTRON]}
    assert M.doc["workloads"][-2] is nemotron and M.doc["configs"][-2]["name"] == "nemotron-3-nano-30b-a3b"
    entry = M.config_entry("nemotron-3-nano-30b-a3b")
    assert entry["file"] == "benchmark/configs/nemotron-3-nano-30b-a3b.json" and "528.1 M" in entry["why"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    # what its tail test asserted, three metrics and a cell further up
    chunks = every["moe.chunks_extra"]
    assert M.doc["per_layer"][-7] is chunks
    assert chunks == {"name": "moe.chunks_extra", "unit": "chunks", "better": "lower", "source": "program_span",
                      "layer": "compiled step", "moves": "tok_s_chip", "workloads": [LFM2, GLM, NEMOTRON, CELL]}
    assert M.doc["per_layer"][-10:-7] == named(LFM2_METRICS)
    assert every["conv.device_ms"]["workloads"] == every["conv.roofline"]["workloads"] == [LFM2, NEMOTRON, CELL]
    assert every["moe.bias_spread"]["workloads"] == [LFM2, GLM, NEMOTRON, CELL]
    assert M.doc["per_layer"][-17:-10] == named(LIFECYCLE)
    for name, source in LIFECYCLE.items():
        m = every[name]
        assert (m["unit"], m["better"], m["source"]) == ("s", "lower", source)
        assert m["layer"] == "entry / lifecycle" and m["moves"] == "setup_s" and m["workloads"] == OLD_CELLS + [CELL]
    assert [m["name"] for m in M.doc["per_layer"][:3]] == [
        "lifecycle.compile_s", "lifecycle.cache_misses", "lifecycle.backend_init_s"]
    assert all("workloads" not in m for m in M.doc["per_layer"][:3])
    assert every["setup_s"] == {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
                                "source": "host_clock"}
    for cell in OLD_CELLS + [CELL]:
        assert set(LIFECYCLE) <= {m["name"] for m in M.metrics_for(cell, "per_layer")}
    assert every["moe.act_zero_share"]["workloads"] == [SMALL, NEMOTRON]
    for name in ("step.mfu_model", "attention.roofline"):
        assert every[name]["workloads"] == [SMALL, LFM2, GLM, NEMOTRON, CELL]
    for name in ("moe.rows_moved_over_held", "moe.share_device_ms"):
        assert every[name]["workloads"] == ["laguna-solo-8k", SMALL, LFM2, GLM, NEMOTRON, CELL]
    for name in ("attention.window_device_ms", "attention.full_device_ms"):
        assert every[name]["workloads"] == ["laguna-solo-8k", SMALL]
    for name in ("moe.load_max_over_mean", "moe.dropped"):
        assert every[name]["workloads"] == ["olmoe-solo", "laguna-solo-8k", SMALL, LFM2, GLM, NEMOTRON, CELL]
    assert M.doc["workloads"][-3]["name"] == GLM and M.doc["configs"][-3]["name"] == "glm-4.7-flash"
    assert M.doc["workloads"][-4]["name"] == LFM2 and M.doc["configs"][-4]["name"] == "lfm2-24b-a2b"
    # attention.device_ms: the gpt2 cells and the four whose only kernels are the full-causal ones it reads
    assert every["attention.device_ms"]["workloads"] == ["medium-solo", "large-solo-4chip", LFM2, GLM, NEMOTRON, CELL]
    # bounds and run_seconds as they were
    assert M.run_seconds == 45 and every["tok_s_chip"]["bound"] == 0.01


def test_reference_check_limits_are_written_with_their_readings():
    rc = CFG["reference_check"]
    # since PR 69 at the cell's timed length: one sequence of the 8,192 tokens the step runs two of
    assert rc["sequences"] == 1 and rc["seq_len"] == 8192 == ref.sizes(CFG)["seq_len"] == CFG["assumed"]["seq_len"]["value"]
    assert 0 < rc["grad_rel_err"] <= 0.15 and 0 < rc["loss_atol"] <= 0.01
    for word in ("flipped", "e4m3", "bfloat16", "left out"):
        assert word in rc["why"], word
    # the readings at that length are in the text, the ones at 4,096 stay as what they were
    for word in ("1 x 8,192", "PR 69", "seven", "mean", "standard deviation", "4,096", "PR 52"):
        assert word in rc["why"], word
    assert "12 bytes a parameter" in rc["size_why"] and "8,192" in rc["size_why"] and "PR 69" in rc["size_why"]
    for variant in ref.VARIANTS:
        assert variant in rc["left_out"], variant
    assert "to be read" not in (rc["why"] + rc["left_out"] + rc["size_why"]).lower()
    assert CFG["loss_band"]["last_minus_first_max"] == 0.5
