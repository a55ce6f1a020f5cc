"""What PR 48 added to the yardstick: the Nemotron-H reference's own consistency
(its gradient against finite differences, its recurrence against a loop in
numpy, which group a head reads, its convolution), the FLOP, byte and parameter
counts against hand sums (the scan's at the tiny size too), the three new
readers (``ssm.device_ms``, ``ssm.roofline``, ``ssm.carry_share``) on a
hand-made trace and span list and the older readers on this cell's kernel
names, and the manifest with the new entries (and what
``test_yardstick_glm4_moe_lite.py`` asserted of the manifest's tail and lists,
three metrics, one cell and one configuration up: see tests/conftest.py)."""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmark import datagen, family_flops, flops, flops_nemotron_h as fl, readers, references, ssd_trace
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.references import nemotron_h as ref
from benchmark.trace import Trace

M = Manifest(REPO_ROOT)
CFG = M.load_config("nemotron-3-nano-30b-a3b")
TINY = M.load_config("tiny-rehearsal-nemotron")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "nemotron3-nano-solo-8k"
GLM, LFM2, SMALL = "glm47-flash-solo-8k", "lfm2-solo-8k", "smallthinker-solo-16k"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


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
        keep = ("['g']", "['a_log']", "['dt_bias']", "['d_skip']", "['conv_w']", "['conv_b']")
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
        eps = 1e-3 * float(jnp.linalg.norm(leaf))
        moved = lambda s: jax.tree_util.tree_unflatten(  # noqa: E731
            treedef, leaves[:i] + [leaf + s * eps * direction] + leaves[i + 1:])
        fd = (float(loss(moved(1.0))) - float(loss(moved(-1.0)))) / (2 * eps)
        want = float(jnp.sum(g * direction))
        assert fd == pytest.approx(want, rel=0.05, abs=6e-4), (i, fd, want)
        zeros += not np.any(np.asarray(g))
    assert zeros == 2  # the two runs' selection biases, and no other leaf


def test_reference_recurrence_is_the_rank_one_update_a_position_and_heads_read_their_group():
    """``_recurrence`` against the recurrence written out in numpy, float64; a
    head reads group ``h // (H / G)`` on the axes ``[G, H / G]`` and ``h % G`` on
    ``[H / G, G]``; the stretches and the reset change what they should."""
    import jax
    import jax.numpy as jnp

    z, t, g, r, p, n = 1, 10, 2, 3, 4, 5
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (z, t, g, r, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (z, t, g, r)))
    a = -jnp.exp(jax.random.normal(k[2], (g, r)))
    b, c = jax.random.normal(k[3], (z, t, g, n)), jax.random.normal(k[4], (z, t, g, n))

    def by_hand(x, dt, a, b_of, c_of, reset=0):
        x, dt, a = (np.asarray(v, np.float64) for v in (x, dt, a))
        out = np.zeros(x.shape)
        for i in range(x.shape[2]):
            for j in range(x.shape[3]):
                s = np.zeros((p, n))
                for pos in range(t):
                    if reset and pos % reset == 0:
                        s[:] = 0
                    s = np.exp(dt[0, pos, i, j] * a[i, j]) * s + dt[0, pos, i, j] * np.outer(x[0, pos, i, j], b_of(pos, i, j))
                    out[0, pos, i, j] = s @ c_of(pos, i, j)
        return out

    bn, cn = np.asarray(b, np.float64), np.asarray(c, np.float64)
    got = ref._recurrence(x, dt, a, b[:, :, :, None], c[:, :, :, None], 0)
    np.testing.assert_allclose(np.asarray(got), by_hand(x, dt, a, lambda s, i, j: bn[0, s, i], lambda s, i, j: cn[0, s, i]),
                               rtol=2e-5, atol=2e-5)
    reset = ref._recurrence(x, dt, a, b[:, :, :, None], c[:, :, :, None], 4)
    np.testing.assert_allclose(
        np.asarray(reset), by_hand(x, dt, a, lambda s, i, j: bn[0, s, i], lambda s, i, j: cn[0, s, i], reset=4),
        rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(reset) - np.asarray(got))[0, 5:].max() > 1e-3 and np.array_equal(
        np.asarray(reset)[0, :4], np.asarray(got)[0, :4])
    # the other axes order: [R, G], the group on the second
    x2, dtt, at = jnp.swapaxes(x, 2, 3), jnp.swapaxes(dt, 2, 3), a.T
    got2 = ref._recurrence(x2, dtt, at, b[:, :, None], c[:, :, None], 0)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(jnp.swapaxes(got, 2, 3)), rtol=2e-5, atol=2e-5)


def test_reference_convolution_is_causal_and_reads_the_last_tap_at_the_position():
    import jax

    u = jax.random.normal(jax.random.PRNGKey(0), (1, 7, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
    got = np.asarray(ref._causal_conv(u, w))
    un, wn = np.asarray(u), np.asarray(w)
    for t in range(7):
        want = sum(wn[j] * un[0, t - 3 + j] for j in range(4) if t - 3 + j >= 0)
        np.testing.assert_allclose(got[0, t], want, rtol=1e-5, atol=1e-6)


def test_reference_attention_reads_the_key_value_head_of_its_group():
    import jax
    import jax.numpy as jnp

    hp = dict(ref.hyper(TINY), heads=4, kv_heads=2, head_dim=4)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    d = 8
    p = {"wq": jax.random.normal(k[0], (d, 16)), "wk": jax.random.normal(k[1], (d, 8)),
         "wv": jax.random.normal(k[2], (d, 8)), "wo": jax.random.normal(k[3], (16, d))}
    n = jax.random.normal(k[4], (1, 6, d))
    got = ref._attention(p, n, hp, None)
    q = (n @ p["wq"]).reshape(1, 6, 4, 4)
    kk, vv = (n @ p["wk"]).reshape(1, 6, 2, 4), (n @ p["wv"]).reshape(1, 6, 2, 4)
    heads = []
    for h in range(4):
        s = jnp.einsum("btd,bsd->bts", q[:, :, h], kk[:, :, h // 2]) / 2.0
        s = jnp.where(jnp.tril(jnp.ones((6, 6), bool)), s, -jnp.inf)
        heads.append(jax.nn.softmax(s, -1) @ vv[:, :, h // 2])
    want = jnp.concatenate(heads, axis=-1) @ p["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert float(jnp.max(jnp.abs(ref._attention(p, n, hp, "rope_applied") - got))) > 1e-3


def test_reference_sizes_and_config_check():
    from distributedvolunteercomputing_tpu.models import get_model

    assert ref.sizes(CFG) == {"n_layer": 7, "d_model": 2688, "seq_len": 8192, "vocab": 16384}
    assert ref.hyper(CFG) == {"heads": 32, "kv_heads": 2, "head_dim": 128, "ssm_heads": 64, "ssm_head_dim": 64,
                              "groups": 8, "state": 128, "taps": 4, "chunk": 128, "blocks": "MEMEM*E", "theta": 10000.0,
                              "eps": 1e-5, "top_k": 6, "offset": 0, "scale": 2.5}
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    ref.check_config(bundle.config, CFG)
    for key, change in (("num_experts_per_tok", 2), ("n_routed_experts", 16), ("moe_intermediate_size", 512),
                        ("moe_shared_expert_intermediate_size", 1856), ("mamba_num_heads", 32), ("mamba_head_dim", 128),
                        ("n_groups", 4), ("ssm_state_size", 64), ("conv_kernel", 3), ("chunk_size", 256),
                        ("head_dim", 64), ("num_key_value_heads", 8), ("expert_offset", 8), ("norm_topk_prob", False),
                        ("n_group", 8), ("attention_bias", True), ("use_conv_bias", False), ("mlp_hidden_act", "silu"),
                        ("norm_eps", 1e-6), ("num_hidden_layers", 8), ("routed_scaling_factor", 1.0),
                        ("n_shared_experts", 2), ("tie_word_embeddings", True), ("time_step_max", 0.2),
                        ("hybrid_override_pattern", "MEMEME"), ("mamba_proj_bias", True)):
        with pytest.raises(ValueError, match=key):
            ref.check_config(bundle.config, dict(CFG, **{key: change}))
    with pytest.raises(ValueError, match="depth"):
        ref.check_config(get_model(CFG["registry_model"]).config, CFG)  # the published model, uncut
    with pytest.raises(ValueError, match="bias_gamma"):
        ref.check_config(dataclasses.replace(bundle.config, bias_gamma=0.01), CFG)
    assumed = CFG["assumed"]
    for key, value, word in (("rotary", {"value": "half"}, "rotary"), ("aux_coefficients", {"load_balancing": 0.01}, "auxiliary"),
                             ("ssm_init", {"value": "normal(0, 0.02)"}, "initialisation"),
                             ("d_inner", {"value": 5376}, "d_inner"), ("blocks_run", {"value": "MEMEMEM"}, "blocks")):
        with pytest.raises(ValueError, match=word):
            ref.check_config(bundle.config, dict(CFG, assumed={**assumed, key: value}))


def test_configuration_file_is_what_the_program_runs_with_its_cut_listed():
    """``test_yardstick_manifest.py``'s check of a configuration, for one whose
    ``reduced`` is not empty (tests/conftest.py marks that case), and every
    number of the catalog row under its own key."""
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    entry = M.config_entry("nemotron-3-nano-30b-a3b")
    assert CFG["source"] == entry["source"] and CFG["reduced"] == entry["reduced"] == REDUCED
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    assert CFG["published"] == {"num_hidden_layers": 52, "hybrid_override_pattern": PATTERN, "n_routed_experts": 128,
                                "vocab_size": 131072}
    assert [CFG[k] for k in CFG["reduced"]] == [7, 8, 16384] and CFG["hybrid_override_pattern"] == PATTERN
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    assert CFG["model_overrides"] == {"n_layers": 7, "experts_held": 8, "expert_offset": 0, "vocab": 16384}
    bundle = get_model(CFG["registry_model"], **CFG["model_overrides"])
    references.load(CFG["family"]).check_config(bundle.config, CFG)
    assert bundle.config.blocks == CFG["assumed"]["blocks_run"]["value"] == "MEMEM*E"
    assert set(CFG["volunteer"]) <= {f.name for f in dataclasses.fields(VolunteerConfig)}
    assert CFG["volunteer"]["batch_size"] == 2 and "17.21 GB" in CFG["assumed"]["batch_size"]
    assert CFG["volunteer"]["warmup_steps"] == CFG["assumed"]["lr_warmup"]["warmup_steps"] == 2000
    assert TINY["volunteer"]["warmup_steps"] == 2000
    assert "sixteen chips share each expert block" in CFG["deployment"] and "pipeline stages" in CFG["deployment"]
    assert "sixteen chips share each layer" in CFG["reduced_why"]["n_routed_experts"]
    assert "16.0 GB" in CFG["reduced_why"]["num_hidden_layers"]
    for key in ("blocks_run", "rotary", "d_inner", "stream_order", "ssm_init", "expert_bias", "aux_coefficients", "router",
                "seq_len", "batch_size", "optimizer", "lr_warmup", "dtypes", "initialisation", "unused_keys"):
        assert key in CFG["assumed"], key
    assert CFG["assumed"]["expert_bias"]["gamma"] == 0.001 and "2408.15664" in CFG["assumed"]["expert_bias"]["why"]
    assert CFG["assumed"]["ssm_init"]["value"] == ref.SSM_INIT
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key  # every width, head and state size under its own key


# -- FLOPs, bytes and parameters ---------------------------------------------------------


def test_flop_byte_and_parameter_counts_against_a_hand_sum():
    t, d, v = 8192, 2688, 16384
    m_mat = d * (4096 + 6144 + 64) + 4096 * d
    a_mat = 2 * d * 4096 + 2 * d * 256
    assert (fl.mamba_matrix_params(CFG), fl.attention_matrix_params(CFG)) == (m_mat, a_mat) == (38_707_200, 23_396_352)
    m_blk = m_mat + 5 * 6144 + 3 * 64 + 4096 + d
    a_blk = a_mat + d
    e_blk = d * 128 + 128 + 8 * 2 * d * 1856 + 2 * d * 3712 + d
    assert (fl.block_params(CFG, "M"), fl.block_params(CFG, "*"), fl.block_params(CFG, "E")) == (
        m_blk, a_blk, e_blk) == (38_744_896, 23_399_040, 100_125_440)
    assert fl.total_params(CFG) == 3 * m_blk + 3 * e_blk + a_blk + 2 * v * d + d == 528_093_120
    # the shared expert whole, the routed ones at their expected rows: 6 x 8 / 128 = 0.375 of an expert a token
    active = 3 * m_mat + a_mat + 3 * (d * 128 + 2 * d * 3712 + 0.375 * 2 * d * 1856) + d * v
    assert fl.active_params(CFG) == active
    pairs = 32 * (t * (t + 1) // 2)
    assert fl.attention_pair_heads(CFG, t) == pairs
    # the scan, a chunk of a sequence: C B^T a group, and a head the masked product, the state's read and its update
    a_chunk = 2 * 128 * 128 * 128 * 8 + 64 * (2 * 128 * 128 * 64 + 4 * 128 * 128 * 64)
    assert fl.ssd_flops(CFG, 1, t, False) == 64 * a_chunk and fl.ssd_flops(CFG, 2, t, True) == 4 * 64 * a_chunk
    assert fl.ssd_flops(CFG, 1, t, False) / t == pytest.approx(3.41e6, rel=0.01)      # the issue's 3.4 MFLOP a token
    assert fl.ssd_flops(CFG, 1, 130, False) == 2 * a_chunk                            # a started chunk is a chunk
    assert fl.train_flops_per_token(CFG, t) == 6 * active + 12 * 128 * pairs / t + 3 * 3 * 64 * a_chunk / t
    assert fl.train_flops_per_token(CFG, t) / 1e9 == pytest.approx(1.766, abs=0.002)
    # bytes of one pass of one block: x', y at 4,096 and B, C at 1,024 channels in bf16, dt float32, the states float32
    states = 2 * 64 * 64 * 64 * 128 * 4
    assert fl.ssd_bytes(CFG, 2, t, False) == 2 * t * (2 * 4096 * 2 + 2 * 1024 * 2 + 64 * 4) + states
    assert fl.ssd_bytes(CFG, 2, t, True) == 2 * t * (3 * 4096 * 2 + 4 * 1024 * 2 + 2 * 64 * 4) + states
    least_f = fl.ssd_least_seconds(CFG, 2, t, False, 197e12, 819e9)
    assert least_f == pytest.approx(fl.ssd_bytes(CFG, 2, t, False) / 819e9)           # the bytes bind: 0.74 ms of 608 MB
    assert least_f > fl.ssd_flops(CFG, 2, t, False) / 197e12 and least_f == pytest.approx(0.7426e-3, rel=0.001)
    # attention: groups of 16 over 2 key/value heads
    assert fl.kernel_flops(CFG, t, 2, False, False) == 4 * 128 * 2 * pairs
    assert fl.kernel_flops(CFG, t, 2, False, True) == 10 * 128 * 2 * pairs and fl.kernel_flops(CFG, t, 2, True, True) == 0
    rows = 2 * t * 128 * 2
    assert fl.kernel_bytes(CFG, t, 2, False, False) == rows * (2 * 32 + 2 * 2)
    assert fl.kernel_bytes(CFG, t, 2, False, True) == rows * (5 * 32 + 2 * 2)
    # THIS convolution: one stream of 6,144 channels in and out, float32 taps and bias
    assert fl.short_conv_bytes(CFG, 2, t, False) == 2 * 2 * t * 6144 * 2 + 5 * 6144 * 4
    assert fl.short_conv_bytes(CFG, 2, t, True) == 3 * 2 * t * 6144 * 2 + 2 * 5 * 6144 * 4


def test_the_scans_counts_at_the_tiny_size_by_hand():
    """4 heads of 8 in 2 groups, state 16, chunks of 16, 40 positions: three chunks a sequence."""
    q, n, g, h, p = 16, 16, 2, 4, 8
    a_chunk = 2 * q * q * n * g + h * (2 * q * q * p + 2 * q * n * p + 2 * q * p * n)
    assert a_chunk == 16384 + 4 * (4096 + 4096 + 4096) == 65536
    assert fl.ssd_flops(TINY, 2, 40, False) == 2 * 3 * a_chunk and fl.ssd_flops(TINY, 2, 40, True) == 2 * 2 * 3 * a_chunk
    states = 2 * 3 * h * p * n * 4
    assert fl.ssd_bytes(TINY, 2, 40, False) == 80 * (2 * 32 * 2 + 2 * 32 * 2 + 4 * 4) + states
    assert fl.total_params(TINY) == TINY_PARAMS
    assert fl.train_flops_per_token(TINY, 40) == pytest.approx(
        6 * fl.active_params(TINY) + 12 * 16 * 4 * (40 * 41 // 2) / 40 + 3 * 3 * 3 * a_chunk / 40)


# 64-wide: M 64 x (32 + 96 + 4) + 32 x 64 + 5 x 96 + 12 + 32 + 64 (d_inner 32, 96 convolved channels);
# * 2 x 64 x 64 + 2 x 64 x 32 + 64; E 64 x 16 + 16 + 4 x 2 x 64 x 32 + 2 x 64 x 48 + 64; embedding and head 512 x 64 each;
# final norm 64
TINY_PARAMS = 3 * (8448 + 2048 + 480 + 12 + 32 + 64) + (8192 + 4096 + 64) + 3 * (1024 + 16 + 16384 + 6144 + 64) + 2 * 32768 + 64


def test_the_program_holds_as_many_parameters_as_the_count_says():
    import jax

    from distributedvolunteercomputing_tpu.models import get_model

    count = lambda b: sum(int(x.size) for x in jax.tree_util.tree_leaves(jax.eval_shape(b.init, jax.random.PRNGKey(0))))  # noqa: E731
    assert count(get_model(CFG["registry_model"], **CFG["model_overrides"])) == fl.total_params(CFG) == \
        CFG["parameters"]["counted_by_the_program"]
    assert count(get_model(TINY["registry_model"], **TINY["model_overrides"])) == fl.total_params(TINY) == TINY_PARAMS
    full = dict(CFG, **CFG["published"])
    assert fl.total_params(full) == 31_577_940_288   # the card's 31.6B
    # at work on a token, the head's 0.35 B among them: the card's "A3.2B"
    assert round(fl.active_params(full) / 1e9, 1) == 3.2 and round((fl.active_params(full) - 2688 * 131072) / 1e9, 1) == 2.9
    by_block = [fl.block_params(CFG, kind) for kind in "MEMEM*E"]
    assert by_block == CFG["parameters"]["by_block"]


def test_family_flops_finds_a_configurations_arithmetic_by_its_family():
    assert family_flops.load(CFG) is fl and family_flops.load(TINY) is fl
    assert references.load(CFG["family"]) is ref
    assert family_flops.load(M.load_config("glm-4.7-flash")).__name__ == "benchmark.flops_glm4_moe_lite"


# -- the readers -----------------------------------------------------------------------

FULL_FWD = "%dvc_flash_fwd.7 = (bf16[2,32,8192,128]{3,2,1,0}) custom-call(%q)"
FULL_BWD = "%dvc_flash_bwd.2 = (bf16[2,32,8192,128]{3,2,1,0}) custom-call(%q)"
SSD_FWD = "%dvc_ssd_fwd.3 = (bf16[2,64,8192,64]{3,2,1,0}, f32[2,64,64,64,128]{4,3,2,1,0}) custom-call(%xd)"
SSD_BWD = "%dvc_ssd_bwd.1 = (bf16[2,64,8192,64]{3,2,1,0}, f32[2,64,64,128]{3,2,1,0}) custom-call(%xd)"
CONV_FWD = "%dvc_short_conv_fwd.5 = bf16[2,8192,6144]{2,1,0} custom-call(%u)"
CONV_BWD = "%dvc_short_conv_bwd.5 = (bf16[2,8192,6144]{2,1,0}, f32[4,6144]{1,0}) custom-call(%u)"
HEAD = "%select_add_fusion.2 = f32[8192,2688]{1,0:T(8,128)} fusion(%x)"
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
    [SSD_FWD, 10 * MS, 20 * MS], [SSD_FWD, 40 * MS, 21 * MS], [SSD_BWD, 100 * MS, 60 * MS],
    [CONV_FWD, 200 * MS, 1 * MS], [CONV_BWD, 210 * MS, 2 * MS],
    [FULL_FWD, 250 * MS, 12 * MS], [FULL_BWD, 300 * MS, 30 * MS],
    [HEAD, 400 * MS, 50 * MS],
    [SSD_FWD, 1100 * MS, 19 * MS], [SSD_BWD, 1200 * MS, 61 * MS],
    [SSD_BWD, 2000 * MS, 99 * MS],                             # in the step the window cuts
]


def scan_span(t0, share):
    return {"trace": "loop", "name": "ssm.scan", "t0": t0, "dur_s": 1e-5, "attrs": {"step": 10, "ssm_carry_share": share}}


def route_span(t0, bias=(0.01, -0.01)):
    attrs = {"step": 10, "moe_load_max": 800.0, "moe_load_mean": 768.0, "moe_dropped": 0.0, "moe_rows_moved": 3 * 7680.0,
             "moe_rows_held": 18000.0, "experts_held": 8, "router_site": "post_attention", "mixers_mamba": 3,
             "mixers_experts": 3, "mixers_attention": 1, "moe_bias_max": bias[0], "moe_bias_min": bias[1],
             "moe_bias_moved": 250.0, "moe_chunks_extra": 0.0, "moe_act_zero_share": 0.5}
    return {"trace": "loop", "name": "moe.route", "t0": t0, "dur_s": 1e-5, "attrs": attrs}


def test_the_scan_readers_read_the_kernels_and_the_spans():
    run = run_of(STEP_OPS, [scan_span(1.0, 0.30), scan_span(2.0, 0.34), scan_span(3.0, 0.31), route_span(1.5)])
    assert ssd_trace.kernel_events(run) == (2, [(False, 20 * MS), (False, 21 * MS), (True, 60 * MS),
                                                (False, 19 * MS), (True, 61 * MS)])
    took = 20 + 21 + 60 + 19 + 61
    assert readers.compute(M.layer_metric_path("ssm.device_ms"), run) == pytest.approx(took / 2)
    least = lambda bwd: fl.ssd_least_seconds(CFG, 2, 8192, bwd, 197e12, 819e9) * 1e3  # noqa: E731
    got = readers.compute(M.layer_metric_path("ssm.roofline"), run)
    assert got == pytest.approx(100 * (3 * least(False) + 2 * least(True)) / took) and 0 < got < 100
    assert readers.compute(M.layer_metric_path("ssm.carry_share"), run) == 0.31       # the median of the spans
    assert readers.compute(M.layer_metric_path("ssm.carry_share"), dict(run, trace=None)) == 0.31
    # a later kernel under the family's name is read with them; a program with none gives nothing, and no error
    later = [["%dvc_ssd_state_fwd.1 = f32[2] custom-call(%x)", 500 * MS, 4 * MS]]
    assert readers.compute(M.layer_metric_path("ssm.device_ms"), run_of(STEP_OPS + later)) == pytest.approx((took + 4) / 2)
    for name in ("ssm.device_ms", "ssm.roofline"):
        assert readers.compute(M.layer_metric_path(name), run_of([STEP_OPS[5], STEP_OPS[7]])) is None
        assert readers.compute(M.layer_metric_path(name), dict(run, trace=None)) is None
    assert readers.compute(M.layer_metric_path("ssm.carry_share"), run_of(STEP_OPS, [route_span(1.0)])) is None
    # the parent's configuration of another family under the same trace: no count, nothing
    other = dict(run, config=M.load_config("glm-4.7-flash"))
    assert readers.compute(M.layer_metric_path("ssm.roofline"), other) is None
    assert readers.compute(M.layer_metric_path("ssm.device_ms"), other) == pytest.approx(took / 2)


def test_older_readers_read_this_cells_kernels_and_spans():
    run = run_of(STEP_OPS, [route_span(1.0, (0.001, -0.001)), route_span(2.0, (0.012, -0.009))])
    assert readers.compute(M.layer_metric_path("attention.device_ms"), run) == pytest.approx((12 + 30) / 2)
    ms = lambda bwd: (10 if bwd else 4) * 128 * 2 * 32 * (8192 * 8193 // 2) / 197e12 * 1e3  # noqa: E731
    got = readers.compute(M.layer_metric_path("attention.roofline"), run)
    assert got == pytest.approx(100 * (ms(False) + ms(True)) / 42) and 0 < got < 100
    assert readers.compute(M.layer_metric_path("conv.device_ms"), run) == pytest.approx(3 / 2)
    conv = readers.compute(M.layer_metric_path("conv.roofline"), run)
    want = (fl.short_conv_bytes(CFG, 2, 8192, False) + fl.short_conv_bytes(CFG, 2, 8192, True)) / 819e9 * 1e3
    assert conv == pytest.approx(100 * want / 3) and 0 < conv < 100
    assert readers.compute(M.layer_metric_path("step.mfu_model"), run) == pytest.approx(
        100 * 16384 * fl.train_flops_per_token(CFG, 8192) / (0.7 * 197e12))
    assert readers.compute(M.layer_metric_path("moe.dropped"), run) == 0.0
    assert readers.compute(M.layer_metric_path("moe.load_max_over_mean"), run) == pytest.approx(800 / 768)
    assert readers.compute(M.layer_metric_path("moe.rows_moved_over_held"), run) == pytest.approx(3 * 7680 / 18000)
    assert readers.compute(M.layer_metric_path("moe.bias_spread"), run) == pytest.approx(0.021)
    assert readers.compute(M.layer_metric_path("moe.chunks_extra"), run) == 0.0
    assert readers.compute(M.layer_metric_path("moe.act_zero_share"), run) == 0.5
    # the share's loops carry a vector over the S x k = 98,304 assignments
    fwd = ("%while.31 = (s32[]{:T(128)}, bf16[16384,2688]{1,0:T(8,128)(2,1)}, s32[]{:T(128)}, s32[]{:T(128)}, "
           "s32[99840]{0:T(1024)}, s32[99840]{0:T(1024)}) while(%tuple.7), condition=%c, body=%b")
    bwd = ("%while.39 = (s32[]{:T(128)}, bf16[16384,2688]{1,0:T(8,128)(2,1)}, f32[98304]{0:T(1024)}, "
           "bf16[8,2688,1856]{2,1,0}) while(%tuple.9), condition=%c, body=%b")
    ops = [[fwd, 510 * MS, 5 * MS], [bwd, 600 * MS, 12 * MS], [fwd, 1300 * MS, 6 * MS]]
    assert readers.compute(M.layer_metric_path("moe.share_device_ms"), run_of(STEP_OPS + ops)) == pytest.approx(
        (5 + 12 + 6) / 2)


# -- the manifest ----------------------------------------------------------------------

APPENDED = ("tok_s_chip", "loop.step_gap_ms", "step.device_ms", "device.idle_share", "device.peak_hbm_GB",
            "moe.load_max_over_mean", "moe.dropped", "moe.rows_moved_over_held", "moe.share_device_ms",
            "attention.device_ms", "step.mfu_model", "attention.roofline", "moe.bias_spread", "moe.chunks_extra",
            "moe.act_zero_share", "conv.device_ms", "conv.roofline")
LIFECYCLE = {"lifecycle.ready_s": "program_span", "lifecycle.net_s": "program_span",
             "lifecycle.init_s": "program_span", "lifecycle.step_build_s": "program_span",
             "lifecycle.first_step_s": "program_span", "lifecycle.trace_lower_s": "program_counter",
             "lifecycle.cache_load_s": "program_counter"}
SSM_METRICS = {"ssm.device_ms": ("ms", "lower", "device_trace"), "ssm.roofline": ("%", "higher", "device_trace"),
               "ssm.carry_share": ("ratio", "higher", "program_span")}
LFM2_METRICS = ("conv.device_ms", "conv.roofline", "moe.bias_spread")
OLD_CELLS = ["medium-solo", "medium-round", "large-solo-4chip", "olmoe-solo", "laguna-solo-8k", SMALL, LFM2, GLM]
# readers that find nothing in this cell's runs: a windowed kernel, Laguna's or OLMoE's keys
NOT_THIS_CELLS = ("attention.window_device_ms", "attention.full_device_ms", "attention.window_roofline",
                  "step.mfu", "step.mfu_active", "step.mfu_held", "moe.device_ms", "moe.gmm_roofline")


def test_manifest_holds_the_new_configuration_cell_and_metrics():
    M.check()
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("nemotron-3-nano-30b-a3b", "solo", 1)
    assert len(cell["why"]) <= 200 and "768 rows" in cell["why"] and "2 x 8,192" in cell["why"]
    assert "state-space" in cell["why"] and "see more" in cell["why"] and "4 do not fit" in cell["why"]
    per_layer = {m["name"]: m for m in M.metrics_for(CELL, "per_layer")}
    every = {m["name"]: m for kind in ("end_to_end", "per_layer") for m in M.doc[kind]}
    assert [m["name"] for m in M.doc["per_layer"][-3:]] == list(SSM_METRICS)
    for name, (unit, better, source) in SSM_METRICS.items():
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
    assert [n for n in per_layer if "roofline" in n] == ["attention.roofline", "conv.roofline", "ssm.roofline"]
    assert M.doc["workloads"][-1] is cell and M.doc["configs"][-1]["name"] == "nemotron-3-nano-30b-a3b"
    assert [w["name"] for w in M.doc["workloads"]] == OLD_CELLS + [CELL] and len(M.doc["configs"]) == 8
    # nine cells: a quarter of them, two, may take four chips; one does
    assert len(M.doc["workloads"]) == 9 and sum(w["chips"] == 4 for w in M.doc["workloads"]) == 1
    entry = M.config_entry("nemotron-3-nano-30b-a3b")
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/nemotron-3-nano-30b-a3b.json" and "528.1 M" in entry["why"]
    assert len(open(os.path.join(REPO_ROOT, "BENCHMARK.json")).read()) < 64 * 1024


def test_manifest_tail_as_the_glm_tests_asserted_it_three_metrics_and_a_cell_up():
    """What ``test_yardstick_glm4_moe_lite.py`` asserted of the manifest's end
    and of its lists (and, through it, the LFM2, lifecycle and attention-metric
    tests), with this PR's metrics, cell and configuration after them
    (tests/conftest.py marks those cases)."""
    every = {m["name"]: m for kind in ("end_to_end", "per_layer") for m in M.doc[kind]}
    named = lambda names: [every[n] for n in names]  # noqa: E731
    chunks = every["moe.chunks_extra"]
    assert M.doc["per_layer"][-4] is chunks
    assert chunks == {"name": "moe.chunks_extra", "unit": "chunks", "better": "lower", "source": "program_span",
                      "layer": "compiled step", "moves": "tok_s_chip", "workloads": [LFM2, GLM, CELL]}
    assert M.doc["per_layer"][-7:-4] == named(LFM2_METRICS)
    assert every["conv.device_ms"]["workloads"] == every["conv.roofline"]["workloads"] == [LFM2, CELL]
    assert every["moe.bias_spread"]["workloads"] == [LFM2, GLM, CELL]
    assert M.doc["per_layer"][-14:-7] == named(LIFECYCLE)
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
    assert every["moe.act_zero_share"]["workloads"] == [SMALL, CELL]
    for name in ("step.mfu_model", "attention.roofline"):
        assert every[name]["workloads"] == [SMALL, LFM2, GLM, CELL]
    for name in ("moe.rows_moved_over_held", "moe.share_device_ms"):
        assert every[name]["workloads"] == ["laguna-solo-8k", SMALL, LFM2, GLM, CELL]
    for name in ("attention.window_device_ms", "attention.full_device_ms"):
        assert every[name]["workloads"] == ["laguna-solo-8k", SMALL]
    for name in ("moe.load_max_over_mean", "moe.dropped"):
        assert every[name]["workloads"] == ["olmoe-solo", "laguna-solo-8k", SMALL, LFM2, GLM, CELL]
    assert M.doc["workloads"][-2]["name"] == GLM and M.doc["configs"][-2]["name"] == "glm-4.7-flash"
    assert M.doc["workloads"][-3]["name"] == LFM2 and M.doc["configs"][-3]["name"] == "lfm2-24b-a2b"
    # attention.device_ms: the gpt2 cells and the three whose only kernels are the full-causal ones it reads
    assert every["attention.device_ms"]["workloads"] == ["medium-solo", "large-solo-4chip", LFM2, GLM, CELL]
    # what the GLM test asserted of its own cell and configuration, a place up
    glm = M.cell(GLM)
    assert (glm["config"], glm["traffic"], glm["chips"]) == ("glm-4.7-flash", "solo", 1)
    assert "1,024 rows" in glm["why"] and "2 x 8,192" in glm["why"]
    assert M.config_entry("glm-4.7-flash")["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size",
                                                          "num_nextn_predict_layers"]
    # bounds and run_seconds as they were
    assert M.run_seconds == 45 and every["tok_s_chip"]["bound"] == 0.01


def test_reference_check_limits_are_written_with_their_readings():
    rc = CFG["reference_check"]
    assert rc["sequences"] == 1 and rc["seq_len"] in (4096, 8192)
    assert 0 < rc["grad_rel_err"] <= 0.15 and 0 < rc["loss_atol"] <= 0.01
    for word in ("flipped", "e4m3", "bfloat16", "left out"):
        assert word in rc["why"], word
    for variant in ref.VARIANTS:
        assert variant in rc["left_out"], variant
    assert "TO BE SET" not in rc["why"] + rc["left_out"] + rc["size_why"]
    assert CFG["loss_band"]["last_minus_first_max"] == 0.5
