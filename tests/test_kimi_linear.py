"""models/kimi_linear.py (Kimi-Linear-48B-A3B-Instruct: Kimi Delta Attention
mixers through ops/kda.py, latent attention without positions whose value head
is narrower than its key head, a dense layer and expert layers under a stepped
selection bias) at a tiny size on the CPU: the program against the benchmark's
plain reference, the terms a mistaken implementation would compute, the
parameter counts, the share's sum, the value head's own width, the step's bias
rule and the loop's spans."""

import dataclasses
import functools
import json
import os
import subprocess
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import datagen
from benchmark.manifest import Manifest
from benchmark.references import kimi_linear as ref
from distributedvolunteercomputing_tpu.models import common, get_model, kimi_linear, moe
from distributedvolunteercomputing_tpu.ops import attention, pallas_attention
from tests import tiny_models

TINY = tiny_models.rehearsal("kimi")
CFG = Manifest().load_config("kimi-linear-48b-a3b")
HP = ref.hyper(TINY)


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def tiny(seed=3, scale=3.0, bias=0.05, **overrides):
    """The tiny bundle, its parameters moved off their initial values (every
    matrix times ``scale``, seeded selection biases and output-gate biases, the
    norms' scales spread out; the decay's leaves and the taps as initialised) by
    a hash of the leaf's name that no ``PYTHONHASHSEED`` moves, and two seeded
    sequences of 40. One tree a set of arguments: no test writes into it or
    donates it."""
    return _tiny(seed, scale, bias, tuple(sorted(overrides.items())))


@functools.lru_cache(maxsize=None)
def _tiny(seed, scale, bias, overrides):
    bundle = tiny_models.bundle("kimi", **dict(overrides))
    params = jax.jit(bundle.init)(jax.random.PRNGKey(seed))

    def moved(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.PRNGKey(11), zlib.crc32(name.encode()) % (2 ** 31))
        if name.endswith("['bias']"):
            return bias * jax.random.normal(key, x.shape)
        if name.endswith("['g']"):
            return x + 0.3 * jax.random.normal(key, x.shape)
        if name.endswith("['gate_b']"):
            return 0.5 * jax.random.normal(key, x.shape)
        if name.endswith(("['a_log']", "['dt_bias']", "['conv_w']")):
            return x
        return x * scale

    params = jax.tree_util.tree_map_with_path(moved, params) if scale else params
    return bundle, params, datagen.lm_arrays(5, 2, 40, TINY["vocab_size"])


# ``reference(grad=False, **static)``: the plain reference's loss (and gradient) as one program a set of static arguments
reference = tiny_models.reference_programs(ref, HP)


def both_sides(bundle, params, batch, variant=None, routes=None):
    tokens, targets = batch["tokens"], batch["targets"]
    program = tiny_models.programs(bundle).loss_and_grad(params, {"tokens": tokens, "targets": targets})
    return program, reference(grad=True, variant=variant)(params, tokens, targets, routes)


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def flat(g):
    return jnp.concatenate([x.ravel() for x in jax.tree_util.tree_leaves(g)])


# -- program against reference ---------------------------------------------------------


@pytest.mark.parametrize("state", ["initial", "moved", "moved_no_remat"])
def test_float32_program_equals_the_reference_on_loss_and_every_leaf(state):
    """The chunked triangular system, the shifted-sum convolutions, latent
    attention through one core call, the sort-and-group share path and the
    layers' scan against the recurrence token by token, a head at a time, and
    every held expert on every token. Both sides float32 at the highest
    precision, so what differs is summation order alone: the loss at 1e-4
    (of 6.3) and each gradient leaf at 2e-4 of its norm (the chunk's
    log2(C)-level products and the inverse by blocks sum in another order than
    40 dependent steps; the largest leaf read 3e-5)."""
    bundle, params, batch = tiny(scale=0.0 if state == "initial" else 3.0,
                                 **({"remat": False} if state.endswith("no_remat") else {}))
    ref.check_config(bundle.config, TINY)
    (lp, gp), (lr, gr) = both_sides(bundle, params, batch)
    assert abs(float(lp) - float(lr)) < 1e-4
    leaves = jax.tree_util.tree_leaves_with_path(gr)
    for (path, want), got in zip(leaves, jax.tree_util.tree_leaves(gp)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):
            assert not np.any(np.asarray(want)) and not np.any(np.asarray(got)), name   # the choice has no gradient
        else:
            assert rel(got, want) < 2e-4, (name, rel(got, want))
    assert len(leaves) == len(jax.tree_util.tree_leaves(params))


def test_routes_given_equal_routes_computed_and_another_share_is_noticed():
    bundle, params, batch = tiny()
    tokens, targets = batch["tokens"], batch["targets"]
    own, routes = reference(with_routes=True)(params, tokens, targets)
    assert routes.shape == (4, 80, 4) and float(reference()(params, tokens, targets, routes)) == float(own)
    _, _, program_routes = tiny_models.programs(bundle).loss_and_routes(params, batch)
    assert np.array_equal(np.asarray(program_routes), np.asarray(routes))
    other = jax.jit(lambda p, r: ref.loss(p, tokens, targets, dict(HP, offset=8), r))
    assert abs(float(other(params, routes)) - float(own)) > 1e-4


def test_a_token_changes_nothing_before_it():
    bundle, params, batch = tiny()
    tokens = jnp.asarray(batch["tokens"][:1])
    targets = jnp.asarray(batch["targets"][:1])

    @jax.jit
    def per_token_loss_inputs(tok):
        # the final hidden states, through the program's own trunk: the loss's head is position-wise
        cfg = dataclasses.replace(bundle.config, remat=False)
        x = params["wte"][tok]
        runs = [(lambda p, x, s, m=m, f=f: kimi_linear._layer(p, x, s, cfg, m, f), n, f == kimi_linear.SPARSE)
                for m, f, n in cfg.runs]
        zero = jnp.zeros(())
        stats = {**moe.zero_share_stats(chunks_extra=True), "kda_carried": zero, "kda_decay_min": zero, "kda_beta": zero}
        return moe.run_layers(runs, params["blocks"], x, stats, False, tok.size, cfg)[0]

    base, moved = per_token_loss_inputs(tokens), per_token_loss_inputs(tokens.at[0, 25].set((tokens[0, 25] + 1) % 512))
    assert float(jnp.max(jnp.abs(base[0, :25] - moved[0, :25]))) == 0.0
    assert float(jnp.max(jnp.abs(base[0, 25:] - moved[0, 25:]))) > 1e-3
    assert targets.shape == tokens.shape


# -- shapes, counts, runs -----------------------------------------------------------------


def test_published_sizes_parameter_counts_and_runs():
    """The program's tree, shapes only: the cut's 602,450,816 by layer, summed
    from the widths, and the published model's 49.1 B; the published order as
    runs of equal layers."""
    count = lambda b: sum(int(x.size) for x in jax.tree_util.tree_leaves(jax.eval_shape(b.init, jax.random.PRNGKey(0))))  # noqa: E731
    d, inner = 2304, 4096
    kda_mixer = (d * 3 * inner + 4 * 3 * inner + (d * 128 + 128 * inner) + 32 + inner + d * 32
                 + (d * 128 + 128 * inner + inner) + 128 + inner * d)
    latent = d * 32 * 192 + d * (512 + 64) + 512 + 512 * 32 * 256 + inner * d
    dense, expert, norms = 3 * d * 9216, 3 * d * 1024, 2 * d
    routed = lambda held: d * 256 + 256 + (1 + held) * expert  # noqa: E731
    assert (kda_mixer, latent) == (39_518_368, 29_114_880)
    cut = get_model(CFG["registry_model"], **CFG["model_overrides"])
    first, kda_layer, latent_layer = kda_mixer + dense + norms, kda_mixer + routed(8) + norms, latent + routed(8) + norms
    assert CFG["parameters"]["by_layer"] == [first, kda_layer, kda_layer, latent_layer, kda_layer]
    want = first + 3 * kda_layer + latent_layer + 2 * 20480 * d + d
    assert count(cut) == want == 602_450_816 == CFG["parameters"]["counted_by_the_program"]
    assert cut.config.runs == (("kda", "dense", 1), ("kda", "sparse", 2), ("latent_attention", "sparse", 1),
                               ("kda", "sparse", 1))
    shapes = jax.eval_shape(cut.init, jax.random.PRNGKey(0))
    run = shapes["blocks"][1]
    assert run["bias"].shape == (2, 256) and run["experts"]["w_up"].shape == (2, 8, 2304, 1024)
    assert run["mixer"]["w_qkv"].shape == (2, 2304, 12288) and run["mixer"]["conv_w"].shape == (2, 4, 12288)
    assert run["mixer"]["dt_bias"].shape == (2, 4096) and run["mixer"]["a_log"].shape == (2, 32)
    assert shapes["blocks"][2]["mixer"]["wkv_b"].shape == (1, 512, 32 * 256)
    assert shapes["blocks"][2]["mixer"]["wq"].shape == (1, 2304, 32 * 192)
    full = get_model(CFG["registry_model"])
    sparse_kda, sparse_latent = kda_mixer + routed(256) + norms, latent + routed(256) + norms
    assert count(full) == first + 19 * sparse_kda + 7 * sparse_latent + 2 * 163840 * d + d == 49_122_763_648
    assert count(full) == CFG["parameters"]["at_the_published_sizes"]
    cfg = full.config
    assert cfg.layer_types.count("kda") == 20 and cfg.layer_types.count("latent_attention") == 7
    assert cfg.layer_types[3] == cfg.layer_types[26] == "latent_attention" and cfg.head_dim == 192 and cfg.kda_dim == 4096
    assert sum(n for _, _, n in cfg.runs) == 27 and cfg.runs[0] == ("kda", "dense", 1) and cfg.runs[1] == ("kda", "sparse", 2)
    assert dataclasses.replace(cfg, kda_layers="1,2,3", full_attn_layers="4", n_layers=4).layer_types[-1] == "latent_attention"
    for bad in ({"n_layers": 28}, {"kda_layers": (1, 2)}, {"experts_held": 300}, {"chunk": 48}, {"dense_layers": 30}):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, **bad)
    assert kimi_linear.KimiLinearConfig.tiny().runs == get_model(TINY["registry_model"], **TINY["model_overrides"]).config.runs


def test_the_decay_leaves_are_initialised_as_the_family_does():
    bundle, params, batch = tiny(scale=0.0)
    m = params["blocks"][1]["mixer"]
    a = np.exp(np.asarray(m["a_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and not np.array_equal(a[0], a[1])        # from the seed, a layer
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    taps = np.asarray(m["conv_w"])
    assert np.abs(taps).max() <= 0.5 and np.abs(taps).mean() > 0.2
    assert not np.any(np.asarray(m["gate_b"])) and not np.any(np.asarray(params["blocks"][1]["bias"]))
    again = bundle.init(jax.random.PRNGKey(3))
    assert np.array_equal(np.asarray(again["blocks"][1]["mixer"]["dt_bias"]), np.asarray(m["dt_bias"]))
    # the counters: with this initialisation every head carries a state across a chunk of 16
    _, metrics, _ = tiny_models.programs(bundle).loss_and_routes(params, batch)
    assert 0.0 < float(metrics["kda_carry_share"]) <= 1.0 and float(metrics["kda_decay_min"]) < 0.0
    assert 0.3 < float(metrics["kda_beta_mean"]) < 0.7


# -- the share ------------------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once():
    """The guide's share test: the four shares' routed parts (16 experts, four
    held each, same router, biases and routes) plus the mixer and the shared
    expert once are the layer with every expert held."""
    bundle, _, _ = tiny(scale=0.0, experts_held=16, expert_offset=0)
    cfg = dataclasses.replace(bundle.config, remat=False)
    params = bundle.init(jax.random.PRNGKey(5))
    p = jax.tree_util.tree_map(lambda a: a[0] * 3.0, params["blocks"][2])      # the latent layer with experts
    p["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 40, 64))
    zero = jnp.zeros(())
    stats = {**moe.zero_share_stats(chunks_extra=True), "kda_carried": zero, "kda_decay_min": zero, "kda_beta": zero}
    layer = lambda p, c: jax.jit(  # noqa: E731 — a program a share: the offset is the trace's
        lambda p: kimi_linear._layer(p, x, stats, c, kimi_linear.LATENT, kimi_linear.SPARSE))(p)
    whole, _, (routes, chosen) = layer(p, cfg)
    mixed = x + kimi_linear._latent(p["mixer"], common.rmsnorm(p["ln_mixer"], x, cfg.rms_eps), cfg)
    h = common.rmsnorm(p["ln_ffn"], mixed, cfg.rms_eps).reshape(80, 64)
    shared = common.swiglu(p["shared"], h).reshape(2, 40, 64)
    routed = jnp.zeros_like(x)
    for offset in range(0, 16, 4):
        part_cfg = dataclasses.replace(cfg, experts_held=4, expert_offset=offset)
        part = {**p, "experts": jax.tree_util.tree_map(lambda a: a[offset:offset + 4], p["experts"])}
        out, part_stats, (part_routes, _) = layer(part, part_cfg)
        assert np.array_equal(np.asarray(part_routes), np.asarray(routes)) and float(part_stats["dropped"]) == 0.0
        routed = routed + (out - mixed - shared)
    np.testing.assert_allclose(np.asarray(mixed + shared + routed), np.asarray(whole), rtol=1e-4, atol=1e-5)
    assert float(jnp.sum(chosen)) == 80 * 4


# -- the value head's own width --------------------------------------------------------------


def plain_softmax(q, k, v):
    t = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["a_key_a_head", "grouped"])
def test_a_value_head_of_128_under_keys_of_192_is_the_plain_softmax(kv_heads):
    """The flash kernels (interpreted) with v, o, dO and dv at the value head's
    width and the scores at the key's: values and the three gradients against a
    plain causal softmax at 1/sqrt(key head); blocks of 16 in a sequence of 40
    (padded rows, the diagonal, skipped blocks)."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(k[0], (2, 4, 40, 24))
    key, v = jax.random.normal(k[1], (2, kv_heads, 40, 24)), jax.random.normal(k[2], (2, kv_heads, 40, 16))
    probe = jax.random.normal(k[3], (2, 4, 40, 16))
    spread = lambda a: jnp.repeat(a, 4 // kv_heads, axis=1)  # noqa: E731
    kernel = lambda q, k_, v_: pallas_attention.flash_attention(q, k_, v_, True, 16, 16, True)  # noqa: E731
    want_fn = lambda q, k_, v_: plain_softmax(q, spread(k_), spread(v_))  # noqa: E731
    got = kernel(q, key, v)
    assert got.shape == (2, 4, 40, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_fn(q, key, v)), rtol=1e-5, atol=1e-5)
    g_got = jax.grad(lambda *a: jnp.sum(kernel(*a) * probe), (0, 1, 2))(q, key, v)
    g_want = jax.grad(lambda *a: jnp.sum(want_fn(*a) * probe), (0, 1, 2))(q, key, v)
    for name, a, b in zip("qkv", g_got, g_want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5, err_msg=name)
    # the XLA core takes the same widths, grouped or not
    attention.set_attention_impl("xla")
    try:
        np.testing.assert_allclose(np.asarray(attention.attention_core(q, key, v, causal=True)),
                                   np.asarray(want_fn(q, key, v)), rtol=1e-5, atol=1e-5)
    finally:
        attention.set_attention_impl("auto")
    assert pallas_attention.kept_bytes(q, key, None, v) < pallas_attention.kept_bytes(
        jnp.zeros((2, 4, 40, 256)), jnp.zeros((2, kv_heads, 40, 256)))


def test_a_value_head_as_wide_as_the_key_head_traces_to_the_program_it_was():
    """``v`` as wide as ``k``: the kernel calls' block shapes, scratch and
    results are what they are without the value width's branch (the parent's
    text; every older configuration's ``step_hlo_hash`` rests on it), and a
    narrower ``v`` changes exactly the v, o, dO and dv blocks."""
    q = jnp.zeros((1, 2, 64, 32), jnp.bfloat16)

    def calls(v_dim):
        v = jnp.zeros((1, 2, 64, v_dim), jnp.bfloat16)
        fn = lambda q, k, v: jax.vjp(lambda *a: pallas_attention.flash_attention(*a, True, 32, 32, True), q, k, v)[1](  # noqa: E731
            jnp.zeros((1, 2, 64, v_dim), jnp.bfloat16))
        jaxpr = jax.make_jaxpr(fn)(q, q, v)
        found = []

        def walk(j):
            for e in j.eqns:
                if e.primitive.name == "pallas_call":
                    gm = e.params["grid_mapping"]
                    found.append(([tuple(b.block_shape) for b in gm.block_mappings],
                                  [tuple(o.shape) for o in e.params["out_avals"]]))
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        return found

    same, narrow = calls(32), calls(16)
    assert len(same) == len(narrow) == 2
    widths = lambda found: [[getattr(s[-1], "block_size", s[-1]) for s in blocks] for blocks, _ in found]  # noqa: E731
    # forward: q, k, v -> o, lse;  backward: q, k, v, do, lse, delta -> dq, dk, dv
    assert widths(same)[0][:4] == [32, 32, 32, 32] and widths(narrow)[0][:4] == [32, 32, 16, 16]
    assert widths(same)[1][:4] == [32, 32, 32, 32] and widths(narrow)[1][:4] == [32, 32, 16, 16]
    assert widths(same)[1][-3:] == [32, 32, 32] and widths(narrow)[1][-3:] == [32, 32, 16]
    assert [o[-1] for o in same[1][1]] == [32, 32, 32] and [o[-1] for o in narrow[1][1]] == [32, 32, 16]
    assert 192 in attention._AUTO_FLASH_HEAD_DIMS and pallas_attention.choose_blocks(8192, 8192, 192, jnp.bfloat16) == (1024, 1024)


def test_latent_attention_builds_keys_of_192_from_one_shared_part_and_rotates_nothing():
    bundle, params, _ = tiny()
    cfg = bundle.config
    p = jax.tree_util.tree_map(lambda a: a[0], params["blocks"][2]["mixer"])
    n = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64))
    q, k, v = kimi_linear.latent_qkv(p, n, cfg)
    assert q.shape == k.shape == (2, 4, 40, 16) and v.shape == (2, 4, 40, 8)
    shared = np.asarray(k[..., cfg.qk_nope_dim:])
    assert np.array_equal(shared[:, 0], shared[:, 3])                                 # ONE part a token, every head's
    np.testing.assert_allclose(shared[:, 0], np.asarray(n @ p["wkv_a"])[..., cfg.kv_lora_rank:], rtol=1e-6)
    # no position enters: the same token at another position has the same q, k and v
    swapped = n[:, ::-1]
    q2, k2, v2 = kimi_linear.latent_qkv(p, swapped, cfg)
    np.testing.assert_allclose(np.asarray(q2[:, :, ::-1]), np.asarray(q), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(k2[:, :, ::-1]), np.asarray(k), rtol=1e-6, atol=1e-6)


# -- the step and the loop ------------------------------------------------------------------


def test_a_step_moves_each_bias_by_gamma_by_the_counts_and_the_decay_leaves_by_the_optimizer():
    from distributedvolunteercomputing_tpu.training.steps import TrainState

    bundle, params, batch = tiny(scale=0.0)
    tx, step = tiny_models.train_step(bundle, "adam", lr=1e-3)
    state = TrainState.create(params, tx, jax.random.PRNGKey(1))
    before = jax.tree_util.tree_map(lambda a: np.array(a, copy=True), params)
    _, metrics, _ = tiny_models.programs(bundle).loss_and_routes(params, batch)
    counts = np.asarray(metrics[moe.COUNTS])
    assert counts.shape == (4, 16) and counts.sum() == 4 * 80 * 4
    state, out = step(state, batch)
    assert moe.COUNTS not in out and {"kda_carry_share", "kda_decay_min", "kda_beta_mean", "moe_chunks_extra"} <= set(out)
    want = 0.001 * np.sign(counts.mean(-1, keepdims=True) - counts)
    got = np.concatenate([np.asarray(p["bias"]) for p in state.params["blocks"] if "bias" in p])
    np.testing.assert_allclose(got, want, atol=1e-9)
    m0, m1 = before["blocks"][0]["mixer"], state.params["blocks"][0]["mixer"]
    for leaf in ("a_log", "dt_bias", "conv_w", "w_qkv", "w_fa", "w_fb", "w_beta", "w_ga", "w_gb", "gate_b", "wo"):
        assert np.any(np.asarray(m1[leaf]) != m0[leaf]), leaf


def _outside_the_loops(jaxpr):
    """Every equation of a jaxpr and of what it calls, but for the bodies of its ``scan`` and ``while`` loops."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name not in ("scan", "while"):
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _outside_the_loops(sub)


def _passes_around_the_scan(fn, *args, shape, nc):
    """What the gradient's program holds outside its loops of the two kinds of
    pass PR 55 took out: (arrays of a stream's size laid out by chunk, leading
    ``[nc, Z]``; ``transpose`` / ``cumsum`` / ``reduce_sum`` equations that read a
    float32 stream by head ``[B, T, H, hd]`` or ``[B, nc, C, H, hd]``; the loops met)."""
    z, size = shape[0], int(np.prod(shape))
    by_chunk, by_head, loops = set(), [], 0
    for eqn in _outside_the_loops(jax.make_jaxpr(fn)(*args).jaxpr):
        loops += eqn.primitive.name in ("scan", "while")
        avals = [v.aval for v in (*eqn.invars, *eqn.outvars) if hasattr(v.aval, "shape")]
        by_chunk |= {a.shape for a in avals if a.shape[:2] == (nc, z) and int(np.prod(a.shape)) == size}
        if eqn.primitive.name in ("transpose", "cumsum", "reduce_sum") and any(
                a.shape[-2:] == shape[-2:] and int(np.prod(a.shape)) == size and a.dtype == jnp.float32
                for a in (v.aval for v in eqn.invars if hasattr(v.aval, "shape"))):
            by_head.append(eqn.primitive.name)
    return by_chunk, sorted(by_head), loops


def test_the_mixers_gradient_holds_no_stream_by_chunk_and_no_float32_pass_by_head_around_the_scan():
    """What the trace of PR 52's step showed as 173 ms around the scan's loops
    (`PERF.md` section 5) were passes over whole streams: float32 by head
    ``[B, T, H, hd]`` (the l2 norms' sums, the decay's running sum, their
    cotangents' transposes) and copies by chunk ``[nc, Z, C, H, hd]``. A jaxpr at
    a tiny size cannot time them; it can say they are gone from the gradient of
    ``_kda`` outside the loops' bodies. What stays by head outside is the head
    norm of ``o`` with its gate (one ``reduce_sum`` forward, two backward:
    ``common.rmsnorm``, left where it was). The same walk over PR 52's
    formulation (``experiments/kda_sweep.parent_kda``) finds both kinds, so it
    can see what it looks for."""
    from experiments.kda_sweep import parent_kda

    cfg = kimi_linear.KimiLinearConfig(**{**kimi_linear.TINY, "chunk": 8})   # states [nc, Z, H, 16, 16]: twice a stream
    b, t, h, hd = 2, 32, cfg.kda_heads, cfg.kda_head_dim
    p = kimi_linear._kda_init(jax.random.split(jax.random.PRNGKey(0), 10), cfg)
    n = jax.random.normal(jax.random.PRNGKey(1), (b, t, cfg.d_model))
    loss = lambda p, n: jnp.sum(kimi_linear._kda(p, n, cfg)[0] ** 2)
    by_chunk, by_head, loops = _passes_around_the_scan(jax.grad(loss, argnums=(0, 1)), p, n, shape=(b, t, h, hd), nc=t // 8)
    assert loops == 2 and not by_chunk, by_chunk
    assert by_head == ["reduce_sum"] * 3, by_head                           # the head norm of o alone

    args = tuple(jax.random.normal(jax.random.PRNGKey(i), (b, t, h, hd)) for i in range(4)) + (jnp.full((b, t, h), 0.5),)
    parent = jax.grad(lambda *a: jnp.sum(parent_kda(*a, 8) ** 2), argnums=(0, 1, 2, 3, 4))
    by_chunk, by_head, loops = _passes_around_the_scan(parent, *args, shape=(b, t, h, hd), nc=t // 8)
    assert loops == 2 and (t // 8, b, 8, h, hd) in by_chunk and (t // 8, b, h, 8, hd) in by_chunk
    assert "cumsum" in by_head and by_head.count("reduce_sum") >= 4 and "transpose" in by_head, by_head


def test_stacked_runs_take_the_sharding_rules(eight_devices):
    from jax.sharding import Mesh

    from distributedvolunteercomputing_tpu.parallel import sharding
    from distributedvolunteercomputing_tpu.parallel.mesh import AXES

    bundle, _, _ = tiny(scale=0.0)
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    mesh = Mesh(np.asarray(eight_devices[:4]).reshape(1, 1, 1, 4, 1), AXES)   # ep = 4
    specs = jax.tree_util.tree_map(lambda s: s.spec, sharding.make_param_shardings(mesh, shapes))
    run = specs["blocks"][1]
    assert "ep" in tuple(run["experts"]["w_up"])
    assert "ep" not in tuple(run["mixer"]["w_qkv"]) and "ep" not in tuple(run["bias"])


def test_the_scan_under_a_mesh_takes_its_chunks_out_of_each_devices_own_shard(eight_devices):
    """Sequences over ``dp`` and heads over ``tp``, as a step's mesh lays the
    mixer's streams: a chunk is a slice along T, which no device divides, so the
    scan's loops and their backward hold no collective, and ``o`` and every
    gradient are what one device computes."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributedvolunteercomputing_tpu.ops import kda

    z, t, h, d, chunk = 2, 40, 4, 8, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v, probe = (jax.random.normal(keys[i], (z, t, h * d)) for i in range(4))
    g = -jax.random.uniform(keys[4], (z, t, h * d), jnp.float32, 1e-3, 0.3)
    beta = jax.random.uniform(keys[5], (z, t, h), jnp.float32, 0.05, 0.95)

    def fwd_bwd(q, k, v, g, beta, probe):
        by_head = lambda a: a.reshape(z, t, h, d)
        o, vjp = jax.vjp(lambda q, k, v, g, beta: kda.kda(by_head(q), by_head(k), by_head(v), by_head(g), beta, chunk)[0]
                         .reshape(z, t, h * d), q, k, v, g, beta)
        return (o, *vjp(probe))

    mesh = Mesh(np.asarray(eight_devices[:4]).reshape(2, 2), ("dp", "tp"))
    laid = NamedSharding(mesh, P("dp", None, "tp"))
    args = (q, k, v, g, beta, probe)
    compiled = jax.jit(fwd_bwd, in_shardings=(laid,) * 6, out_shardings=(laid,) * 6).lower(*args).compile()
    text = compiled.as_text()
    assert not [w for w in ("all-gather", "all-reduce", "all-to-all", "collective-permute", "reduce-scatter") if w in text]
    for got, want in zip(compiled(*(jax.device_put(a, laid) for a in args)), jax.jit(fwd_bwd)(*args)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_train_loop_records_the_scan_span_beside_the_route_span():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    bundle = get_model(TINY["registry_model"], **TINY["model_overrides"])
    trainer = Trainer(bundle, batch_size=2, lr=1e-3, optimizer="adam", tracer=tel.tracer)
    trainer.run(steps=11, log_every=5)
    routes = [s for s in tel.tracer.spans() if s["name"] == "moe.route"]
    scans = [s for s in tel.tracer.spans() if s["name"] == "kda.scan"]
    assert len(routes) == len(scans) >= 2 and not [s for s in tel.tracer.spans() if s["name"] == "ssm.scan"]
    for s in scans:
        # no ``kda_form``: ops/kda.py has one form, and a span says which only where a module has several
        assert set(s["attrs"]) == {"step", "kda_carry_share", "kda_decay_min", "kda_beta_mean"}
        assert 0.0 < s["attrs"]["kda_carry_share"] <= 1.0 and s["attrs"]["kda_decay_min"] < 0.0
    attrs = routes[-1]["attrs"]
    assert attrs["mixers_kda"] == 4 and attrs["mixers_latent_attention"] == 1
    assert attrs["experts_held"] == 4 and "moe_chunks_extra" in attrs and "kda_carry_share" not in attrs
    assert set(bundle.spans["kda.scan"].keys) == {"kda_carry_share", "kda_decay_min", "kda_beta_mean"}
    assert not bundle.spans["kda.scan"].noted


def test_run_volunteer_knows_the_model_and_no_training_code_names_it():
    from distributedvolunteercomputing_tpu.models import registry
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    assert "kimi_linear_48b_a3b" in registry.list_models()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = subprocess.run(["git", "grep", "-n", "-i", "kimi", "--", "distributedvolunteercomputing_tpu/training",
                            "distributedvolunteercomputing_tpu/swarm"], cwd=root, capture_output=True, text=True)
    assert found.stdout == ""
    assert not [f.name for f in dataclasses.fields(VolunteerConfig) if "kda" in f.name or "kimi" in f.name]


def test_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``tiny-rehearsal-kimi:solo`` through ``benchmark/run.py``: volunteer,
    probe, window, a traced run, the reference check, the result line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--rehearse",
         "tiny-rehearsal-kimi:solo", "--seed", "4200000052", "--seconds", "3", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert '"reference": true' in out.stderr and '"no_compile_in_window": true' in out.stderr
