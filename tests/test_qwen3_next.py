"""models/qwen3_next.py (Qwen3-Next-80B-A3B-Instruct: Gated DeltaNet mixers
through ops/gdn.py, output-gated grouped-query attention at a partial rotary, a
softmax router over a share of the experts beside a gated shared expert) at a
tiny size on the CPU: the program against the benchmark's plain reference, the
terms a mistaken implementation would compute, the parameter counts, the shares'
sum, the decay leaves' initialisation and the loop's spans."""

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
from benchmark.references import qwen3_next as ref
from distributedvolunteercomputing_tpu.models import common, get_model, moe, qwen3_next
from tests import tiny_models

TINY = tiny_models.rehearsal("qwen3-next")
CFG = Manifest().load_config("qwen3-next-80b-a3b")
HP = ref.hyper(TINY)


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def tiny(seed=3, scale=3.0, **overrides):
    """The tiny bundle, its parameters moved off their initial values (every
    matrix times ``scale``; the zero-centred norms' weights, the output norm's
    scale, ``A_log``, ``dt_bias`` and the shared expert's gate drawn; the taps as
    initialised) by a hash of the leaf's name that no ``PYTHONHASHSEED`` moves,
    and two seeded sequences of 40. One tree a set of arguments: no test writes
    into it or donates it."""
    return _tiny(seed, scale, tuple(sorted(overrides.items())))


@functools.lru_cache(maxsize=None)
def _tiny(seed, scale, overrides):
    bundle = tiny_models.bundle("qwen3-next", **dict(overrides))
    params = jax.jit(bundle.init)(jax.random.PRNGKey(seed))

    def moved(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.PRNGKey(11), zlib.crc32(name.encode()) % (2 ** 31))
        if name.endswith(("['w']", "['g']", "['a_log']", "['dt_bias']")):
            return x + 0.3 * jax.random.normal(key, x.shape)
        if name.endswith("['shared_gate']"):
            return 0.5 * jax.random.normal(key, x.shape)
        if name.endswith("['conv_w']"):
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
    """The chunked scalar-decay system at 16 key heads' worth of in-chunk
    products, the one-stream convolution, gated attention through one merged
    call with its partial rotary, the sort-and-group share path and the period's
    scans against the recurrence token by token over q and k repeated to the
    value heads, a head at a time, and every held expert on every token. Both
    sides float32 at the highest precision, so what differs is summation order
    alone: the loss at 1e-4 (of 6.3) and each gradient leaf at 2e-4 of its norm."""
    bundle, params, batch = tiny(scale=0.0 if state == "initial" else 3.0,
                                 **({"remat": False} if state.endswith("no_remat") else {}))
    ref.check_config(bundle.config, TINY)
    (lp, gp), (lr, gr) = both_sides(bundle, params, batch)
    assert abs(float(lp) - float(lr)) < 1e-4
    leaves = jax.tree_util.tree_leaves_with_path(gr)
    for (path, want), got in zip(leaves, jax.tree_util.tree_leaves(gp)):
        assert rel(got, want) < 2e-4, (jax.tree_util.keystr(path), rel(got, want))
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
    hidden = jax.jit(lambda p, t: qwen3_next._trunk(p, t, bundle.config)[0])
    tokens = jnp.asarray(batch["tokens"])
    base = hidden(params, tokens)
    later = hidden(params, tokens.at[:, 25].set((tokens[:, 25] + 1) % TINY["vocab_size"]))
    np.testing.assert_array_equal(np.asarray(later[:, :25]), np.asarray(base[:, :25]))
    assert float(jnp.max(jnp.abs(later[:, 25:] - base[:, 25:]))) > 1e-3


# -- shapes, counts -----------------------------------------------------------------------


def test_published_sizes_parameter_counts_and_periods():
    """The program's tree, shapes only: the cut's 424,340,544 by layer, summed
    from the widths as ISSUE 67 sums them, and the published model's 79.67 B."""
    count = lambda b: common.count_params(jax.eval_shape(b.init, jax.random.PRNGKey(0)))  # noqa: E731
    d = 2048
    delta = d * 12288 + d * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * d
    attention = d * 8192 + 2 * d * 512 + 4096 * d + 2 * 256
    around = d * 512 + 3 * d * 512 + d + 2 * d
    expert = 3 * d * 512
    assert (delta, attention, around, expert) == (33_718_464, 27_263_488, 4_200_448, 3_145_728)
    cut = get_model(CFG["registry_model"], **CFG["model_overrides"])
    delta_layer, attention_layer = delta + around + 16 * expert, attention + around + 16 * expert
    assert CFG["parameters"]["by_layer"] == [delta_layer] * 3 + [attention_layer] == [88_250_560] * 3 + [81_795_584]
    want = 3 * delta_layer + attention_layer + 2 * 18992 * d + d
    assert count(cut) == want == 424_340_544 == CFG["parameters"]["counted_by_the_program"]
    shapes = jax.eval_shape(cut.init, jax.random.PRNGKey(0))
    linear, full = shapes["blocks"]["linear"], shapes["blocks"]["full"]
    assert linear["mixer"]["w_qkvz"].shape == (1, 3, d, 12288) and linear["mixer"]["conv_w"].shape == (1, 3, 4, 8192)
    assert linear["mixer"]["a_log"].shape == linear["mixer"]["dt_bias"].shape == (1, 3, 32)
    assert linear["experts"]["w_up"].shape == (1, 3, 16, d, 512) and linear["shared_gate"].shape == (1, 3, d, 1)
    assert full["mixer"]["wq"].shape == (1, d, 16 * 512) and full["mixer"]["wk"].shape == (1, d, 512)
    assert full["mixer"]["q_norm"]["w"].shape == (1, 256) and full["router"].shape == (1, d, 512)
    whole = get_model(CFG["registry_model"])
    assert count(whole) == 36 * (delta + around + 512 * expert) + 12 * (attention + around + 512 * expert) \
        + 2 * 151936 * d + d == 79_674_391_296 == CFG["parameters"]["at_the_published_sizes"]
    cfg = whole.config
    assert cfg.layer_types.count("linear") == 36 and cfg.layer_types.count("full") == 12 and cfg.periods == 12
    assert cfg.layer_types[:4] == ("linear", "linear", "linear", "full") and cfg.rotary_dim == 64
    assert (cfg.key_dim, cfg.value_dim, cfg.conv_dim) == (2048, 4096, 8192)
    for bad in ({"n_layers": 6}, {"experts_held": 600}, {"chunk": 48}, {"value_heads": 24}, {"n_kv_heads": 3}):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, **bad)
    assert qwen3_next.Qwen3NextConfig.tiny() == get_model(TINY["registry_model"], **TINY["model_overrides"]).config
    assert whole.stepped is None     # no selection bias exists: the step moves nothing of its own


def test_the_decay_leaves_are_initialised_as_the_family_does():
    bundle, params, batch = tiny(scale=0.0)
    m = params["blocks"]["linear"]["mixer"]
    a = np.exp(np.asarray(m["a_log"]))
    assert a.shape == (1, 3, 4) and a.min() > 0.0 and a.max() <= 16.0 and not np.array_equal(a[0, 0], a[0, 1])
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    taps = np.asarray(m["conv_w"])
    assert np.abs(taps).max() <= 0.5 and np.abs(taps).mean() > 0.2
    for name in ("ln_mixer", "ln_ffn"):    # the zero-centred norms start at 0, the output norm at 1
        assert not np.any(np.asarray(params["blocks"]["linear"][name]["w"]))
    assert not np.any(np.asarray(params["blocks"]["full"]["mixer"]["q_norm"]["w"])) and not np.any(np.asarray(params["ln_f"]["w"]))
    assert np.all(np.asarray(m["o_norm"]["g"]) == 1.0)
    again = bundle.init(jax.random.PRNGKey(3))
    assert np.array_equal(np.asarray(again["blocks"]["linear"]["mixer"]["dt_bias"]), np.asarray(m["dt_bias"]))
    # the counters: with this initialisation heads carry a state across a chunk of 16
    _, metrics, _ = tiny_models.programs(bundle).loss_and_routes(params, batch)
    assert 0.0 < float(metrics["gdn_carry_share"]) <= 1.0 and float(metrics["gdn_decay_min"]) < 0.0
    assert 0.3 < float(metrics["gdn_beta_mean"]) < 0.7
    assert 0.4 < float(metrics["attn_gate_mean"]) < 0.6 and 0.4 < float(metrics["shared_gate_mean"]) < 0.6


def test_the_zero_centred_norm_is_the_shared_rmsnorm_handed_one_plus_w():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (8,))
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1.0 + w)
    np.testing.assert_allclose(np.asarray(qwen3_next.norm({"w": w}, x, 1e-6)), np.asarray(want), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(qwen3_next.norm(qwen3_next.norm_init(8), x, 1e-6)),
                                  np.asarray(common.rmsnorm(common.rmsnorm_init(8), x, 1e-6)))


# -- the share ------------------------------------------------------------------------------


def test_the_thirty_two_shares_add_up_to_the_uncut_layer_with_the_rest_counted_once():
    """The guide's share test at the published split: thirty-two shares' routed
    parts (64 experts, two held each, same router and routes) plus the mixer and
    the gated shared expert once are the layer with every expert held."""
    bundle, _, _ = tiny(scale=0.0, n_experts=64, experts_held=64, expert_offset=0)
    cfg = dataclasses.replace(bundle.config, remat=False)
    params = bundle.init(jax.random.PRNGKey(5))
    p = jax.tree_util.tree_map(lambda a: a[0] * 3.0, params["blocks"]["full"])
    p["shared_gate"] = 0.5 * jax.random.normal(jax.random.PRNGKey(6), p["shared_gate"].shape)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 40, 64))
    zero = jnp.zeros(())
    stats = {**moe.zero_share_stats(balanced=64, chunks_extra=True), "gdn_carried": zero, "gdn_decay_min": zero,
             "gdn_beta": zero, "attn_gate": zero, "shared_gate": zero}
    layer = jax.jit(lambda p, c: qwen3_next._layer(p, x, stats, c, qwen3_next.FULL), static_argnums=1)
    whole, _, routes = layer(p, cfg)
    mixed = x + qwen3_next._attention(p["mixer"], qwen3_next.norm(p["ln_mixer"], x, cfg.rms_eps), cfg)[0]
    h = qwen3_next.norm(p["ln_ffn"], mixed, cfg.rms_eps).reshape(80, 64)
    shared = (common.swiglu(p["shared"], h) * jax.nn.sigmoid(h @ p["shared_gate"])).reshape(2, 40, 64)
    routed = jnp.zeros_like(x)
    for offset in range(0, 64, 2):
        part_cfg = dataclasses.replace(cfg, experts_held=2, expert_offset=offset)
        part = {**p, "experts": jax.tree_util.tree_map(lambda a: a[offset:offset + 2], p["experts"])}
        out, part_stats, part_routes = layer(part, part_cfg)
        assert np.array_equal(np.asarray(part_routes), np.asarray(routes)) and float(part_stats["dropped"]) == 0.0
        routed = routed + (out - mixed - shared)
    np.testing.assert_allclose(np.asarray(mixed + shared + routed), np.asarray(whole), rtol=1e-4, atol=2e-5)


# -- the loop ---------------------------------------------------------------------------------


def test_train_loop_records_the_scan_and_gate_spans_beside_the_route_span():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    bundle = get_model(TINY["registry_model"], **TINY["model_overrides"])
    trainer = Trainer(bundle, batch_size=2, lr=1e-3, optimizer="adam", tracer=tel.tracer)
    trainer.run(steps=11, log_every=5)
    spans = {name: [s for s in tel.tracer.spans() if s["name"] == name] for name in bundle.spans}
    assert set(spans) == {"moe.route", "gdn.scan", "attention.gate"}
    assert len(spans["moe.route"]) == len(spans["gdn.scan"]) == len(spans["attention.gate"]) >= 2
    for s in spans["gdn.scan"]:
        assert set(s["attrs"]) == {"step", "gdn_carry_share", "gdn_decay_min", "gdn_beta_mean", "gdn_form"}
        assert s["attrs"]["gdn_form"] == "scalar_decay_xla" and 0.0 < s["attrs"]["gdn_carry_share"] <= 1.0
    assert set(spans["attention.gate"][-1]["attrs"]) == {"step", "attn_gate_mean"}
    attrs = spans["moe.route"][-1]["attrs"]
    assert attrs["mixers_linear"] == 3 and attrs["mixers_full"] == 1 and attrs["experts_held"] == 4
    assert 0.0 < attrs["shared_gate_mean"] < 1.0 and "moe_chunks_extra" in attrs and "gdn_carry_share" not in attrs
    assert not [s for s in tel.tracer.spans() if s["name"] in ("kda.scan", "ssm.scan")]


def test_run_volunteer_knows_the_model_and_no_training_code_names_it():
    from distributedvolunteercomputing_tpu.models import registry
    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    assert "qwen3_next_80b_a3b" in registry.list_models()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = subprocess.run(["git", "grep", "-n", "-i", "-e", "qwen", "-e", "gdn", "--",
                            "distributedvolunteercomputing_tpu/training", "distributedvolunteercomputing_tpu/swarm",
                            "distributedvolunteercomputing_tpu/parallel"], cwd=root, capture_output=True, text=True)
    assert found.stdout == ""
    assert not [f.name for f in dataclasses.fields(VolunteerConfig) if "gdn" in f.name or "qwen" in f.name]


def test_rehearsal_cell_runs_end_to_end_on_the_cpu():
    """``tiny-rehearsal-qwen3-next:solo`` through ``benchmark/run.py``: volunteer,
    probe, window, a traced run, the reference check, the result line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--rehearse",
         "tiny-rehearsal-qwen3-next:solo", "--seed", "4200000067", "--seconds", "3", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert '"reference": true' in out.stderr and '"no_compile_in_window": true' in out.stderr
