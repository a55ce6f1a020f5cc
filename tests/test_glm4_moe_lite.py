"""GLM-4.7-Flash on the normal path (models/glm4_moe_lite.py), at a tiny size on
the CPU: the program against the plain reference
(benchmark/references/glm4_moe_lite.py) on the loss and every leaf's gradient,
with the dense layer, a held share, a run of two stacked expert layers, the
shared expert and an untied head; the reference against each term computed as a
mistake would (the ones the initial parameters hide at scaled weights);
causality; the one rotary key really shared by the heads; the selection bias in
the choice and not in the weights, at this family's 1e-20 and 1.8; the step's
rule moving the bias by exactly ``gamma`` and nothing else touching it; the
shares adding up to the uncut layer; the list-of-runs tree through
count_params, the sharding rules, a checkpoint and the train loop's span."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from benchmark.references import glm4_moe_lite as ref
from distributedvolunteercomputing_tpu.models import common, get_model, glm4_moe_lite as glm, moe
from distributedvolunteercomputing_tpu.ops import attention, moe_dispatch
from distributedvolunteercomputing_tpu.training import steps
from distributedvolunteercomputing_tpu.utils import traced
from tests import tiny_models
from tests.test_tpu_compile import _CORE, _noted, as_on_the_chip  # noqa: F401 — the fixture is used by name

TINY = tiny_models.rehearsal("glm")
OVERRIDES = TINY["model_overrides"]
MODEL = "glm4_7_flash"
HP = ref.hyper(TINY)
# the variants that the initial parameters hide (a bias of zero; norms, scale and rotary turn under a
# softmax that is nearly flat): held here at scaled weights and a seeded bias
INIT_BLIND = ("bias_in_weights", "no_latent_norm", "no_query_norm", "scale_by_192", "rope_on_whole_head",
              "rope_key_per_head")


def seeded(scale: float = 3.0, bias: float = 0.0, **overrides):
    """The tiny model (a dense layer and a run of two expert layers; 4 heads of
    12 + 4 over a latent of 16 and a query rank of 24; experts 4..7 of 16 held,
    top-4, a shared expert) with matrices scaled up so that every term matters,
    a seeded selection bias of that size where asked, norm scales seeded about
    1, and two seeded sequences."""
    bundle = tiny_models.bundle("glm", **overrides)
    params = jax.jit(bundle.init)(jax.random.PRNGKey(3))

    def scaled(path, x):
        name = jax.tree_util.keystr(path)
        if moe.is_bias(path):
            return bias * jax.random.normal(jax.random.PRNGKey(11), x.shape)
        if "_a_norm" in name:  # the two inner norms' learned scales: not all 1, so that they matter
            return x + 0.3 * jax.random.normal(jax.random.PRNGKey(13), x.shape)
        return x if "ln_" in name else x * scale

    params = jax.tree_util.tree_map_with_path(scaled, params)
    rng = np.random.default_rng(0)
    t, v = bundle.config.max_len, bundle.config.vocab
    batch = {"tokens": jnp.asarray(rng.integers(0, v, (2, t))),
             "targets": jnp.asarray(rng.integers(0, v, (2, t)))}
    return bundle, params, batch


def leaf_errors(got, want):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
            for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                    jax.tree_util.tree_leaves(want))}


def whole_error(got, want):
    num = sum(float(jnp.sum((a.astype(jnp.float32) - b) ** 2)) for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))
    den = sum(float(jnp.sum(b ** 2)) for b in jax.tree_util.tree_leaves(want))
    return math.sqrt(num / den)


def one_layer(params, run, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], params["blocks"][run])


def as_published(x, cfg, rotary=True):
    """The model's merged ``x`` [B, T, H * D] by head, [B, H, T, D], with a
    head's columns put back where the published layout has them: from
    ``[rope, "half" pairs | nope]`` to ``[nope | rope, neighbouring pairs]``
    (``rotary`` False: a value, whose columns never moved)."""
    x = attention.split_heads(x, cfg.n_heads)
    if not rotary:
        return x
    rot = cfg.qk_rope_dim
    halves = x[..., :rot].reshape(*x.shape[:-1], 2, rot // 2)
    return jnp.concatenate([x[..., rot:], jnp.swapaxes(halves, -1, -2).reshape(*x.shape[:-1], rot)], axis=-1)


def one_chip_mesh():
    """A step mesh of one device: what ``parallel/train_step`` announces on one chip (without one, a process
    of several devices keeps the kernels off the path)."""
    from jax.sharding import Mesh

    from distributedvolunteercomputing_tpu.parallel.mesh import AXES

    return Mesh(np.asarray(jax.devices()[:1]).reshape((1,) * len(AXES)), AXES)


def qkv_by_head(p, n, cfg):
    """The model's q, k, v as the published layout has them by head."""
    q, k, v = glm.qkv(p, n, cfg)
    return as_published(q, cfg), as_published(k, cfg), as_published(v, cfg, rotary=False)


def qkv_as_it_was(p, n, cfg):
    """``models/glm4_moe_lite.qkv`` until PR 64, kept here as the yardstick of
    the merged one: q, k, v built at ``[B, H, T, head_dim]``, a head's columns
    ``[nope | rope]``, neighbouring pairs turned, the one rotary key broadcast
    to the heads and concatenated."""
    dtype = n.dtype
    b, t, _ = n.shape
    h, nope, rot = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    cq = common.rmsnorm(p["q_a_norm"], n @ p["wq_a"].astype(dtype), cfg.rms_eps)
    q = (cq @ p["wq_b"].astype(dtype)).reshape(b, t, h, nope + rot).transpose(0, 2, 1, 3)
    ckr = n @ p["wkv_a"].astype(dtype)
    c, k_rope = ckr[..., :cfg.kv_lora_rank], ckr[..., cfg.kv_lora_rank:]
    kv = common.rmsnorm(p["kv_a_norm"], c, cfg.rms_eps) @ p["wkv_b"].astype(dtype)
    kv = kv.reshape(b, t, h, nope + cfg.v_head_dim).transpose(0, 2, 1, 3)
    q = jnp.concatenate(
        [q[..., :nope], attention.rope(q[..., nope:], base=cfg.rope_theta, layout="interleaved")], axis=-1)
    k_rope = attention.rope(k_rope[:, None], base=cfg.rope_theta, layout="interleaved")
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (b, h, t, rot))], axis=-1)
    return q, k, kv[..., nope:]


# ``reference(grad=False, **static)``: the plain reference's loss (and gradient) as one program a set of static arguments
reference = tiny_models.reference_programs(ref, HP)


# the reference's own loss-and-gradient as the harness calls it, under one jit
REFERENCE = jax.jit(ref.make_loss_and_grad(TINY))


# -- the program against the reference ---------------------------------------------


@pytest.mark.parametrize("remat", [True, False])
def test_float32_program_equals_the_reference_on_loss_and_every_leaf(remat):
    bundle, params, batch = seeded(bias=0.05, remat=remat)
    cfg = bundle.config
    ref.check_config(dataclasses.replace(cfg, remat=True), TINY)
    # what the comparison covers: the dense layer, a stacked run, a share, every head its own non-rotary key
    assert cfg.runs == (("dense", 1), ("sparse", 2)) and cfg.layer_types == ("latent_attention",) * 3
    assert (cfg.experts_held, cfg.expert_offset, cfg.n_experts, cfg.n_shared) == (4, 4, 16, 1)
    lp, gp = tiny_models.programs(bundle).loss_and_grad(params, batch)
    lr, gr = REFERENCE(params, batch["tokens"], batch["targets"])
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    errors = leaf_errors(gp, gr)
    # 12 leaves of the dense layer, 17 of the expert run, 3 outside (embedding, final norm, head)
    assert len(errors) == 32
    biases = [k for k in errors if k.endswith("['bias']")]
    assert len(biases) == 1 and not np.any(np.asarray(gp["blocks"][1]["bias"])) and not np.any(
        np.asarray(gr["blocks"][1]["bias"]))
    assert max(v for k, v in errors.items() if k not in biases) < 1e-4, max(errors.items(), key=lambda kv: kv[1])


def test_bf16_program_equals_the_reference_given_its_routes(monkeypatch):
    """bf16 compute against float32, with the program's own routes handed to
    the reference so that arithmetic is compared and not near-ties. At the
    initialisation's scale, as ``tests/test_lfm2.py`` has it: this size reads a
    whole-gradient relative error of about 0.01 and a loss apart by under 0.001;
    the limits are about three times that."""
    monkeypatch.setattr(common, "compute_dtype", lambda: jnp.bfloat16)
    bundle, params, batch = seeded(scale=1.0)
    (got_l, routes), got_g = tiny_models.programs(bundle).loss_routes_and_grad(params, batch)
    assert routes.shape == (2, 128, 4)
    want_l, want_g = REFERENCE(params, batch["tokens"], batch["targets"], routes)
    assert abs(float(got_l) - float(want_l)) < 0.002
    assert whole_error(got_g, want_g) < 0.03
    errors = leaf_errors(got_g, want_g)
    assert max(v for k, v in errors.items() if not k.endswith("['bias']")) < 0.05


def test_the_configuration_builds_what_the_file_says_and_refuses_what_no_core_takes():
    cfg = glm.Glm4MoeLiteConfig()
    assert (cfg.n_layers, cfg.head_dim, cfg.v_head_dim, cfg.n_heads) == (47, 256, 256, 20)
    assert cfg.runs == (("dense", 1), ("sparse", 46)) and cfg.layer_types.count("latent_attention") == 47
    shapes = jax.eval_shape(get_model(MODEL, **OVERRIDES).init, jax.random.PRNGKey(0))
    dense, sparse = shapes["blocks"]
    assert dense["wq_a"].shape == (1, 64, 24) and dense["wq_b"].shape == (1, 24, 4 * 16)
    assert dense["wkv_a"].shape == (1, 64, 16 + 4)       # the latent and ONE rotary key of 4
    assert dense["wkv_b"].shape == (1, 16, 4 * (12 + 16))  # each head's non-rotary key and its value
    assert dense["wo"].shape == (1, 4 * 16, 64) and dense["mlp"]["w_gate"].shape == (1, 64, 128)
    assert dense["q_a_norm"]["g"].shape == (1, 24) and dense["kv_a_norm"]["g"].shape == (1, 16)
    assert sparse["experts"]["w_gate"].shape == (2, 4, 64, 32) and sparse["shared"]["w_gate"].shape == (2, 64, 32)
    assert sparse["router"].shape == (2, 64, 16) and sparse["bias"].shape == (2, 16)  # the router keeps its width
    assert shapes["lm_head"].shape == (64, 512) and shapes["wte"].shape == (512, 64)   # untied
    for bad in (dict(v_head_dim=8), dict(qk_rope_dim=3, qk_nope_dim=13), dict(top_k=17), dict(dense_layers=4),
                dict(experts_held=8, expert_offset=12), dict(n_layers=0)):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, **{**OVERRIDES, **bad})
    # the file must be what the program runs: a changed key is refused by name
    with pytest.raises(ValueError, match="routed_scale"):
        ref.check_config(dataclasses.replace(get_model(MODEL, **OVERRIDES).config, routed_scale=1.0), TINY)
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        ref.check_config(get_model(MODEL, **OVERRIDES).config, dict(TINY, num_nextn_predict_layers=1))


_AS_WRITTEN = []


def _as_written():
    """The reference as written on the seeded model, once for all variants:
    (its arguments, the program's loss, the routes, the reference's gradient)."""
    if not _AS_WRITTEN:
        bundle, params, batch = seeded(bias=0.1)
        args = (params, batch["tokens"], batch["targets"])
        program = float(tiny_models.programs(bundle).loss(params, batch))
        right, routes = reference(with_routes=True)(*args)
        assert program == pytest.approx(float(right), rel=1e-5)
        _AS_WRITTEN.append((args, program, routes, reference(grad=True)(*args, routes)[1]))
    return _AS_WRITTEN[0]


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_reference_notices_a_term_left_out(variant):
    """Each term of the layer equations computed as a mistaken implementation
    would changes the loss and the gradient, at matrices scaled by 3, inner
    norm scales seeded about 1 and a seeded selection bias (the state the
    harness's check on initial parameters cannot see: ``INIT_BLIND``); the
    program agrees with the reference as written."""
    args, program, routes, g_right = _as_written()
    if variant == "softmax_for_sigmoid":  # other scores pick other experts: its own routes
        wrong, g_wrong = reference(grad=True, variant=variant)(*args)
    else:
        wrong, g_wrong = reference(grad=True, variant=variant)(*args, routes)
    assert abs(float(wrong) - program) > 1e-4, variant
    assert whole_error(g_wrong, g_right) > 0.01, variant
    with pytest.raises(ValueError, match="unknown variant"):
        ref.loss(*args, HP, variant="nothing")


def test_the_init_blind_variants_are_variants_and_the_bias_one_is_blind_at_zero():
    assert set(INIT_BLIND) <= set(ref.VARIANTS)
    bundle, params, batch = seeded(bias=0.0)
    args = (params, batch["tokens"], batch["targets"])
    right, routes = reference(with_routes=True)(*args)
    assert float(reference(variant="bias_in_weights")(*args, routes)) == pytest.approx(float(right), rel=1e-6)


def test_routes_given_equal_routes_computed_and_another_share_is_noticed():
    bundle, params, batch = seeded(bias=0.05)
    loss, routes = reference(with_routes=True)(params, batch["tokens"], batch["targets"])
    assert routes.shape == (2, batch["tokens"].size, 4)  # the two expert layers, in layer order
    _, _, mine = tiny_models.programs(bundle).loss_and_routes(params, batch)
    assert np.array_equal(np.sort(np.asarray(mine), -1), np.sort(np.asarray(routes), -1))
    fn = REFERENCE
    l0, g0 = fn(params, batch["tokens"], batch["targets"])
    l1, g1 = fn(params, batch["tokens"], batch["targets"], routes)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6) == pytest.approx(float(loss), rel=1e-6)
    errors = leaf_errors(g1, g0)
    assert max(v for k, v in errors.items() if not k.endswith("['bias']")) < 1e-5
    other = float(jax.jit(lambda p, tok, tgt: ref.loss(p, tok, tgt, dict(HP, offset=0)))(
        params, batch["tokens"], batch["targets"]))
    assert abs(float(loss) - other) > 1e-4


# -- latent attention -------------------------------------------------------------------------


@pytest.mark.parametrize("run", [0, 1])
def test_a_token_changes_nothing_before_it(run):
    bundle, params, batch = seeded()
    cfg = bundle.config
    x = params["wte"][batch["tokens"]][:1]
    p = one_layer(params, run)
    ffn = "dense" if run == 0 else "sparse"
    at = 40
    other = x.at[0, at].set(x[0, at] + 1.0)
    layer = jax.jit(lambda x: glm._layer(p, x, moe.zero_share_stats(chunks_extra=True), cfg, ffn)[0])
    a, b = layer(x), layer(other)
    diff = np.abs(np.asarray(a - b)).max(axis=-1)[0]
    assert diff[:at].max() == 0.0 and diff[at] > 0 and np.nonzero(diff)[0].max() == cfg.max_len - 1


def test_the_rotary_key_is_one_vector_a_token_shared_by_every_head():
    """The last ``qk_rope_dim`` columns of ``wkv_a`` make ONE key of 4 a token:
    it is the last 4 coordinates of every head's key, identical across heads
    (and no column of ``wkv_b`` reaches them); perturbing those columns moves
    every head's scores, perturbing one head's block of ``wkv_b`` moves that
    head alone. The query's rotary part is each head's own. The non-rotary
    coordinates carry no position: rolling the sequence rolls them."""
    bundle, params, batch = seeded()
    cfg = bundle.config
    p = one_layer(params, 0)
    n = common.rmsnorm(p["ln_mixer"], params["wte"][batch["tokens"]][:1], cfg.rms_eps)
    nope, rot, h = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.n_heads
    assert [a.shape for a in glm.qkv(p, n, cfg)] == [(1, cfg.max_len, h * (nope + rot))] * 3   # as the kernels read them
    q, k, v = qkv_by_head(p, n, cfg)
    assert q.shape == k.shape == v.shape == (1, h, cfg.max_len, nope + rot)
    shared = np.asarray(k[..., nope:])
    assert np.all(shared == shared[:, :1]) and np.abs(shared).max() > 0        # one key, every head's
    assert not np.allclose(np.asarray(q[:, 0, :, nope:]), np.asarray(q[:, 1, :, nope:]))  # the query's are its own
    assert not np.allclose(np.asarray(k[:, 0, :, :nope]), np.asarray(k[:, 1, :, :nope]))

    def scores(p):
        q, k, _ = qkv_by_head(p, n, cfg)
        return np.asarray(jnp.einsum("bhqd,bhkd->bhqk", q, k))

    base = scores(p)
    bumped = scores(dict(p, wkv_a=p["wkv_a"].at[:, cfg.kv_lora_rank:].multiply(1.5)))
    assert all(np.abs(bumped[0, i] - base[0, i]).max() > 1e-3 for i in range(h))       # every head moves
    per_head = nope + cfg.v_head_dim
    one = scores(dict(p, wkv_b=p["wkv_b"].at[:, per_head:2 * per_head].multiply(1.5)))  # head 1's block
    moved = [np.abs(one[0, i] - base[0, i]).max() > 1e-6 for i in range(h)]
    assert moved == [False, True, False, False]
    # position lives in the rotary coordinates alone
    rolled = jnp.roll(n, 5, axis=1)
    q2, k2, v2 = qkv_by_head(p, rolled, cfg)
    np.testing.assert_allclose(np.asarray(jnp.roll(k[..., :nope], 5, axis=2)), np.asarray(k2[..., :nope]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.roll(v, 5, axis=2)), np.asarray(v2), atol=1e-5)
    assert np.abs(np.asarray(jnp.roll(k[..., nope:], 5, axis=2) - k2[..., nope:])).max() > 1e-3
    # the turn is a rotation of neighbouring pairs: each pair's length is the unturned key's
    raw = np.asarray(n @ p["wkv_a"])[..., cfg.kv_lora_rank:]
    np.testing.assert_allclose(np.hypot(shared[0, 0, :, 0], shared[0, 0, :, 1]), np.hypot(raw[0, :, 0], raw[0, :, 1]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(shared[0, 0, 0], raw[0, 0], atol=1e-6)  # position 0 is not turned


def test_latent_attention_goes_through_the_core_at_one_head_dim():
    bundle, params, batch = seeded()
    cfg = bundle.config
    p = one_layer(params, 0)
    x = params["wte"][batch["tokens"]][:1]
    seen = []
    with traced.subscribe(lambda kind, labels: seen.append((kind, labels))):
        got = glm._attention(p, x, cfg)
    # D = 12 + 4 = the value head, a key head a query head
    assert seen == [("attention_core", dict(impl="xla", T=64, D=16, dtype="float32", window="none", kv_heads=4,
                                            layout="heads", rotary="none", computed_over_band="none"))]
    with jax.default_matmul_precision("highest"):
        n = ref._rmsnorm(p["ln_mixer"]["g"], x, cfg.rms_eps)
        want = x + ref._latent_attention(p, n, ref.hyper(TINY), None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    assert 256 in attention._AUTO_FLASH_HEAD_DIMS  # the published head takes the kernel on a chip


# -- q, k and v where the kernels read them (PR 65) ------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2 ** -7)], ids=["float32", "bfloat16"])
def test_merged_qkv_split_by_head_is_what_the_by_head_qkv_built(dtype, tol):
    """``qkv`` makes q, k and v ``[B, T, H * D]`` by the projections' own
    products (the column orders, the zero lanes and the 0/1 spread taken of the
    weights). Split by head and with a head's columns put back, they are what
    the model built at ``[B, H, T, D]`` until PR 64 (``qkv_as_it_was``: the
    published order, neighbouring pairs, the shared key broadcast and
    concatenated), to the compute dtype's rounding: each value is the same
    products' sum, a pad and a spread add exact zeros, and the turn is the same
    float32 arithmetic rounded once."""
    bundle, params, batch = seeded()
    cfg = bundle.config
    for run in (0, 1):
        p = one_layer(params, run)
        n = common.rmsnorm(p["ln_mixer"], params["wte"][batch["tokens"]], cfg.rms_eps).astype(dtype)
        got, want = qkv_by_head(p, n, cfg), qkv_as_it_was(p, n, cfg)
        for name, a, b in zip("qkv", got, want):
            assert a.shape == b.shape == (2, cfg.n_heads, cfg.max_len, cfg.head_dim) and a.dtype == b.dtype == dtype
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.abs(b).max() > 1.0, name   # seeded weights: values of some size
            np.testing.assert_allclose(a, b, rtol=tol, atol=tol, err_msg=name)
    # the shared key reaches every head through the product: identical lanes, not merely close ones
    k = np.asarray(attention.split_heads(glm.qkv(p, n, cfg)[1], cfg.n_heads))
    assert np.all(k[..., :cfg.qk_rope_dim] == k[:, :1, :, :cfg.qk_rope_dim])


def test_the_kernel_path_at_a_head_of_256_matches_the_reference_in_the_published_layout():
    """The path the chip takes, interpreted: two heads of 192 + 64 = 256 (whole
    lane tiles), the flash route forced, a step mesh of one chip. q is turned
    by ``rotary_merged``'s one pass in the merged layout, the call is noted
    ``merged`` with no rotary of its own, and loss and the gradients of
    ``wq_b``, ``wkv_a``, ``wkv_b`` and ``wo``, leaves that keep the PUBLISHED
    column order, match the plain reference (which turns neighbouring pairs of
    a head's last 64 coordinates) at the float32 test's tolerance."""
    sizes = dict(n_heads=2, qk_nope_dim=192, qk_rope_dim=64, v_head_dim=256)
    bundle, params, batch = seeded(bias=0.05, **sizes)
    cfg = bundle.config
    hp = dict(HP, heads=2, nope=192, rot=64, v_dim=256)
    attention.set_attention_impl("flash")
    try:
        with attention.step_mesh(one_chip_mesh()), _noted("attention_core", *_CORE) as seen:
            lp, gp = jax.jit(jax.value_and_grad(lambda q: glm.loss_and_routes(q, batch, cfg)[0]))(params)
    finally:
        attention.set_attention_impl("auto")
    # the dense layer and the scanned expert layer
    assert seen == [("flash", cfg.max_len, 256, "none", 2, "merged", "none")] * 2, seen
    lr, gr = jax.jit(jax.value_and_grad(lambda q: ref.loss(q, batch["tokens"], batch["targets"], hp)))(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    errors = leaf_errors(gp, gr)
    latent = {k: v for k, v in errors.items() if any(k.endswith(f"['{w}']") for w in ("wq_b", "wkv_a", "wkv_b", "wo"))}
    assert len(latent) == 8 and max(latent.values()) < 1e-4, latent
    assert max(v for k, v in errors.items() if not k.endswith("['bias']")) < 1e-4


def test_a_head_of_256_at_8192_fits_the_kernels_only_without_a_turn_inside_them(as_on_the_chip):
    """Why ``qkv`` turns q BESIDE the kernels and ``_attention`` hands
    ``attention_merged`` no rotary (shapes only, nothing compiled): at D = 256,
    T = 8,192 the kernels hold 66,060,288 of their 67,108,864 bytes of VMEM; a
    call that turns q on the forward kernel's tile adds 2 x 2 x 1,024 x 256 x 4
    = 4,194,304 bytes of tables, ``choose_blocks`` finds no blocks, and
    ``attention_merged`` falls back to the by-head path without a word: every
    copy this layout exists to avoid would be back."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention

    cfg = glm.Glm4MoeLiteConfig()
    h, d, t = cfg.n_heads, cfg.head_dim, cfg.max_len
    x = jax.ShapeDtypeStruct((2, t, h * d), jnp.bfloat16)
    turned = attention.Rotary(base=cfg.rope_theta, layout="half", rotary_dim=cfg.qk_rope_dim)
    assert pallas_attention.vmem_bytes(t, t, d, x.dtype, 1024, 1024) == 66_060_288
    assert pallas_attention.vmem_bytes(t, t, d, x.dtype, 1024, 1024, turned=True) == 66_060_288 + 4_194_304
    assert pallas_attention.VMEM_BUDGET_BYTES == 67_108_864
    one_chip = one_chip_mesh()
    with attention.step_mesh(one_chip):
        assert attention.merged_in_place(x, x, x, h, h, True, None, None)
        assert not attention.merged_in_place(x, x, x, h, h, True, None, turned)
        assert attention.chips_in_step() == 1
    # half the sequence leaves room for the tables: the trap is this shape's, not the entry's
    half = jax.ShapeDtypeStruct((2, t // 2, h * d), jnp.bfloat16)
    with attention.step_mesh(one_chip):
        assert attention.merged_in_place(half, half, half, h, h, True, None, turned)


# -- the selection bias (the router itself: tests/test_expert_families.py) -----------------------------------------------------


@pytest.mark.parametrize("optimizer", ["adam", "adamw"])
def test_a_step_moves_each_bias_by_gamma_by_the_counts_and_nothing_else_differs(optimizer):
    """After one step every selection bias is its old value +gamma, -gamma or
    +0, by the sign of (mean count - its count); every other leaf, and the
    optimizer's state, are what the step without the rule gives, bit for bit."""
    from distributedvolunteercomputing_tpu.training.optim import make_optimizer

    bundle, params, batch = seeded(scale=1.0, bias=0.05)
    tx = make_optimizer(optimizer, lr=1e-2, weight_decay=0.1)
    state = steps.TrainState.create(params, tx, jax.random.PRNGKey(0))
    step = steps.make_train_step(bundle.loss_fn, tx, donate=False, stepped=bundle.stepped)
    new, metrics = step(state, batch)
    assert moe.COUNTS not in metrics and float(metrics["aux_loss"]) == 0.0
    _, m = tiny_models.programs(bundle).loss_and_routes(params, batch)[:2]
    counts = np.asarray(m[moe.COUNTS])
    assert counts.shape == (2, 16) and np.all(counts.sum(-1) == 2 * 64 * 4)
    plain = steps.make_train_step(
        lambda p, b, r: (lambda l, m: (l, {k: v for k, v in m.items() if k != moe.COUNTS}))(*bundle.loss_fn(p, b, r)),
        tx, donate=False)
    off, _ = plain(state, batch)
    gamma = np.float32(bundle.config.bias_gamma)
    sign = np.sign(counts.mean(-1, keepdims=True) - counts).astype(np.float32)
    old, got = np.asarray(params["blocks"][1]["bias"]), np.asarray(new.params["blocks"][1]["bias"])
    assert np.array_equal(got, old + gamma * sign)
    assert set(np.unique(np.round((got - old) / gamma))) <= {-1.0, 0.0, 1.0}
    assert 0 < int(np.count_nonzero(sign)) == int(metrics["moe_bias_moved"]) <= 32
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(new.params), jax.tree_util.tree_leaves(off.params)):
        if not moe.is_bias(path):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))
    for a, b in zip(jax.tree_util.tree_leaves(new.opt_state), jax.tree_util.tree_leaves(off.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if optimizer == "adamw":  # left to the optimizer, the bias would have decayed
        assert not np.array_equal(np.asarray(off.params["blocks"][1]["bias"]), old)
    owned = bundle.stepped.owns(params)
    names = [jax.tree_util.keystr(p) for p, own in jax.tree_util.tree_leaves_with_path(owned) if own]
    assert names == ["['blocks'][1]['bias']"]


# -- the share -----------------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once():
    """The guide's share test: the routed parts of the four shares of four
    experts each (at the cell's sizes eight of eight), with what every chip
    computes alike (latent attention, the residual, the SHARED expert) counted
    once, are the uncut reference's output for the whole uncut layer."""
    uncut = dict(TINY, n_routed_experts=16, expert_offset=0)
    bundle, params, batch = seeded(bias=0.05, experts_held=16, expert_offset=0)
    hp = ref.hyper(uncut)
    x = params["wte"][batch["tokens"]][:1]
    p = one_layer(params, 1)
    with jax.default_matmul_precision("highest"):
        block = jax.jit(lambda p: ref._block(p, x, None, hp)[0])
        whole = block(p)
        no_experts = jax.tree_util.tree_map(jnp.zeros_like, p["experts"])
        alike = block(dict(p, experts=no_experts))   # mixer, residual, shared expert
        no_shared = block(dict(p, experts=no_experts, shared=jax.tree_util.tree_map(jnp.zeros_like, p["shared"])))
    assert float(jnp.max(jnp.abs(alike - no_shared))) > 1e-2   # the shared expert is in what is counted once
    total = alike
    for offset in range(0, 16, 4):
        cfg = dataclasses.replace(bundle.config, experts_held=4, expert_offset=offset)
        held = jax.tree_util.tree_map(lambda a: a[offset:offset + 4], p["experts"])
        y, stats, _ = jax.jit(lambda p: glm._layer(  # a program a share: the offset is the trace's
            p, x, moe.zero_share_stats(chunks_extra=True), cfg, "sparse"))(dict(p, experts=held))
        assert float(stats["dropped"]) == 0.0
        total = total + (y - alike)  # this share's routed experts' part alone
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=2e-4, atol=2e-4)
    assert float(jnp.max(jnp.abs(y - whole))) > 1e-2  # one share is not the whole


def test_the_model_takes_the_default_chunk_and_counts_what_a_smaller_one_would_cost(monkeypatch):
    """The model passes the dispatch's default of three even shares (its own
    reading on the chip refuted the levelled quarter: models/glm4_moe_lite.py);
    a smaller chunk computes the same and ``moe_chunks_extra`` counts its cost."""
    assert glm.SHARE_ROWS_SLACK == moe_dispatch.SHARE_ROWS_SLACK == 3.0
    bundle, params, batch = seeded()
    c = bundle.config
    first = c.expert_offset
    lifted = jax.tree_util.tree_map_with_path(
        lambda path, x: x.at[..., first:first + 2].set(2.0) if moe.is_bias(path) else x, params)
    read = {}
    for slack in (moe_dispatch.SHARE_ROWS_SLACK_LEVELLED, moe_dispatch.SHARE_ROWS_SLACK):
        monkeypatch.setattr(glm, "SHARE_ROWS_SLACK", slack)
        (loss, m), grads = tiny_models.programs(bundle).loss_metrics_and_grad(lifted, batch)  # keyed by the slack in force
        cap = moe_dispatch.share_rows_bound(batch["tokens"].size, c.top_k, c.experts_held, c.n_experts, slack)
        held = np.asarray(m[moe.COUNTS])[:, first:first + c.experts_held].sum(axis=1)
        assert float(m["moe_dropped"]) == 0.0 and float(m["moe_chunks_extra"]) == (np.ceil(held / cap) - 1).sum()
        read[slack] = (float(loss), grads, cap, float(m["moe_chunks_extra"]))
    (loss_a, grads_a, cap_a, extra_a), (loss_b, grads_b, cap_b, extra_b) = read.values()
    assert (cap_a, extra_a, cap_b, extra_b) == (160, 2.0, 384, 0.0)   # two layers, a second chunk each at a quarter over
    assert loss_a == pytest.approx(loss_b, rel=1e-6) and max(leaf_errors(grads_a, grads_b).values()) < 1e-5


# -- the list-of-runs tree through the rest of the system ----------------------------------


def test_published_sizes_and_parameter_counts():
    from benchmark import flops_glm4_moe_lite as fl

    full = jax.eval_shape(get_model(MODEL).init, jax.random.PRNGKey(0))
    n = common.count_params(full)
    cell = Manifest().load_config("glm-4.7-flash")
    published = dict(cell, **cell["published"])
    assert n == fl.total_params(published) == 29_943_393_920 and round(n / 1e9) == 30  # "30B"
    cut = jax.eval_shape(get_model(MODEL, **cell["model_overrides"]).init, jax.random.PRNGKey(0))
    assert common.count_params(cut) == 591_294_976 == cell["parameters"]["counted_by_the_program"] == fl.total_params(cell)
    by_layer = [common.count_params(cut["blocks"][0])] + [common.count_params(cut["blocks"][1]) // 4] * 4
    assert by_layer == cell["parameters"]["by_layer"] == [84_677_888] + [106_829_120] * 4
    assert common.count_params(cut["wte"]) == common.count_params(cut["lm_head"]) == cell["parameters"]["head"] == 39_649_280
    assert fl.attention_matrix_params(cell) + 768 + 512 == 21_759_232


def test_stacked_runs_take_the_sharding_rules(eight_devices):
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from distributedvolunteercomputing_tpu.parallel import sharding
    from distributedvolunteercomputing_tpu.parallel.mesh import AXES

    mesh = Mesh(np.array(eight_devices).reshape(1, 1, 1, 4, 2), AXES)  # ep=4, tp=2
    shapes = jax.eval_shape(get_model(MODEL, **OVERRIDES).init, jax.random.PRNGKey(0))
    specs = jax.tree_util.tree_map(lambda s: s.spec, sharding.make_param_shardings(mesh, shapes))
    dense, sparse = specs["blocks"]
    # right-aligned: a run's layer axis stays whole
    assert sparse["experts"]["w_gate"] == P(None, "ep", None, "tp") and sparse["experts"]["w_down"] == P(None, "ep", "tp", None)
    assert sparse["shared"]["w_up"] == P(None, None, "tp") and sparse["shared"]["w_down"] == P(None, "tp", None)
    # the latent's second products fan out by head, the output projection folds them back
    assert dense["wq_b"] == dense["wkv_b"] == P(None, None, "tp") and dense["wo"] == P(None, "tp", None)
    assert specs["lm_head"] == P(None, "tp")
    for whole in (sparse["router"], sparse["bias"], dense["wq_a"], dense["wkv_a"], dense["q_a_norm"]["g"],
                  dense["kv_a_norm"]["g"], specs["wte"]):
        assert whole == P()


def test_save_and_restore_carry_the_bias(tmp_path):
    from distributedvolunteercomputing_tpu.training import checkpoint
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    make = lambda seed: Trainer(  # noqa: E731
        get_model(MODEL, **OVERRIDES), batch_size=2, optimizer="adam", lr=1e-3, init_seed=seed)
    tr = make(1)
    tr.run(steps=3)
    assert np.any(np.asarray(tr.state.params["blocks"][1]["bias"]))
    checkpoint.save(tr, str(tmp_path))
    fresh = make(2)
    assert checkpoint.maybe_restore(fresh, str(tmp_path)) and int(fresh.state.step) == 3
    assert jax.tree_util.tree_structure(fresh.state.params) == jax.tree_util.tree_structure(tr.state.params)
    for a, b in zip(jax.tree_util.tree_leaves(fresh.state.params), jax.tree_util.tree_leaves(tr.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    before = float(tr.run(steps=2)["final_loss"])
    assert float(fresh.run(steps=2)["final_loss"]) == pytest.approx(before, rel=1e-5)


def test_train_loop_records_the_bias_and_the_mixers_on_the_route_span():
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    tel = Telemetry(peer_id="v", enabled=True)
    tr = Trainer(get_model(MODEL, **OVERRIDES), batch_size=2, optimizer="adam", lr=1e-3, tracer=tel.tracer)
    summary = tr.run(steps=11, log_every=5)
    assert math.isfinite(summary["final_loss"])
    routes = [s for s in tel.tracer.spans() if s["name"] == "moe.route"]
    assert [s["attrs"]["step"] for s in routes] == [5, 10]
    for s in routes:
        a = s["attrs"]
        assert a["router_site"] == "post_attention" and a["experts_held"] == 4 and a["moe_dropped"] == 0.0
        assert a["mixers_latent_attention"] == 3 and not [k for k in a if k.startswith("mixers_") and "latent" not in k]
        assert a["aux_loss"] == 0.0 and a["lm_loss"] > 0
        assert a["moe_load_mean"] == 2 * 64 * 4 / 16 and 0 < a["moe_rows_held"] <= 2 * 2 * 64 * 4
        assert 0 < a["moe_bias_moved"] <= 2 * 16
        assert a["moe_chunks_extra"] == 0.0 and a["moe_rows_moved"] == 2 * 384   # one chunk of three even shares a layer
        reach = (a["step"] - 1) * 0.001
        assert -reach - 1e-7 <= a["moe_bias_min"] < 0 < a["moe_bias_max"] <= reach + 1e-7
    assert tel.summary()["moe"]["dropped_total"] == 0.0 and tel.summary()["moe"]["chunks_extra"] == 0.0


def test_run_volunteer_knows_the_model_and_no_training_code_names_it():
    import os
    import subprocess

    from distributedvolunteercomputing_tpu.models import registry

    assert MODEL in registry.list_models()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkg = os.path.join(repo, "distributedvolunteercomputing_tpu")
    hits = subprocess.run(["grep", "-rliE", "glm4|glm_4|glm-4", os.path.join(pkg, "training"), os.path.join(pkg, "swarm")],
                          capture_output=True, text=True).stdout.split()
    assert hits == []
