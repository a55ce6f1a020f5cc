"""Model zoo tests at tiny configs (full-size zoo compiles are bench-only).

Covers the five reference workloads (BASELINE.json:7-11): shapes, finite
losses, gradient flow, and LoRA's frozen-base guarantee.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.models.common import count_params
from distributedvolunteercomputing_tpu.training.optim import make_optimizer
from distributedvolunteercomputing_tpu.training.steps import TrainState, make_train_step

TINY = {
    "mnist_mlp": dict(d_hidden=32),
    "cifar10_resnet18": dict(stage_sizes=(1, 1), widths=(8, 16), stem_width=8, groups=2),
    "cifar10_vit": dict(d_model=32, n_heads=2, n_layers=2, d_ff=64, patch_size=8),
    "bert_mlm": dict(vocab=256, max_len=32, d_model=32, n_heads=2, n_layers=2, d_ff=64),
    "gpt2_small": dict(vocab=256, max_len=32, d_model=32, n_heads=2, n_layers=2, d_ff=64),
    "llama_lora": dict(vocab=256, max_len=32, d_model=32, n_heads=2, n_kv_heads=2, n_layers=2, d_ff=64, lora_rank=4),
}


@pytest.mark.parametrize("name", sorted(TINY))
def test_loss_finite_and_grads_flow(name):
    bundle = get_model(name, **TINY[name])
    params = bundle.init(jax.random.PRNGKey(0))
    batch = bundle.make_batch(jax.random.PRNGKey(1), 4)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(bundle.loss_fn, has_aux=True))(
        params, batch, jax.random.PRNGKey(2)
    )
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree_util.tree_leaves(grads))
    assert gnorm > 0, "no gradient flow"


@pytest.mark.parametrize("name", ["cifar10_resnet18", "cifar10_vit", "gpt2_small"])
def test_few_steps_reduce_loss(name):
    bundle = get_model(name, **TINY[name])
    tx = make_optimizer("adam", lr=3e-3)
    step = make_train_step(bundle.loss_fn, tx)
    batch = bundle.make_batch(jax.random.PRNGKey(1), 8)
    losses = []
    state = TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(3))
    for _ in range(25):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses[:3] + losses[-3:]


class TestLoRA:
    def test_base_params_frozen(self):
        bundle = get_model("llama_lora", **TINY["llama_lora"])
        params = bundle.init(jax.random.PRNGKey(0))
        assert set(params) == {"base", "lora"}
        batch = bundle.make_batch(jax.random.PRNGKey(1), 2)
        grads = jax.jit(jax.grad(lambda p, b, r: bundle.loss_fn(p, b, r)[0]))(
            params, batch, jax.random.PRNGKey(2)
        )
        base_gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree_util.tree_leaves(grads["base"]))
        lora_gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree_util.tree_leaves(grads["lora"]))
        assert base_gnorm == 0.0, "base must be frozen under LoRA"
        assert lora_gnorm > 0.0, "lora adapters must receive gradients"

    def test_zero_init_adapters_are_identity(self):
        # B=0 at init => logits identical with/without the lora subtree applied.
        from distributedvolunteercomputing_tpu.models import llama

        cfg = llama.LlamaConfig(**TINY["llama_lora"])
        params = llama.init(jax.random.PRNGKey(0), cfg)
        cfg_off = llama.LlamaConfig(**{**TINY["llama_lora"], "lora_rank": 0})
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
        out_with = llama.forward(params, toks, cfg)
        out_without = llama.forward(params["base"], toks, cfg_off)
        np.testing.assert_allclose(np.asarray(out_with), np.asarray(out_without), atol=1e-5)

    def test_lora_payload_much_smaller(self):
        bundle = get_model("llama_lora", **TINY["llama_lora"])
        params = bundle.init(jax.random.PRNGKey(0))
        assert count_params(params["lora"]) < count_params(params["base"]) / 10


class TestGQA:
    """Grouped-query attention (n_kv_heads < n_heads) — the llama2/3 memory
    saver. Exactness contract: GQA must equal full MHA whose K/V projections
    are the GQA ones with each KV head's columns DUPLICATED n_rep times
    (that is literally what _repeat_kv does to the activations)."""

    def test_gqa_equals_mha_with_duplicated_kv_heads(self):
        from distributedvolunteercomputing_tpu.models import llama

        base_kw = dict(
            vocab=128, max_len=16, d_model=32, n_layers=2, d_ff=64,
            lora_rank=0, remat=False,
        )
        n_heads, n_kv = 4, 2
        n_rep = n_heads // n_kv
        d_head = base_kw["d_model"] // n_heads

        cfg_gqa = llama.LlamaConfig(**base_kw, n_heads=n_heads, n_kv_heads=n_kv)
        cfg_mha = llama.LlamaConfig(**base_kw, n_heads=n_heads, n_kv_heads=n_heads)
        params = llama.init(jax.random.PRNGKey(0), cfg_gqa)

        def widen(w):  # [L, d, n_kv*dh] -> [L, d, n_heads*dh], heads repeated
            L, d, _ = w.shape
            w4 = w.reshape(L, d, n_kv, d_head)
            return jnp.repeat(w4, n_rep, axis=2).reshape(L, d, n_heads * d_head)

        params_mha = jax.tree_util.tree_map(lambda x: x, params)
        params_mha["blocks"] = dict(params["blocks"])
        params_mha["blocks"]["wk"] = widen(params["blocks"]["wk"])
        params_mha["blocks"]["wv"] = widen(params["blocks"]["wv"])

        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
        batch = {"tokens": toks, "targets": toks}
        rng = jax.random.PRNGKey(2)
        loss_gqa, _ = llama.loss_fn(params, batch, rng, cfg_gqa)
        loss_mha, _ = llama.loss_fn(params_mha, batch, rng, cfg_mha)
        np.testing.assert_allclose(float(loss_gqa), float(loss_mha), rtol=1e-5)

    def test_gqa_trains_and_lora_shapes(self):
        # The GQA path (n_rep > 1) through the full bundle incl. LoRA's
        # d_kv-shaped v adapter: finite loss, grads reach the kv weights.
        bundle = get_model(
            "llama_lora", vocab=128, max_len=16, d_model=32, n_heads=4,
            n_kv_heads=2, n_layers=2, d_ff=64, lora_rank=4, remat=False,
        )
        params = bundle.init(jax.random.PRNGKey(0))
        assert params["base"]["blocks"]["wk"].shape == (2, 32, 16)  # d_kv = 2*8
        batch = bundle.make_batch(jax.random.PRNGKey(1), 4)
        (loss, _), grads = jax.jit(jax.value_and_grad(
            lambda p: bundle.loss_fn(p, batch, jax.random.PRNGKey(2)), has_aux=True
        ))(params)
        assert np.isfinite(float(loss))
        # LoRA contract: the base stays FROZEN (zero grads) while the
        # adapters — including the d_kv-shaped v adapter — receive gradient.
        assert float(jnp.abs(grads["base"]["blocks"]["wk"]).max()) == 0
        lora_leaves = jax.tree_util.tree_leaves(grads["lora"])
        assert any(float(jnp.abs(g).max()) > 0 for g in lora_leaves)


def _count_eqns(jaxpr, counts=None):
    """primitive name -> how many equations, through every nested jaxpr (a scan's body, a jit's)."""
    import collections

    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count_eqns(sub, counts)
    return counts


class TestChunkedXent:
    """The streamed vocab-projection loss (common.lm_xent_chunked) must be
    numerically identical to materializing the full [B,T,V] logits — in
    value AND gradients — on its real multi-chunk path (n > 1 chunks),
    which production configs hit (T=1024, chunk=128), in both layouts of the
    head, under a 0/1 mask and under weights over a divisor of the caller's.
    Its gradients are made in the loop that makes the loss (a custom_vjp): the
    structural cases hold it to three vocabulary-sized products and one loop."""

    B, T, D, V, CHUNK = 2, 16, 8, 11, 4
    LAYOUTS = ("vd", "dv")
    KINDS = ("plain", "mask01", "weights_over_denominator")

    def _data(self, layout="vd", kind="plain", dtype=jnp.float32):
        """(full(x, head), chunked(x, head), x, head): the loss from whole logits
        under plain autodiff, the streamed one, and their arguments."""
        from distributedvolunteercomputing_tpu.models import common

        k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(k1, (self.B, self.T, self.D), jnp.float32).astype(dtype)
        head = jax.random.normal(k2, (self.V, self.D), jnp.float32)
        head = head if layout == "vd" else head.T
        labels = jax.random.randint(k3, (self.B, self.T), 0, self.V)
        u = jax.random.uniform(k4, (self.B, self.T))
        m, denominator = {
            "plain": (None, None),
            "mask01": ((u < 0.4).astype(jnp.float32), None),
            "weights_over_denominator": (u * 5, float(self.B * self.T)),
            "mask_all_zero": (jnp.zeros((self.B, self.T)), None),
        }[kind]

        def full(x, head):
            return common.softmax_xent(common._project_vocab(x, head, layout), labels, m, denominator)

        def chunked(x, head):
            return common.lm_xent_chunked(x, head, labels, mask=m, chunk=self.CHUNK, head_layout=layout,
                                          denominator=denominator)

        assert self.T // self.CHUNK > 1  # really exercising the scan path
        return full, chunked, x, head

    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_matches_full_logits(self, layout, kind):
        full, chunked, x, head = self._data(layout, kind)
        np.testing.assert_allclose(float(chunked(x, head)), float(full(x, head)), rtol=1e-6)
        g_full = jax.grad(full, argnums=(0, 1))(x, head)
        g_chunk = jax.grad(chunked, argnums=(0, 1))(x, head)
        for a, b in zip(g_chunk, g_full):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_bf16_rows_match_full_logits_as_the_checkpointed_loop_did(self, layout):
        """``x`` in the compute dtype of the chip: the products take bf16 operands
        and a float32 ``dlogits``, as autodiff's did. The checkpointed loop this
        one replaces stood within 1.3e-3 of whole logits here (dhead; its dx was
        equal), this one within 1.0e-3: held to that distance, not a wider one."""
        full, chunked, x, head = self._data(layout, "mask01", jnp.bfloat16)
        np.testing.assert_allclose(float(chunked(x, head)), float(full(x, head)), rtol=1e-6)
        g_full = jax.grad(full, argnums=(0, 1))(x, head)
        g_chunk = jax.grad(chunked, argnums=(0, 1))(x, head)
        assert [g.dtype for g in g_chunk] == [jnp.bfloat16, jnp.float32]
        for a, b in zip(g_chunk, g_full):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=0, atol=1.3e-3)

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_a_cotangent_other_than_one_scales_both_gradients(self, layout):
        full, chunked, x, head = self._data(layout, "weights_over_denominator")
        g_full = jax.grad(lambda x, h: 3.0 * full(x, h), argnums=(0, 1))(x, head)
        g_chunk = jax.grad(lambda x, h: 3.0 * chunked(x, h), argnums=(0, 1))(x, head)
        for a, b in zip(g_chunk, g_full):
            # float32 rounding of sums whose terms are 3 x 5 times the unit case's: its atol by as much
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1.5e-6)

    def test_a_mask_of_zeros_divides_by_one_and_gives_zero_gradients(self):
        _, chunked, x, head = self._data("vd", "mask_all_zero")
        loss, grads = jax.value_and_grad(chunked, argnums=(0, 1))(x, head)
        assert float(loss) == 0.0
        assert all(np.all(np.asarray(g) == 0.0) for g in grads)  # zeros, not NaN

    def test_indivisible_t_falls_back(self):
        from distributedvolunteercomputing_tpu.models import common

        full, _, x, head = self._data()
        labels = jax.random.randint(jax.random.split(jax.random.PRNGKey(0), 4)[2], (self.B, self.T), 0, self.V)

        def one_chunk(x, head):
            return common.lm_xent_chunked(x, head, labels, chunk=5)  # 16 % 5 != 0: one chunk, the same rule

        np.testing.assert_allclose(float(one_chunk(x, head)), float(full(x, head)), rtol=1e-6)
        for a, b in zip(jax.grad(one_chunk, argnums=(0, 1))(x, head), jax.grad(full, argnums=(0, 1))(x, head)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_three_products_and_one_loop_differentiated_one_product_evaluated(self, layout):
        """The differentiated loss is ONE scan whose chunk holds the logits
        product and the two gradient products (no second loop, no recomputed
        logits); an undifferentiated call pays for no gradient."""
        _, chunked, x, head = self._data(layout, "mask01")
        differentiated = _count_eqns(jax.make_jaxpr(jax.value_and_grad(chunked, argnums=(0, 1)))(x, head).jaxpr)
        assert (differentiated["dot_general"], differentiated["scan"]) == (3, 1), differentiated
        assert not differentiated["checkpoint"] and not differentiated["remat2"]
        evaluated = _count_eqns(jax.make_jaxpr(chunked)(x, head).jaxpr)
        assert (evaluated["dot_general"], evaluated["scan"]) == (1, 1), evaluated
        assert evaluated["exp"] == 1  # the log-sum-exp's, no softmax for a dlogits nobody asked for

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_a_head_nobody_differentiates_costs_no_accumulator(self, layout):
        """An adapter-only finetune freezes the head (models/llama.py's
        ``stop_gradient`` over the base): the rule sees it is not perturbed and
        neither multiplies for its gradient nor carries a float32 [V, d] sum.
        bf16 storage here, so any float32 array of the head's shape in the
        lowered program could only be that accumulator."""
        from distributedvolunteercomputing_tpu.models import common

        _, _, x, head = self._data(layout)
        x, head = x.astype(jnp.bfloat16), head.astype(jnp.bfloat16)
        labels = jnp.zeros((self.B, self.T), jnp.int32)
        accumulator = "tensor<%dx%dxf32>" % head.shape

        def loss(x, head, freeze):
            head = jax.lax.stop_gradient(head) if freeze else head
            return common.lm_xent_chunked(x, head, labels, chunk=self.CHUNK, head_layout=layout)

        def lowered(fn):  # as for the chip: the CPU's lowering widens a bf16 operand of a product itself
            return fn.trace(x, head).lower(lowering_platforms=("tpu",)).as_text()

        frozen = jax.jit(jax.grad(lambda x, h: loss(x, h, True), argnums=(0, 1)))
        assert accumulator not in lowered(frozen)
        counts = _count_eqns(jax.make_jaxpr(frozen)(x, head).jaxpr)
        assert (counts["dot_general"], counts["scan"]) == (2, 1), counts
        dx, dhead = frozen(x, head)
        assert float(jnp.abs(dx.astype(jnp.float32)).max()) > 0 and not np.any(np.asarray(dhead, np.float32))
        trained = jax.jit(jax.grad(lambda x, h: loss(x, h, False), argnums=(0, 1)))
        assert accumulator in lowered(trained)  # the control: this is how it would show


class TestViT:
    def test_patchify_is_invertible_partition(self):
        # Patchification must PARTITION the image: every pixel appears in
        # exactly one patch (sum over patches == sum over image, and
        # un-patchifying restores the array).
        from distributedvolunteercomputing_tpu.models import vit

        cfg = vit.ViTConfig(image_size=8, patch_size=4, channels=3)
        x = jnp.arange(2 * 8 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 8, 3)
        p = vit._patchify(x, cfg)
        assert p.shape == (2, cfg.n_patches, cfg.patch_dim)
        np.testing.assert_allclose(float(p.sum()), float(x.sum()))
        s = 8 // 4
        back = (
            p.reshape(2, s, s, 4, 4, 3).transpose(0, 1, 3, 2, 4, 5).reshape(2, 8, 8, 3)
        )
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    def test_indivisible_patch_rejected(self):
        from distributedvolunteercomputing_tpu.models import vit

        with pytest.raises(ValueError, match="patch_size"):
            vit.init(jax.random.PRNGKey(0), vit.ViTConfig(image_size=30, patch_size=4))

    def test_logits_shape(self):
        from distributedvolunteercomputing_tpu.models import vit

        cfg = vit.ViTConfig(
            image_size=16, patch_size=8, d_model=32, n_heads=2, n_layers=2,
            d_ff=64, remat=False,
        )
        params = vit.init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 16, 3))
        assert vit.forward(params, x, cfg).shape == (3, cfg.n_classes)

    def test_head_reads_cls_position(self):
        # With ZERO blocks the trunk is the identity, so the head sees only
        # ln(cls + pos[0]) — logits must be image-INDEPENDENT. Any head that
        # reads a patch position or pools over patches varies with the
        # image, so this pins `h[:, 0]` exactly (a bidirectional-attention
        # perturbation test cannot: with blocks, everything affects
        # everything).
        from distributedvolunteercomputing_tpu.models import vit

        cfg = vit.ViTConfig(
            image_size=16, patch_size=8, d_model=32, n_heads=2, n_layers=0,
            d_ff=64, remat=False,
        )
        params = vit.init(jax.random.PRNGKey(0), cfg)
        xa = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
        xb = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16, 3))
        la = np.asarray(vit.forward(params, xa, cfg))
        lb = np.asarray(vit.forward(params, xb, cfg))
        np.testing.assert_array_equal(la, lb)


def test_full_size_configs_have_expected_scale():
    # Param counts at REAL config sizes (init on CPU is cheap enough).
    gpt2 = get_model("gpt2_small")
    n = count_params(gpt2.init(jax.random.PRNGKey(0)))
    assert 110e6 < n < 130e6, f"GPT-2 small should be ~124M params, got {n/1e6:.1f}M"


def test_gpt2_presets_have_expected_scale():
    # Abstract shapes only (jax.eval_shape, the pattern the Llama-7B preset
    # test uses) — no multi-GB init allocation just to count params.
    import dataclasses as dc

    from distributedvolunteercomputing_tpu.models.gpt2 import GPT2Config

    def abstract_params(cfg_cls):
        bundle = get_model("gpt2_small", **dc.asdict(cfg_cls()))
        shapes = jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0)))
        return sum(
            int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)
        )

    n = abstract_params(GPT2Config.medium)
    assert 330e6 < n < 380e6, f"GPT-2 medium should be ~355M params, got {n/1e6:.1f}M"
    n = abstract_params(GPT2Config.large)
    assert 730e6 < n < 810e6, f"GPT-2 large should be ~774M params, got {n/1e6:.1f}M"


def test_gpt2_scale_presets_are_registry_names():
    """gpt2_medium / gpt2_large are first-class registry names (r5: the
    CLI's --model and a benchmark configuration can name the scale rungs
    directly), overrides still apply on top, and a tiny-config step runs."""
    import jax
    import numpy as np

    from distributedvolunteercomputing_tpu.models import get_model, list_models
    from distributedvolunteercomputing_tpu.training.optim import make_optimizer
    from distributedvolunteercomputing_tpu.training.steps import (
        TrainState, make_train_step,
    )

    assert "gpt2_medium" in list_models() and "gpt2_large" in list_models()
    b = get_model("gpt2_medium", n_layers=2, vocab=256, max_len=32)
    assert b.name == "gpt2_medium"
    assert b.config.d_model == 1024 and b.config.n_heads == 16  # preset kept
    assert b.config.n_layers == 2  # override applied on top
    tx = make_optimizer("adamw", lr=1e-4)
    st = TrainState.create(b.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(1))
    step = make_train_step(b.loss_fn, tx)
    _, m = step(st, b.make_batch(jax.random.PRNGKey(2), 2))
    assert np.isfinite(float(m["loss"]))
    assert get_model("gpt2_large", n_layers=1, vocab=64).config.d_model == 1280
