"""Sharded train step on the 8-device virtual CPU mesh (SURVEY.md §4).

Validates: mesh construction, TP partition rules by path, divisibility
fallback, and that a dp x tp sharded step computes the SAME numbers as the
single-device step — sharding must be a pure performance annotation.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.parallel import (
    make_mesh,
    make_param_shardings,
    partition_spec_for_path,
)
from distributedvolunteercomputing_tpu.parallel.train_step import (
    make_sharded_train_step,
    put_batch,
    shard_train_state,
)
from distributedvolunteercomputing_tpu.training.optim import make_optimizer
from distributedvolunteercomputing_tpu.training.steps import TrainState, make_train_step

TINY_GPT2 = dict(vocab=128, max_len=32, d_model=64, n_heads=4, n_layers=2, d_ff=128, remat=False)


@functools.lru_cache(maxsize=None)
def single_device_step():
    """``(state, metrics)`` after ONE step of the tiny gpt2 on one device
    (Adam at 1e-3, parameters from key 0, 16 rows from key 1, the state's key
    2): what five tests hold a sharded step to, compiled and run once."""
    bundle = get_model("gpt2_small", **TINY_GPT2)
    tx = make_optimizer("adam", lr=1e-3)
    state = TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(2))
    return make_train_step(bundle.loss_fn, tx, donate=False)(state, bundle.make_batch(jax.random.PRNGKey(1), 16))


def test_make_mesh_shapes(eight_devices):
    mesh = make_mesh(dp=2, sp=1, tp=4)
    assert mesh.axis_names == ("dp", "sp", "pp", "ep", "tp")
    assert mesh.devices.shape == (2, 1, 1, 1, 4)
    mesh_pp = make_mesh(dp=2, pp=2, tp=2)
    assert mesh_pp.devices.shape == (2, 1, 2, 1, 2)
    mesh_ep = make_mesh(dp=2, ep=4)
    assert mesh_ep.devices.shape == (2, 1, 1, 4, 1)
    with pytest.raises(ValueError):
        make_mesh(dp=4, sp=2, tp=4)  # 32 > 8


def test_partition_rules(eight_devices):
    mesh = make_mesh(dp=2, tp=4)
    # column-parallel, stacked scan-over-layers layout (leading L axis)
    assert partition_spec_for_path("blocks/qkv/w", (2, 64, 192), mesh) == P(None, None, "tp")
    assert partition_spec_for_path("blocks/wq", (2, 64, 64), mesh) == P(None, None, "tp")
    # same rules right-align onto unstacked leaves
    assert partition_spec_for_path("blocks/0/qkv/w", (64, 192), mesh) == P(None, "tp")
    # row-parallel
    assert partition_spec_for_path("blocks/attn_out/w", (2, 64, 64), mesh) == P(None, "tp", None)
    assert partition_spec_for_path("blocks/w_down", (2, 128, 64), mesh) == P(None, "tp", None)
    # stacked column-parallel bias: shard the trailing feature dim
    assert partition_spec_for_path("blocks/qkv/b", (2, 192), mesh) == P(None, "tp")
    # default replicated
    assert partition_spec_for_path("wte", (50257, 768), mesh) == P()
    assert partition_spec_for_path("blocks/ln1/g", (2, 64), mesh) == P()


def test_divisibility_fallback(eight_devices):
    mesh = make_mesh(dp=2, tp=4)
    # 50257 not divisible by 4 → the tp axis is dropped, not an error
    assert partition_spec_for_path("lm_head", (64, 50257), mesh) == P(None, None)


def test_param_shardings_cover_tree(eight_devices):
    mesh = make_mesh(dp=2, tp=4)
    bundle = get_model("gpt2_small", **TINY_GPT2)
    params = bundle.init(jax.random.PRNGKey(0))
    shardings = make_param_shardings(mesh, params)
    qkv = shardings["blocks"]["qkv"]["w"]
    assert qkv.spec == P(None, None, "tp")
    assert shardings["wte"].spec == P()


@pytest.mark.parametrize("dp,tp", [(8, 1), (2, 4)])
def test_sharded_step_matches_single_device(eight_devices, dp, tp):
    bundle = get_model("gpt2_small", **TINY_GPT2)
    tx = make_optimizer("adam", lr=1e-3)
    rng = jax.random.PRNGKey(0)
    params = bundle.init(rng)
    batch = bundle.make_batch(jax.random.PRNGKey(1), 16)

    ref_state, ref_metrics = single_device_step()

    mesh = make_mesh(dp=dp, tp=tp)
    state = TrainState.create(params, tx, jax.random.PRNGKey(2))
    state, _ = shard_train_state(state, mesh, tx)
    step = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False)
    sbatch = put_batch(batch, mesh)
    state, metrics = step(state, sbatch)

    np.testing.assert_allclose(
        float(metrics["loss"]), float(ref_metrics["loss"]), rtol=2e-4
    )
    # params after one step agree leaf-for-leaf
    ref_leaf = ref_state.params["blocks"]["qkv"]["w"]
    got_leaf = jax.device_get(state.params["blocks"]["qkv"]["w"])
    np.testing.assert_allclose(got_leaf, np.asarray(ref_leaf), rtol=1e-3, atol=1e-5)
    # and a second step runs (no recompilation blowups / donation issues)
    state, metrics2 = step(state, sbatch)
    assert float(metrics2["loss"]) == float(metrics2["loss"])


# The four models that keep q, k and v in one fused leaf, small enough for the
# CPU and with a width both head counts below divide.
_LM = dict(vocab=128, max_len=32, d_model=96, n_layers=2, d_ff=192)
FUSED_QKV_MODELS = {
    "gpt2_small": _LM,
    "bert_mlm": _LM,
    "cifar10_vit": dict(image_size=16, patch_size=4, d_model=96, n_layers=2, d_ff=192),
    "gpt2_moe": dict(_LM, n_experts=4),
}


@pytest.fixture
def qkv_layouts():
    """Counts of ``swarm.qkv_projection`` as a volunteer's telemetry takes them: by the ``tp`` the note carries."""
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.utils import traced

    tel = Telemetry(peer_id="t")
    with traced.subscribe(tel.count_traced):
        yield lambda: tel.summary()["qkv_projection"]  # what coord.status shows per peer


@pytest.mark.parametrize("n_heads", [4, 3])
@pytest.mark.parametrize("model", sorted(FUSED_QKV_MODELS))
def test_qkv_over_tp_matches_single_device(eight_devices, qkv_layouts, model, n_heads):
    """dp=2,tp=2: q, k and v are born merged ([B, T, d] each) either way, as
    column-parallel products off the leaf's head-aligned view where tp divides
    the heads and off the leaf's three column ranges where it does not; the
    loss and every gradient leaf (plain SGD at lr 1: the step's change of a
    leaf) are the single-device step's."""
    import optax

    bundle = get_model(model, n_heads=n_heads, remat=False, **FUSED_QKV_MODELS[model])
    tx = optax.sgd(1.0)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = bundle.make_batch(jax.random.PRNGKey(1), 8)

    def grads(state):
        return jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), params, state.params)

    ref_state, ref_metrics = make_train_step(bundle.loss_fn, tx, donate=False)(
        TrainState.create(params, tx, jax.random.PRNGKey(2)), batch
    )
    assert qkv_layouts() == {"1": 1}  # no step mesh: one trace, q, k and v [B, T, d]

    mesh = make_mesh(dp=2, tp=2)
    state, _ = shard_train_state(TrainState.create(params, tx, jax.random.PRNGKey(2)), mesh, tx)
    stored = jax.tree_util.tree_map(lambda x: x.sharding, state.params)
    state, metrics = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False)(
        state, put_batch(batch, mesh)
    )
    assert qkv_layouts() == {"1": 1, "2": 1}  # the step's mesh has tp = 2, whether or not it divides the heads
    # the head-aligned view lives inside the step: leaves keep their stored layout
    assert stored["blocks"]["qkv"]["w"].spec == P(None, None, "tp")
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda x, was: x.sharding.is_equivalent_to(was, x.ndim), state.params, stored
    )))

    np.testing.assert_allclose(float(metrics["loss"]), float(ref_metrics["loss"]), rtol=2e-4)
    ref_grads = grads(ref_state)
    assert float(np.abs(ref_grads["blocks"]["qkv"]["w"]).max()) > 1e-4  # not vacuous
    jax.tree_util.tree_map(
        lambda got, ref: np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-5),
        grads(state), ref_grads,
    )


@pytest.mark.parametrize("dp,tp", [(2, 2), (4, 1)], ids=["dp2-tp2", "dp4-tp1"])
def test_remat_layer_keeps_the_reduced_attention_product_over_tp(eight_devices, monkeypatch, dp, tp):
    """A rematerialised gpt2 step on a mesh: where ``tp`` divides the layer the
    block's checkpoint keeps the row-parallel attention product's result after
    its sum over ``tp`` (so the backward repeats no all-reduce) and
    ``swarm.remat_kept`` counts a chip's share of it; where ``tp`` is 1 nothing
    is named or counted (the CPU's layers run the XLA core). Either way the
    loss and every gradient leaf (plain SGD at lr 1) are those of the same
    step under a bare ``jax.checkpoint`` and of the single-device step."""
    import optax

    from distributedvolunteercomputing_tpu.models import common
    from distributedvolunteercomputing_tpu.ops import attention
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.utils import traced

    NAMED = f"name={attention.TP_REDUCED}]"
    cfg = dict(_LM, n_heads=4, remat=True)
    tx = optax.sgd(1.0)
    bundle = get_model("gpt2_small", **cfg)
    params, batch = bundle.init(jax.random.PRNGKey(0)), bundle.make_batch(jax.random.PRNGKey(1), 8)

    def step_on(mesh):  # a new bundle a step: a traced loss is cached
        bundle = get_model("gpt2_small", **cfg)
        state = TrainState.create(params, tx, jax.random.PRNGKey(2))
        if mesh is None:
            step, put = make_train_step(bundle.loss_fn, tx, donate=False), batch
        else:
            state, _ = shard_train_state(state, mesh, tx)
            step, put = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False), put_batch(batch, mesh)
        text = str(step.trace(state, put).jaxpr)  # the name is in the jaxpr, not in what it lowers to
        state, metrics = step(state, put)
        grads = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), params, state.params)
        return float(metrics["loss"]), grads, text

    tel = Telemetry(peer_id="t")
    with traced.subscribe(tel.count_traced):
        ref_loss, ref_grads, ref_text = step_on(None)
        assert tel.traced_summary()["remat_kept"] == {} and NAMED not in ref_text
        loss, grads, text = step_on(make_mesh(dp=dp, tp=tp))
        kept = tel.traced_summary()["remat_kept"]
    if tp > 1:
        # one scanned block traced for both layers: a chip's [8 / dp, 32, 96] f32 each
        assert kept == {"traced_layers": 1, "bytes_a_step": 2 * (8 // dp) * 32 * 96 * 4}
        assert text.count(NAMED) == 1
    else:
        assert kept == {} and NAMED not in text
    monkeypatch.setattr(common, "remat_layer", lambda body, *layers_and_calls: jax.checkpoint(body))
    bare_loss, bare_grads, bare_text = step_on(make_mesh(dp=dp, tp=tp))
    # what the name buys: the backward's recomputed forward runs no attention output product
    assert bare_text.count("dot_general") - text.count("dot_general") == (1 if tp > 1 else 0)

    assert float(np.abs(ref_grads["blocks"]["attn_out"]["w"]).max()) > 1e-4  # not vacuous
    for want_loss, want in ((bare_loss, bare_grads), (ref_loss, ref_grads)):
        np.testing.assert_allclose(loss, want_loss, rtol=2e-4)
        jax.tree_util.tree_map(
            lambda got, ref: np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-5), grads, want)


@pytest.fixture
def tp_streams():
    """Counts of ``swarm.tp_streams`` as a volunteer's telemetry takes them."""
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry
    from distributedvolunteercomputing_tpu.utils import traced

    tel = Telemetry(peer_id="t")
    with traced.subscribe(tel.count_traced):
        yield lambda: tel.summary()["tp_streams"]  # what coord.status shows per peer


def _without_row_streams(monkeypatch):
    """``common.scan_blocks`` as it was before it knew of row streams."""
    from distributedvolunteercomputing_tpu.models import common

    scan_blocks = common.scan_blocks
    monkeypatch.setattr(common, "scan_blocks", lambda *a, rows_independent=False, **kw: scan_blocks(*a, **kw))


@pytest.mark.parametrize("rows,dp,tp,streams", [
    (16, 2, 2, 2),  # large-solo-4chip's layout: 8 rows a replica, two streams of 4
    (8, 2, 4, 2),
    (2, 2, 2, 1),   # one row a replica
    (6, 2, 2, 1),   # an odd count a replica
    (16, 4, 1, 1),  # no tp to sum over
], ids=["dp2-tp2", "dp2-tp4", "one-row", "odd-rows", "dp4-tp1"])
def test_two_row_streams_over_tp_match_one_stream_and_single_device(
        eight_devices, monkeypatch, tp_streams, rows, dp, tp, streams):
    """A rematerialised gpt2 step on a mesh: where ``tp`` divides the layer and
    each replica's rows are even the scanned body runs them as two independent
    streams (``swarm.tp_streams`` reads 2) and the loss and every gradient leaf
    (plain SGD at lr 1) are those of the one-stream body on the same mesh and
    of the single-device step; where a replica holds one row or an odd count,
    or ``tp`` is 1, the count reads 1 and the step's jaxpr IS the one-stream
    body's."""
    import re

    import optax

    cfg = dict(_LM, n_heads=4, remat=True)
    tx = optax.sgd(1.0)
    params = get_model("gpt2_small", **cfg).init(jax.random.PRNGKey(0))
    batch = get_model("gpt2_small", **cfg).make_batch(jax.random.PRNGKey(1), rows)

    def step_on(mesh, run=True):  # a new bundle a step: a traced loss is cached
        bundle = get_model("gpt2_small", **cfg)
        state = TrainState.create(params, tx, jax.random.PRNGKey(2))
        if mesh is None:
            step, put = make_train_step(bundle.loss_fn, tx, donate=False), batch
        else:
            state, _ = shard_train_state(state, mesh, tx)
            step, put = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False), put_batch(batch, mesh)
        text = re.sub(r"0x[0-9a-f]+", "0x", str(step.trace(state, put).jaxpr))  # a function's address
        if not run:
            return None, None, text
        state, metrics = step(state, put)
        grads = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), params, state.params)
        return float(metrics["loss"]), grads, text

    ref_loss, ref_grads, _ = step_on(None)
    assert tp_streams() == {"1": 1}  # no step mesh: one stream, one trace
    loss, grads, text = step_on(make_mesh(dp=dp, tp=tp))
    assert tp_streams() == ({"1": 1, "2": 1} if streams == 2 else {"1": 2})
    _without_row_streams(monkeypatch)
    # where the jaxprs are one the one-stream step is this step: traced for its text, run only where they differ
    one_loss, one_grads, one_text = step_on(make_mesh(dp=dp, tp=tp), run=streams == 2)
    assert (text == one_text) == (streams == 1)

    assert float(np.abs(ref_grads["blocks"]["attn_out"]["w"]).max()) > 1e-4  # not vacuous
    for want_loss, want in ((one_loss, one_grads), (ref_loss, ref_grads))[streams == 1:]:
        np.testing.assert_allclose(loss, want_loss, rtol=2e-4)
        jax.tree_util.tree_map(
            lambda got, ref: np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-5), grads, want)


def test_row_streams_keep_each_replica_s_rows_on_its_replica(eight_devices):
    """The streams are halves of each ``dp`` replica's rows, not of the global
    batch: ``split_rows`` of rows 0..15 over dp=2 gives rows 0-3 + 8-11 and
    4-7 + 12-15, each stream laid out over ``dp`` with no row leaving its
    replica, and ``merge_rows`` puts them back in order."""
    from distributedvolunteercomputing_tpu.ops import attention

    mesh = make_mesh(dp=2, tp=2)
    x = jnp.arange(16 * 3, dtype=jnp.float32).reshape(16, 3)

    @jax.jit
    def there_and_back(x):
        with attention.step_mesh(mesh):
            parts = attention.split_rows(x, 2)
            return parts, attention.merge_rows(parts)

    x = jax.device_put(x, jax.sharding.NamedSharding(mesh, P("dp")))
    (a, b), back = there_and_back(x)
    np.testing.assert_array_equal(np.asarray(a)[:, 0] // 3, [0, 1, 2, 3, 8, 9, 10, 11])
    np.testing.assert_array_equal(np.asarray(b)[:, 0] // 3, [4, 5, 6, 7, 12, 13, 14, 15])
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    for part in (a, b, back):
        assert part.sharding.spec[0] == "dp", part.sharding
    text = there_and_back.lower(x).compile().as_text()
    assert "all-to-all" not in text and "collective-permute" not in text and "all-gather" not in text


def test_sharded_multi_step_runs_the_two_stream_body(eight_devices, tp_streams):
    """``make_sharded_multi_step`` scans the SAME traced body as the single
    step: over dp=2,tp=2 its layer scan runs two row streams too, and N
    scanned steps give the N per-step calls' losses and parameters."""
    from distributedvolunteercomputing_tpu.parallel.train_step import make_sharded_multi_step

    bundle = get_model("gpt2_small", **dict(TINY_GPT2, remat=True))
    tx = make_optimizer("adam", lr=1e-3)
    batches = [bundle.make_batch(jax.random.PRNGKey(10 + i), 8) for i in range(2)]
    mesh = make_mesh(dp=2, tp=2)
    ref_state, _ = shard_train_state(
        TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(2)), mesh, tx)
    step = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False)
    losses_ref = []
    for b in batches:
        ref_state, m = step(ref_state, put_batch(b, mesh))
        losses_ref.append(float(m["loss"]))
    assert tp_streams() == {"2": 1}

    state, _ = shard_train_state(
        TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(2)), mesh, tx)
    multi = make_sharded_multi_step(bundle.loss_fn, tx, mesh)
    state, losses = multi(state, jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches))
    assert tp_streams() == {"2": 2}
    np.testing.assert_allclose(np.asarray(losses), np.asarray(losses_ref), rtol=2e-4)
    jax.tree_util.tree_map(
        lambda got, ref: np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-3, atol=1e-5),
        state.params, ref_state.params)


# heads of 64 under dp=2, tp=2 -> (what the three products read of the leaf, the layout and the core attention_merged hands on, kernels forced)
_QKV_OVER_TP = {
    4: ("view", "merged", "flash"),   # two heads a chip: one 128-lane block, the pair kernels per shard
    3: ("ranges", "heads", "xla"),    # tp does not divide the heads: nothing laid out, GSPMD's own core
    6: ("view", "heads", "flash"),    # three heads a chip are no whole blocks: the by-head fallback, per shard
}


@pytest.mark.parametrize("n_heads", list(_QKV_OVER_TP))
def test_merged_projection_over_tp_is_the_no_mesh_projection(eight_devices, n_heads):
    """``common.qkv_heads`` and ``fused_qkv_attention`` under a step mesh of
    dp=2, tp=2 against the same call with no mesh, float32: q, k, v, the
    attention's output and every gradient (the leaf's weight and bias, ``x``).
    Where tp divides the heads the three products read the leaf's head-aligned
    view; where a chip's heads are whole blocks the pair kernels take them in
    place, and where not ``attention_merged`` falls back to ``split_heads`` +
    ``attention_core`` + ``merge_heads``: by head lives there and nowhere else."""
    from jax.sharding import NamedSharding

    from distributedvolunteercomputing_tpu.models import common
    from distributedvolunteercomputing_tpu.ops.attention import set_attention_impl, step_mesh
    from distributedvolunteercomputing_tpu.utils import traced

    projection, layout, core = _QKV_OVER_TP[n_heads]
    d = 64 * n_heads
    mesh = make_mesh(dp=2, tp=2)
    leaf = common.dense_init(jax.random.PRNGKey(0), d, 3 * d)
    leaf["b"] = jax.random.normal(jax.random.PRNGKey(3), (3 * d,)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 128, d))
    cots = jax.random.normal(jax.random.PRNGKey(2), (4, 4, 128, d))

    def loss(leaf, x):
        layout, qkv = common.qkv_heads(leaf, x, n_heads)
        assert layout == "merged"
        out = common.fused_qkv_attention(leaf, x, n_heads, causal=True)
        return jnp.sum(cots * jnp.stack([*qkv, out])), (qkv, out)

    seen = []
    set_attention_impl("flash")
    try:
        want = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(leaf, x)
        with step_mesh(mesh), traced.subscribe(lambda kind, labels: seen.append((kind, labels))):
            fn = jax.jit(
                jax.value_and_grad(loss, argnums=(0, 1), has_aux=True),
                in_shardings=({"w": NamedSharding(mesh, P(None, "tp")), "b": NamedSharding(mesh, P("tp"))},
                              NamedSharding(mesh, P("dp"))),
            ).trace(leaf, x)
            got = fn.lower().compile()(leaf, x)
    finally:
        set_attention_impl("auto")
    assert {labels["tp"] for kind, labels in seen if kind == "qkv_projection"} == {2}
    assert ("sharding_constraint" in str(fn.jaxpr)) == (projection == "view")
    assert [(labels["layout"], labels["impl"]) for kind, labels in seen if kind == "attention_core"] == [(layout, core)]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4), got, want)
    assert float(jnp.abs(want[1][0]["b"]).max()) > 1e-3  # not vacuous


def test_qkv_stays_fused_where_tp_is_manual(eight_devices, qkv_layouts):
    """Inside a ``shard_map`` that has made ``tp`` manual the trace sees one
    chip's share: nothing is left to divide, and the projection reads the
    fused leaf's three column ranges with no view and no constraint
    (``tp`` noted 1); a manual ``pp`` (a pipeline stage) leaves ``tp`` to
    divide, and the three products are column-parallel off the head-aligned
    view (``tp`` noted 2). Merged, [B, T, d] each, both ways."""
    from distributedvolunteercomputing_tpu.models import common
    from distributedvolunteercomputing_tpu.ops.attention import step_mesh
    from distributedvolunteercomputing_tpu.parallel.mesh import shard_map_manual
    from distributedvolunteercomputing_tpu.utils import traced

    mesh = make_mesh(dp=2, pp=2, tp=2)
    leaf = common.dense_init(jax.random.PRNGKey(0), 32, 96)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
    layout, want = common.qkv_heads(leaf, x, 4)
    assert layout == "merged" and want[0].shape == (4, 8, 32)
    for n, (axis, tp) in enumerate((("tp", 1), ("pp", 2)), start=2):
        def project(x):
            layout, qkv = common.qkv_heads(leaf, x, 4)
            assert layout == "merged"
            return jnp.stack(qkv)

        seen = []
        with step_mesh(mesh), traced.subscribe(lambda kind, labels: seen.append((kind, labels))):
            fn = jax.jit(shard_map_manual(project, mesh, P(), P(), axis)).trace(x)  # traced once
            viewed = "sharding_constraint" in str(fn.jaxpr)
            got = fn.lower().compile()(x)
        np.testing.assert_allclose(got, jnp.stack(want), rtol=1e-5, atol=1e-6)
        assert sum(qkv_layouts().values()) == n and qkv_layouts()[str(tp)] == (2 if tp == 1 else 1)
        assert {labels["tp"] for kind, labels in seen if kind == "qkv_projection"} == {tp}
        assert viewed == (tp == 2)


def test_sharded_step_with_accum_matches_single_device(eight_devices):
    # Gradient accumulation inside the SHARDED step: dp-sharded [accum*B]
    # batch scanned as microbatches; numerics must still match the
    # single-device big-batch step.
    bundle = get_model("gpt2_small", **TINY_GPT2)
    tx = make_optimizer("adam", lr=1e-3)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = bundle.make_batch(jax.random.PRNGKey(1), 16)

    ref_state, ref_metrics = single_device_step()

    mesh = make_mesh(dp=2, tp=4)
    state = TrainState.create(params, tx, jax.random.PRNGKey(2))
    state, _ = shard_train_state(state, mesh, tx)
    step = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False, accum_steps=2)
    state, metrics = step(state, put_batch(batch, mesh))

    np.testing.assert_allclose(
        float(metrics["loss"]), float(ref_metrics["loss"]), rtol=2e-4
    )
    got = jax.device_get(state.params["blocks"]["qkv"]["w"])
    np.testing.assert_allclose(
        got, np.asarray(ref_state.params["blocks"]["qkv"]["w"]), rtol=1e-3, atol=1e-5
    )


def test_sharded_step_llama_lora(eight_devices):
    bundle = get_model(
        "llama_lora", vocab=256, max_len=32, d_model=64, n_heads=4, n_kv_heads=4,
        n_layers=2, d_ff=128, lora_rank=4, remat=False,
    )
    tx = make_optimizer("adam", lr=1e-3)
    mesh = make_mesh(dp=2, tp=4)
    state = TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(2))
    state, shardings = shard_train_state(state, mesh, tx)
    assert shardings["base"]["blocks"]["wq"].spec == P(None, None, "tp")
    assert shardings["base"]["lm_head"].spec == P(None, "tp")
    step = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False)
    batch = put_batch(bundle.make_batch(jax.random.PRNGKey(1), 16), mesh)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


class TestZero1:
    """ZeRO-1 optimizer-state sharding over dp (make_zero1_opt_shardings):
    moments live distributed, numerics identical to the replicated step."""

    def _mu_leaf(self, opt_state):
        # The optimizer is a chain (grad clip, adam core, ...); find the
        # ScaleByAdamState anywhere in it and grab mu's qkv/w leaf.
        found = []

        def visit(node):
            if hasattr(node, "mu"):
                found.append(node)
                return True
            return False

        jax.tree_util.tree_leaves(opt_state, is_leaf=visit)
        assert found, "no adam moment state in opt_state"
        return found[0].mu["blocks"]["qkv"]["w"]

    def test_moments_are_dp_sharded_and_numerics_match(self, eight_devices):
        bundle = get_model("gpt2_small", **TINY_GPT2)
        tx = make_optimizer("adam", lr=1e-3)
        params = bundle.init(jax.random.PRNGKey(0))
        batch = bundle.make_batch(jax.random.PRNGKey(1), 16)

        ref_state, ref_metrics = single_device_step()

        mesh = make_mesh(dp=2, tp=4)
        state = TrainState.create(params, tx, jax.random.PRNGKey(2))
        state, _ = shard_train_state(state, mesh, tx, zero1=True)
        mu = self._mu_leaf(state.opt_state)
        # [L, d_in, d_out] qkv moment: dp on the layer axis, tp on features
        assert mu.sharding.spec == P("dp", None, "tp")
        shard_elems = mu.addressable_shards[0].data.size
        assert shard_elems == mu.size // 8  # dp2 x tp4 of 8 devices

        step = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False, zero1=True)
        state, metrics = step(state, put_batch(batch, mesh))
        np.testing.assert_allclose(
            float(metrics["loss"]), float(ref_metrics["loss"]), rtol=2e-4
        )
        got = jax.device_get(state.params["blocks"]["qkv"]["w"])
        np.testing.assert_allclose(
            got, np.asarray(ref_state.params["blocks"]["qkv"]["w"]), rtol=1e-3, atol=1e-5
        )
        # moments agree with the single-device run AND stay dp-sharded after
        # the step (the in-step constraint is what prevents re-replication)
        mu2 = self._mu_leaf(state.opt_state)
        assert mu2.sharding.spec == P("dp", None, "tp")
        np.testing.assert_allclose(
            jax.device_get(mu2),
            np.asarray(self._mu_leaf(ref_state.opt_state)),
            rtol=1e-3,
            atol=1e-6,
        )

    def test_embedding_moment_shards_on_feature_dim(self, eight_devices):
        # wte is [V, D] with V=128 here; dp lands on dim 0 when divisible.
        # With the real vocab 50257 (prime) dim 0 doesn't divide — the rule
        # must fall through to the feature dim instead of replicating.
        from distributedvolunteercomputing_tpu.parallel import make_zero1_opt_shardings

        mesh = make_mesh(dp=2, tp=4)
        fake = {"wte": jnp.zeros((50257, 64)), "ln_f": {"g": jnp.zeros((63,))}}
        sh = make_zero1_opt_shardings(mesh, fake)
        assert sh["wte"].spec == P(None, "dp")
        # 63 divides by neither dp nor tp → replicated
        assert sh["ln_f"]["g"].spec == P()

    def test_second_step_and_donation(self, eight_devices):
        bundle = get_model("gpt2_small", **TINY_GPT2)
        tx = make_optimizer("adamw", lr=1e-3)
        mesh = make_mesh(dp=4, tp=2)
        state = TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(2))
        state, _ = shard_train_state(state, mesh, tx, zero1=True)
        step = make_sharded_train_step(bundle.loss_fn, tx, mesh, zero1=True)
        batch = put_batch(bundle.make_batch(jax.random.PRNGKey(1), 8), mesh)
        state, m1 = step(state, batch)
        state, m2 = step(state, batch)
        assert np.isfinite(float(m2["loss"]))
        # L=2 doesn't divide dp=4, so dp falls through to the d_in dim
        assert self._mu_leaf(state.opt_state).sharding.spec == P(None, "dp", "tp")


class TestFSDP:
    """ZeRO-3 / FSDP: params themselves dp-sharded; weights+grads+opt state
    all at 1/dp per chip, numerics identical to the replicated step."""

    def test_params_sharded_and_numerics_match(self, eight_devices):
        bundle = get_model("gpt2_small", **TINY_GPT2)
        tx = make_optimizer("adam", lr=1e-3)
        params = bundle.init(jax.random.PRNGKey(0))
        batch = bundle.make_batch(jax.random.PRNGKey(1), 16)

        ref_state, ref_metrics = single_device_step()

        mesh = make_mesh(dp=2, tp=4)
        state = TrainState.create(params, tx, jax.random.PRNGKey(2))
        state, shardings = shard_train_state(state, mesh, tx, fsdp=True)
        w = state.params["blocks"]["qkv"]["w"]  # [L=2, 64, 192]
        assert w.sharding.spec == P("dp", None, "tp")
        assert w.addressable_shards[0].data.size == w.size // 8

        step = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False, fsdp=True)
        state, metrics = step(state, put_batch(batch, mesh))
        np.testing.assert_allclose(
            float(metrics["loss"]), float(ref_metrics["loss"]), rtol=2e-4
        )
        got = jax.device_get(state.params["blocks"]["qkv"]["w"])
        np.testing.assert_allclose(
            got, np.asarray(ref_state.params["blocks"]["qkv"]["w"]), rtol=1e-3, atol=1e-5
        )
        # updated params STAY dp-sharded (the in-step constraint)
        assert state.params["blocks"]["qkv"]["w"].sharding.spec == P("dp", None, "tp")
        # second step runs under donation-free path
        state, m2 = step(state, put_batch(batch, mesh))
        assert np.isfinite(float(m2["loss"]))

    def test_fsdp_dp_only_mesh(self, eight_devices):
        # Pure-dp FSDP (no tp): the common volunteer-slice shape.
        bundle = get_model("gpt2_small", **TINY_GPT2)
        tx = make_optimizer("adamw", lr=1e-3)
        mesh = make_mesh(dp=8)
        state = TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(2))
        state, _ = shard_train_state(state, mesh, tx, fsdp=True)
        # wte [128, 64]: dp=8 divides dim 0
        assert state.params["wte"].sharding.spec == P("dp")
        step = make_sharded_train_step(bundle.loss_fn, tx, mesh, fsdp=True)
        batch = put_batch(bundle.make_batch(jax.random.PRNGKey(1), 16), mesh)
        state, m = step(state, batch)
        state, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))
        assert state.params["wte"].sharding.spec == P("dp")


class TestLlama7BScale:
    """Config-5 at its NOMINAL scale (BASELINE.json:11 finetunes Llama-2-7B):
    validated abstractly via eval_shape — shapes, param count, and the
    per-chip memory arithmetic under FSDP — without allocating 7B params."""

    def test_7b_preset_shapes_and_fsdp_fit(self, eight_devices):
        from distributedvolunteercomputing_tpu.models import llama
        from distributedvolunteercomputing_tpu.parallel import make_fsdp_param_shardings

        cfg = llama.LlamaConfig.llama2_7b()
        abstract = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
        n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(abstract))
        assert 6.5e9 < n_params < 7.2e9, n_params  # the 7B in Llama-2-7B

        # FSDP over a dp=8 slice: every big leaf must actually shard.
        mesh = make_mesh(dp=8)
        shardings = make_fsdp_param_shardings(mesh, abstract)

        def frac_sharded(leaf, sh):
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            denom = 1
            spec = list(sh.spec) + [None] * (len(leaf.shape) - len(sh.spec))
            for ax in spec:
                if ax is not None:
                    denom *= sizes[ax]
            return denom

        total = 0
        per_chip = 0
        for leaf, sh in zip(
            jax.tree_util.tree_leaves(abstract), jax.tree_util.tree_leaves(shardings)
        ):
            sz = int(np.prod(leaf.shape))
            total += sz
            per_chip += sz // frac_sharded(leaf, sh)
        # weights f32 + AdamW mu/nu (moments shard identically): per-chip
        # bytes must fit a 16 GB chip with room for activations; replicated
        # they cannot (~27 GB params alone at f32... 7e9*4 = 28 GB).
        bytes_per_chip = per_chip * 4 * 3  # params + mu + nu, f32
        assert bytes_per_chip < 16e9, f"{bytes_per_chip / 1e9:.1f} GB/chip"
        assert total * 4 > 16e9  # replicated would not fit — fsdp is load-bearing

    def test_7b_lora_payload_is_small(self):
        import dataclasses

        from distributedvolunteercomputing_tpu.models import llama

        cfg = llama.LlamaConfig.llama2_7b()
        bundle = get_model("llama_lora", **dataclasses.asdict(cfg))
        abstract = jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0)))
        adapters = bundle.avg_select(abstract)
        n_adapter = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(adapters))
        n_total = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(abstract))
        # the WAN round ships adapters only: orders of magnitude less
        assert n_adapter < n_total / 500, (n_adapter, n_total)


class TestTrainerOnMesh:
    """A volunteer that owns a multi-chip slice: the Trainer drives the
    sharded step over an in-slice mesh while the WAN tier (the averager
    callback) still sees host numpy pytrees — the per-volunteer-slice
    contract (SURVEY.md §1 TPU mapping)."""

    def test_params_mode_with_averaging_and_fsdp(self, eight_devices):
        from distributedvolunteercomputing_tpu.training.trainer import Trainer

        bundle = get_model("gpt2_small", **TINY_GPT2)
        mesh = make_mesh(dp=2, tp=4)
        calls = []

        def averager(payload, step_no):
            # WAN contract: host numpy in, averaged pytree out.
            assert all(isinstance(x, np.ndarray) for x in jax.tree_util.tree_leaves(payload))
            calls.append(step_no)
            return jax.tree_util.tree_map(lambda x: x * 0.5, payload)

        t = Trainer(
            bundle, batch_size=16, lr=1e-3, mesh=mesh, fsdp=True,
            average_every=3, averager=averager, overlap=False,
        )
        summary = t.run(steps=7, log_every=0)
        assert np.isfinite(summary["final_loss"])
        assert calls == [3, 6]
        # after the averaging swap, params are STILL mesh-sharded (fsdp)
        w = t.state.params["blocks"]["qkv"]["w"]
        assert w.sharding.spec == P("dp", None, "tp")

    def test_grads_mode_on_mesh_matches_replicated(self, eight_devices):
        from distributedvolunteercomputing_tpu.training.trainer import Trainer

        bundle = get_model("gpt2_small", **TINY_GPT2)

        def identity_avg(payload, step_no):
            return payload  # group of one: average == own grads

        kw = dict(
            batch_size=16, lr=1e-3, seed=0, init_seed=0,
            average_every=4, averager=identity_avg, average_what="grads",
        )
        ref = Trainer(bundle, **kw)
        ref_summary = ref.run(steps=3, log_every=0)

        mesh = make_mesh(dp=2, tp=4)
        t = Trainer(bundle, mesh=mesh, **kw)
        summary = t.run(steps=3, log_every=0)
        np.testing.assert_allclose(
            summary["final_loss"], ref_summary["final_loss"], rtol=2e-4
        )

    def test_checkpoint_restore_keeps_mesh_placement(self, eight_devices, tmp_path):
        # A restarted mesh/fsdp volunteer must come back SHARDED: a plain
        # device_put restore would replicate a model that only fits at 1/dp.
        from distributedvolunteercomputing_tpu.training import checkpoint
        from distributedvolunteercomputing_tpu.training.trainer import Trainer

        bundle = get_model("gpt2_small", **TINY_GPT2)
        mesh = make_mesh(dp=2, tp=4)
        t = Trainer(bundle, batch_size=8, mesh=mesh, fsdp=True)
        t.run(steps=2, log_every=0)
        checkpoint.save(t, str(tmp_path))

        t2 = Trainer(bundle, batch_size=8, mesh=mesh, fsdp=True)
        assert checkpoint.maybe_restore(t2, str(tmp_path))
        w = t2.state.params["blocks"]["qkv"]["w"]
        assert w.sharding.spec == P("dp", None, "tp")
        assert w.addressable_shards[0].data.size == w.size // 8
        assert int(t2.state.step) == 2
        s = t2.run(steps=1, log_every=0)
        assert np.isfinite(s["final_loss"])

    def test_config_validation(self, eight_devices):
        from distributedvolunteercomputing_tpu.parallel.mesh import parse_mesh_spec
        from distributedvolunteercomputing_tpu.training.trainer import Trainer

        bundle = get_model("mnist_mlp")
        with pytest.raises(ValueError, match="require a mesh"):
            Trainer(bundle, fsdp=True)
        with pytest.raises(ValueError, match="params-mode"):
            Trainer(
                bundle, mesh=make_mesh(dp=2), fsdp=True,
                averager=lambda p, s: p, average_what="grads",
            )
        assert parse_mesh_spec("dp=2,tp=2,") == {"dp": 2, "tp": 2}
        for bad in ("dp2", "x=2", "dp=", "dp=0", ""):
            with pytest.raises(ValueError, match="mesh spec"):
                parse_mesh_spec(bad)

    def test_evaluate_under_fsdp(self, eight_devices):
        # evaluate() on a ZeRO-3-sharded trainer: jit respects the params'
        # input shardings (the fsdp hazard is OUTPUT state drift, which eval
        # has none of) — must run and leave the params sharded.
        from distributedvolunteercomputing_tpu.training.trainer import Trainer

        bundle = get_model("gpt2_small", **TINY_GPT2)
        mesh = make_mesh(dp=2, tp=4)
        t = Trainer(bundle, batch_size=8, mesh=mesh, fsdp=True, eval_every=2, eval_batches=2)
        ev = t.evaluate()
        assert np.isfinite(ev)
        t.run(steps=2, log_every=0)
        assert t.state.params["blocks"]["qkv"]["w"].sharding.spec == P("dp", None, "tp")

    def test_adopt_params_keeps_mesh_placement(self, eight_devices):
        from distributedvolunteercomputing_tpu.training.trainer import Trainer

        bundle = get_model("gpt2_small", **TINY_GPT2)
        mesh = make_mesh(dp=2, tp=4)
        t = Trainer(bundle, batch_size=8, mesh=mesh, fsdp=True)
        host = jax.tree_util.tree_map(np.asarray, jax.device_get(t.state.params))
        t.adopt_params(host, step=5)
        assert t.state.params["blocks"]["qkv"]["w"].sharding.spec == P("dp", None, "tp")
        s = t.run(steps=2, log_every=0)
        assert np.isfinite(s["final_loss"])


def test_shard_train_state_preserves_warm_opt_state(eight_devices):
    # A checkpoint-resumed state has non-zero Adam moments; placing it on the
    # mesh must keep their VALUES (re-initialising would silently cold-start
    # the optimizer while keeping step/rng — a loss spike with no error).
    bundle = get_model("gpt2_small", **TINY_GPT2)
    tx = make_optimizer("adam", lr=1e-2)
    state = TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(1))
    step1 = make_train_step(bundle.loss_fn, tx, donate=False)
    batch = bundle.make_batch(jax.random.PRNGKey(2), 4)
    for _ in range(2):
        state, _ = step1(state, batch)

    warm_flat = [np.asarray(x) for x in jax.tree_util.tree_leaves(state.opt_state)]
    assert any(np.abs(x).max() > 0 for x in warm_flat if x.ndim > 0)

    mesh = make_mesh(dp=4, tp=2)
    sharded, shardings = shard_train_state(state, mesh, tx)
    for before, after in zip(warm_flat, jax.tree_util.tree_leaves(sharded.opt_state)):
        np.testing.assert_array_equal(before, np.asarray(after))
    assert int(sharded.step) == 2
    # params-shaped moment subtrees carry the params' shardings
    mu = jax.tree_util.tree_leaves(sharded.opt_state)[1]
    step2 = make_sharded_train_step(bundle.loss_fn, tx, mesh)
    with mesh:
        sharded, m = step2(sharded, put_batch(batch, mesh))
    assert np.isfinite(float(m["loss"]))


def test_sharded_multi_step_matches_per_step(eight_devices):
    """make_sharded_multi_step (r4 VERDICT missing #5): N scanned sharded
    steps must be bit-compatible with N per-step calls of the sharded step
    — dispatch granularity, not different math — including under fsdp,
    whose in-step re-constraints the scan body must carry."""
    from distributedvolunteercomputing_tpu.parallel.train_step import (
        make_sharded_multi_step,
    )

    bundle = get_model("gpt2_small", **TINY_GPT2)
    tx = make_optimizer("adam", lr=1e-3)
    batches = [bundle.make_batch(jax.random.PRNGKey(10 + i), 8) for i in range(3)]

    for fsdp in (False, True):
        # Fresh init per arm: on the CPU backend device_put of a replicated
        # leaf can ALIAS the source buffer, and the donating multi-step
        # then deletes it out from under a reused params tree (the same
        # donation gotcha the verify recipe documents).
        params = bundle.init(jax.random.PRNGKey(0))
        mesh = make_mesh(dp=2, tp=4)
        ref_state = TrainState.create(params, tx, jax.random.PRNGKey(2))
        ref_state, _ = shard_train_state(ref_state, mesh, tx, fsdp=fsdp)
        step = make_sharded_train_step(
            bundle.loss_fn, tx, mesh, donate=False, fsdp=fsdp
        )
        losses_ref = []
        for b in batches:
            ref_state, m = step(ref_state, put_batch(b, mesh))
            losses_ref.append(float(m["loss"]))

        params2 = bundle.init(jax.random.PRNGKey(0))
        state = TrainState.create(params2, tx, jax.random.PRNGKey(2))
        state, _ = shard_train_state(state, mesh, tx, fsdp=fsdp)
        multi = make_sharded_multi_step(bundle.loss_fn, tx, mesh, fsdp=fsdp)
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)
        state, losses = multi(state, stacked)

        np.testing.assert_allclose(
            np.asarray(losses), np.asarray(losses_ref), rtol=2e-4,
            err_msg=f"fsdp={fsdp}",
        )
        ref_leaf = jax.device_get(ref_state.params["blocks"]["qkv"]["w"])
        got_leaf = jax.device_get(state.params["blocks"]["qkv"]["w"])
        np.testing.assert_allclose(got_leaf, ref_leaf, rtol=1e-3, atol=1e-5)


def test_trainer_mesh_steps_per_call(eight_devices):
    """Trainer accepts steps_per_call > 1 WITH a mesh (previously rejected)
    and lands on the same params as the per-step mesh trainer."""
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    kw = dict(batch_size=8, lr=1e-3, optimizer="adam", seed=3, init_seed=7)
    bundle = get_model("gpt2_small", **TINY_GPT2)
    t1 = Trainer(bundle, mesh=make_mesh(dp=2, tp=4), **kw)
    s1 = t1.run(steps=6, log_every=0)
    bundle2 = get_model("gpt2_small", **TINY_GPT2)
    t2 = Trainer(bundle2, mesh=make_mesh(dp=2, tp=4), steps_per_call=3, **kw)
    s2 = t2.run(steps=6, log_every=0)
    np.testing.assert_allclose(s1["final_loss"], s2["final_loss"], rtol=2e-4)
    a = jax.device_get(t1.state.params["blocks"]["qkv"]["w"])
    b = jax.device_get(t2.state.params["blocks"]["qkv"]["w"])
    np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)
