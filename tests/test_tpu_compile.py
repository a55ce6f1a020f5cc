"""The main path's Pallas kernels, compiled for a TPU v5e that is described
and not attached (the `on-chip-measurement` guide's third rehearsal).

Interpret mode accepts what Mosaic refuses — vector loads from HBM refs,
slices off the tiling, too much VMEM — so every kernel a volunteer runs on
the chip is compiled here at its real width, by the chip's own compiler:
flash attention fwd+bwd at the cells' shapes and inside the whole train
step (one chip, and dp=2,tp=2 where it must run per shard), the codec's (512, 128) bf16
kernels, and the ring fold / ring all-gather on a 2x2 codec mesh at the
1 MiB wire chunk a real round uses. Nothing runs: a pass says the compiler
takes the kernel, not that its results are right (chip_smoke.py does that).

The program's backend checks see the CPU here, so each test steers
compiled-vs-interpret itself (``interpret=False``, ``pallas="compiled"``).

The whole steps of the six share-model cells (laguna, smallthinker, lfm2,
glm47-flash, nemotron, kimi-linear) are compiled in a file each,
``test_tpu_compile_<family>.py``, on this file's helpers and fixtures: each
takes one and a half to two minutes and shares nothing with another test.
"""

import contextlib
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from distributedvolunteercomputing_tpu.ops import mesh_codec
from distributedvolunteercomputing_tpu.ops.mesh_collective import RingMeanFolder
from distributedvolunteercomputing_tpu.ops.pallas_attention import flash_attention
from distributedvolunteercomputing_tpu.utils import traced

CHUNK_ELEMS = (1 << 20) // 2  # a 1 MiB wire chunk of bf16


_CORE = ("impl", "T", "D", "window", "kv_heads", "layout", "rotary")   # of an "attention_core" note
_KEPT = ("layers", "bytes")                                             # of a "remat_kept" note


@contextlib.contextmanager
def _noted(kind, *labels):
    """What a trace inside the block notes of ``kind`` (``utils/traced.py``): a list of tuples, ``labels``' values a note."""
    seen = []
    with traced.subscribe(lambda noted, said: noted == kind and seen.append(tuple(said[label] for label in labels))):
        yield seen


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described v5e 2x2 host."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    return topo.devices


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize(
    "shape,blocks",
    [
        ((16, 16, 1024, 64), (None, None)),  # medium-solo, the geometry the code chooses
        ((16, 10, 1024, 64), (None, None)),  # large-solo-4chip's shard
        ((16, 16, 1024, 64), (512, 512)),    # several blocks: loops, diagonal, skipped ones
        ((8, 12, 1024, 64), (128, 128)),
        ((2, 16, 8192, 64), (None, None)),   # a long head still fits VMEM
        ((4, 12, 197, 64), (None, None)),    # one padded block
    ],
)
def test_flash_fwd_bwd_compiles(v5e, shape, blocks):
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return flash_attention(q, k, v, True, *blocks, False).astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_bwd, x, x, x)
    assert text.count("tpu_custom_call") >= 2  # forward, fused backward
    assert "dvc_flash_fwd" in text and "dvc_flash_bwd" in text  # the names a trace shows


@pytest.mark.parametrize(
    "b,t,heads,causal",
    [
        (16, 1024, 16, True),    # medium-solo's layer: one block forward
        (8, 512, 12, False),     # BERT's layer at its 512 positions: every key seen
        (2, 4096, 16, False),    # several blocks a sequence, none skipped
        (2, 4096, 16, True),     # the same under the diagonal
        (4, 197, 12, False),     # ViT's 197 patches: one padded block, its tail masked
    ],
)
def test_a_block_of_two_heads_of_64_compiles(v5e, b, t, heads, causal):
    """The kernels on ``[B, T, H * 64]`` (two heads a 128-lane block, PR 66),
    forward and backward with the delta pass, for Mosaic and not only
    interpreted: causal as gpt2 calls them and NOT causal as BERT and ViT do
    (``common.fused_qkv_attention``), one block, several, and a padded one."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    x = jax.ShapeDtypeStruct((b, t, heads * 64), jnp.bfloat16, sharding=SingleDeviceSharding(v5e[0]))
    assert pa.heads_a_block(64, 64, heads, heads) == 2

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return pa.flash_attention_merged(q, k, v, None, None, (heads, heads), causal, None, None, False) \
                .astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    calls = _kernel_calls(_compiled_text(fwd_bwd, x, x, x))
    names = sorted(n.split(".")[0] for n in _kernel_names(calls))
    assert names == ["dvc_attn_delta", "dvc_flash_bwd", "dvc_flash_fwd"], names


def test_block_diffusion_fwd_bwd_compiles_at_the_cells_shape(v5e):
    """The kernels under the three-part mask at sdar-solo-4k's layer: 2 x 8,192
    rows, 32 query heads over 4, head 128, on the projections' own layout, q
    turned on the tile from tables built of positions 0..4,095 twice."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    one = SingleDeviceSharding(v5e[0])
    b, t, h, hkv, d = 2, 8192, 32, 4, 128
    q = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, t, hkv * d), jnp.bfloat16, sharding=one)

    def fwd_bwd(q, k, v):
        cos, sin = pa.rotary_tables(t, d, 1e6, positions=jnp.tile(jnp.arange(t // 2), 2))

        def loss(q, k, v):
            k = pa.rotary_merged(k, cos, sin, d, False)
            out = pa.flash_attention_merged(q, k, v, cos, sin, (h, hkv), False, None, d, False, 4)
            return out.astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_bwd, q, kv, kv)
    assert "dvc_flash_bd_fwd" in text and "dvc_flash_bd_bwd" in text  # the names a trace shows
    assert "dvc_flash_fwd" not in text and "dvc_flash_win" not in text


@pytest.mark.parametrize("b,t,h,hkv,window,form", [
    (4, 8192, 64, 8, 512, "slab"),      # laguna-solo-8k's sliding layer: a window of one 512-block
    (2, 16384, 28, 4, 4096, "edge"),    # smallthinker-solo-16k's: four 1,024-blocks, 63 of 64 MiB VMEM
])
def test_windowed_strips_compile_at_the_cells_shapes(v5e, b, t, h, hkv, window, form):
    """The windowed kernels with their edge tiles as strips (PR 73), at the two
    cells' layers, on the projections' own layout with q turned on the strip:
    Mosaic takes the dynamic slab starts (whole strips of the resident head),
    the backward's statistics as rows of a strip side by side and the strips'
    static column ranges of a tile, inside the VMEM the blocks were chosen for;
    the blocks are the ones ``choose_blocks`` gave before there were strips."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    one = SingleDeviceSharding(v5e[0])
    d = 128
    blocks = pa.choose_blocks(t, t, d, jnp.bfloat16, window, turned=True)
    assert blocks == (min(window, 1024),) * 2 and pa.strip_form(t, window, *blocks) == form
    q = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, t, hkv * d), jnp.bfloat16, sharding=one)

    def fwd_bwd(q, k, v):
        cos, sin = pa.rotary_tables(t, d, 1e6)

        def loss(q, k, v):
            k = pa.rotary_merged(k, cos, sin, d, False)
            return pa.flash_attention_merged(q, k, v, cos, sin, (h, hkv), True, window, d, False).astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_bwd, q, kv, kv)
    assert "dvc_flash_win_fwd" in text and "dvc_flash_win_bwd" in text  # the names a trace tells them by
    assert "dvc_flash_fwd" not in text and "dvc_flash_bwd" not in text


# sha256 (16 digits) of the LOWERED forward + backward of a causal and a windowed call, by head and
# on the merged layout with the rotary turn, for the described chip with name stacks only (no Python
# frames: ``jax_traceback_in_locations_limit`` 0). Read at the parent of PR 60 (9422aa4) and at the
# change by one script; a kernel PR that means to change them reads them again.
_LOWERED_AS_BEFORE = {
    ("merged", None): "bf44953dbfd233c5", ("heads", None): "382405a0cf5b529c",
    # the by-head windowed call is the kernels themselves: since PR 73 its window of one 512-block runs as strips
    # over a slab (dcf7cb4c87f2a2d9 until then); the merged one here is the XLA core (eight devices, no step mesh)
    ("merged", 512): "7b498243f1bbf819", ("heads", 512): "4d5f51bea675b593",
}


@pytest.mark.parametrize("layout,window", list(_LOWERED_AS_BEFORE))
def test_a_causal_and_a_windowed_call_lower_as_before_the_third_mask(v5e, monkeypatch, layout, window):
    """The block-diffusion mask is a keyword the causal and windowed calls never
    pass: what they lower to, kernels' bodies included, is the text it was."""
    import hashlib

    from distributedvolunteercomputing_tpu.ops import attention, pallas_attention
    from distributedvolunteercomputing_tpu.utils import jaxenv

    monkeypatch.setattr(jaxenv, "tpu_backend", lambda: True)
    monkeypatch.setattr(pallas_attention, "tpu_backend", lambda: True)
    one = SingleDeviceSharding(v5e[0])

    def x(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    def call(q, k, v):
        if layout == "merged":
            return attention.attention_merged(
                q, k, v, 8, 2, causal=True, window=window, rotary=attention.Rotary(layout="half"))
        return flash_attention(q, k, v, True, None, None, False, window)

    def fwd_bwd(q, k, v):
        return jax.value_and_grad(lambda q, k, v: call(q, k, v).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    shapes = ((x(2, 2048, 8 * 128), x(2, 2048, 2 * 128), x(2, 2048, 2 * 128)) if layout == "merged"
              else (x(2, 8, 2048, 64), x(2, 2, 2048, 64), x(2, 2, 2048, 64)))
    before = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = jax.jit(fwd_bwd).lower(*shapes).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", before)
    got = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert got == _LOWERED_AS_BEFORE[(layout, window)], f"LOWERED {layout} {window} {got}"


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The program's backend checks answer as they do on the chip: bf16
    compute, auto routing to the kernel, compiled (not interpreted) kernels."""
    from distributedvolunteercomputing_tpu.ops import kda, pallas_attention, short_conv, ssd
    from distributedvolunteercomputing_tpu.utils import jaxenv

    monkeypatch.setattr(jaxenv, "tpu_backend", lambda: True)
    for module in (kda, pallas_attention, short_conv, ssd):  # each bound the name when it was imported
        monkeypatch.setattr(module, "tpu_backend", lambda: True)


def _traced_step(v5e, model: str, dp: int, tp: int, batch: int, n_layers: int = 2, **overrides):
    """The sharded train step of ``model`` (two layers: the scanned block
    appears once whatever the depth; None for a model whose depth is its list
    of layers), traced for a dp x tp mesh of described chips."""
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.parallel import sharding
    from distributedvolunteercomputing_tpu.parallel.mesh import AXES
    from distributedvolunteercomputing_tpu.parallel.train_step import (
        _map_params_shaped_subtrees,
        make_sharded_train_step,
    )
    from distributedvolunteercomputing_tpu.training.optim import make_optimizer
    from distributedvolunteercomputing_tpu.training.steps import TrainState

    mesh = Mesh(np.asarray(v5e[: dp * tp]).reshape(dp, 1, 1, 1, tp), AXES)
    if n_layers is not None:
        overrides = dict(overrides, n_layers=n_layers)
    bundle = get_model(model, **overrides)
    tx = make_optimizer("adam", lr=1e-3)
    abstract = jax.eval_shape(
        lambda: TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(1))
    )

    def placed(x, s):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)

    shardings = sharding.make_param_shardings(mesh, abstract.params)
    replicated = NamedSharding(mesh, P())
    state = TrainState(
        params=jax.tree_util.tree_map(placed, abstract.params, shardings),
        opt_state=_map_params_shaped_subtrees(
            abstract.opt_state, jax.tree_util.tree_structure(abstract.params),
            lambda node: jax.tree_util.tree_map(placed, node, shardings),
            lambda leaf: placed(leaf, replicated),
        ),
        step=placed(abstract.step, replicated),
        rng=placed(abstract.rng, replicated),
    )
    batch_shape = jax.eval_shape(lambda: bundle.make_batch(jax.random.PRNGKey(2), batch))
    batch_shape = jax.tree_util.tree_map(
        lambda x: placed(x, sharding.batch_sharding(mesh)), batch_shape
    )
    step = make_sharded_train_step(bundle.loss_fn, tx, mesh, stepped=bundle.stepped)
    with mesh:
        return step.trace(state, batch_shape)


def _lowered_step(v5e, model: str, dp: int, tp: int, batch: int, n_layers: int = 2, **overrides):
    """That step, lowered."""
    return _traced_step(v5e, model, dp, tp, batch, n_layers, **overrides).lower()


_STEP_TEXTS: dict = {}  # three of the steps are read by two tests each (15-22 s a compile): compiled once


def _step_text(v5e, model: str, dp: int, tp: int, batch: int, n_layers: int = 2, **overrides) -> str:
    """The compiled HLO of that step."""
    key = (model, dp, tp, batch, n_layers, tuple(sorted(overrides.items())))
    if key not in _STEP_TEXTS:
        _STEP_TEXTS[key] = _lowered_step(v5e, model, dp, tp, batch, n_layers, **overrides).compile().as_text()
    return _STEP_TEXTS[key]


def _kernel_calls(text: str):
    return [ln for ln in text.splitlines() if "tpu_custom_call" in ln and "custom-call(" in ln]


# By COUNT most of a module is parameters and tuple plumbing, which carry no scope (by time `other` is 4-12%
# of a step: the chip's reading, `PERF.md` section 5); a scope word that stopped reaching the HLO would pass this.
SCOPE_OTHER_AT_MOST = 0.80


def _step_holds_the_groups_its_cell_lists(text: str, cell: str) -> None:
    """The step's scope map, from the text the chip's own compiler gives at the
    cell's size (``utils/step_scopes.scope_map``: what a traced run of ``cell``
    joins to its device events), holds every group whose ``scope.<group>_ms``
    metric lists ``cell`` in ``BENCHMARK.json`` and no other; every
    checkpointed block scope is there forward, recomputed and backward; and
    ``other`` holds under a stated share of the instructions."""
    import collections

    from benchmark.manifest import Manifest
    from distributedvolunteercomputing_tpu.utils import step_scopes

    listed = {m["name"][len("scope."):-len("_ms")] for m in Manifest().doc["per_layer"]
              if m["name"].startswith("scope.") and m["name"].endswith("_ms") and cell in m["workloads"]}
    assert listed >= {"attention", "loss_head", "optimizer", "other"}, listed
    got = step_scopes.scope_map(text)
    seen = collections.Counter((step_scopes.group_of(r["scope"]), r["pass"]) for r in got.values())
    assert {group for group, _ in seen} == listed, (sorted(seen), listed)
    for group in listed - {"loss_head", "optimizer", "other"}:
        assert all(seen[(group, which)] for which in step_scopes.PASSES), (group, seen)
    other = sum(n for (group, _), n in seen.items() if group == "other")
    assert other / len(got) <= SCOPE_OTHER_AT_MOST, (other, len(got))


def _head_makes_its_gradients_in_the_loop_of_its_loss(text: str, vocab: int) -> None:
    """The loss head of a compiled step (``models/common.lm_xent_chunked``, a
    ``custom_vjp`` since PR 61): exactly three products over the vocabulary
    under ``loss_head`` (a chunk's logits, ``dx`` and ``dhead``), none of them or
    of anything else of the head recomputed, all three forward (the backward
    rule is the residuals times a cotangent that is the literal 1.0 under
    ``value_and_grad``: no pass over the head's shape is left for it), and every
    instruction that names the head resolves to it in the scope map."""
    import re

    from distributedvolunteercomputing_tpu.utils import step_scopes

    def origin(ln):  # the first of the origins XLA joined: the one the scope map counts
        return ln.split('op_name="')[1].split('"')[0].split(";")[0]

    named = [ln for ln in text.splitlines() if 'op_name="' in ln and "loss_head" in origin(ln)]
    assert named and not [ln for ln in named if "rematted_computation" in origin(ln)]
    products = [ln for ln in named if re.search(r" (convolution|dot)\(", ln)]
    results = dict(re.findall(r"^\s+(?:ROOT )?(%[^\s=]+) = (\S+)", text, re.M))  # every instruction's result type

    def over_the_vocabulary(ln):  # its result or one of its operands holds the vocabulary's axis
        operands = re.search(r" (?:convolution|dot)\(([^)]*)\)", ln).group(1).split(", ")
        return any(re.search(rf"[\[,]{vocab}[\],]", t) for t in [ln.split(" = ")[1], *(results[o] for o in operands)])

    assert len(products) == 3 and all(over_the_vocabulary(ln) for ln in products), products
    assert not [ln for ln in products if "transpose(" in origin(ln)]
    got = step_scopes.scope_map(text)
    head = {name: r for name, r in got.items() if r["scope"] == "loss_head"}
    assert head and {r["pass"] for r in head.values()} <= {"fwd", "bwd"}
    assert not [name for name, r in head.items() if r["pass"] == "bwd" and str(vocab) in r["result"]]
    for ln in named:  # none of the head's instructions falls to another group or to ``other``
        name = ln.split(" = ")[0].replace("ROOT", "").strip().lstrip("%")
        assert name not in got or step_scopes.group_of(got[name]["scope"]) == "loss_head", ln[:200]


def test_medium_step_holds_the_kernel(v5e, as_on_the_chip):
    """medium-solo's step, auto routing: T=1,024 bf16 takes the fused core,
    forward and backward, on the projections' own ``[16, 1024, 16 x 64]`` arrays
    (two heads of 64 a 128-lane block, PR 66), with the backward's delta by
    ``dvc_attn_delta`` in the same layout; the recomputed forward holds no
    kernel (the layer's checkpoint kept its output and row statistics:
    ``common.remat_layer``); and no array by head exists anywhere in the
    optimised step: not q, k, v, o or a cotangent as ``[16,16,1024,64]``, not
    the kept stack as ``[L,16,16,1024,64]`` (it is ``bf16[L,16,1024,1024]``,
    lane-dense), not the ``[16,1024,3072]`` result of one fused product cut in
    three."""
    import re

    text = _step_text(v5e, "gpt2_medium", 1, 1, 16)
    calls = _kernel_calls(text)
    names = sorted(n.split(".")[0] for n in _kernel_names(calls))
    assert names == ["dvc_attn_delta", "dvc_flash_bwd", "dvc_flash_fwd"], names
    flash = [ln for ln in calls if "dvc_flash_" in ln]
    assert len(flash) == 2 and all(ln.count("bf16[16,1024,1024]") >= 4 for ln in flash)  # q, k, v and o (dO) alike
    assert not re.search(r"\[(?:\d+,)?16,16,1024,64\]|\[16,1024,16,64\]|\[16,1024,3072\]", text)
    assert "bf16[2,16,1024,1024]" in text  # the kept outputs of the two layers, a row of d lanes
    _step_holds_the_groups_its_cell_lists(text, "medium-solo")
    _head_makes_its_gradients_in_the_loop_of_its_loss(text, 50257)


def test_bert_step_holds_the_pair_kernels_where_every_key_is_seen(v5e, as_on_the_chip):
    """BERT's step at its 512 positions (12 heads of 64, bf16, auto routing)
    reaches the same pair kernels as gpt2's through ``common.fused_qkv_attention``,
    NOT causal: the optimised step for the described chip holds the forward,
    the backward and the delta pass on ``bf16[8,512,768]`` operands and no
    array by head, and the trace notes ``merged/none``. (No cell runs BERT; the
    chip has run this call alone: ``experiments/attention_head64_sweep.py``,
    its ``full`` lines.)"""
    import re

    with _noted("attention_core", "impl", "T", "D", "layout", "rotary") as cores:
        text = _step_text(v5e, "bert_mlm", 1, 1, 8)
    assert cores == [("flash", 512, 64, "merged", "none")], cores
    calls = _kernel_calls(text)
    names = sorted(n.split(".")[0] for n in _kernel_names(calls))
    assert names == ["dvc_attn_delta", "dvc_flash_bwd", "dvc_flash_fwd"], names
    assert all(ln.count("bf16[8,512,768]") >= 4 for ln in calls if "dvc_flash_" in ln)  # q, k, v and o (dO) alike
    assert not re.search(r"\[(?:\d+,)?8,12,512,64\]|\[8,512,12,64\]|\[8,512,2304\]", text)


def test_ouro_step_is_one_loop_of_four_passes_over_one_traced_layer_and_one_head_loop(v5e, as_on_the_chip):
    """ouro-solo-4k's step at the cell's widths, sequence and batch, two of
    its six layers (the scanned block appears once whatever the depth; 25 s):
    ONE traced layer body, so one attention kernel forward and one backward at
    ``bf16[2,4096,2048]`` (the projections' own arrays) whatever the passes, and
    none in the recomputed forward; the forward loop over the passes has a
    trip count of 4 with the layer scan inside it, and so has the backward;
    the head is ONE loop over all four passes' rows (``[R * B, chunk, V]``
    logits, three vocabulary-sized products) with ONE float32 ``[d, V]``
    accumulator; the loop's own instructions resolve to ``recur`` in the scope
    map (group ``other``), the blocks' inside it to ``attention`` and ``mlp``;
    and ``swarm.remat_kept`` counts layers x passes of the one trace."""
    import re

    from distributedvolunteercomputing_tpu.utils import step_scopes

    with _noted("remat_kept", *_KEPT) as kept:
        text = _step_text(v5e, "ouro_2_6b", 1, 1, 2, max_len=4096)
    calls = _kernel_calls(text)
    flash = sorted(n.split(".")[0] for n in _kernel_names(calls) if n.startswith("dvc_flash"))
    assert flash == ["dvc_flash_bwd", "dvc_flash_fwd"], flash
    assert all(ln.count("bf16[2,4096,2048]") >= 3 for ln in calls if "dvc_flash_" in ln)
    # two layers, four passes: the kernel's bf16 output and a float32 log-sum-exp a row, eight times over
    assert kept == [(2, 2 * 4 * 2 * 16 * 4096 * (128 * 2 + 4))]
    _step_holds_the_groups_its_cell_lists(text, "ouro-solo-4k")
    _head_makes_its_gradients_in_the_loop_of_its_loss(text, 49152)
    # the loops by where they come from: the passes' loop forward and backward, the layer scan inside each, the
    # head's chunks, and the compiler's own loop over the layers (it casts the shared weights ONCE, outside the passes)
    whiles = {ln.split('op_name="')[1].split('"')[0]: ln.split(" while(")[0]
              for ln in text.splitlines() if re.search(r" while\(", ln)}
    inner = "/while/body/closed_call/while"
    assert set(whiles) == {"jit(step)/jvp(recur)/while", "jit(step)/jvp(recur)" + inner,
                           "jit(step)/transpose(jvp(recur))/while", "jit(step)/transpose(jvp(recur))" + inner,
                           "jit(step)/jvp(loss_head)/while", "jit(step)/while"}, sorted(whiles)
    # the passes' loop carries the four passes' states and, of passes x layers, a layer-run's input and kept output
    carried = whiles["jit(step)/jvp(recur)/while"]
    assert "bf16[4,2,4096,2048]" in carried and carried.count("bf16[4,2,2,4096,2048]") == 2
    # ONE head loop over all four passes' rows, 32 chunks of [R * B, 128], with ONE float32 [d, V] accumulator
    head_loop = whiles["jit(step)/jvp(loss_head)/while"]
    assert "bf16[32,8,128,2048]" in head_loop and head_loop.count("f32[2048,49152]") == 1
    assert "f32[8,128,49152]" in text
    got = step_scopes.scope_map(text)
    scopes = {r["scope"] for r in got.values()}
    assert {"recur", "attention", "mlp", "loss_head", "optimizer"} <= scopes and step_scopes.group_of("recur") == "other"


# ``slow`` since PR 58: one cell-size compile for a described v5e, 42 s of the tier-1 run's six
# workers and 3.5 to 6 GB of host memory, that shares nothing with another test; the run's other tests did not fit
# the command's limit beside the eight such compiles (ROADMAP D3). Run it before any chip run of a PR that touches a
# model's step: ``python -m pytest -m slow tests/test_tpu_compile*.py`` (the verify skill).
@pytest.mark.slow
def test_olmoe_step_holds_its_kernels(v5e, as_on_the_chip, monkeypatch):
    """olmoe-solo's step (one layer of OLMoE-1B-7B at its published widths,
    4 x 4,096 tokens): the fused attention core at head dim 128 and T=4,096,
    forward and backward; the recomputed forward holds no kernel; and twelve megablox calls over
    the 131,072 routed rows (gate, up, down: forward, recomputed forward, the
    backward by the rows' side; three by the weights' side), under the names
    the benchmark's readers match. That it compiles says it fits the chip."""
    import re

    from benchmark import moe_trace
    from distributedvolunteercomputing_tpu.ops import moe_dispatch

    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    # the choice itself counts this host's 8 CPUs as chips
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    text = _step_text(v5e, "olmoe_1b_7b", 1, 1, 4, n_layers=1)
    _step_holds_the_groups_its_cell_lists(text, "olmoe-solo")
    _head_makes_its_gradients_in_the_loop_of_its_loss(text, 50304)
    calls = _kernel_calls(text)
    names = [re.match(r"\s*%([\w.\-]+) =", ln).group(1) for ln in calls]
    flash = [n for n in names if n.startswith("dvc_flash_")]
    gmm = [n for n in names if moe_trace.GMM_RE.search(n)]
    # beside them since PR 59: the backward's delta rows and four merged-layout rotary passes (k and its
    # cotangent, the backward's q and its dq; the forward's q is turned on the kernel's tile)
    beside = sorted(n.split(".")[0] for n in names if n.startswith(("dvc_attn_", "dvc_rotary")))
    assert beside == ["dvc_attn_delta"] + ["dvc_rotary"] * 3 + ["dvc_rotary_back"] * 2, names
    assert len(flash) == 2 and len(gmm) == 12 and len(names) == 14 + len(beside), names
    assert sum(n.startswith("tgmm") for n in gmm) == 3
    # q, k, v and the output where the projections leave them (PR 59): no array by head in the step
    assert all("bf16[4,4096,2048]" in ln and "bf16[4,16,4096,128]" not in ln for ln in calls if "dvc_flash_" in ln)
    _no_pass_over_a_head_shaped_array(text, 4, 4096, (16,), float32_too=False)
    assert all("[131072," in ln or "bf16[64," in ln for ln in calls if "gmm" in ln)


def _kernel_names(calls):
    import re

    return [re.match(r"\s*%([\w.\-]+) =", ln).group(1) for ln in calls]


def _level_products_by_loop(text: str, state, c: int) -> list:
    """For each ``while`` loop that carries an array of ``state``'s shape, in the
    program's order: how many products of two [.., c, c] matrices (a level of a
    chunk's triangular inverse) its body and every computation that reaches hold.
    A product is a ``convolution`` to the TPU compiler, alone or inside a fusion."""
    import re

    from benchmark import kda_trace

    comps, name = {}, None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", ln)
        if head and not ln.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(ln)
    counts = []
    for ln in text.splitlines():
        if " while(" not in ln or state not in kda_trace.carried(ln.strip()):
            continue
        reached = [re.search(r"body=%?([\w.\-]+)", ln).group(1)]
        for comp in reached:    # grows as it is walked: what the body calls, and what that calls
            reached += [called for inner in comps[comp]
                        for called in re.findall(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", inner) if called not in reached]
        n = 0
        for comp in reached:
            shapes = {m.group(1): m.group(2).split(",")[-2:]        # an instruction's name -> the last two of its result's dims
                      for m in (re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]", inner) for inner in comps[comp]) if m}
            products = [re.findall(r"%[\w.\-]+", inner.split(" convolution(", 1)[1].split(")", 1)[0])
                        for inner in comps[comp] if " convolution(" in inner]
            n += sum(all(shapes[name] == [str(c), str(c)] for name in pair) for pair in products)
        counts.append(n)
    return counts


def _no_pass_over_a_head_shaped_array(text: str, b: int, t: int, heads, d: int = 128, float32_too: bool = True) -> None:
    """No instruction of the compiled step's entry computation gives an array
    by head ([B, H, T, D] or the transposed [B, T, H, D], whole or as rotary's
    halves and quarters) or a float32 copy of a merged [B, T, H * D], for any
    of the head counts ``heads``: between a projection and a ``dvc_flash_*``
    call the arrays stay [B, T, H * D] in the compute dtype (PR 59).
    ``float32_too`` False where H * D is the model's own width (OLMoE: the
    residual stream's float32 norms have that shape)."""
    import re

    counts = "|".join(str(h) for h in heads)
    widths = "|".join(str(w) for w in (d, d // 2, d // 4))
    merged = "|".join(str(h * d) for h in heads)
    refused = re.compile(
        rf"(?:bf16|f32)\[{b},(?:(?:{counts}),{t}|{t},(?:{counts})),(?:{widths})\]"
        + (rf"|f32\[{b},{t},(?:{merged})\]" if float32_too else ""))
    found = []
    for ln in text[text.index("\nENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\(.*?\)|\S+) ", ln)
        if m and refused.search(m.group(1)):
            found.append(ln.strip()[:160])
    assert not found, found[:5]


def _share_chunks_hold_seven_grouped_matmuls(names, text: str, layers: int, rows: int, d: int, f: int) -> None:
    """Of the compiled step's kernel ``names`` and ``text``: each of the
    ``layers`` traced expert layers runs two grouped matmuls in its forward
    chunk loop (gate-up, down) and five in its backward one (gate-up again,
    the cotangent of ``hidden``, the rows', and the two by the weights' side);
    the recomputed forward of a rematerialised layer runs none. Twelve a layer
    before PR 36. Gate and up are one product ``2 f`` wide, and no buffer of
    ``rows + 1`` rows exists (a token takes its run's last row from the
    ``[rows, d]`` buffer itself). Since PR 38 a token's run is summed by ONE
    product over the buffer in each loop (``_combine`` forward, and backward as
    the transpose of the rows' gather): a convolution over its ``[rows / 128,
    128, d]`` tiles with the 0/1 matrices and the carry over a tile's edge
    fused into it, written once in bfloat16; the three shifted slices
    (``[rows - 1 | 2 | 4, d]``) and the pad-add fusions over ``[rows, d]``
    that it replaced are gone."""
    import re

    from benchmark import moe_trace

    gmm = [n.split(".")[0] for n in names if moe_trace.GMM_RE.search(n)]
    assert len(gmm) == 7 * layers, gmm
    assert gmm.count("gmm") == 2 * layers and gmm.count("jvp_jit_gmm__") == layers, gmm
    assert gmm.count("transpose_jvp_jit_gmm___") == gmm.count("transpose_jvp_jit_tgmm___") == 2 * layers, gmm
    assert f"bf16[{rows},{2 * f}]" in text and f"[{rows + 1},{d}]" not in text
    tiles = rf"\[{rows // 128},128,{d}\]"
    assert len(re.findall(rf"= f32{tiles}\S* convolution\(", text)) == 2 * layers
    assert len(re.findall(rf"= bf16{tiles}\S* fusion\(.*kind=kOutput", text)) == 2 * layers
    assert not any(f"[{rows - shift},{d}]" in text for shift in (1, 2, 4))
    assert not re.search(rf"pad_add_fusion[\w.]* = bf16\[{rows},{d}\]", text)


@pytest.mark.parametrize("model,batch,layers,shape", [
    ("gpt2_medium", 16, 2, "bf16[16,1024,1024]"), ("olmoe_1b_7b", 4, 1, "bf16[4,4096,2048]")])
def test_other_steps_keep_their_kernel_names(v5e, as_on_the_chip, monkeypatch, model, batch, layers, shape):
    """The gpt2 and OLMoE cells trace the attention kernels under the names
    they had before the windowed ones existed: ``dvc_flash_fwd`` and
    ``dvc_flash_bwd`` once a scanned layer, forward and backward; the
    recomputed forward holds no kernel; at equal head counts, and no windowed
    name. Since PR 59 OLMoE's are handed the projections' own ``[4, 4096, 16 *
    128]`` arrays, since PR 66 gpt2's too (``[16, 1024, 16 * 64]``: two heads
    of 64 a block)."""
    from distributedvolunteercomputing_tpu.ops import moe_dispatch

    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    calls = _kernel_calls(_step_text(v5e, model, 1, 1, batch, n_layers=layers))
    flash = sorted(n.split(".")[0] for n in _kernel_names(calls) if n.startswith("dvc_flash"))
    assert flash == ["dvc_flash_bwd", "dvc_flash_fwd"], flash
    assert all(ln.count(shape) >= 3 for ln in calls if "dvc_flash_" in ln)  # q, k and v alike


# ``slow`` since PR 58: one cell-size compile for a described v5e, 59 s of the tier-1 run's six
# workers and 3.5 to 6 GB of host memory, that shares nothing with another test; the run's other tests did not fit
# the command's limit beside the eight such compiles (ROADMAP D3). Run it before any chip run of a PR that touches a
# model's step: ``python -m pytest -m slow tests/test_tpu_compile*.py`` (the verify skill).
@pytest.mark.slow
def test_four_chip_step_calls_the_kernel_per_shard(v5e, as_on_the_chip):
    """large-solo-4chip's step (dp=2, tp=2, batch 32, 20 heads): each chip's
    kernel sees its own 10 heads, five pairs where their projections left them
    (``bf16[8,1024,640]`` since PR 71), of ONE row stream, 8 of the replica's
    16 rows (``common.scan_blocks`` runs the rows as two streams over ``tp``),
    forward and backward a stream; the recomputed forward holds no kernel (the
    kept names pass through the per-shard ``shard_map``); and nothing gathered
    feeds it."""
    import re

    text = _step_text(v5e, "gpt2_large", 2, 2, 32)
    _step_holds_the_groups_its_cell_lists(text, "large-solo-4chip")
    calls = _kernel_calls(text)
    names = sorted(n.split(".")[0] for n in _kernel_names(calls))  # a stream's backward makes its delta in the same layout
    assert names == ["dvc_attn_delta"] * 2 + ["dvc_flash_bwd"] * 2 + ["dvc_flash_fwd"] * 2, names
    assert all("bf16[8,1024,640]" in ln for ln in calls)
    assert not any(re.search(r"\[(16|32),", ln.split("custom-call(")[1].split(")")[0]) for ln in calls)
    gathered = set(re.findall(r"(%all-gather[\w.\-]*) =", text))
    for ln in calls:
        operands = set(re.findall(r"%[\w.\-]+", ln.split("custom-call(")[1]))
        assert not (gathered & operands), (gathered & operands)


def _collectives(text: str):
    """(result type, kind, line) of every collective in a compiled program."""
    import re

    kinds = r"(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)(?:-start)?\("
    return [(m.group(1), m.group(2), ln) for ln in text.splitlines()
            if (m := re.search(r"= (.+?) " + kinds, ln))]


STREAM = r"bf16\[8,1024,1280\]"  # one row stream's activations on a chip: 8 of the replica's 16 rows


def _computations(text: str):
    """{name: its instruction lines, in the order the compiler scheduled them}."""
    import re

    out, name = {}, None
    for ln in text.splitlines():
        if m := re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", ln):
            name = m.group(1)
            out[name] = []
        elif name is not None and " = " in ln:
            out[name].append(ln)
    return out


def _stream_all_reduces(text: str):
    """The all-reduces of one row stream's activations in a compiled step, as
    (pass, scope, asynchronous?, the lines scheduled between start and done).
    A synchronous one is an ``all-reduce`` of the loop body itself; an
    asynchronous one is this compiler's pair of fusions ``async-collective-start``
    / ``-done`` (the ``all-reduce`` instructions inside fused computations are
    the pair's parts, told by their ``chain_id``)."""
    import re

    def where(ln):
        m = re.search(r'op_name="jit\(step\)/(.*?)/while/.*/(attention|mlp)/', ln)
        return ("bwd" if m.group(1).startswith("transpose") else "fwd", m.group(2))

    found = []
    for lines in _computations(text).values():
        for i, ln in enumerate(lines):
            if re.search(r"= " + STREAM + r"\S* all-reduce\(", ln) and "chain_id" not in ln:
                found.append((*where(ln), False, []))
            elif m := re.match(r"\s*(%async-collective-start[\w.]*) = \(" + STREAM, ln):
                done = next(j for j in range(i + 1, len(lines))
                            if re.search(re.escape(m.group(1).replace("start", "done")) + r" = ", lines[j]))
                found.append((*where(lines[done]), True, lines[i + 1:done]))
    return found


def test_four_chip_step_moves_no_activation_for_qkv(v5e, as_on_the_chip):
    """large-solo-4chip's step: q, k and v are born on the chip that runs their
    heads (``common.qkv_heads``: three column-parallel products off the leaf's
    head-aligned view, ``[8,1024,640]`` a chip, merged as on one chip since PR 71), so no
    all-to-all and no collective-permute carries them or their cotangents
    (the two row streams are each laid out over dp: no activation crosses dp
    either). What crosses a link at an activation's size is Megatron's price
    alone, four all-reduces a layer and STREAM (``common.scan_blocks`` runs a
    replica's 16 rows as two streams of 8): after each row-parallel product in
    the forward (attn_out, mlp_out) and before each column-parallel one in the
    backward (mlp_in, qkv), eight of ``bf16[8,1024,1280]`` and none of the
    whole ``[16,1024,1280]``. The backward's recomputed forward moves no
    activation: the layer's ONE checkpoint around both streams kept attn_out's
    reduced result of each (``common.remat_layer``, ``attention.keep_tp_reduced``).
    The kernels see a chip's own 10 heads as five pairs where the products
    left them, forward and backward a stream, and no array by head is left in
    the step, compiled or lowered."""
    import collections
    import re

    text = _step_text(v5e, "gpt2_large", 2, 2, 32)
    found = _collectives(text)
    kinds = {kind for _, kind, _ in found}
    assert "all-to-all" not in kinds and "collective-permute" not in kinds, kinds
    assert not [ln for result, _, ln in found if re.search(r"\[16,1024,\d+\]", result)]
    sites = collections.Counter((which, scope) for which, scope, _, _ in _stream_all_reduces(text))
    # two streams at each of the four sites, by the scopes their products carry
    assert sites == {("fwd", "attention"): 2, ("fwd", "mlp"): 2, ("bwd", "attention"): 2, ("bwd", "mlp"): 2}, sites
    # the recomputed forward, by the name its operations carry: no all-reduce and nothing of an
    # activation's size; what it still gathers is the qkv leaf's head-aligned view (weight and bias)
    recomputed = [(result, kind) for result, kind, ln in found if "rematted_computation" in ln]
    assert recomputed and {kind for _, kind in recomputed} == {"all-gather"}, recomputed
    assert all(re.match(r"bf16\[(1280,3840|\d+,1,1920)\]", result) for result, _ in recomputed), recomputed
    calls = _kernel_calls(text)
    flash = [ln for ln in calls if "dvc_flash_" in ln]  # beside them a stream's ``dvc_attn_delta``, as on one chip
    assert len(flash) == 4 and all(ln.count("bf16[8,1024,640]") >= 4 for ln in flash)  # q, k, v and o (dO) alike
    assert len(calls) == 6 and all("bf16[8,1024,640]" in ln for ln in calls)
    assert not [ln for ln in calls if "rematted_computation" in ln]
    # nothing by head, compiled or lowered: no [.., 10, 1024, 64] / [.., 1024, 10, 64] a chip, none of the 20 heads' whole
    assert not re.search(r",10,1024,64\]|,1024,10,64\]", text)
    assert not re.search(r"x20x1024x64x|x1024x20x64x", _lowered_step(v5e, "gpt2_large", 2, 2, 32).as_text())


def test_four_chip_step_hides_a_stream_s_all_reduce_behind_the_other_s_products(v5e, as_on_the_chip):
    """large-solo-4chip's step is compiled with asynchronous collectives
    (``train_step.step_compiler_options``: its mesh has a ``tp`` axis) and the
    scanned body holds two independent row streams, so at each of the four
    sites where a layer sums over ``tp`` one stream's all-reduce is a start /
    done pair with a product or a kernel call scheduled between them, the
    other stream's work; never the two streams' results combined into one
    all-reduce, which would wait for both. What the compiler leaves on the
    instruction stream is the stream that comes second at a site: nothing of
    the layer is left to run beside it. PR 57 left five of the eight as pairs;
    with q, k and v born merged (PR 71) the compiler leaves four, the first
    stream's backward ``mlp`` one turning synchronous: the fifth pair went with
    PR 71, and ``large-solo-4chip`` gained 4% end to end regardless (PERF.md,
    Findings of PR 71; getting it back is ROADMAP S1's next item)."""
    import re

    text = _step_text(v5e, "gpt2_large", 2, 2, 32)
    comps = _computations(text)

    def is_work(ln):  # a kernel call, or a fusion that holds a product
        if "tpu_custom_call" in ln:
            return True
        called = re.search(r"calls=(%[\w.\-]+)", ln)
        return bool(called) and any(" convolution(" in inner for inner in comps.get(called.group(1), []))

    reduces = _stream_all_reduces(text)
    hidden = [(which, scope) for which, scope, asynchronous, _ in reduces if asynchronous]
    assert set(hidden) == {("fwd", "attention"), ("fwd", "mlp"), ("bwd", "attention"), ("bwd", "mlp")}, hidden
    assert len(hidden) >= 4, hidden
    for which, scope, asynchronous, between in reduces:
        assert not asynchronous or any(is_work(ln) for ln in between), (which, scope, between)
    # one stream's result each: a combined all-reduce would carry two
    assert not re.search(r"= \(" + STREAM + r"\S*, " + STREAM + r"\S*\) all-reduce\(", text)


@pytest.mark.parametrize("model,dp,tp,batch,asked,overrides", [
    ("gpt2_medium", 1, 1, 16, [1], {}),         # medium-solo, medium-round: one chip
    ("gpt2_large", 4, 1, 32, [1], {}),          # a dp-only mesh of the four chips
    ("smallthinker_21b_a3b", 1, 1, 2, [], dict(n_layers=4, experts_held=8, vocab=18992)),  # a share model: never asks
], ids=["gpt2_medium-1x1", "gpt2_large-dp4", "smallthinker-1x1"])
def test_steps_without_tp_are_the_programs_they_were(v5e, as_on_the_chip, monkeypatch, model, dp, tp, batch, asked, overrides):
    """Where the step's mesh has no ``tp`` to divide a layer the rows are not
    split and no compiler option is passed: the step lowers to the text it
    lowers to with the split taken out of ``scan_blocks`` altogether, so the
    one-chip cells' programs and cache keys are what they were. A model whose
    layers couple rows (a share of experts) never asks for streams."""
    from distributedvolunteercomputing_tpu.models import common
    from distributedvolunteercomputing_tpu.ops import moe_dispatch
    from distributedvolunteercomputing_tpu.parallel.mesh import AXES
    from distributedvolunteercomputing_tpu.parallel.train_step import step_compiler_options

    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    overrides = dict(overrides)
    n_layers = overrides.pop("n_layers", 2)
    mesh = Mesh(np.asarray(v5e[: dp * tp]).reshape(dp, 1, 1, 1, tp), AXES)
    assert step_compiler_options(mesh) == {}
    assert step_compiler_options(Mesh(np.asarray(v5e).reshape(2, 1, 1, 1, 2), AXES))  # and some with one
    # name stacks only: with Python frames a kernel's serialised module follows every line on the way to it
    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        with _noted("tp_streams", "streams") as seen:
            text = _lowered_step(v5e, model, dp, tp, batch, n_layers, **overrides).as_text()
        assert [streams for (streams,) in seen] == asked
        scan_blocks = common.scan_blocks
        monkeypatch.setattr(common, "scan_blocks", lambda *a, rows_independent=False, **kw: scan_blocks(*a, **kw))
        assert _lowered_step(v5e, model, dp, tp, batch, n_layers, **overrides).as_text() == text
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)


def test_one_chip_step_names_nothing_more_to_keep(v5e, as_on_the_chip):
    """medium-solo's step traces with no ``tp`` to divide a layer: the block's
    checkpoint names the kernel's two results and nothing else (on one chip
    attn_out's result costs a product to make again, not an all-reduce), and
    ``swarm.remat_kept`` reads the kernel's bytes: since PR 66 the output where
    ``attn_out`` reads it, ``bf16[16,1024,1024]`` a layer, half of what the
    by-head ``[16,16,1024,64]`` took at 128 lanes a row. The four-chip step names
    attn_out's reduced result in its traced block and counts a chip's
    ``bf16[16,1024,1280]`` of it a layer beside the kernel's, which since PR 71
    is the merged call's too (a chip's ten heads, ``bf16[16,1024,640]`` a
    layer): the bytes it counted as one stream, now two halves of the same
    stacks (the block is traced once, at one stream's 8 rows, and runs twice)."""
    import re

    def kept(*model_mesh_batch):
        with _noted("remat_kept", *_KEPT) as seen:
            jaxpr = str(_traced_step(v5e, *model_mesh_batch).jaxpr)
        return sorted(set(re.findall(r"name\[name=(\w+)\]", jaxpr))), seen  # the names, however often printed

    def merged(b, h, t, d=64):  # PR 66: a row of the kept output is the model's d lanes of bf16, two heads of 64 a tile
        return b * t * (h * d * 2 + h * 4)

    assert kept("gpt2_medium", 1, 1, 16) == (["attention_lse", "attention_out"], [(2, 2 * merged(16, 16, 1024))])
    assert kept("gpt2_large", 2, 2, 32) == (
        ["attention_lse", "attention_out", "tp_reduced"], [(2, 2 * (merged(16, 10, 1024) + 41_943_040))])


# (model, dp, tp, batch, n_layers, overrides) of each cell's step, and what ``swarm.attention_core`` hears of it
_CELL_LAYOUTS = {
    "medium-solo": (("gpt2_medium", 1, 1, 16, 2, {}), {"merged/none": 1}),  # two heads of 64 a block
    "large-solo-4chip": (("gpt2_large", 2, 2, 32, 2, {}), {"merged/none": 1}),  # five pairs a chip, per shard of tp
    "olmoe-solo": (("olmoe_1b_7b", 1, 1, 4, 1, {}), {"merged/kernel": 1}),
    "laguna-solo-8k": (
        ("laguna_xs2", 1, 1, 4, 5, dict(experts_held=16, vocab=12544)), {"merged/kernel": 5}),
    "smallthinker-solo-16k": (
        ("smallthinker_21b_a3b", 1, 1, 2, 4, dict(experts_held=8, vocab=18992)),
        {"merged/kernel": 1, "merged/none": 1}),  # one traced sliding layer (rotary), one global (none)
    "lfm2-solo-8k": (
        ("lfm2_24b_a2b", 1, 1, 4, None, dict(
            layer_types="conv,full_attention,conv,conv,conv", dense_layers=1, experts_held=8, vocab=8192)),
        {"heads/none": 1}),  # a head of 64: two heads a lane tile
    "glm47-flash-solo-8k": (
        # the dense layer and one scanned expert layer: q turned BESIDE the kernels (the tables do not fit their VMEM at D = 256)
        ("glm4_7_flash", 1, 1, 2, 5, dict(experts_held=8, vocab=19360)), {"merged/none": 2}),
    "nemotron3-nano-solo-8k": (
        ("nemotron3_nano_30b_a3b", 1, 1, 2, 7, dict(experts_held=8, vocab=16384)), {"heads/none": 1}),
    "kimi-linear-solo-8k": (
        ("kimi_linear_48b_a3b", 1, 1, 2, 5, dict(experts_held=8, vocab=20480)), {"heads/none": 1}),
    "sdar-solo-4k": (  # one scanned layer under the block-diffusion mask, turned by position on the tile
        ("sdar_30b_a3b", 1, 1, 2, 5, dict(experts_held=16, vocab=18992, mask_id=18991)), {"merged/kernel": 1}),
    "ouro-solo-4k": (  # one scanned layer, traced once inside the loop over the four passes
        ("ouro_2_6b", 1, 1, 2, 6, dict(max_len=4096)), {"merged/kernel": 1}),
    "qwen3-next-solo-8k": (  # the period's one attention layer: q and k turned BESIDE the kernels, as GLM's (D = 256)
        ("qwen3_next_80b_a3b", 1, 1, 2, 4, dict(experts_held=16, vocab=18992)), {"merged/none": 1}),
    "xing4-solo": (  # the dense layer and one scanned expert layer: a key of 192 padded to 256 lanes through the weights
        ("xing4_29b_a4b", 1, 1, 1, 5, dict(dense_layers=1, experts_held=8, vocab=16384, max_len=4096)), {"merged/none": 2}),
}


@pytest.mark.parametrize("cell", list(_CELL_LAYOUTS))
def test_which_cells_hand_the_kernels_the_projections_own_arrays(v5e, as_on_the_chip, monkeypatch, cell):
    """Every cell's step, traced at the cell's size as the chip would trace it
    (no compile): Laguna's, SmallThinker's and OLMoE's attention calls all take
    the flash kernels on the merged ``[B, T, H * D]`` layout, a rotary layer's q
    turned on the kernel's tile (``merged/kernel``) and a layer without
    position encoding turning nothing (``merged/none``: GLM's latent layers too
    since PR 65, which turn q and the one shared key themselves before the
    call); GPT-2's heads of 64 go two a block, on one chip (PR 66) and per
    shard of ``tp`` in the four-chip step (PR 71: five pairs a chip); every
    other cell's calls are handed ``[B, H, T, D]`` as before (LFM2's head of 64,
    Kimi's latent key of 192 concatenated by head, a model that calls
    ``attention_core`` itself): the shapes decide, and
    ``swarm.attention_core`` says which (PR 59)."""
    from distributedvolunteercomputing_tpu.ops import moe_dispatch
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry

    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    (model, dp, tp, batch, n_layers, overrides), want = _CELL_LAYOUTS[cell]
    tel = Telemetry()
    with traced.subscribe(tel.count_traced):
        _traced_step(v5e, model, dp, tp, batch, n_layers, **overrides)
    assert tel.traced_summary()["attention_layout"] == want
    assert tel.traced_summary()["attention_core"] == {"flash": sum(want.values())}
    # a windowed call's pairs computed over its band's (PR 73): Laguna's window of one block as strips over one
    # slab (2.0 as two whole tiles), SmallThinker's four blocks with their edge tiles as strips (1.25 as five)
    band = {"laguna-solo-8k": {"512/1.55": 3, "none/none": 2}, "smallthinker-solo-16k": {"4096/1.06": 1, "none/none": 1}}
    assert tel.traced_summary()["attention_band"] == band.get(cell, {"none/none": sum(want.values())})


# sha256 (16 digits) of each OTHER cell's lowered step (its kernels' serialised modules included), as the
# chip would trace it, name stacks only. Read at the parent of PR 66 (da43337, ``git archive`` with this file copied
# over it) and at the change: a PR that means to change one of these programs reads them again at its parent.
_LOWERED_AS_AT_THE_PARENT = {
    "olmoe-solo": "5b9dc2fbe8b901cf",
    # the two cells with a window, the programs PR 73 means to change (read again at that change; at its parent 13642af
    # they read 81c4c0af1909080a and cfbe8ccf289f955e): their windowed kernels run the edge tiles as strips
    "laguna-solo-8k": "3577b463ed3e94e8", "smallthinker-solo-16k": "1fdee51d3596d911",
    "lfm2-solo-8k": "ad3a2efc0d42feb8", "glm47-flash-solo-8k": "bf05df60b5edbf58", "nemotron3-nano-solo-8k": "e9d8feb1a8c3196f",
    "kimi-linear-solo-8k": "d71a310a813ced9a", "sdar-solo-4k": "440bade2e2273aa6", "ouro-solo-4k": "a00909f76052a41a",
    # and the four-chip step, the one program PR 71 means to change (read again at that change; at its parent 015a255 it
    # read e4334fc0b07a1554): over ``tp`` q, k and v are born merged too (``common.qkv_heads``; PERF.md, PR 71)
    "large-solo-4chip": "32301bfc6f027be2",
    # and medium-solo's (medium-round's) step, which PR 71 means to leave: read at 015a255 and at the change (PERF.md, PR 71)
    "medium-solo": "50cd9036352e2a67",
}


@pytest.mark.parametrize("cell", list(_LOWERED_AS_AT_THE_PARENT))
def test_the_other_cells_lower_to_the_text_the_parent_lowers(v5e, as_on_the_chip, monkeypatch, cell):
    """PR 66 gives the flash kernels' merged entry a block of two heads of 64
    and GPT-2's block the merged entry on one chip. A head of whole tiles is
    one head a block and its kernels are traced to the text they were
    (``heads_a_block`` == 1); LFM2, Nemotron and Kimi call ``attention_core``
    themselves. So every cell's step program but ``medium-solo``'s (and
    ``medium-round``'s, the same step), and with it its compile-cache key and
    its ``tok_s_chip``, was the parent's. PR 71 gives the step whose mesh
    divides GPT-2's heads over ``tp`` the merged projection and entry too: the
    four-chip step's hash is read again there, the nine others are untouched.
    PR 73 changes what a WINDOWED call's kernels compute: Laguna's and
    SmallThinker's hashes are read again there, and the nine cells that run no
    window keep the text they had (``experiments/step_hlo_hash.py`` says the
    same of the CPU's lowering, where no kernel is: thirteen equal hashes)."""
    import hashlib

    from distributedvolunteercomputing_tpu.ops import moe_dispatch

    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    (model, dp, tp, batch, n_layers, overrides), _ = _CELL_LAYOUTS[cell]
    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = _lowered_step(v5e, model, dp, tp, batch, n_layers, **overrides).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)
    got = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert got == _LOWERED_AS_AT_THE_PARENT[cell], f"LOWERED {cell} {got}"


def test_one_chip_step_makes_q_k_and_v_by_three_products_off_the_fused_leaf(v5e, as_on_the_chip):
    """medium-solo's step (PR 66; until then one [.., d] x [d, 3d] product whose
    3d-wide result fed a split and three ``split_heads``): with one chip q, k
    and v are three [.., d] x [d, d] products off the fused leaf's three column
    ranges, each result [16, 1024, 1024] as the kernels read it; no activation
    3d wide and none by head exists in the lowered step, and
    ``common.qkv_heads`` lays nothing out (no head-major weight view, no
    sharding constraint on it)."""
    import re

    text = _lowered_step(v5e, "gpt2_medium", 1, 1, 16).as_text()
    thirds = re.findall(
        r"stablehlo\.slice.*tensor<1024x3072xbf16>\) -> tensor<1024x1024xbf16>", text)
    assert len(thirds) >= 3  # the forward's three column ranges (the recomputed forward's again)
    assert re.search(r"stablehlo\.dot_general.*tensor<16x1024x1024xbf16>, tensor<1024x1024xbf16>\) -> tensor<16x1024x1024xbf16>", text)
    assert "16x1024x3072xbf16" not in text and "16x16x1024x64" not in text and "16x1024x16x64" not in text
    assert "1024x3x16x64" not in text  # the weight as [d, 3, H, hd]
    assert not re.search(r"sharding_constraint.*x16x64xbf16>", text)


def test_round_programs_copy_and_donate(v5e):
    """medium-round's two whole-tree programs at their real size (gpt2-medium,
    1.42 GB of float32): the device-side copy a launch or a snapshot takes
    writes buffers of its own (nothing aliased: a later step's donation must
    not reach it), and the merge writes its result over the donated
    parameters and the snapshot's copy of it over the donated ``launched``
    term, with no temporaries: a merge allocates nothing parameter-sized."""
    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.training import trainer

    one = SingleDeviceSharding(v5e[0])
    bundle = get_model("gpt2_medium")
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0))),
    )
    n_bytes = sum(int(np.prod(x.shape)) * 4 for x in jax.tree_util.tree_leaves(params))
    copy = jax.jit(trainer._param_copy).lower(params).compile()
    stats = copy.memory_analysis()
    assert stats.alias_size_in_bytes == 0 and stats.temp_size_in_bytes == 0
    assert n_bytes <= stats.output_size_in_bytes < n_bytes + (1 << 20)
    assert "jit__param_copy" in copy.as_text()
    merge = jax.jit(
        trainer._make_round_merge(bundle.avg_select, bundle.avg_merge), donate_argnums=(0, 2)
    ).lower(params, params, params).compile()
    stats = merge.memory_analysis()
    assert stats.temp_size_in_bytes == 0
    assert 2 * n_bytes <= stats.alias_size_in_bytes < 2 * n_bytes + (1 << 20)  # tiles pad a little
    assert stats.output_size_in_bytes - stats.alias_size_in_bytes < (1 << 20)
    assert "jit_round_merge" in merge.as_text()


@pytest.mark.parametrize("kernel", ["encode", "decode_axpy"])
def test_codec_bf16_kernels(v5e, kernel):
    codec = mesh_codec.MeshCodec(
        mesh=Mesh(np.asarray(v5e[:1]), ("x",)), backend="mesh", pallas="compiled"
    )
    n = 4 * 512 * 128  # whole (512, 128) blocks
    assert codec._pallas_mode == "compiled" and codec._pallas_eligible(n)
    one = SingleDeviceSharding(v5e[0])
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one)
    u16 = jax.ShapeDtypeStruct((n,), jnp.uint16, sharding=one)
    w = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one)
    if kernel == "encode":
        text = _compiled_text(codec._pallas_encode_local, f32)
    else:
        text = _compiled_text(codec._pallas_dec_axpy_local, u16, f32, w)
    assert "tpu_custom_call" in text


@pytest.fixture
def ring_folder(v5e):
    """A RingMeanFolder on the 2x2 host's codec mesh, compiled lowering, at
    the real tile size: 3 tiles of one wire chunk each."""
    codec = mesh_codec.MeshCodec(
        mesh=Mesh(np.asarray(v5e), ("codec",)), backend="mesh", pallas="compiled",
        collective="ring",
    )
    folder = RingMeanFolder(codec, 3 * CHUNK_ELEMS, CHUNK_ELEMS, 3, "bf16")
    assert folder._lower_cfg == "compiled"
    return folder


def _on(folder, spec):
    return NamedSharding(folder.codec._ensure_mesh(), spec)


def test_ring_fold_kernel(ring_folder):
    f = ring_folder
    per_dev = 4  # a full 16 MiB flush: 16 chunks over 4 devices
    kb = per_dev * f.codec._ndev
    assert f._lower_for(per_dev) == "compiled", f.codec.ring_lower_fallback
    acc = jax.ShapeDtypeStruct(
        (f.n_tiles, f.tile_elems), jnp.float32, sharding=_on(f, P(None, "codec"))
    )
    bits = jax.ShapeDtypeStruct(
        (kb, f.tile_elems), jnp.uint16, sharding=_on(f, P("codec", None))
    )
    tiles = jax.ShapeDtypeStruct((kb,), jnp.int32, sharding=_on(f, P("codec")))
    ws = jax.ShapeDtypeStruct((kb,), jnp.float32, sharding=_on(f, P("codec")))
    text = f._build_flush("compiled", per_dev).lower(acc, bits, tiles, ws).compile().as_text()
    assert "tpu_custom_call" in text


def test_ring_all_gather_kernel(ring_folder):
    f = ring_folder
    acc = jax.ShapeDtypeStruct(
        (f.n_tiles, f.tile_elems), jnp.float32, sharding=_on(f, P(None, "codec"))
    )
    text = f._build_gather().lower(acc).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("t,d,head_dim,group", [(8192, 2048, 64, 4)])
def test_lfm2_kernel_blocks_at_head_dim_64_group_4_and_8k(t, d, head_dim, group):
    """lfm2-solo-8k's attention shape (32 query heads over 8 key/value heads of
    64, T=8,192): a head of 64 pads to 128 lanes in VMEM, so the whole-head
    kernels hold what Laguna's full layers hold at 128, 1,024 x 1,024 blocks,
    two thirds of the budget, and the next doubling still fits; the
    convolution's kernel takes blocks of 256 positions over all 2,048 channels."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention, short_conv

    assert pallas_attention.choose_blocks(t, t, head_dim, jnp.bfloat16) == (1024, 1024)
    used = pallas_attention.vmem_bytes(t, t, head_dim, jnp.bfloat16, 1024, 1024)
    assert used == pallas_attention.vmem_bytes(t, t, 128, jnp.bfloat16, 1024, 1024)  # 64 pads to a lane tile
    assert 0.6 * pallas_attention.VMEM_BUDGET_BYTES < used <= 0.7 * pallas_attention.VMEM_BUDGET_BYTES
    assert pallas_attention.choose_blocks(2 * t, 2 * t, head_dim, jnp.bfloat16) == (1024, 1024)
    assert pallas_attention.choose_blocks(4 * t, 4 * t, head_dim, jnp.bfloat16) is None
    assert short_conv.choose_block(t, d, 3) == short_conv.BLOCK_T == 256
    # a block's buffers, double: the streams in and their cotangent out, the output's cotangent, two edges each
    block = 2 * (2 * 256 * 3 * d + 256 * d + 3 * 16 * 3 * d + 16 * d) * 2
    assert block < 0.25 * short_conv._VMEM_LIMIT


def test_short_conv_fwd_bwd_compiles_without_a_copy(v5e):
    """The convolution's two kernels at the cell's shape ([4, 8192, 6144]
    bfloat16 streams, float32 taps), by the chip's own compiler, under the
    names a trace shows; the program around them holds no temporary (the
    streams are read where the projection left them, the cotangent is written
    once)."""
    from distributedvolunteercomputing_tpu.ops import short_conv

    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((4, 8192, 3 * 2048), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((3, 2048), jnp.float32, sharding=one)
    dy = jax.ShapeDtypeStruct((4, 8192, 2048), jnp.bfloat16, sharding=one)

    def fwd_bwd(x, w, dy):
        y, vjp = jax.vjp(lambda a, b: short_conv.short_conv_kernel(a, b, 256, False), x, w)
        return y, vjp(dy)

    compiled = jax.jit(fwd_bwd).lower(x, w, dy).compile()
    names = _kernel_names(_kernel_calls(compiled.as_text()))
    # differentiated alone the names carry the transform's (``jvp_dvc_short_conv_fwd_``); inside the step
    # they are bare, which the step's test and the benchmark's readers match
    assert len(names) == 2 and sum("dvc_short_conv_fwd" in n for n in names) == 1
    assert sum("dvc_short_conv_bwd" in n for n in names) == 1
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_ssd_and_causal_conv_kernels_compile_at_the_published_mixer(v5e):
    """nemotron3-nano-solo-8k's state-space kernels by the chip's own compiler,
    under the names a trace shows: the chunked scan forward and backward at 64
    heads of 64 in 8 groups, state 128, chunks of 128, two sequences of 8,192
    (a group's eight heads a grid step, two heads a lane tile, each head's
    [64, 128] float32 state resident), reading x', B and C out of the
    convolution's ONE [2, 8192, 6144] array and writing y token-major, and the
    one-stream convolution at 6,144 channels and 4 taps."""
    from distributedvolunteercomputing_tpu.ops import short_conv, ssd

    one = SingleDeviceSharding(v5e[0])
    z, h, t, p, g, n, q = 2, 64, 8192, 64, 8, 128, 128
    assert ssd.kernel_takes(h, g, p, n, q) and short_conv.choose_block(t, 6144, 4) == 256
    assert ssd.heads_a_tile(h // g, p) == 2
    xbc = jax.ShapeDtypeStruct((z, t, h * p + 2 * g * n), jnp.bfloat16, sharding=one)
    dt = jax.ShapeDtypeStruct((z, t, h), jnp.float32, sharding=one)
    by_head = jax.ShapeDtypeStruct((h,), jnp.float32, sharding=one)
    rows = jax.ShapeDtypeStruct((z, t, h * p), jnp.bfloat16, sharding=one)

    def scan_fwd_bwd(xbc, dt, a_log, d, dy):
        y, vjp = jax.vjp(lambda *a: ssd.ssd(*a, g, n, q, ssd.KERNEL)[0], xbc, dt, a_log, d)
        return y, vjp(dy)

    compiled = jax.jit(scan_fwd_bwd).lower(xbc, dt, by_head, by_head, rows).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    names = _kernel_names(calls)
    assert len(names) == 2 and sum("dvc_ssd_fwd" in n for n in names) == 1 and sum("dvc_ssd_bwd" in n for n in names) == 1
    # the kernels' streams are the mixer's own: xbc three times over, y / dy / dx' [2, 8192, 4096], nothing by head
    assert all(ln.count("bf16[2,8192,6144]") >= 3 and "bf16[2,8192,4096]" in ln for ln in calls), calls
    assert "[2,64,8192,64]" not in text and "[2,8192,64,64]" not in text
    # between the two kernels: the chunk-boundary states, float32 [2, 64, 128, 64 x 64] = 0.27e9, and dB, dC before
    # they are put into d xbc in place (1.1e9 with the streams by head at a head of 64 in tiles of 128 lanes)
    assert compiled.memory_analysis().temp_size_in_bytes <= 0.32e9
    assert "concatenate" not in text and text.count("dynamic-update-slice(") == 2

    u = jax.ShapeDtypeStruct((z, t, 6144), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((4, 6144), jnp.float32, sharding=one)
    bias = jax.ShapeDtypeStruct((6144,), jnp.float32, sharding=one)

    def conv_fwd_bwd(u, w, bias, dy):
        y, vjp = jax.vjp(lambda *a: short_conv.causal_conv_kernel(*a, 256, False), u, w, bias)
        return y, vjp(dy)

    compiled = jax.jit(conv_fwd_bwd).lower(u, w, bias, u).compile()
    names = _kernel_names(_kernel_calls(compiled.as_text()))
    assert len(names) == 2 and sum("dvc_short_conv_fwd" in n for n in names) == 1
    assert sum("dvc_short_conv_bwd" in n for n in names) == 1


def test_the_delta_rule_scan_and_a_value_head_of_128_under_keys_of_192_compile_at_the_published_mixers(v5e):
    """kimi-linear-solo-8k's new mixers by the chip's own compiler: the
    delta-rule scan forward and backward at 32 heads of 128 keys and 128 values,
    chunks of 64, two sequences of 8,192 (XLA's loops over the 128 chunks, each
    carrying the heads' [2, 32, 128, 128] float32 states: what
    ``benchmark/kda_trace.py`` tells them by in a trace), and the flash kernels
    with q and k at a head of 192 and v, o, dO and dv at 128."""
    from benchmark import kda_trace
    from distributedvolunteercomputing_tpu.ops import kda, pallas_attention

    one = SingleDeviceSharding(v5e[0])
    z, t, h, d, chunk = 2, 8192, 32, 128, 64
    # the streams with the heads side by side, as the projections and the convolution leave them; split by head in
    # the call, as models/kimi_linear._kda does (a reshape the scan takes back)
    stream = jax.ShapeDtypeStruct((z, t, h * d), jnp.bfloat16, sharding=one)
    decay = jax.ShapeDtypeStruct((z, t, h * d), jnp.float32, sharding=one)
    beta = jax.ShapeDtypeStruct((z, t, h), jnp.float32, sharding=one)
    by_head = lambda a: a.reshape(z, t, h, d)

    def scan_fwd_bwd(q, k, v, g, beta, do):
        o, vjp = jax.vjp(lambda q, k, v, g, beta: kda.kda(by_head(q), by_head(k), by_head(v), by_head(g), beta, chunk)[0]
                         .reshape(z, t, h * d), q, k, v, g, beta)
        return o, vjp(do)

    compiled = jax.jit(scan_fwd_bwd).lower(stream, stream, stream, decay, beta, stream).compile()
    text = compiled.as_text()
    loops = [kda_trace.carried(ln.strip()) for ln in text.splitlines() if " while(" in ln]
    scans = [shapes for shapes in loops if (z, h, d, d) in shapes]
    held = sorted(sum(s[:2] == (t // chunk, z) for s in shapes) for shapes in scans)
    # one loop forward and one backward. Since PR 55 a step takes its chunk out of the streams IN PLACE: a loop
    # carries them whole ([2, 8192, 4096], the heads side by side) and holds by chunk only the states it stacks or
    # reads, so benchmark/kda_trace.py's count (a backward loop holds more than FORWARD_HOLDS_AT_MOST arrays by
    # chunk) calls both forward: kda.roofline's least time reads 1.64 ms a loop where a backward one's is 2.46
    # (PERF.md section 7: the next benchmark issue tells a backward loop by another mark)
    assert len(scans) == 2 and held == [1, 1] and held[1] <= kda_trace.FORWARD_HOLDS_AT_MOST, (len(loops), held)
    whole = sorted(sum(s == (z, t, h * d) for s in shapes) for shapes in scans)
    assert whole == [5, 9], whole       # forward: q, k, v, g and o; backward: the four, dO and the four cotangents (beta's are [2, 8192, 32])
    # nothing of a stream's size by chunk or by head anywhere in the program, and every chunk's place in a stream
    # known to the compiler as a multiple of the chunk's 64 rows (the low six bits of the index: zeroes 63)
    assert "[128,2,64," not in text and "[2,128,64," not in text and f"[{z},{t},{h},{d}]" not in text
    updates = [ln for ln in text.splitlines() if " dynamic-update-slice(" in ln and f"[{z},{t},{h * d}]" in ln.split(" dynamic-update-slice(")[0]]
    assert len(updates) == 5 and all('{"zeroes":"63","ones":"0","bitwidth":"32"}' in ln for ln in updates), len(updates)
    assert compiled.memory_analysis().temp_size_in_bytes <= 0.6e9      # 0.54e9: the states (2.16e9 with the streams by chunk)

    assert pallas_attention.choose_blocks(t, t, 192, jnp.bfloat16) == (1024, 1024)
    q = jax.ShapeDtypeStruct((z, h, t, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((z, h, t, 128), jnp.bfloat16, sharding=one)

    def attention_fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(lambda *a: flash_attention(*a, causal=True, interpret=False), q, k, v)
        return o, vjp(do)

    calls = _kernel_calls(jax.jit(attention_fwd_bwd).lower(q, q, v, v).compile().as_text())
    fwd, bwd = (next(ln for ln in calls if name in ln) for name in ("dvc_flash_fwd", "dvc_flash_bwd"))
    assert len(calls) == 2 and fwd.split(" custom-call(")[0].count("bf16[2,32,8192,128]") == 1    # o at the value width
    assert bwd.split(" custom-call(")[0].count("bf16[2,32,8192,192]") == 2 and "bf16[2,32,8192,128]" in bwd.split(" custom-call(")[0]


def test_the_scalar_decay_scan_the_8192_channel_convolution_and_grouped_attention_at_256_compile_at_the_published_mixers(v5e, as_on_the_chip):
    """qwen3-next-solo-8k's new mixers by the chip's own compiler: the
    scalar-decay delta rule forward and backward at 16 key heads and 32 value
    heads of 128, chunks of 64, two sequences of 8,192, fed the convolution's ONE
    [2, 8192, 8192] array (XLA's loops over the 128 chunks, each carrying the
    value heads' [2, 32, 128, 128] float32 states: what ``benchmark/gdn_trace.py``
    tells them by in a trace, and the whole streams a loop carries what it tells a
    backward loop from a forward one by); the one-stream convolution at 8,192
    channels (a block of 128 positions: 256 does not fit the backward's VMEM);
    and the flash kernels at a head of 256, sixteen query heads over two."""
    from benchmark import gdn_trace, kda_trace
    from distributedvolunteercomputing_tpu.ops import gdn, pallas_attention, short_conv

    one = SingleDeviceSharding(v5e[0])
    z, t, hk, hv, d, chunk = 2, 8192, 16, 32, 128, 64
    qkv = jax.ShapeDtypeStruct((z, t, 2 * hk * d + hv * d), jnp.bfloat16, sharding=one)
    by_value_head = jax.ShapeDtypeStruct((z, t, hv), jnp.float32, sharding=one)
    rows = jax.ShapeDtypeStruct((z, t, hv * d), jnp.bfloat16, sharding=one)

    def scan_fwd_bwd(qkv, g, beta, do):
        o, vjp = jax.vjp(lambda *a: gdn.gdn_with_sums(*a, hk, hv, d, chunk)[0], qkv, g, beta)
        return o, vjp(do)

    compiled = jax.jit(scan_fwd_bwd).lower(qkv, by_value_head, by_value_head, rows).compile()
    text = compiled.as_text()
    scans = [shapes for shapes in (kda_trace.carried(ln.strip()) for ln in text.splitlines() if " while(" in ln)
             if (z, hv, d, d) in shapes]
    whole = sorted(sum(len(s) == 3 and s[:2] == (z, t) for s in shapes) for shapes in scans)
    # one loop forward and one backward, told by the whole streams they carry: qkv, g, beta and o forward; the three,
    # dO and the three cotangents backward (benchmark/gdn_trace.FORWARD_CARRIES_AT_MOST lies between)
    assert len(scans) == 2 and whole == [4, 7], (len(scans), whole)
    assert whole[0] <= gdn_trace.FORWARD_CARRIES_AT_MOST < whole[1]
    # q and k are never at the value heads' count at a stream's size, the decay never by channel: of [2, 8192, 4096]
    # there are o and dO (and the cotangent's v part is written into the ONE [2, 8192, 8192]), nothing float32 of
    # that size and nothing by head
    assert f"f32[{z},{t},{hv * d}]" not in text and f"[{z},{t},{hv},{d}]" not in text and f"[{z},{hv},{t},{d}]" not in text
    for shapes, want in zip(sorted(scans, key=len), (1, 2)):    # forward: o and qkv; backward: dO, qkv and its cotangent
        assert sum(s == (z, t, hv * d) for s in shapes) == 1 and sum(s == (z, t, 2 * hk * d + hv * d) for s in shapes) == want
    # THE MECHANISM (PR 68): a chunk's T = (I + L)^-1 (levels of two [64, 64] x [64, 64] products each) is made by the
    # FORWARD loop alone and handed over by chunk ([128, 2, 16, 2, 64, 64] bfloat16, a scan's xs: not a whole
    # stream, so the counts above stand); the backward loop's body holds no such product. Were a level to come
    # back into it, this fails
    levels = dict(zip((sum(len(s) == 3 and s[:2] == (z, t) for s in shapes) for shapes in scans),
                      _level_products_by_loop(text, (z, hv, d, d), chunk)))
    assert levels == {4: 10, 7: 0}, levels       # five levels of two products past the first, forward; none backward
    assert f"bf16[{t // chunk},{z},{hk},{hv // hk},{chunk},{chunk}]" in text
    # 0.67e9 (my compile, PR 68): the states, 128 x 2 x 32 x 128 x 128 float32 (0.54e9), and every chunk's T (0.07e9)
    assert compiled.memory_analysis().temp_size_in_bytes <= 0.7e9

    channels = 2 * hk * d + hv * d
    assert short_conv.choose_stream_block(t, channels, 4) == 128 and short_conv.choose_stream_block(t, 6144, 4) == 256
    assert short_conv.choose_stream_block(t, 4096, 4) == short_conv.choose_block(t, 4096, 4) == 256
    w = jax.ShapeDtypeStruct((4, channels), jnp.float32, sharding=one)
    bias = jax.ShapeDtypeStruct((channels,), jnp.float32, sharding=one)

    def conv_fwd_bwd(u, w, bias, dy):
        y, vjp = jax.vjp(lambda *a: short_conv.causal_conv_kernel(*a, 128, False), u, w, bias)
        return y, vjp(dy)

    names = _kernel_names(_kernel_calls(jax.jit(conv_fwd_bwd).lower(qkv, w, bias, qkv).compile().as_text()))
    assert len(names) == 2 and sum("dvc_short_conv_fwd" in n for n in names) == 1
    assert sum("dvc_short_conv_bwd" in n for n in names) == 1

    h, kv, hd = 16, 2, 256
    assert pallas_attention.choose_blocks(t, t, hd, jnp.bfloat16) == (1024, 1024)
    assert pallas_attention.choose_blocks(t, t, hd, jnp.bfloat16, None, True) is None      # no room for a turn on the tile
    q = jax.ShapeDtypeStruct((z, t, h * hd), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((z, t, kv * hd), jnp.bfloat16, sharding=one)
    assert pallas_attention.heads_a_block(hd, hd, h, kv) == 1

    def attention_fwd_bwd(q, k, v, do):   # as attention_merged hands them on one chip: no rotary (q and k come turned)
        o, vjp = jax.vjp(lambda *a: pallas_attention.flash_attention_merged(*a, None, None, (h, kv), True, None, None, False),
                         q, k, v)
        return o, vjp(do)

    calls = _kernel_calls(jax.jit(attention_fwd_bwd).lower(q, k, k, q).compile().as_text())
    names = _kernel_names(calls)
    assert sum("dvc_flash_fwd" in n for n in names) == 1 and sum("dvc_flash_bwd" in n for n in names) == 1
