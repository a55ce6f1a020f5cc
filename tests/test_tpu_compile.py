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
"""

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

CHUNK_ELEMS = (1 << 20) // 2  # a 1 MiB wire chunk of bf16


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


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The program's backend checks answer as they do on the chip: bf16
    compute, auto routing to the kernel, compiled (not interpreted) kernels."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention, short_conv
    from distributedvolunteercomputing_tpu.utils import jaxenv

    monkeypatch.setattr(jaxenv, "tpu_backend", lambda: True)
    monkeypatch.setattr(pallas_attention, "tpu_backend", lambda: True)
    monkeypatch.setattr(short_conv, "tpu_backend", lambda: True)


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


def test_medium_step_holds_the_kernel(v5e, as_on_the_chip):
    """medium-solo's step, auto routing: T=1,024 bf16 takes the fused core,
    forward and backward; the recomputed forward holds no kernel (the layer's
    checkpoint kept its output and row statistics: ``common.remat_layer``)."""
    text = _step_text(v5e, "gpt2_medium", 1, 1, 16)
    calls = _kernel_calls(text)
    assert len(calls) == 2
    assert all("bf16[16,16,1024,64]" in ln for ln in calls)
    _step_holds_the_groups_its_cell_lists(text, "medium-solo")


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
    calls = _kernel_calls(text)
    names = [re.match(r"\s*%([\w.\-]+) =", ln).group(1) for ln in calls]
    flash = [n for n in names if n.startswith("dvc_flash_")]
    gmm = [n for n in names if moe_trace.GMM_RE.search(n)]
    assert len(flash) == 2 and len(gmm) == 12 and len(names) == 14, names
    assert sum(n.startswith("tgmm") for n in gmm) == 3
    assert all("bf16[4,16,4096,128]" in ln for ln in calls if "dvc_flash_" in ln)
    assert all("[131072," in ln or "bf16[64," in ln for ln in calls if "gmm" in ln)


def _kernel_names(calls):
    import re

    return [re.match(r"\s*%([\w.\-]+) =", ln).group(1) for ln in calls]


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


def test_laguna_step_holds_the_windowed_and_the_full_kernel(v5e, as_on_the_chip, monkeypatch):
    """laguna-solo-8k's step (five layers of Laguna-XS.2 at its published
    widths, sixteen of 256 experts held, an eighth of the vocabulary,
    4 x 8,192 tokens): the full-causal kernel at 48 query heads over 8
    key/value heads (layers 0 and 4) and the windowed one at 64 (layers 1-3),
    each forward and backward (the recomputed forward holds no kernel), under
    the names a device trace tells them by; the expert layers' grouped matmuls over the bounded
    chunk of rows, never the S x k = 262,144, seven a layer. That it compiles
    says the step fits the chip beside its state; its temporaries are what
    they were before PR 38 (8.1079e9 then, 8.1090e9 after it: the float32 carry
    of a run over a tile's edge, ``[rows / 128, d]`` a call; 8.1105e9 since PR 46)."""
    from distributedvolunteercomputing_tpu.ops import moe_dispatch

    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    compiled = _lowered_step(
        v5e, "laguna_xs2", 1, 1, 4, n_layers=5, experts_held=16, vocab=12544).compile()
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "laguna-solo-8k")
    calls = _kernel_calls(text)
    names = _kernel_names(calls)
    full = [n for n in names if n.startswith(("dvc_flash_fwd", "dvc_flash_bwd"))]
    win = [n for n in names if n.startswith("dvc_flash_win_")]
    assert len(full) == 4 and sum(n.startswith("dvc_flash_bwd") for n in full) == 2, names
    assert len(win) == 6 and sum(n.startswith("dvc_flash_win_bwd") for n in win) == 3, names
    assert all("bf16[4,48,8192,128]" in ln for ln in calls if "dvc_flash_fwd" in ln or "dvc_flash_bwd" in ln)
    assert all("bf16[4,64,8192,128]" in ln for ln in calls if "dvc_flash_win_" in ln)
    assert all("bf16[4,8,8192,128]" in ln for ln in calls if "dvc_flash_" in ln)  # 8 key/value heads
    rows = moe_dispatch.share_rows_bound(4 * 8192, 8, 16, 256)
    assert rows == 49152  # three times the even share of 16,384: one chunk a layer on the chip
    assert f"[{rows},2048]" in text and "[262144,2048]" not in text
    _share_chunks_hold_seven_grouped_matmuls(names, text, layers=4, rows=rows, d=2048, f=512)
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert total < 15.75e9, total
    # the parent of PR 36: 8.1125e9; of PR 38: 8.1079e9; of PR 46: 8.1090e9, and 8.1105e9 since (three select passes
    # a layer fewer and the same buffers alive: the heap packs 1.5 MB worse)
    assert mem.temp_size_in_bytes <= 8.112e9, mem.temp_size_in_bytes


def test_smallthinker_step_runs_every_layer_on_the_flash_kernels_at_16k(v5e, as_on_the_chip, monkeypatch):
    """smallthinker-solo-16k's step (one period of SmallThinker-21BA3B at its
    published widths, eight of 64 experts held, an eighth of the vocabulary,
    2 x 16,384 tokens): at T=16,384 the whole-head-resident kernels still fit
    their VMEM budget (1,024 x 1,024 blocks with or without the 4,096 window),
    so both layer kinds take them, 28 query heads over 4 key/value heads, and
    no layer falls to the XLA core's [28, 16384, 16384] scores. The model is
    scanned by period with an inner scan over the three sliding layers: ONE
    windowed and ONE full kernel, each forward and backward, whatever the
    depth. The share's grouped matmuls see the bounded chunk of 104,448 rows
    (the model's own slack, 4.25 times the even share: models/smallthinker.py),
    never the S x k = 196,608, seven a traced layer; arguments and temporaries
    stay under 15.0e9. The temporaries: 9.5213e9 before PR 36, 9.3057e9 with
    it, 9.6039e9 since PR 38, whose step needs LESS at once (XLA's live-range
    peak 10.598e9 against 10.781e9 with the arguments; two ``[rows, d]``
    buffers in a run's sum where the shifted adds held three) and whose heap
    packs worse: the scheduler now runs the down stack's ``tgmm`` after the
    run's product, the heap simulator lays 0.24e9 more out, and a tile of 256
    rows compiles to the same (PERF.md, Findings of PR 38)."""
    from distributedvolunteercomputing_tpu.models import smallthinker
    from distributedvolunteercomputing_tpu.ops import attention, moe_dispatch, pallas_attention

    t, d = 16384, 128
    for window in (None, 4096):
        assert pallas_attention.choose_blocks(t, t, d, jnp.bfloat16, window) == (1024, 1024)
    used = pallas_attention.vmem_bytes(t, t, d, jnp.bfloat16, 1024, 1024)
    assert 0.9 * pallas_attention.VMEM_BUDGET_BYTES < used <= pallas_attention.VMEM_BUDGET_BYTES
    assert pallas_attention.choose_blocks(2 * t, 2 * t, d, jnp.bfloat16) is None  # the next doubling does not fit
    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    seen = []
    attention.set_core_observer(lambda impl, t, d, dtype, window=None, kv_heads=None: seen.append(
        (impl, t, window, kv_heads)))
    try:
        compiled = _lowered_step(
            v5e, "smallthinker_21b_a3b", 1, 1, 2, n_layers=4, experts_held=8, vocab=18992).compile()
    finally:
        attention.set_core_observer(None)
    assert sorted(set(seen), key=str) == [("flash", t, 4096, 4), ("flash", t, None, 4)], seen
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "smallthinker-solo-16k")
    calls = _kernel_calls(text)
    flash = sorted(n.split(".")[0] for n in _kernel_names(calls) if n.startswith("dvc_flash"))
    assert flash == ["dvc_flash_bwd", "dvc_flash_fwd", "dvc_flash_win_bwd", "dvc_flash_win_fwd"], flash
    assert all("bf16[2,28,16384,128]" in ln and "bf16[2,4,16384,128]" in ln for ln in calls if "dvc_flash_" in ln)
    assert moe_dispatch.share_rows_bound(2 * t, 6, 8, 64) == 73728  # the dispatch's default, three even shares
    rows = moe_dispatch.share_rows_bound(2 * t, 6, 8, 64, smallthinker.SHARE_ROWS_SLACK)
    assert rows == 104448  # 3.19 S: three held experts that each take every token fit one chunk
    assert f"[{rows},2560]" in text and "[196608,2560]" not in text and "[73728,2560]" not in text
    # one trace a layer kind: the scan's body holds each kind's loops once
    _share_chunks_hold_seven_grouped_matmuls(_kernel_names(calls), text, layers=2, rows=rows, d=2560, f=768)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9, (
        mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes <= 9.61e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("model,batch,layers,shape", [
    ("gpt2_medium", 16, 2, "bf16[16,16,1024,64]"), ("olmoe_1b_7b", 4, 1, "bf16[4,16,4096,128]")])
def test_other_steps_keep_their_kernel_names(v5e, as_on_the_chip, monkeypatch, model, batch, layers, shape):
    """The gpt2 and OLMoE cells trace the attention kernels under the names
    they had before the windowed ones existed: ``dvc_flash_fwd`` and
    ``dvc_flash_bwd`` once a scanned layer, forward and backward; the
    recomputed forward holds no kernel; at equal head counts, and no windowed
    name."""
    from distributedvolunteercomputing_tpu.ops import moe_dispatch

    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    calls = _kernel_calls(_step_text(v5e, model, 1, 1, batch, n_layers=layers))
    flash = sorted(n.split(".")[0] for n in _kernel_names(calls) if n.startswith("dvc_flash"))
    assert flash == ["dvc_flash_bwd", "dvc_flash_fwd"], flash
    assert all(ln.count(shape) >= 3 for ln in calls if "dvc_flash_" in ln)  # q, k and v alike


def test_four_chip_step_calls_the_kernel_per_shard(v5e, as_on_the_chip):
    """large-solo-4chip's step (dp=2, tp=2, batch 32, 20 heads): each chip's
    kernel sees its own 16 rows and 10 heads, forward and backward; the
    recomputed forward holds no kernel (the kept names pass through the
    per-shard ``shard_map``); and nothing gathered feeds it."""
    import re

    text = _step_text(v5e, "gpt2_large", 2, 2, 32)
    _step_holds_the_groups_its_cell_lists(text, "large-solo-4chip")
    calls = _kernel_calls(text)
    assert len(calls) == 2
    assert all("bf16[16,10,1024,64]" in ln for ln in calls)
    assert not any("[32," in ln.split("custom-call(")[1].split(")")[0] for ln in calls)
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


def test_four_chip_step_moves_no_activation_for_qkv(v5e, as_on_the_chip):
    """large-solo-4chip's step: q, k and v are born on the chip that runs their
    heads (``common.qkv_heads`` divides the projection by head over tp), so no
    all-to-all and no collective-permute carries them or their cotangents. What
    crosses a link at an activation's size is Megatron's price alone, FOUR
    all-reduces a layer: after each row-parallel product in the forward
    (attn_out, mlp_out) and before each column-parallel one in the backward
    (mlp_in, qkv). The backward's recomputed forward moves no activation: the
    layer's checkpoint kept attn_out's reduced result (``common.remat_layer``,
    ``attention.keep_tp_reduced``). The kernel still sees its own 10 heads,
    forward and backward."""
    import re

    text = _step_text(v5e, "gpt2_large", 2, 2, 32)
    found = _collectives(text)
    kinds = {kind for _, kind, _ in found}
    assert "all-to-all" not in kinds and "collective-permute" not in kinds, kinds
    activation_sized = [(kind, ln) for result, kind, ln in found if re.search(r"\[16,1024,\d+\]", result)]
    assert [kind for kind, _ in activation_sized] == ["all-reduce"] * 4, activation_sized
    # two in the forward scan's body, two in the backward's, by the scopes their products carry
    where = sorted(re.search(r'op_name="jit\(step\)/(.*?)/while/.*/(attention|mlp)/', ln).groups()
                   for _, ln in activation_sized)
    assert where == [("jvp()", "attention"), ("jvp()", "mlp"),
                     ("transpose(jvp())", "attention"), ("transpose(jvp())", "mlp")], where
    # the recomputed forward, by the name its operations carry: no all-reduce and nothing of an
    # activation's size; what it still gathers is the qkv leaf's head-aligned view (weight and bias)
    recomputed = [(result, kind) for result, kind, ln in found if "rematted_computation" in ln]
    assert recomputed and {kind for _, kind in recomputed} == {"all-gather"}, recomputed
    assert all(re.match(r"bf16\[(1280,3840|\d+,1,1920)\]", result) for result, _ in recomputed), recomputed
    calls = _kernel_calls(text)
    assert len(calls) == 2 and all("bf16[16,10,1024,64]" in ln for ln in calls)
    assert not [ln for ln in calls if "rematted_computation" in ln]


def test_one_chip_step_names_nothing_more_to_keep(v5e, as_on_the_chip):
    """medium-solo's step traces with no ``tp`` to divide a layer: the block's
    checkpoint names the kernel's two results and nothing else (on one chip
    attn_out's result costs a product to make again, not an all-reduce), and
    ``swarm.remat_kept`` reads the kernel's bytes. The four-chip step names
    attn_out's reduced result once a traced block and counts a chip's
    ``bf16[16,1024,1280]`` of it a layer beside the kernel's."""
    import re

    from distributedvolunteercomputing_tpu.ops import attention

    def kept(*model_mesh_batch):
        seen = []
        attention.set_kept_observer(lambda layers, nbytes: seen.append((layers, nbytes)))
        try:
            jaxpr = str(_traced_step(v5e, *model_mesh_batch).jaxpr)
        finally:
            attention.set_kept_observer(None)
        return sorted(re.findall(r"name\[name=(\w+)\]", jaxpr)), seen

    def kernel(b, h, t):  # the output's rows at 128 lanes of bf16 and a float32 log-sum-exp a row
        return b * h * t * (128 * 2 + 4)

    assert kept("gpt2_medium", 1, 1, 16) == (["attention_lse", "attention_out"], [(2, 2 * kernel(16, 16, 1024))])
    assert kept("gpt2_large", 2, 2, 32) == (
        ["attention_lse", "attention_out", "tp_reduced"], [(2, 2 * (kernel(16, 10, 1024) + 41_943_040))])


def test_one_chip_step_keeps_the_fused_qkv_product(v5e, as_on_the_chip):
    """medium-solo's step is the program it was: with one chip the projection
    is one [.., d] x [d, 3d] product whose 3d-wide result feeds the split,
    and ``common.qkv_heads`` lays nothing out (no head-major weight view, no
    sharding constraint on it)."""
    import re

    text = _lowered_step(v5e, "gpt2_medium", 1, 1, 16).as_text()
    assert re.search(r"stablehlo\.dot_general.*-> tensor<16x1024x3072xbf16>", text)
    assert re.search(r"stablehlo\.slice.*tensor<16x1024x3072xbf16>\) -> tensor<16x1024x1024xbf16>", text)
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


def test_lfm2_step_holds_its_kernels_one_trace_a_layer_shape(v5e, as_on_the_chip, monkeypatch):
    """lfm2-solo-8k's step (published layers 0 and 2-5 of LFM2-24B-A2B at its
    published widths, eight of 64 experts held, an eighth of the vocabulary,
    4 x 8,192 tokens): three traced layer shapes, the dense conv layer, the
    attention expert layer and ONE scanned conv expert layer for the three.
    The attention layer takes the flash kernel at head dim 64 with four query
    heads a key/value head, forward and backward only (its recomputed forward
    holds none: ``remat_layer`` kept the output and row statistics); each conv
    layer shape runs the convolution's kernel forward, again in the recomputed
    forward (it keeps nothing of its mixer) and backward: two shapes, six
    calls; the share's grouped matmuls see the levelled router's chunk of 20,480
    rows (the even share of 16,384 and a quarter: the model passes
    ``SHARE_ROWS_SLACK_LEVELLED``), never the dispatch's default of 49,152 nor
    the S x k = 131,072, seven a traced expert layer. That it compiles says it
    fits the chip; its temporaries are 6.186e9 (7.359e9 at 49,152 rows, PR 39)."""
    from distributedvolunteercomputing_tpu.models import lfm2
    from distributedvolunteercomputing_tpu.ops import attention, moe_dispatch

    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    seen = []
    attention.set_core_observer(lambda impl, t, d, dtype, window=None, kv_heads=None: seen.append(
        (impl, t, d, window, kv_heads)))
    try:
        compiled = _lowered_step(
            v5e, "lfm2_24b_a2b", 1, 1, 4, n_layers=None, layer_types="conv,full_attention,conv,conv,conv",
            dense_layers=1, experts_held=8, vocab=8192).compile()
    finally:
        attention.set_core_observer(None)
    assert set(seen) == {("flash", 8192, 64, None, 8)}, seen
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "lfm2-solo-8k")
    calls = _kernel_calls(text)
    names = _kernel_names(calls)
    flash = sorted(n.split(".")[0] for n in names if n.startswith("dvc_flash"))
    assert flash == ["dvc_flash_bwd", "dvc_flash_fwd"], flash
    assert all("bf16[4,32,8192,64]" in ln and "bf16[4,8,8192,64]" in ln for ln in calls if "dvc_flash_" in ln)
    conv = sorted(n.split(".")[0] for n in names if n.startswith("dvc_short_conv"))
    assert conv == ["dvc_short_conv_bwd"] * 2 + ["dvc_short_conv_fwd"] * 4, conv
    assert all("bf16[4,8192,6144]" in ln for ln in calls if "dvc_short_conv" in ln)
    assert moe_dispatch.share_rows_bound(4 * 8192, 4, 8, 64) == 49152  # the dispatch's default, three even shares
    rows = moe_dispatch.share_rows_bound(4 * 8192, 4, 8, 64, lfm2.SHARE_ROWS_SLACK)
    assert rows == 20480  # the even share of 16,384 and a quarter: forty megablox row tiles
    assert f"[{rows},2048]" in text and "[131072,2048]" not in text and "[49152,2048]" not in text
    _share_chunks_hold_seven_grouped_matmuls(names, text, layers=2, rows=rows, d=2048, f=1536)
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert total < 13.5e9, total
    assert mem.temp_size_in_bytes <= 6.22e9, mem.temp_size_in_bytes  # 6.186e9; at 49,152 rows (PR 39) 7.359e9


def test_glm47_flash_step_runs_latent_attention_on_the_flash_kernels_at_a_head_of_256(v5e, as_on_the_chip, monkeypatch):
    """glm47-flash-solo-8k's step (published layers 0-4 of GLM-4.7-Flash at its
    published widths, eight of 64 experts held, an eighth of the vocabulary,
    2 x 8,192 tokens). A head of 256 at T=8,192 is the edge of what the
    whole-head-resident kernels hold: 1,024 x 1,024 blocks are 66.06e6 of the
    67.1e6-byte budget (the same resident bytes as D=128 at T=16,384), and the
    next doubling of either does not fit. The model builds q, k and v at
    ``[2, 20, 8192, 256]`` (the one rotary key broadcast to the 20 heads) and
    both traced layer shapes, the dense layer and ONE scanned expert layer for
    the four, take the kernel forward and backward only (``remat_layer`` kept
    the output and row statistics). The share's grouped matmuls see the
    dispatch's default chunk of 24,576 rows (three even shares of 8,192: the
    model's own reading refuted the levelled quarter), seven a traced expert
    layer. Arguments and temporaries are
    16.18e9 (7.096 + 9.080): OVER the 15.0e9 line of the other share cells'
    tests, and what the chip still loads and runs (PERF.md, Findings of PR 42);
    the line here says that nothing more fits."""
    from distributedvolunteercomputing_tpu.models import glm4_moe_lite
    from distributedvolunteercomputing_tpu.ops import attention, moe_dispatch, pallas_attention

    t, d = 8192, 256
    assert 256 in attention._AUTO_FLASH_HEAD_DIMS
    assert pallas_attention.choose_blocks(t, t, d, jnp.bfloat16) == (1024, 1024)
    used = pallas_attention.vmem_bytes(t, t, d, jnp.bfloat16, 1024, 1024)
    assert used == 66_060_288 and 0.98 * pallas_attention.VMEM_BUDGET_BYTES < used <= pallas_attention.VMEM_BUDGET_BYTES
    # D=128 at T=16,384 (smallthinker-solo-16k) holds the same resident bytes and smaller streamed blocks: 61.0 MiB
    assert 63.9e6 < pallas_attention.vmem_bytes(2 * t, 2 * t, 128, jnp.bfloat16, 1024, 1024) < used
    assert pallas_attention.choose_blocks(2 * t, 2 * t, d, jnp.bfloat16) is None   # a head of 256 beyond 8,192: none
    assert pallas_attention.choose_blocks(t, t, 2 * d, jnp.bfloat16) is None
    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    seen, kept = [], []
    attention.set_core_observer(lambda impl, t, d, dtype, window=None, kv_heads=None: seen.append(
        (impl, t, d, window, kv_heads)))
    attention.set_kept_observer(lambda layers, nbytes: kept.append((layers, nbytes)))
    try:
        compiled = _lowered_step(v5e, "glm4_7_flash", 1, 1, 2, n_layers=5, experts_held=8, vocab=19360).compile()
    finally:
        attention.set_core_observer(None)
        attention.set_kept_observer(None)
    assert seen == [("flash", t, d, None, 20)] * 2, seen          # one traced dense layer, one traced scan body
    # the output at 20 x 256 a token and the f32 row statistics: 168.8 MB a layer, 845.4 MB a step
    assert kept == [(1, 169_082_880), (4, 4 * 169_082_880)], kept
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "glm47-flash-solo-8k")
    calls = _kernel_calls(text)
    names = _kernel_names(calls)
    flash = sorted(n.split(".")[0] for n in names if n.startswith("dvc_flash"))
    assert flash == ["dvc_flash_bwd"] * 2 + ["dvc_flash_fwd"] * 2, flash
    assert all("bf16[2,20,8192,256]" in ln for ln in calls if "dvc_flash_" in ln)
    assert moe_dispatch.share_rows_bound(2 * t, 4, 8, 64, moe_dispatch.SHARE_ROWS_SLACK_LEVELLED) == 10240
    rows = moe_dispatch.share_rows_bound(2 * t, 4, 8, 64, glm4_moe_lite.SHARE_ROWS_SLACK)
    assert rows == 24576  # three even shares of 8,192: forty-eight megablox row tiles
    assert f"[{rows},2048]" in text and "[65536,2048]" not in text   # never the S x k assignments
    _share_chunks_hold_seven_grouped_matmuls(names, text, layers=1, rows=rows, d=2048, f=1536)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(7.0957e9, rel=1e-3)  # float32 parameters and two Adam moments
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < 16.25e9, total                        # 16.176e9: the chip takes about 16.9e9 and ran it
    assert mem.temp_size_in_bytes <= 9.12e9, mem.temp_size_in_bytes   # 9.080e9 (9.219e9 at the levelled chunk)


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


def test_nemotron_step_holds_its_kernels_one_trace_a_unit_shape(v5e, as_on_the_chip, monkeypatch):
    """nemotron3-nano-solo-8k's step (published blocks 0-6, MEMEM*E, of
    Nemotron-3-Nano-30B-A3B at its published widths, eight of 128 experts held,
    an eighth of the vocabulary, 2 x 8,192 tokens): two traced unit shapes, a
    scan over the two ``ME`` and one ``M*E``, every block rematerialised by
    itself. Each traced state-space block runs the scan's kernel forward, again
    in its recomputed forward (it keeps nothing) and backward, and the
    convolution's likewise: two traces, six calls each. The attention block
    takes the flash kernel at a head of 128 with SIXTEEN query heads a key/value
    head, forward and backward only. The experts' width of 1,856 is no whole
    number of megablox's 128-column tiles (14.5), so the share's grouped
    products run padded: ``[7680, 3072] x [8, 3072, 2048]`` in tiles of 512 x
    1,024 x 1,024, seven a traced expert block, over the levelled router's
    chunk of 7,680 rows (the even share of 6,144 and a quarter), never the
    S x k = 98,304. That it compiles says it fits the chip."""
    from distributedvolunteercomputing_tpu.models import nemotron_h
    from distributedvolunteercomputing_tpu.ops import attention, moe_dispatch, ssd

    monkeypatch.setattr(ssd, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    assert moe_dispatch._megablox_tiling(7680, 2688, 1856) is None and moe_dispatch._megablox_tiling(7680, 1856, 2688) is None
    assert (moe_dispatch._padded(2688), moe_dispatch._padded(1856)) == (3072, 2048)
    assert moe_dispatch._megablox_tiling(7680, 3072, 2048) == (512, 1024, 1024)
    seen, kept = [], []
    attention.set_core_observer(lambda impl, t, d, dtype, window=None, kv_heads=None: seen.append(
        (impl, t, d, window, kv_heads)))
    attention.set_kept_observer(lambda layers, nbytes: kept.append((layers, nbytes)))
    try:
        compiled = _lowered_step(v5e, "nemotron3_nano_30b_a3b", 1, 1, 2, n_layers=7, experts_held=8, vocab=16384).compile()
    finally:
        attention.set_core_observer(None)
        attention.set_kept_observer(None)
    assert seen == [("flash", 8192, 128, None, 2)], seen
    # what the blocks keep: the attention block's output and row statistics; a state-space block nothing
    assert kept == [(1, 2 * 32 * 8192 * (128 * 2 + 4))], kept
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "nemotron3-nano-solo-8k")
    calls = _kernel_calls(text)
    names = [n.split(".")[0] for n in _kernel_names(calls)]
    assert sorted(n for n in names if n.startswith("dvc_flash")) == ["dvc_flash_bwd", "dvc_flash_fwd"]
    assert all("bf16[2,32,8192,128]" in ln and "bf16[2,2,8192,128]" in ln for ln in calls if "dvc_flash_" in ln)
    assert sorted(n for n in names if n.startswith("dvc_ssd")) == ["dvc_ssd_bwd"] * 2 + ["dvc_ssd_fwd"] * 4
    assert all("bf16[2,8192,6144]" in ln and "bf16[2,8192,4096]" in ln for ln in calls if "dvc_ssd_" in ln)
    assert "[2,64,8192,64]" not in text and "[2,8192,64,64]" not in text   # no stream by head: nothing to transpose
    assert sorted(n for n in names if n.startswith("dvc_short_conv")) == ["dvc_short_conv_bwd"] * 2 + ["dvc_short_conv_fwd"] * 4
    assert all("bf16[2,8192,6144]" in ln for ln in calls if "dvc_short_conv" in ln)
    rows = moe_dispatch.share_rows_bound(2 * 8192, 6, 8, 128, nemotron_h.SHARE_ROWS_SLACK)
    assert rows == 7680  # the even share of 6,144 and a quarter: fifteen row tiles
    assert f"[{rows},2688]" in text and f"[{rows},1856]" in text and "[98304,2688]" not in text
    from benchmark import moe_trace

    gmm = [n for n in names if moe_trace.GMM_RE.search(n)]
    assert len(gmm) == 7 * 2 and "ragged-dot" not in text, gmm      # two traced expert blocks, seven products each
    assert f"bf16[{rows},3072]" in text and "bf16[8,3072,2048]" in text and "bf16[8,2048,3072]" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(6.3373e9, rel=1e-3)  # float32 parameters and two Adam moments
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.3e9     # at 4 x 8,192: 17.21e9, over the chip


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


def test_kimi_linear_step_holds_its_kernels_one_trace_a_layer_shape(v5e, as_on_the_chip, monkeypatch):
    """kimi-linear-solo-8k's step (published layers 1-5 of
    Kimi-Linear-48B-A3B-Instruct at its published widths, eight of 256 experts
    held, an eighth of the vocabulary, 2 x 8,192 tokens): four traced layer
    shapes (layer 1, layers 2-3 as one scanned body, layer 4, layer 5), every
    layer rematerialised. Each traced KDA layer runs the scan's loop forward,
    again in its recomputed forward (it keeps nothing) and backward, and each of
    its three convolutions' kernels likewise; the latent layer takes the flash kernel at
    keys of 192 over values of 128, forward and backward only. The share's
    grouped products see the dispatch's default chunk of 12,288 rows (three even
    shares of 4,096), seven a traced expert layer. That it compiles says it fits
    the chip."""
    from benchmark import kda_trace
    from distributedvolunteercomputing_tpu.models import kimi_linear
    from distributedvolunteercomputing_tpu.ops import attention, kda, moe_dispatch

    monkeypatch.setattr(kda, "tpu_backend", lambda: True)     # bfloat16 products as the chip takes them
    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    seen, kept = [], []
    attention.set_core_observer(lambda impl, t, d, dtype, window=None, kv_heads=None: seen.append(
        (impl, t, d, window, kv_heads)))
    attention.set_kept_observer(lambda layers, nbytes: kept.append((layers, nbytes)))
    try:
        compiled = _lowered_step(v5e, "kimi_linear_48b_a3b", 1, 1, 2, n_layers=5, experts_held=8, vocab=20480).compile()
    finally:
        attention.set_core_observer(None)
        attention.set_kept_observer(None)
    assert seen == [("flash", 8192, 192, None, 32)], seen
    # what the layers keep: the latent layer's output at 32 x 128 a token and its row statistics; a KDA layer nothing
    assert kept == [(1, 2 * 32 * 8192 * (128 * 2 + 4))], kept
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "kimi-linear-solo-8k")
    calls = _kernel_calls(text)
    names = [n.split(".")[0] for n in _kernel_names(calls)]
    assert sorted(n for n in names if n.startswith("dvc_flash")) == ["dvc_flash_bwd", "dvc_flash_fwd"]
    # the scan's loops, told as benchmark/kda_trace.py tells them in a trace: three traced KDA layers, each forward twice and backward
    scans = [shapes for shapes in (kda_trace.carried(ln.strip()) for ln in text.splitlines() if " while(" in ln)
             if (2, 32, 128, 128) in shapes]
    # nine loops carry the heads' states. Since PR 55 each takes its chunks out of the whole streams it carries
    # ([2, 8192, 4096]) and holds by chunk the states alone, so the reader's count (more than FORWARD_HOLDS_AT_MOST
    # arrays by chunk: a backward loop) calls none of the nine backward: kda.roofline's least time is nine forward
    # loops' where three are backward ones (PERF.md section 7). By the whole streams they carry the three are plain:
    # q, k, v, g and o forward; q, k, v, g, dO and the four cotangents backward
    assert [sum(s[:2] == (128, 2) for s in shapes) for shapes in scans] == [1] * 9 and kda_trace.FORWARD_HOLDS_AT_MOST == 8
    assert sorted(sum(s == (2, 8192, 4096) for s in shapes) for shapes in scans) == [5] * 6 + [9] * 3
    assert sorted(n for n in names if n.startswith("dvc_short_conv")) == ["dvc_short_conv_bwd"] * 9 + ["dvc_short_conv_fwd"] * 18
    assert all("bf16[2,8192,4096]" in ln for ln in calls if "dvc_short_conv" in ln)
    rows = moe_dispatch.share_rows_bound(2 * 8192, 8, 8, 256, kimi_linear.SHARE_ROWS_SLACK)
    assert rows == 12288 and f"[{rows},2304]" in text and "[131072,2304]" not in text   # never the S x k assignments
    from benchmark import moe_trace

    gmm = [n for n in names if moe_trace.GMM_RE.search(n)]
    assert len(gmm) == 7 * 3 and "ragged-dot" not in text, gmm      # three traced expert layers, seven products each
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(7.2296e9, rel=1e-3)  # float32 parameters and two Adam moments
    # 15.37e9 by this analysis (8.14e9 of temporaries; 17.16e9 and 9.93e9 until PR 55 took the streams' by-chunk and
    # by-head copies out): under the 17.16e9 that the chip's own compile loaded and ran beside the reference check
    # (memory_peak_bytes 15.04e9 of 16.9e9 then, 15.02e9 now: my chip runs, PR 52 calls 9-10, PR 55 call 1)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.6e9
