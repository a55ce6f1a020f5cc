"""Flash attention (pallas) vs the plain-XLA core: forward + grads.

Runs on the CPU mesh via interpret mode (conftest forces JAX_PLATFORMS=cpu),
so the exact kernel code that compiles on TPU is what's being checked.
Small block sizes force the multi-block online-softmax loop and the
padding path (T not a multiple of the block).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedvolunteercomputing_tpu.ops.attention import attention_core, set_attention_impl
from distributedvolunteercomputing_tpu.ops.pallas_attention import flash_attention
from distributedvolunteercomputing_tpu.utils import traced


def _qkv(rng, b=2, h=2, tq=40, tk=40, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, h, tq, d), dtype)
    k = jax.random.normal(kk, (b, h, tk, d), dtype)
    v = jax.random.normal(kv, (b, h, tk, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("tq,tk", [(32, 32), (40, 40), (16, 48)])
def test_forward_matches_xla(causal, tq, tk):
    if causal and tq != tk:
        pytest.skip("causal requires square here")
    q, k, v = _qkv(jax.random.PRNGKey(0), tq=tq, tk=tk)
    ref = attention_core(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, 16, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_xla(causal):
    q, k, v = _qkv(jax.random.PRNGKey(1), tq=40, tk=40)
    cot = jax.random.normal(jax.random.PRNGKey(2), q.shape)

    def loss_ref(q, k, v):
        return jnp.sum(attention_core(q, k, v, causal=causal) * cot)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, 16, 16) * cot)

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    g_fl = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


def test_bf16_forward_close():
    q, k, v = _qkv(jax.random.PRNGKey(3), tq=32, tk=32, dtype=jnp.bfloat16)
    ref = attention_core(q, k, v, causal=True).astype(jnp.float32)
    out = flash_attention(q, k, v, True, 16, 16).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-2, rtol=3e-2)


def test_flash_inside_sharded_step(eight_devices):
    # The flagship TPU configuration is flash attention INSIDE the pjit'd
    # dp x tp train step — pallas_call must lower under GSPMD partitioning.
    import numpy as np
    from jax.sharding import Mesh

    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.parallel.train_step import (
        make_sharded_train_step,
        put_batch,
        shard_train_state,
    )
    from distributedvolunteercomputing_tpu.training.optim import make_optimizer
    from distributedvolunteercomputing_tpu.training.steps import TrainState

    bundle = get_model(
        "gpt2_small", n_layers=2, d_model=64, n_heads=4, d_ff=128,
        vocab=256, max_len=32, remat=False,
    )
    mesh = Mesh(np.array(eight_devices).reshape(4, 2), ("dp", "tp"))
    tx = make_optimizer("adam", lr=1e-3)
    state = TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(1))
    state, _ = shard_train_state(state, mesh, tx)
    step = make_sharded_train_step(bundle.loss_fn, tx, mesh)
    batch = put_batch(bundle.make_batch(jax.random.PRNGKey(2), 8), mesh)
    try:
        set_attention_impl("flash")
        with mesh:
            state, m = step(state, batch)
        loss = float(m["loss"])
    finally:
        set_attention_impl("auto")
    assert np.isfinite(loss)


def test_impl_switch_routes_models():
    # "flash" forces the pallas path even on CPU (interpret mode); the GPT-2
    # block must produce the same logits either way.
    from distributedvolunteercomputing_tpu.models import get_model

    bundle = get_model(
        "gpt2_small", n_layers=2, d_model=64, n_heads=2, d_ff=128,
        vocab=256, max_len=64, remat=False,
    )
    params = bundle.init(jax.random.PRNGKey(0))
    batch = bundle.make_batch(jax.random.PRNGKey(1), 2)
    rng = jax.random.PRNGKey(2)
    try:
        set_attention_impl("xla")
        loss_xla, _ = bundle.loss_fn(params, batch, rng)
        set_attention_impl("flash")
        loss_flash, _ = bundle.loss_fn(params, batch, rng)
    finally:
        set_attention_impl("auto")
    np.testing.assert_allclose(float(loss_xla), float(loss_flash), atol=1e-3, rtol=1e-4)


# -- PR 27: routing by what the code observes, blocks sized for the shape ----


class _Shape:
    """Just what the router reads of an array."""

    def __init__(self, shape, dtype):
        self.shape, self.dtype = shape, jnp.dtype(dtype)


def _routes(monkeypatch, backend_is_tpu, dtype, tq, tk, d, mask, causal, impl="auto",
            devices=1):
    from distributedvolunteercomputing_tpu.ops import attention
    from distributedvolunteercomputing_tpu.utils import jaxenv

    monkeypatch.setattr(jaxenv, "tpu_backend", lambda: backend_is_tpu)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    monkeypatch.setattr(attention, "_impl", impl)
    q, k = _Shape((2, 4, tq, d), dtype), _Shape((2, 4, tk, d), dtype)
    return attention._route_to_flash(q, k, causal, "a mask" if mask else None)


@pytest.mark.parametrize(
    "backend_is_tpu,dtype,tq,tk,d,mask,causal,want",
    [
        # the cells' shape, and the measured crossover on either side of it
        (True, "bfloat16", 1024, 1024, 64, False, True, True),
        (True, "bfloat16", 512, 512, 64, False, True, True),
        (True, "bfloat16", 256, 256, 64, False, True, False),
        (True, "bfloat16", 2048, 2048, 64, False, False, True),
        (True, "bfloat16", 1024, 1024, 128, False, True, True),
        # vit's 197 tokens, bert's padding mask, rectangular attention
        (True, "bfloat16", 197, 197, 64, False, False, False),
        (True, "bfloat16", 1024, 1024, 64, True, False, False),
        (True, "bfloat16", 512, 1024, 64, False, True, False),
        (True, "bfloat16", 512, 1024, 64, False, False, False),
        # float32 as bf16; a head dim nobody measured; a dtype nobody measured
        (True, "float32", 256, 256, 64, False, True, False),
        (True, "float32", 512, 512, 64, False, True, True),
        (True, "bfloat16", 1024, 1024, 80, False, True, False),
        (True, "float16", 1024, 1024, 64, False, True, False),
        # every other backend keeps the XLA core
        (False, "bfloat16", 1024, 1024, 64, False, True, False),
        (False, "float32", 4096, 4096, 64, False, True, False),
    ],
)
def test_auto_router_table(monkeypatch, backend_is_tpu, dtype, tq, tk, d, mask, causal, want):
    assert _routes(monkeypatch, backend_is_tpu, dtype, tq, tk, d, mask, causal) is want


@pytest.mark.parametrize(
    "impl,tq,tk,mask,causal,want",
    [
        ("flash", 40, 40, False, True, True),     # forced: any backend, any length
        ("flash", 16, 48, False, False, True),
        ("flash", 16, 48, False, True, False),    # rectangular causal never
        ("flash", 40, 40, True, False, False),    # a mask never
        ("xla", 4096, 4096, False, True, False),
    ],
)
def test_forced_router(monkeypatch, impl, tq, tk, mask, causal, want):
    assert _routes(monkeypatch, True, "bfloat16", tq, tk, 64, mask, causal, impl) is want


def test_auto_router_needs_to_know_the_layout(monkeypatch, eight_devices):
    """Four chips: a step that announced its mesh gets the kernel (per
    shard); a bare jit whose partitioning nobody announced keeps XLA."""
    from jax.sharding import Mesh

    from distributedvolunteercomputing_tpu.ops import attention
    from distributedvolunteercomputing_tpu.parallel.mesh import AXES

    cell = (True, "bfloat16", 1024, 1024, 64, False, True)
    assert _routes(monkeypatch, *cell, devices=4) is False
    mesh = Mesh(np.array(eight_devices[:4]).reshape(2, 1, 1, 1, 2), AXES)
    with attention.step_mesh(mesh):
        assert _routes(monkeypatch, *cell, devices=4) is True
    # forced: the caller answers for the layout (the CPU suite's interpreter)
    assert _routes(monkeypatch, *cell, impl="flash", devices=4) is True


def test_router_constants_carry_their_origin():
    import inspect

    from distributedvolunteercomputing_tpu.ops import attention

    assert attention._AUTO_FLASH_MIN_T == {"bfloat16": 512, "float32": 512}
    src = inspect.getsource(attention)
    assert "MEASURED, PR 27" in src and "TPU v5e" in src
    assert "NOT MEASURED" not in src


@pytest.mark.parametrize(
    "tq,tk,d,dtype,want",
    [
        (1024, 1024, 64, jnp.bfloat16, (1024, 1024)),   # medium-solo, large-solo-4chip
        (2048, 2048, 64, jnp.bfloat16, (1024, 1024)),
        (512, 512, 64, jnp.bfloat16, (512, 512)),
        (768, 768, 64, jnp.bfloat16, (256, 256)),
        (197, 197, 64, jnp.bfloat16, (208, 208)),       # one padded block
        (1300, 1300, 64, jnp.float32, (1024, 1024)),    # padded to two blocks
        (128, 640, 128, jnp.bfloat16, (128, 128)),      # Tq != Tk
    ],
)
def test_choose_blocks(tq, tk, d, dtype, want):
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    blocks = pa.choose_blocks(tq, tk, d, dtype)
    assert blocks == want
    bq, bk = blocks
    # what Mosaic accepts: a multiple of 128 on the lane axis of the
    # statistics' row, or the whole (tile-rounded) sequence in one block
    assert bq % 128 == 0 or bq >= tq
    assert bk % 128 == 0 or bk >= tk
    assert pa.vmem_bytes(tq, tk, d, dtype, bq, bk) <= pa.VMEM_BUDGET_BYTES
    # the grid at the cells' batch: hundreds of steps, not tens of thousands
    steps = 16 * 16 * max(-(-tq // bq), -(-tk // bk))
    assert steps <= 2048


def test_choose_blocks_refuses_a_head_that_does_not_fit():
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    assert pa.choose_blocks(1 << 20, 1 << 20, 128, jnp.float32) is None
    huge = _Shape((1, 1, 1 << 20, 128), jnp.float32)
    with pytest.raises(ValueError, match="one head in VMEM"):
        pa._resolve(huge, huge, None, None, True)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-5), (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize(
    "t,bq,bk",
    [
        (256, 64, 64),    # 4 x 4 blocks: full, diagonal and skipped ones
        (256, 128, 64),   # the diagonal crosses two k-blocks of a q-block
        (256, 64, 128),   # ... and two q-blocks of a k-block
        (200, 64, 64),    # ragged: padded rows and padded keys
    ],
)
def test_kernel_matches_xla_across_blocks(dtype, tol, t, bq, bk):
    q, k, v = _qkv(jax.random.PRNGKey(5), b=1, h=2, tq=t, tk=t, d=32, dtype=dtype)
    cot = jax.random.normal(jax.random.PRNGKey(6), q.shape, dtype)

    def run(core):
        def loss(q, k, v):
            return jnp.sum((core(q, k, v) * cot).astype(jnp.float32))

        return jax.jit(lambda q, k, v: (core(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v)))(q, k, v)

    ref = run(lambda q, k, v: attention_core(q, k, v, causal=True))
    got = run(lambda q, k, v: flash_attention(q, k, v, True, bq, bk))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = max(1.0, float(np.max(np.abs(b))))
        assert np.max(np.abs(a - b)) <= tol * scale, (name, np.max(np.abs(a - b)), scale)


@pytest.fixture
def strip_of_32(monkeypatch):
    """Strips of 32 rows for the interpreted by-head calls below (the kernels
    read the constant when they are traced, and ``flash_attention`` is traced a
    call): the same bounds, slabs and clamps at an eighth of the rows. The
    compiled strips at their real 256 are held by tests/test_tpu_compile.py."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "WINDOW_STRIP", 32)
    return 32


# t, window, block (in strips), query heads, key/value heads, dtype, tolerance, the form the call's shape reads
_STRIP_CASES = {
    "a window of one block, 8 heads over 1": (6, 2, 2, 8, 1, jnp.float32, 5e-5, "slab"),
    "a window of one block, two blocks in all: every slab is clamped": (4, 2, 2, 2, 2, jnp.float32, 5e-5, "slab"),
    "a window of one block of four strips in bfloat16": (12, 4, 4, 4, 2, jnp.bfloat16, 4e-2, "slab"),
    "a block of one strip": (4, 1, 1, 2, 1, jnp.float32, 5e-5, "slab"),
    "a window of four blocks, 4 heads over 1": (12, 8, 2, 4, 1, jnp.float32, 5e-5, "edge"),
    "a window of two blocks in bfloat16": (12, 4, 2, 4, 2, jnp.bfloat16, 4e-2, "edge"),
    "a window of four blocks as wide as the sequence less one": (10, 8, 2, 2, 2, jnp.float32, 5e-5, "edge"),
    "an unaligned window keeps the whole tiles": (8, None, 2, 4, 2, jnp.float32, 5e-5, None),
    "a padded sequence keeps the whole tiles": (None, 2, 2, 4, 2, jnp.float32, 5e-5, None),
    "blocks that are not square keep the whole tiles": (8, 2, (2, 1), 2, 2, jnp.float32, 5e-5, None),
    "a window as wide as the sequence keeps the whole tiles": (4, 4, 2, 2, 1, jnp.float32, 5e-5, None),
}


@pytest.mark.parametrize("case", list(_STRIP_CASES))
def test_windowed_strips_match_the_xla_core_forward_and_all_three_gradients(case, strip_of_32):
    """Where a window's edges fall corner to corner through the tiles the two
    kernels run the edge tiles as strips (``strip_form``: over ONE slab where
    the window is a block, over the columns a strip keeps where it is several),
    anywhere else as the masked whole tiles they were: the output and dq, dk
    and dv against the XLA core either way, the first query block (its slab
    clamped at key 0) and the last key block (clamped at the last row) among
    them, with grouped key/value heads."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa
    from distributedvolunteercomputing_tpu.ops.attention import attention_core_local

    t, window, blocks, h, hkv, dtype, tol, form = _STRIP_CASES[case]
    unit = strip_of_32
    t = 7 * unit + 40 if t is None else t * unit                # None: a sequence that pads
    window = unit + 72 if window is None else window * unit     # None: no whole number of strips
    bq, bk = (b * unit for b in (blocks if isinstance(blocks, tuple) else (blocks, blocks)))
    assert pa.strip_form(t, window, bq, bk) == form
    q = jax.random.normal(jax.random.PRNGKey(11), (1, h, t, 32), dtype)
    k, v = (jax.random.normal(jax.random.PRNGKey(s), (1, hkv, t, 32), dtype) for s in (12, 13))
    cot = jax.random.normal(jax.random.PRNGKey(14), q.shape, dtype)

    def run(core):
        return jax.jit(lambda q, k, v: (lambda out, vjp: (out, *vjp(cot)))(*jax.vjp(core, q, k, v)))(q, k, v)

    try:
        set_attention_impl("xla")
        want = run(lambda q, k, v: attention_core_local(q, k, v, True, None, window))
    finally:
        set_attention_impl("auto")
    got = run(lambda q, k, v: flash_attention(q, k, v, True, bq, bk, True, window))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = max(1.0, float(np.max(np.abs(b))))
        assert np.max(np.abs(a - b)) <= tol * scale, (name, np.max(np.abs(a - b)), scale)


def _pairs_by_brute_force(t, window, bq, bk, form, unit):
    """(pairs the loops compute, pairs in the band) from the mask itself: a
    tile is visited where it holds a kept pair; whole, or, where an edge
    crosses it and the strips engage, a strip at a time over the columns from
    the strip's first kept one to its last (whole strips of them); a slab is a
    strip's kept columns over both its tiles, never fewer than the window's
    and a strip's."""
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    keep = (j <= i) & (j > i - window)
    computed = 0
    for r0 in range(0, t, bq):
        for c0 in range(0, t, bk):
            tile = keep[r0:r0 + bq, c0:c0 + bk]
            if not tile.any():
                continue
            if form is None or tile.all():
                computed += bq * bk
            elif form == "edge":
                for s0 in range(0, bq, unit):
                    cols = np.flatnonzero(tile[s0:s0 + unit].any(axis=0))
                    computed += unit * (cols[-1] // unit - cols[0] // unit + 1) * unit
        if form == "slab":
            computed += bq * (window + unit)
    return computed, int(keep.sum())


@pytest.mark.parametrize("t,window,blocks,form", [
    (16, 4, 4, "slab"), (24, 16, 4, "edge"), (8, None, 2, None), (12, 4, 2, "edge"),
])
def test_window_tiles_counts_what_the_mask_says_the_loops_compute(t, window, blocks, form, strip_of_32):
    """``window_tiles`` (the kernels' own bounds and strips, in numpy) against a
    count off the mask itself, forward and backward, at a window of one block,
    of several and one that no strip can cut."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    unit = strip_of_32
    t, window, b = t * unit, unit + 72 if window is None else window * unit, blocks * unit
    computed, band = _pairs_by_brute_force(t, window, b, b, form, unit)
    assert pa.window_tiles(t, window, b, b) == {"fwd": computed, "bwd": computed, "band": band, "form": form or "tiles"}


def test_the_cells_windowed_calls_compute_little_more_than_their_band():
    """Laguna's window of 512 at 512 x 512 and T = 8,192: two whole tiles a
    query block computed 2.0 of the band, the strips over one slab 1 + strip /
    window; SmallThinker's 4,096 at 1,024 x 1,024 and T = 16,384: five whole
    tiles for four computed 1.25, its edge tiles as strips a few hundredths
    over 1. The note of a traced call carries the ratio."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    unit = pa.WINDOW_STRIP
    laguna, small = pa.window_tiles(8192, 512, 512, 512), pa.window_tiles(16384, 4096, 1024, 1024)
    assert (laguna["form"], small["form"]) == ("slab", "edge")
    assert laguna["fwd"] == laguna["bwd"] == 8192 * (512 + unit)
    assert laguna["fwd"] / laguna["band"] == pytest.approx((512 + unit) / 512, rel=0.04)  # 1.55 at strips of 256
    # an edge tile costs (1 + strip / block) / 2 of itself; 28 of the 70 tiles a head visits are edge tiles
    assert small["fwd"] == small["bwd"] == (42 + 28 * (1 + unit / 1024) / 2) * 1024 * 1024
    assert 1.0 < small["fwd"] / small["band"] < 1.1
    whole = lambda *a: None  # noqa: E731
    form, pa.strip_form = pa.strip_form, whole
    try:
        assert pa.window_tiles(8192, 512, 512, 512)["fwd"] / laguna["band"] == pytest.approx(2.0, rel=1e-3)
        assert pa.window_tiles(16384, 4096, 1024, 1024)["fwd"] / small["band"] == pytest.approx(1.25, rel=1e-3)
    finally:
        pa.strip_form = form


@pytest.mark.parametrize("causal", [False, True])
def test_kernel_at_the_chosen_geometry(causal):
    # No explicit blocks: choose_blocks' own geometry (one padded block here).
    q, k, v = _qkv(jax.random.PRNGKey(7), b=1, h=2, tq=72, tk=72, d=16)
    ref = attention_core(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_noncausal_rectangular_grads():
    q, k, v = _qkv(jax.random.PRNGKey(8), b=1, h=2, tq=48, tk=80, d=16)
    cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    g_ref = jax.jit(jax.grad(lambda *a: jnp.sum(attention_core(*a) * cot), argnums=(0, 1, 2)))(q, k, v)
    g_fl = jax.jit(jax.grad(
        lambda *a: jnp.sum(flash_attention(*a, False, 32, 32) * cot), argnums=(0, 1, 2)
    ))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


def _tiny_gpt2(**kw):
    from distributedvolunteercomputing_tpu.models import get_model

    return get_model(
        "gpt2_small", n_layers=2, d_model=64, n_heads=4, d_ff=128,
        vocab=256, max_len=32, **kw,
    )


def test_per_shard_call_gives_the_unsharded_loss(eight_devices):
    """dp=4, tp=2: the kernel runs under shard_map on each chip's batch rows
    and heads; the loss and the updated parameters are the one-device step's."""
    from jax.sharding import Mesh

    from distributedvolunteercomputing_tpu.parallel.mesh import AXES
    from distributedvolunteercomputing_tpu.parallel.train_step import (
        make_sharded_train_step,
        put_batch,
        shard_train_state,
    )
    from distributedvolunteercomputing_tpu.training.optim import make_optimizer
    from distributedvolunteercomputing_tpu.training.steps import TrainState, make_train_step

    bundle = _tiny_gpt2(remat=True)
    tx = make_optimizer("adam", lr=1e-3)

    def fresh():
        return TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(1))

    batch = bundle.make_batch(jax.random.PRNGKey(2), 8)
    mesh = Mesh(np.array(eight_devices).reshape(4, 1, 1, 1, 2), AXES)
    try:
        set_attention_impl("flash")
        one_step = make_train_step(bundle.loss_fn, tx, donate=False)
        one_state, one = one_step(fresh(), batch)
        _, one_next = one_step(one_state, batch)
        state, _ = shard_train_state(fresh(), mesh, tx)
        step = make_sharded_train_step(bundle.loss_fn, tx, mesh, donate=False)
        text = step.lower(state, put_batch(batch, mesh)).as_text()
        with mesh:
            state, m = step(state, put_batch(batch, mesh))
            _, m_next = step(state, put_batch(batch, mesh))
    finally:
        set_attention_impl("auto")
    assert "shard_map" in text or "manual" in text  # the kernel's call is per shard
    np.testing.assert_allclose(float(m["loss"]), float(one["loss"]), rtol=1e-5)
    # the second step's loss is a function of the first step's gradients
    np.testing.assert_allclose(float(m_next["loss"]), float(one_next["loss"]), rtol=1e-4)
    assert float(m_next["loss"]) < float(m["loss"])


def test_undivisible_batch_keeps_the_xla_core_under_a_mesh(eight_devices):
    from jax.sharding import Mesh

    from distributedvolunteercomputing_tpu.ops import attention
    from distributedvolunteercomputing_tpu.parallel.mesh import AXES

    mesh = Mesh(np.array(eight_devices).reshape(4, 1, 1, 1, 2), AXES)
    q = _Shape((8, 4, 64, 16), jnp.float32)
    try:
        set_attention_impl("flash")
        with attention.step_mesh(mesh):
            assert attention._route_to_flash(q, q, True, None)
            assert attention._shard_axes(q) == ("dp", "tp")
            odd = _Shape((6, 4, 64, 16), jnp.float32)      # 6 rows over dp=4
            assert not attention._route_to_flash(odd, odd, True, None)
            heads = _Shape((8, 3, 64, 16), jnp.float32)    # 3 heads over tp=2
            assert not attention._route_to_flash(heads, heads, True, None)
        assert attention._shard_axes(q) == (None, None)
    finally:
        set_attention_impl("auto")


@pytest.mark.parametrize("enabled,want", [(True, {"xla": 2}), (False, {})])
def test_trace_time_counter(enabled, want):
    """One count per traced attention call (the scanned block is traced once,
    its rematerialised forward once more), none per executed step, and none
    with telemetry off."""
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry

    tel = Telemetry(peer_id="t", enabled=enabled)
    bundle = _tiny_gpt2(remat=True)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = bundle.make_batch(jax.random.PRNGKey(1), 2)
    grad = jax.jit(jax.grad(lambda p: bundle.loss_fn(p, batch, jax.random.PRNGKey(2))[0]))
    with traced.subscribe(tel.count_traced):
        jax.block_until_ready(grad(params))
        cores = tel.traced_summary()["attention_core"]
        jax.block_until_ready(grad(params))  # a compiled step counts nothing
        assert tel.traced_summary()["attention_core"] == cores
    assert set(cores) == set(want)
    if enabled:
        assert cores["xla"] >= 1
        assert tel.summary()["attention_core"] == cores
        rec = tel.registry.counter("swarm.attention_core")._scrape()["values"][0]
        assert rec["labels"] == {"impl": "xla", "T": "32", "D": "16", "dtype": "float32",
                                 "window": "none", "kv_heads": "4", "layout": "heads", "rotary": "none",
                                 "computed_over_band": "none"}
        assert tel.summary()["attention_band"] == {"none/none": cores["xla"]}
        assert tel.summary()["attention_layout"] == {"heads/none": cores["xla"]}
    else:
        assert tel.summary()["attention_core"] == {}


# -- what a rematerialised layer keeps of the kernel (models/common.remat_layer) --


def _kernel_eqns(jaxpr, out=None):
    """The names of the ``pallas_call`` equations of ``jaxpr`` and of every
    jaxpr nested in it (a scan's body is there once whatever its length)."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_eqns(sub, out)
    return out


def _tiny_laguna(**kw):
    """The benchmark's rehearsal cut of Laguna: five unrolled layers, two full
    (6 heads) and three windowed (8 heads), T=64, head dim 16."""
    from benchmark.manifest import Manifest
    from distributedvolunteercomputing_tpu.models import get_model

    return get_model(
        "laguna_xs2", **Manifest().load_config("tiny-rehearsal-laguna")["model_overrides"], **kw)


def _tiny_ouro(**kw):
    """The benchmark's rehearsal cut of Ouro: three scanned layers of 4 heads, T=32, inside a loop of four passes."""
    from benchmark.manifest import Manifest
    from distributedvolunteercomputing_tpu.models import get_model

    return get_model("ouro_2_6b", **Manifest().load_config("tiny-rehearsal-ouro")["model_overrides"], **kw)


def _kept(b, h, t):
    """Bytes a chip keeps of one f32 call: an output row takes 128 lanes
    whatever the head dim, and a log-sum-exp a row."""
    return b * h * t * (128 * 4 + 4)


def _grad_of(bundle, batch_size=2):
    params = bundle.init(jax.random.PRNGKey(0))
    batch = bundle.make_batch(jax.random.PRNGKey(1), batch_size)
    return jax.grad(lambda p: bundle.loss_fn(p, batch, jax.random.PRNGKey(2))[0]), params


@pytest.fixture
def bare_checkpoint(monkeypatch):
    """Switches ``remat_layer`` to the bare ``jax.checkpoint`` it replaced
    (take a new ``_grad_of`` after it: a traced function is cached)."""
    from distributedvolunteercomputing_tpu.models import common

    def switch():
        monkeypatch.setattr(common, "remat_layer", lambda body, *layers_and_calls: jax.checkpoint(body))

    return switch


def _assert_bit_equal(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("model,kept,bare", [
    # a scanned block is in the jaxpr once: forward + backward, where the bare
    # checkpoint's recomputed forward called the kernel again
    (_tiny_gpt2, {"dvc_flash_fwd": 1, "dvc_flash_bwd": 1}, {"dvc_flash_fwd": 2, "dvc_flash_bwd": 1}),
    # unrolled: two full layers and three windowed ones, each kind under its name
    (_tiny_laguna,
     {"dvc_flash_fwd": 2, "dvc_flash_bwd": 2, "dvc_flash_win_fwd": 3, "dvc_flash_win_bwd": 3},
     {"dvc_flash_fwd": 4, "dvc_flash_bwd": 2, "dvc_flash_win_fwd": 6, "dvc_flash_win_bwd": 3}),
], ids=["scanned-gpt2", "unrolled-laguna"])
def test_remat_layer_runs_the_forward_kernel_once(model, kept, bare, bare_checkpoint):
    """The gradient of a rematerialised model holds one forward kernel call a
    layer (the layer's checkpoint kept the kernel's output and row statistics,
    so the recomputed forward's call is dead code), and its gradients are the
    bare ``jax.checkpoint``'s bit for bit."""
    from collections import Counter

    grad, params = _grad_of(model(remat=True))
    try:
        set_attention_impl("flash")
        got_kernels = Counter(_kernel_eqns(jax.make_jaxpr(grad)(params).jaxpr))
        got = jax.jit(grad)(params)
        bare_checkpoint()
        grad, params = _grad_of(model(remat=True))
        want_kernels = Counter(_kernel_eqns(jax.make_jaxpr(grad)(params).jaxpr))
        want = jax.jit(grad)(params)
    finally:
        set_attention_impl("auto")
    assert got_kernels == kept and want_kernels == bare
    _assert_bit_equal(got, want)


def test_remat_layer_keeps_through_the_per_shard_call(eight_devices, bare_checkpoint):
    """dp=2, tp=2: the kept names pass through ``_flash_per_shard``'s
    ``shard_map``; one forward kernel call a layer and ROW STREAM (a replica's
    two rows run as two streams of one over ``tp``: ``common.scan_blocks``),
    the bare checkpoint's gradients bit for bit, and the bytes counted are one
    chip's share of the kernel's results and of the reduced attention product,
    what they were as one stream."""
    from jax.sharding import Mesh

    from distributedvolunteercomputing_tpu.ops import attention
    from distributedvolunteercomputing_tpu.parallel.mesh import AXES

    mesh = Mesh(np.array(eight_devices[:4]).reshape(2, 1, 1, 1, 2), AXES)
    grad, params = _grad_of(_tiny_gpt2(remat=True), batch_size=4)
    seen = []
    kept = traced.subscribe(lambda kind, said: kind == "remat_kept" and seen.append((said["layers"], said["bytes"])))
    try:
        set_attention_impl("flash")
        with attention.step_mesh(mesh):
            jaxpr = jax.make_jaxpr(grad)(params)
            got = jax.jit(grad)(params)
            bare_checkpoint()
            grad, params = _grad_of(_tiny_gpt2(remat=True), batch_size=4)
            bare = _kernel_eqns(jax.make_jaxpr(grad)(params).jaxpr)
            want = jax.jit(grad)(params)
    finally:
        set_attention_impl("auto")
        kept.close()
    assert "shard_map" in str(jaxpr)
    assert sorted(_kernel_eqns(jaxpr.jaxpr)) == ["dvc_flash_bwd"] * 2 + ["dvc_flash_fwd"] * 2
    assert sorted(bare) == ["dvc_flash_bwd"] * 2 + ["dvc_flash_fwd"] * 4
    _assert_bit_equal(got, want)
    # a chip's share, [2, 2, 32, 16] of [4, 4, 32, 16], for two layers, and, tp
    # dividing the layer, the [2, 32, 64] f32 rows of the reduced attention
    # product (``keep_tp_reduced``); by the one trace of the helper's layer,
    # never by the bare checkpoint's
    assert seen == [(2, 2 * (_kept(2, 2, 32) + 2 * 32 * 64 * 4))]


def test_remat_layer_on_the_xla_core_is_the_bare_checkpoint(bare_checkpoint):
    """A layer that ran the XLA core names nothing, so the policy keeps
    nothing: the gradient's program is the bare checkpoint's, line for line."""
    grad, params = _grad_of(_tiny_gpt2(remat=True))
    got = jax.jit(grad).lower(params).as_text()
    assert not _kernel_eqns(jax.make_jaxpr(grad)(params).jaxpr)
    bare_checkpoint()
    grad, params = _grad_of(_tiny_gpt2(remat=True))
    assert got == jax.jit(grad).lower(params).as_text()


@pytest.mark.parametrize("model,impl,want,scanned", [
    (_tiny_gpt2, "flash", {"traced_layers": 1, "bytes_a_step": 2 * _kept(2, 4, 32)}, "2"),
    (_tiny_laguna, "flash", {"traced_layers": 5, "bytes_a_step": 2 * _kept(2, 6, 64) + 3 * _kept(2, 8, 64)}, "1"),
    # one traced body that a scan of 3 layers runs inside a loop of 4 passes: 12 layer-runs' results are kept
    (_tiny_ouro, "flash", {"traced_layers": 1, "bytes_a_step": 3 * 4 * _kept(2, 4, 32)}, "3"),
    (_tiny_gpt2, "auto", {}, None),  # the CPU's auto routing: the XLA core, nothing kept
    (_tiny_laguna, "auto", {}, None),
], ids=["gpt2-flash", "laguna-flash", "ouro-flash", "gpt2-xla", "laguna-xla"])
def test_remat_kept_counter(model, impl, want, scanned):
    """``swarm.remat_kept``: one count per TRACED layer whose checkpoint kept
    a kernel's results (a scanned block is traced once for all its layers, and
    once for every pass of a loop around the scan) with the bytes kept a step,
    the passes counted (output rows of 128 lanes whatever the head dim: the
    chip's layout), none per executed step, and nothing where the layers ran
    the XLA core; in the summary that ``coord.status`` shows per peer."""
    from distributedvolunteercomputing_tpu.swarm.telemetry import Telemetry

    tel = Telemetry(peer_id="t", enabled=True)
    grad, params = _grad_of(model(remat=True))
    grad = jax.jit(grad)
    counting = traced.subscribe(tel.count_traced)
    try:
        set_attention_impl(impl)
        jax.block_until_ready(grad(params))
        assert tel.traced_summary()["remat_kept"] == want
        jax.block_until_ready(grad(params))  # a compiled step counts nothing
    finally:
        set_attention_impl("auto")
        counting.close()
    assert tel.summary()["remat_kept"] == want
    if want:
        layers = {r["labels"]["layers"] for r in tel.registry.counter("swarm.remat_kept")._scrape()["values"]}
        assert layers == {scanned}


def test_remat_off_keeps_nothing():
    """``remat=False`` is the body unwrapped: no checkpoint, nothing counted."""
    seen = []
    grad, params = _grad_of(_tiny_gpt2(remat=False))
    kept = traced.subscribe(lambda kind, said: kind == "remat_kept" and seen.append(said))
    try:
        set_attention_impl("flash")
        jaxpr = jax.make_jaxpr(grad)(params)
    finally:
        set_attention_impl("auto")
        kept.close()
    assert not seen
    assert sorted(_kernel_eqns(jaxpr.jaxpr)) == ["dvc_flash_bwd", "dvc_flash_fwd"]


# -- the projections' own layout, [B, T, H * D] (ops/attention.attention_merged) --


def _merged_qkv(b, t, h, hkv, d, dv, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = [(b, t, h * d), (b, t, hkv * d), (b, t, hkv * dv), (b, t, h * dv)]
    return [jax.random.normal(k, s, dtype) for k, s in zip(ks, shapes)]


def _by_head_path(q, k, v, h, hkv, window, rotary, causal=True):
    """What every model ran before the merged entry, and what the entry falls back to."""
    from distributedvolunteercomputing_tpu.ops import attention as A

    qh, kh, vh = A.split_heads(q, h), A.split_heads(k, hkv), A.split_heads(v, hkv)
    if rotary is not None:
        qh, kh = A.rope(qh, **rotary._asdict()), A.rope(kh, **rotary._asdict())
    return A.merge_heads(A.attention_core(qh, kh, vh, causal=causal, window=window))


def _half(**kw):
    from distributedvolunteercomputing_tpu.ops.attention import Rotary

    return Rotary(layout="half", **kw)


def _interleaved():
    from distributedvolunteercomputing_tpu.ops.attention import Rotary

    return Rotary()


def _yarn():
    from distributedvolunteercomputing_tpu.ops.attention import yarn_inv_freq

    return _half(rotary_dim=64, inv_freq=yarn_inv_freq(64, 500000.0, 64.0, 64, 64.0, 1.0), scale=1.4)


# (b, t, h, hkv, d, dv, window, rotary, the layout and the turn the observer hears, exact[, causal, dtype])
_MERGED_CASES = {
    "full rotary at D = 128": (2, 128, 2, 2, 128, 128, None, _half, "merged/kernel", False),
    "partial rotary 64 of 128, yarn and a scale": (1, 128, 2, 1, 128, 128, None, _yarn, "merged/kernel", False),
    "no rotary": (1, 128, 2, 2, 128, 128, None, None, "merged/none", False),
    "grouped heads, 8 over 2": (1, 128, 8, 2, 128, 128, None, _half, "merged/kernel", False),
    "a window narrower than a block": (1, 256, 2, 1, 128, 128, 64, _half, "merged/kernel", False),
    "a window wider than a block": (1, 256, 2, 1, 128, 128, 200, _yarn, "merged/kernel", False),
    "a sequence that pads": (1, 200, 4, 2, 128, 128, None, _half, "merged/kernel", False),
    "a padded sequence under a window": (1, 200, 2, 2, 128, 128, 64, _yarn, "merged/kernel", False),
    "a base of its own": (1, 128, 2, 1, 128, 128, None, lambda: _half(base=1.5e6), "merged/kernel", False),
    # a window of one block, its edges corner to corner through the tiles: strips over one slab (PR 73)
    "a window of one block: strips over a slab, 8 heads over 1": (
        1, 1024, 8, 1, 128, 128, 512, _half, "merged/kernel", False),
    "a window of one block in bfloat16": (1, 1024, 2, 1, 128, 128, 512, _yarn, "merged/kernel", False, True, jnp.bfloat16),
    "a value head of 256 under keys of 128": (1, 128, 2, 1, 128, 256, None, _half, "merged/kernel", False),
    "a head of 256": (1, 128, 2, 1, 256, 256, None, _half, "merged/kernel", False),
    "a value head of 64: the by-head path": (1, 128, 2, 1, 128, 64, None, _half, "heads/outside", True),
    "D = 64: the by-head path": (1, 128, 4, 2, 64, 64, None, _half, "heads/outside", True),
    "D = 64 without rotary: the by-head path": (1, 128, 4, 4, 64, 64, 32, None, "heads/none", True),
    "interleaved pairs: the by-head path": (1, 128, 2, 2, 128, 128, None, _interleaved, "heads/outside", True),
    # a head of 64 is half of a 128-lane block: two heads a grid step, each on its own lanes (PR 66)
    "a head of 64, 16 heads": (2, 128, 16, 16, 64, 64, None, None, "merged/none", False),
    "a head of 64, not causal": (2, 128, 16, 16, 64, 64, None, None, "merged/none", False, False),
    "a head of 64, a sequence that pads": (1, 200, 16, 16, 64, 64, None, None, "merged/none", False),
    "a head of 64, a padded sequence, not causal": (1, 200, 16, 16, 64, 64, None, None, "merged/none", False, False),
    "a head of 64, several blocks a sequence": (1, 2048, 2, 2, 64, 64, None, None, "merged/none", False),
    "a head of 64 in bfloat16": (2, 128, 16, 16, 64, 64, None, None, "merged/none", False, True, jnp.bfloat16),
    "a head of 64 in bfloat16, not causal": (
        1, 200, 16, 16, 64, 64, None, None, "merged/none", False, False, jnp.bfloat16),
    "a head of 32: four a block": (1, 128, 8, 8, 32, 32, None, None, "merged/none", False),
    "an odd head count at D = 64: the by-head path": (1, 128, 3, 3, 64, 64, None, None, "heads/none", True),
    "grouped heads at D = 64: the by-head path": (1, 128, 4, 2, 64, 64, None, None, "heads/none", True),
    "a rotary at D = 64: the by-head path": (1, 128, 4, 4, 64, 64, None, _half, "heads/outside", True),
}


@pytest.mark.parametrize("case", list(_MERGED_CASES))
def test_merged_entry_equals_the_by_head_path(case):
    """``attention_merged`` against ``split_heads`` + ``rope`` + ``attention_core``
    + ``merge_heads``, both on the kernel (interpreted): the output and the
    gradients of q, k and v. Where the shapes keep the by-head path (a head or a
    value head that is not whole lanes, interleaved pairs) the entry IS that
    path and the results are equal to the bit; elsewhere the kernels read the
    merged arrays and turn the pairs by a lane roll, float32 on the tile as
    ``rope``, so float32 inputs agree to rounding."""
    from distributedvolunteercomputing_tpu.ops import attention as A

    b, t, h, hkv, d, dv, window, rotary, heard, exact, *rest = _MERGED_CASES[case]
    causal, dtype = (*rest, *(True, jnp.float32)[len(rest):])
    rotary = None if rotary is None else rotary()
    q, k, v, cot = _merged_qkv(b, t, h, hkv, d, dv, dtype)
    seen = []
    cores = traced.subscribe(lambda kind, said: seen.append("{impl}:{layout}/{rotary}".format(**said)))
    try:
        set_attention_impl("flash")
        got, vjp = jax.vjp(lambda q, k, v: A.attention_merged(
            q, k, v, h, hkv, causal=causal, window=window, rotary=rotary), q, k, v)
        got = (got, *vjp(cot))
        assert seen == [f"flash:{heard}"], seen
        want, vjp = jax.vjp(lambda q, k, v: _by_head_path(q, k, v, h, hkv, window, rotary, causal), q, k, v)
        want = (want, *vjp(cot))
    finally:
        set_attention_impl("auto")
        cores.close()
    assert got[0].shape == (b, t, h * dv) and got[0].dtype == dtype
    for name, x, y in zip(("out", "dq", "dk", "dv"), got, want):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        if exact:
            assert np.array_equal(x, y), name
        elif dtype == jnp.float32:
            np.testing.assert_allclose(x, y, atol=2e-5, rtol=2e-5, err_msg=name)
        else:  # bfloat16: each side a rounding of the same float32 sums, a step of 2**-8 of the largest
            np.testing.assert_allclose(x, y, atol=2e-2 * max(1.0, float(np.max(np.abs(y)))), rtol=2e-2, err_msg=name)


def test_merged_entry_rounds_where_rope_rounds():
    """bfloat16 in: a call without rotary is the by-head kernels' arithmetic
    on another block index, equal to the bit; a rotary call turns in float32
    and rounds q and k to bfloat16 as ``rope`` does, and its dq loses no more
    than the by-head path's two roundings."""
    from distributedvolunteercomputing_tpu.ops import attention as A

    q, k, v, cot = _merged_qkv(1, 128, 4, 2, 128, 128, jnp.bfloat16)
    try:
        set_attention_impl("flash")
        for rotary, tol in ((None, 0.0), (_half(), 2e-2)):
            got, vjp = jax.vjp(lambda q, k, v: A.attention_merged(q, k, v, 4, 2, causal=True, rotary=rotary), q, k, v)
            want, vjp_w = jax.vjp(lambda q, k, v: _by_head_path(q, k, v, 4, 2, None, rotary), q, k, v)
            for x, y in zip((got, *vjp(cot)), (want, *vjp_w(cot))):
                x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
                assert np.max(np.abs(x - y)) <= tol * max(1.0, np.max(np.abs(y)))
    finally:
        set_attention_impl("auto")


def test_rotary_tables_are_ropes_own_values():
    """The tables the kernels turn by hold ``rope``'s cosines and sines lane by
    lane (a partner's sine with its sign; ones and zeros past ``rotary_dim``),
    and one pass of ``rotary_merged`` is ``rope`` on every head."""
    from distributedvolunteercomputing_tpu.ops import attention as A
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    rot = _yarn()
    cos, sin = pa.rotary_tables(40, 128, rot.base, rot.rotary_dim, rot.inv_freq, rot.scale)
    angles = np.arange(40)[:, None] * np.asarray(rot.inv_freq)[None, :]
    np.testing.assert_allclose(np.asarray(cos[:, :32]), 1.4 * np.cos(angles), rtol=1e-5, atol=1e-6)
    assert np.array_equal(np.asarray(cos[:, :32]), np.asarray(cos[:, 32:64]))
    assert np.array_equal(np.asarray(sin[:, :32]), -np.asarray(sin[:, 32:64]))
    assert np.all(np.asarray(cos[:, 64:]) == 1.0) and np.all(np.asarray(sin[:, 64:]) == 0.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 3 * 128))
    got, vjp = jax.vjp(lambda x: pa.rotary_merged(x, cos, sin, 64, True), x)
    want, vjp_w = jax.vjp(lambda x: A.merge_heads(A.rope(A.split_heads(x, 3), **rot._asdict())), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vjp(got)[0]), np.asarray(vjp_w(want)[0]), rtol=1e-5, atol=1e-5)


def test_merged_blocks_count_the_tables():
    """Both cells' shapes keep their blocks with a turned call's table blocks
    in the estimate (two float32 ``[bq, D]`` blocks, double-buffered, on top of
    the backward's: 63.0 of the budget's 64 MiB at T=16,384), and a call that
    turns nothing is estimated as it always was, so that no other caller's
    kernel is compiled under another limit."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    bf16 = jnp.bfloat16
    assert pa.choose_blocks(16384, 16384, 128, bf16, window=4096, turned=True) == (1024, 1024)
    assert pa.choose_blocks(8192, 8192, 128, bf16, window=512, turned=True) == (512, 512)
    assert pa.choose_blocks(8192, 8192, 128, bf16, turned=True) == (1024, 1024)
    plain = pa.vmem_bytes(16384, 16384, 128, bf16, 1024, 1024)
    assert plain == 63963136  # 61.0 MiB: the parent's number, the backward's
    assert pa.vmem_bytes(16384, 16384, 128, bf16, 1024, 1024, turned=True) == plain + 2 * 2 * 1024 * 128 * 4
    assert plain + 2 * 2 * 1024 * 128 * 4 <= pa.VMEM_BUDGET_BYTES
    assert pa.vmem_bytes(1024, 1024, 64, bf16, 1024, 1024) == 30539776  # gpt2's, as at the parent


def test_merged_entry_per_shard_under_a_mesh(eight_devices):
    """Under a step's dp x tp mesh the merged call runs per shard like the
    by-head one: ``tp`` cuts the last axis into whole heads, the key/value heads
    with their query heads, the tables whole on every chip; the loss and the
    gradients are the unsharded call's."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from distributedvolunteercomputing_tpu.ops import attention as A

    mesh = Mesh(np.array(eight_devices[:4]).reshape(2, 2), ("dp", "tp"))
    q, k, v, cot = _merged_qkv(2, 128, 4, 2, 128, 128)
    rot = _yarn()

    def loss(q, k, v):
        return jnp.sum(cot * A.attention_merged(q, k, v, 4, 2, causal=True, window=96, rotary=rot))

    seen = []
    cores = traced.subscribe(lambda kind, said: seen.append((said["layout"], said["rotary"])))
    try:
        set_attention_impl("flash")
        want = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        spec = NamedSharding(mesh, P("dp", None, "tp"))
        with A.step_mesh(mesh):
            got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)), in_shardings=(spec,) * 3)(q, k, v)
    finally:
        set_attention_impl("auto")
        cores.close()
    assert seen == [("merged", "kernel")] * 2
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for x, y in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("heads,heard", [(8, "merged"), (6, "heads")])
def test_a_head_of_64_per_shard_under_a_mesh(eight_devices, heads, heard):
    """A head of 64 under a step's dp x tp mesh, the path ``large-solo-4chip``
    runs since PR 71 (gpt2-large's 20 heads over tp = 2: five pairs a chip):
    where a chip's share of the heads is whole blocks of two (8 heads over
    tp = 2) the merged call runs per shard, a chip's pairs a contiguous part of
    the last axis; where it is not (6 heads: three a chip) the entry is the
    by-head path, as on one chip with an odd count. Loss and gradients are the
    unsharded call's either way."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from distributedvolunteercomputing_tpu.ops import attention as A

    mesh = Mesh(np.array(eight_devices[:4]).reshape(2, 2), ("dp", "tp"))
    q, k, v, cot = _merged_qkv(2, 128, heads, heads, 64, 64)

    def loss(q, k, v):
        return jnp.sum(cot * A.attention_merged(q, k, v, heads, heads, causal=True))

    seen = []
    cores = traced.subscribe(lambda kind, said: seen.append(said["layout"]))
    try:
        set_attention_impl("flash")
        want = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        spec = NamedSharding(mesh, P("dp", None, "tp"))
        with A.step_mesh(mesh):
            got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)), in_shardings=(spec,) * 3)(q, k, v)
    finally:
        set_attention_impl("auto")
        cores.close()
    assert seen == ["merged" if heads % 2 == 0 else "heads", heard]  # one chip: pairs of the whole count
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for x, y in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5, rtol=2e-5)


# -- the block-diffusion mask (rows [x_0 ; x_t], models/sdar_moe.py) -------------------------------

# (b, heads, key/value heads, rows t = 2L, head dim, block length, block_q, block_k)
_BD_CASES = {
    "blocks of 4, whole tiles": (2, 2, 2, 64, 16, 4, 16, 16),
    "blocks of 32, whole tiles": (1, 2, 2, 256, 16, 32, 64, 64),
    "grouped heads, 4 over 1": (1, 4, 1, 64, 16, 4, 16, 16),
    "a length that is not a whole tile": (1, 4, 2, 72, 16, 4, 16, 16),
    "a half that is not whole tiles, blocks of 8": (1, 2, 1, 80, 16, 8, 32, 32),
    "query tiles wider than key tiles": (1, 2, 2, 128, 16, 4, 32, 16),
    "key tiles wider than query tiles": (1, 2, 2, 128, 16, 32, 16, 64),
    "the blocks the code chooses": (1, 2, 1, 64, 16, 4, None, None),
}


@pytest.mark.parametrize("case", list(_BD_CASES))
def test_block_diffusion_kernel_matches_the_xla_core_forward_and_all_three_gradients(case):
    """The kernels under the three-part mask (interpreted) against the XLA core
    under the same mask as an explicit array: the output and dq, dk, dv."""
    from distributedvolunteercomputing_tpu.ops.attention import attention_core_local

    b, h, hkv, t, d, bd, bq, bk = _BD_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, cot = (jax.random.normal(k, (b, h, t, d)) for k in (ks[0], ks[3]))
    k, v = (jax.random.normal(kk, (b, hkv, t, d)) for kk in ks[1:3])
    set_attention_impl("xla")
    try:
        want, vjp = jax.vjp(lambda q, k, v: attention_core_local(q, k, v, block_diffusion=bd), q, k, v)
        want = (want, *vjp(cot))
    finally:
        set_attention_impl("auto")
    got, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, False, bq, bk, True, None, bd), q, k, v)
    for name, x, y in zip(("out", "dq", "dk", "dv"), (got, *vjp(cot)), want):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5, rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("t,bd,bq,bk", [
    (64, 4, 16, 16), (64, 4, 32, 16), (64, 4, 16, 32), (48, 4, 16, 16), (80, 8, 32, 32), (72, 4, 16, 16),
    (256, 32, 64, 64), (144, 4, 144, 144), (40, 4, 16, 16), (96, 12, 32, 32), (2048, 4, 512, 512),
])
def test_block_diffusion_loops_visit_every_tile_with_a_kept_pair_once_and_mask_where_an_edge_crosses(t, bd, bq, bk):
    """Both kernels' loop bounds against the mask itself, tile by tile: every
    tile that holds a kept pair is visited exactly once, a tile visited with no
    mask arithmetic is wholly kept, and where the halves are whole tiles no
    tile is visited for nothing."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa
    from distributedvolunteercomputing_tpu.ops.attention import block_diffusion_mask

    half, n_q, n_k = t // 2, -(-t // bq), -(-t // bk)
    padded = np.zeros((n_q * bq, n_k * bk), bool)
    padded[:t, :t] = np.asarray(block_diffusion_mask(t, bd))
    any_kept = padded.reshape(n_q, bq, n_k, bk).any(axis=(1, 3))
    wholly = np.array([[padded[i * bq:min((i + 1) * bq, t), j * bk:(j + 1) * bk].all() for j in range(n_k)]
                       for i in range(n_q)])
    a, b, c, d = pa._bd_fwd_bounds(np.arange(n_q), bq, bk, n_k, half, bd, np)
    s1, e1, e2, s3, e3, e4 = pa._bd_bwd_bounds(np.arange(n_k), bq, bk, n_q, half, bd, np)
    fwd, fwd_bare = np.zeros((n_q, n_k), int), np.zeros((n_q, n_k), bool)
    bwd, bwd_bare = np.zeros((n_q, n_k), int), np.zeros((n_q, n_k), bool)
    for i in range(n_q):
        for lo, hi, bare in ((0, a[i], True), (a[i], b[i], False), (c[i], d[i], False)):
            fwd[i, lo:hi] += 1
            fwd_bare[i, lo:hi] |= bare
    for j in range(n_k):
        for lo, hi, bare in ((s1[j], e1[j], False), (e1[j], e2[j], True), (s3[j], e3[j], False), (e3[j], e4[j], True)):
            bwd[lo:hi, j] += 1
            bwd_bare[lo:hi, j] |= bare
    for visits, bare in ((fwd, fwd_bare), (bwd, bwd_bare)):
        assert visits.max() == 1 and (visits[any_kept] == 1).all() and wholly[bare].all()
        if half % bq == 0 and half % bk == 0:
            assert not visits[~any_kept].any()
    tiles = pa.bd_tiles(t, bd, bq, bk)
    assert (tiles["fwd"], tiles["bwd"]) == (fwd.sum(), bwd.sum())
    causal = np.tril(np.ones((n_q * bq, n_k * bk), bool)).reshape(n_q, bq, n_k, bk).any(axis=(1, 3))
    assert tiles["causal_fwd"] == tiles["causal_bwd"] == causal.sum()


def test_block_diffusion_blocks_and_the_tiles_they_visit_at_the_cells_shape():
    """``choose_blocks`` / ``vmem_bytes`` at Tq = Tk = 2L = 8,192, head 128,
    bfloat16, a turned call: blocks of 512 (Laguna's windowed answer), inside
    the budget, and the loops then visit 80 of a causal mask's 136 tiles; a
    causal or windowed call's answer is what it was."""
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    bf16 = jnp.bfloat16
    assert pa.choose_blocks(8192, 8192, 128, bf16, None, True, 4) == (pa.BD_BLOCK, pa.BD_BLOCK) == (512, 512)
    assert pa.vmem_bytes(8192, 8192, 128, bf16, 512, 512, turned=True) <= pa.VMEM_BUDGET_BYTES
    assert pa.choose_blocks(8192, 8192, 128, bf16, None, True) == (1024, 1024)
    assert pa.choose_blocks(8192, 8192, 128, bf16, 512, True) == (512, 512)
    tiles = pa.bd_tiles(8192, 4, 512, 512)
    assert tiles == {"fwd": 80, "bwd": 80, "causal_fwd": 136, "causal_bwd": 136}  # n^2 + 2n of 2n^2 + n, n = 8
    assert pa.bd_tiles(8192, 4, 1024, 1024)["fwd"] == 24  # of 36: why the blocks are not the preferred 1,024
    assert pa._kernel_name("fwd", None, 4) == "dvc_flash_bd_fwd" and pa._kernel_name("bwd", None, 4) == "dvc_flash_bd_bwd"
    assert pa._kernel_name("fwd", None, None) == "dvc_flash_fwd" and pa._kernel_name("bwd", 512, None) == "dvc_flash_win_bwd"


def test_block_diffusion_merged_entry_turns_by_position_and_refuses_what_it_is_not():
    """``attention_merged`` under the mask with positions 0..L-1 twice, on the
    kernels (interpreted, D = 128: the merged layout, q turned on the tile from
    tables built of the positions), against the XLA core by head with ``rope`` at
    the same positions; and what the mask is not (causal, windowed, a length that
    is not two halves of whole blocks) is refused by kernel and core alike."""
    from distributedvolunteercomputing_tpu.ops import attention as A
    from distributedvolunteercomputing_tpu.ops import pallas_attention as pa

    b, t, h, hkv, d, bd = 1, 128, 2, 1, 128, 4
    q, k, v, cot = _merged_qkv(b, t, h, hkv, d, d)
    rotary = A.Rotary(base=1e6, layout="half", positions=jnp.tile(jnp.arange(t // 2), 2))
    seen = []
    cores = traced.subscribe(lambda kind, said: seen.append("{impl}:{layout}/{rotary}".format(**said)))
    try:
        set_attention_impl("flash")
        got, vjp = jax.vjp(lambda q, k, v: A.attention_merged(
            q, k, v, h, hkv, rotary=rotary, block_diffusion=bd), q, k, v)
        got = (got, *vjp(cot))
        set_attention_impl("xla")
        want, vjp = jax.vjp(lambda q, k, v: A.attention_merged(
            q, k, v, h, hkv, rotary=rotary, block_diffusion=bd), q, k, v)
        want = (want, *vjp(cot))
    finally:
        set_attention_impl("auto")
        cores.close()
    assert seen == ["flash:merged/kernel", "xla:heads/outside"], seen
    for name, x, y in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5, rtol=2e-5, err_msg=name)
    # positions matter: the row index in their place is another result
    plain = A.attention_merged(q, k, v, h, hkv, rotary=A.Rotary(base=1e6, layout="half"), block_diffusion=bd)
    assert float(jnp.max(jnp.abs(plain - want[0]))) > 1e-3
    qh = jnp.zeros((1, 2, 64, 16))
    for bad in (dict(causal=True, block_diffusion=4), dict(causal=True, window=8, block_diffusion=4),
                dict(block_diffusion=5)):
        with pytest.raises(ValueError):
            A.attention_core_local(qh, qh, qh, **bad)
        with pytest.raises(ValueError):
            pa.flash_attention(qh, qh, qh, bad.get("causal", False), None, None, True, bad.get("window"), bad["block_diffusion"])
