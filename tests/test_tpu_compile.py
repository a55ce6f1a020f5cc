"""The main path's Pallas kernels, compiled for a TPU v5e that is described
and not attached (the `on-chip-measurement` guide's third rehearsal).

Interpret mode accepts what Mosaic refuses — vector loads from HBM refs,
slices off the tiling, too much VMEM — so every kernel a volunteer runs on
the chip is compiled here at its real width, by the chip's own compiler:
flash attention fwd+bwd at the flagship shape, the codec's (512, 128) bf16
kernels, and the ring fold / ring all-gather on a 2x2 codec mesh at the
1 MiB wire chunk a real round uses. Nothing runs: a pass says the compiler
takes the kernel, not that its results are right (chip_smoke.py does that).

The program's backend checks see the CPU here, so each test steers
compiled-vs-interpret itself (``interpret=False``, ``pallas="on"``).
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


def test_flash_fwd_bwd_flagship_shape(v5e):
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((8, 12, 1024, 64), jnp.bfloat16, sharding=one)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return flash_attention(q, k, v, True, 128, 128, False).astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(fwd_bwd, x, x, x)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dk/dv


@pytest.mark.parametrize("kernel", ["encode", "decode_axpy"])
def test_codec_bf16_kernels(v5e, kernel):
    codec = mesh_codec.MeshCodec(
        mesh=Mesh(np.asarray(v5e[:1]), ("x",)), backend="mesh", pallas="on"
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
        mesh=Mesh(np.asarray(v5e), ("codec",)), backend="mesh", pallas="on",
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
