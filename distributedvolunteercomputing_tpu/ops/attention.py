"""Attention ops shared by the transformer zoo (BERT / GPT-2 / Llama).

Plain-XLA reference path: one fused einsum-softmax-einsum that XLA maps onto
the MXU. The pallas flash kernel (ops/pallas_attention.py) and the ring
attention sequence-parallel path (parallel/ring_attention.py) are drop-in
replacements for ``multi_head_attention``'s core.

Softmax statistics run in float32 even when q/k/v are bfloat16 — MXU matmuls
in bf16, reductions in f32, the standard TPU recipe.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import jax.numpy as jnp

# Active sequence-parallel context: (mesh, axis_name, impl) or None. When
# set, the attention core routes to the chosen SP implementation so the
# model code is unchanged between single-device and sp-sharded runs. Set by
# make_sharded_train_step at TRACE time (it wraps the step body), or manually.
# impl: "ring" (K/V rotate via ppermute — works for any head count, memory
# O(T/sp) per device) or "ulysses" (all-to-all swaps seq<->heads around a
# full-sequence attention — fewer collective hops on ICI; needs H % sp == 0).
_seq_ctx = None


@contextlib.contextmanager
def sequence_parallel(mesh, axis: str = "sp", impl: str = "ring"):
    if impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown sequence-parallel impl {impl!r}")
    global _seq_ctx
    prev = _seq_ctx
    _seq_ctx = (mesh, axis, impl)
    try:
        yield
    finally:
        _seq_ctx = prev

# Attention core selection: "xla" (fused einsum-softmax-einsum), "flash"
# (pallas kernel, ops/pallas_attention.py), or "auto" (flash on TPU for
# mask-free sequences long enough to fill a block, xla otherwise).
#
# auto routing is dtype-aware: flash from T=1024 in f32, from T=4096 in any
# other dtype. The crossovers are NOT MEASURED ON THE CURRENT CODE: they
# were chosen from sweeps of an earlier kernel, and the redesigned kernel
# has been checked on the v5e for numerics only (chip_smoke.py). On the TPU
# the models compute in bf16 (models/common.py), so at T=1024 the flagship
# step takes the XLA core. At long T flash is the memory-feasible option
# (no [T,T] score matrix); DVC_ATTN_IMPL=xla|flash forces either core.
_impl = os.environ.get("DVC_ATTN_IMPL", "auto")
# Crossovers for auto routing (see block comment above).
_AUTO_FLASH_MIN_T_F32 = 1024
_AUTO_FLASH_MIN_T_OTHER = 4096


def set_attention_impl(name: str) -> None:
    """Select the attention core for subsequent TRACES.

    The impl is read at trace time: computations already jitted (and cached
    by shape) keep whatever core they were traced with — call this before
    the first train step, not between steps.
    """
    global _impl
    if name not in ("auto", "xla", "flash"):
        raise ValueError(f"unknown attention impl {name!r}")
    _impl = name


def get_attention_impl() -> str:
    return _impl


def _route_to_flash(q: jax.Array, k: jax.Array, causal: bool, mask) -> bool:
    if mask is not None:  # flash path has no additive-mask support
        return False
    if causal and q.shape[-2] != k.shape[-2]:
        # The flash kernel's causal mask is top-left aligned (row i sees keys
        # 0..i); this XLA core is bottom-right aligned for Tq != Tk. Only the
        # square case agrees, so rectangular causal always takes the XLA path.
        return False
    if _impl == "flash":
        return True
    from distributedvolunteercomputing_tpu.utils.jaxenv import tpu_backend

    min_t = (
        _AUTO_FLASH_MIN_T_F32 if q.dtype == jnp.float32 else _AUTO_FLASH_MIN_T_OTHER
    )
    return _impl == "auto" and tpu_backend() and q.shape[-2] >= min_t


def attention_core(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, H, Tk, D]
    v: jax.Array,  # [B, H, Tk, D]
    causal: bool = False,
    mask: Optional[jax.Array] = None,  # [B, 1|H, Tq, Tk] additive-able bool
) -> jax.Array:
    if _seq_ctx is not None and mask is None and q.shape[-2] == k.shape[-2]:
        mesh, axis, impl = _seq_ctx
        if impl == "ulysses":
            from distributedvolunteercomputing_tpu.parallel.ulysses import (
                ulysses_attention_bhtd,
            )

            return ulysses_attention_bhtd(q, k, v, mesh, axis, causal)
        from distributedvolunteercomputing_tpu.parallel.ring_attention import ring_attention_bhtd

        return ring_attention_bhtd(q, k, v, mesh, axis, causal)
    return attention_core_local(q, k, v, causal, mask)


def attention_core_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """The single-device core (flash kernel or fused XLA), with no
    sequence-parallel routing — also the inner attention the Ulysses path
    runs per head-group after its all-to-all."""
    if _route_to_flash(q, k, causal, mask):
        from distributedvolunteercomputing_tpu.ops.pallas_attention import flash_attention

        bq, bk = _flash_blocks()
        return flash_attention(q, k, v, causal, bq, bk)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        logits = jnp.where(causal_mask, logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _flash_blocks() -> tuple:
    """Flash block-size tuning knobs for block-shape sweeps.

    Read at TRACE time and captured into the compiled program: changing the
    env after a function has compiled does not retrace it, so block A/Bs
    must use fresh processes or freshly-defined jitted closures.
    Validated here so a bad value names the knob instead of failing deep
    inside Mosaic with a zero-sized grid."""
    try:
        bq = int(os.environ.get("DVC_FLASH_BLOCK_Q") or "128")
        bk = int(os.environ.get("DVC_FLASH_BLOCK_K") or "128")
    except ValueError:
        raise ValueError(
            "DVC_FLASH_BLOCK_Q / DVC_FLASH_BLOCK_K must be integers; got "
            f"{os.environ.get('DVC_FLASH_BLOCK_Q')!r} / "
            f"{os.environ.get('DVC_FLASH_BLOCK_K')!r}"
        ) from None
    if bq < 8 or bk < 8 or bq % 8 or bk % 8:
        raise ValueError(
            f"DVC_FLASH_BLOCK_Q/K must be multiples of 8 and >= 8 (TPU "
            f"sublane tiling), got {bq}/{bk}"
        )
    return bq, bk


def split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, t, d = x.shape
    return x.reshape(b, t, n_heads, d // n_heads).transpose(0, 2, 1, 3)


def merge_heads(x: jax.Array) -> jax.Array:
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def multi_head_attention(
    q: jax.Array,  # [B, T, d_model] (already projected)
    k: jax.Array,
    v: jax.Array,
    n_heads: int,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    out = attention_core(
        split_heads(q, n_heads), split_heads(k, n_heads), split_heads(v, n_heads),
        causal=causal, mask=mask,
    )
    return merge_heads(out)


def rope(x: jax.Array, positions: Optional[jax.Array] = None, base: float = 10000.0) -> jax.Array:
    """Rotary position embedding over the last dim of ``x`` [B, H, T, D]."""
    d = x.shape[-1]
    t = x.shape[-2]
    if positions is None:
        positions = jnp.arange(t)
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [T, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    rx1 = x1 * cos - x2 * sin
    rx2 = x1 * sin + x2 * cos
    out = jnp.stack([rx1, rx2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)
