"""Pytree <-> contiguous-buffer conversion for WAN tensor exchange.

The reference's GradientAverager hands NCCL/gloo a list of torch tensors
(BASELINE.json:5). The TPU-native equivalent moves a whole param/grad pytree
across DCN as ONE contiguous host buffer: a single allocation, chunkable,
checksummable, and cheap to average in-place with numpy on the host.

All averaging math on the WAN path happens on host in float32 regardless of
the on-device dtype (bf16 params would lose precision when averaged over many
peers).
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Sequence, Tuple

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    """Shape/dtype of one leaf inside a flattened buffer."""

    shape: Tuple[int, ...]
    dtype: str  # numpy dtype name of the ORIGINAL leaf (restored on unflatten)

    @property
    def size(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n


def tree_specs(tree: Any) -> Tuple[List[TensorSpec], Any]:
    """(specs, treedef) of a pytree WITHOUT materializing the flat buffer —
    for callers that only need the schema (e.g. validating an incoming
    buffer's length before adopting it)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    specs = [
        TensorSpec(
            tuple(np.shape(x)),
            str(x.dtype) if hasattr(x, "dtype") else str(np.asarray(x).dtype),
        )
        for x in leaves
    ]
    return specs, treedef


def flatten_to_buffer(tree: Any) -> Tuple[np.ndarray, List[TensorSpec], Any]:
    """Flatten a pytree of arrays into one contiguous float32 host buffer.

    Returns ``(buffer, specs, treedef)``. The buffer is always float32 so host
    averaging across peers is numerically safe; original dtypes are recorded in
    ``specs`` and restored by :func:`unflatten_from_buffer`.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return np.zeros((0,), dtype=np.float32), [], treedef
    host = [np.asarray(x) for x in leaves]
    specs = [TensorSpec(h.shape, str(h.dtype)) for h in host]
    buf = np.concatenate([h.astype(np.float32).ravel() for h in host])
    return buf, specs, treedef


def unflatten_from_buffer(buf: np.ndarray, specs: Sequence[TensorSpec], treedef: Any) -> Any:
    """Inverse of :func:`flatten_to_buffer` (restores shapes and dtypes)."""
    leaves = []
    offset = 0
    for spec in specs:
        n = spec.size
        chunk = buf[offset : offset + n].reshape(spec.shape).astype(spec.dtype)
        leaves.append(chunk)
        offset += n
    if offset != buf.size:
        raise ValueError(f"buffer size {buf.size} != specs total {offset}")
    return jax.tree_util.tree_unflatten(treedef, leaves)


def tree_size_bytes(tree: Any) -> int:
    return sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree))


def tree_zeros_like(tree: Any) -> Any:
    return jax.tree_util.tree_map(lambda x: np.zeros_like(np.asarray(x)), tree)


def cast_floating(tree: Any, dtype: Any) -> Any:
    """Cast every FLOATING leaf to ``dtype``, leaving integer tables, bools,
    and step counters untouched — the one bf16-training cast shared by the
    Trainer's param_dtype and checkpoint restore (which must re-apply a
    configured dtype over a snapshot taken under another one)."""
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    return jax.tree_util.tree_map(
        lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )
