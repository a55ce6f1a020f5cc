"""Heads side by side in one tile: where a head is narrower than the 128 lanes
of a tile (two heads of 64), a kernel holds several in one ``[rows, width]``
block and does each head's work on its own lanes, with nothing sliced: a
product that contracts the lanes is handed ``of_head``'s operand (the other
heads' lanes zeroed), a product that keeps them is right on the head's own
lanes and ``by_head`` picks each head's result there. Used by the SSD kernels
(``ops/ssd.py``, PR 49) and the flash kernels (``ops/pallas_attention.py``,
PR 66); only ``jax.numpy``, so either may import it."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lanes_of(x) -> jax.Array:
    """Each element's lane (int32) in a ``[rows, width]`` block or ref."""
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)


def by_head(values, lane, head_dim: int):
    """One array from a tile's per-head ``values``: where the ``lane`` of the
    tile (int32) falls in head ``i``, ``values[i]``."""
    out = values[-1]
    for i in range(len(values) - 2, -1, -1):
        out = jnp.where(lane < (i + 1) * head_dim, values[i], out)
    return out


def of_head(x, lane, i: int, head_dim: int):
    """``x`` where the ``lane`` of the tile falls in head ``i``, 0 elsewhere."""
    return jnp.where((lane >= i * head_dim) & (lane < (i + 1) * head_dim), x, 0.0)
