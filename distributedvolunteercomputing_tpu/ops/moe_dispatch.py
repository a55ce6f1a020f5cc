"""Dropless expert dispatch: every routed (token, expert) pair is computed.

The S x k assignments of a step are sorted by expert (a stable sort, so an
expert's rows keep token order), the rows gathered, three grouped matrix
multiplications run over the E uneven groups (gate, up, down: SwiGLU experts),
and each row, scaled by its gate, is summed back into its token. Shapes are
static (S x k rows always); only the group sizes are data. No ``[S, E, C]``
tensor, no capacity, nothing dropped: ``models/moe.py``'s dense one-hot
dispatch (gpt2_moe) is 5.4 GB a tensor at OLMoE's step and drops over capacity.

Rows move by gathers in both directions. The sorted order is a permutation of
the S x k assignments, so the transpose of "take row ``perm[i]``" is "take row
``inv[i]``"; XLA cannot know that and would differentiate a gather into a
scatter-add of 131,072 rows, which a TPU runs as a serial loop. The two
``custom_vjp`` functions below say it.

The grouped matmul is megablox ``gmm`` (a Pallas kernel in JAX's tree) on one
TPU chip and ``jax.lax.ragged_dot`` elsewhere, from a measurement on the v5e at
OLMoE's shapes (experiments/gmm_sweep.py; the table is in PERF.md, Findings of
PR 28).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.utils.jaxenv import tpu_backend

# Megablox on one TPU chip where its tiles divide the shapes, ragged_dot
# elsewhere. Measured (PR 28, TPU v5e, bf16, 131,072 rows in 64 uneven groups):
# megablox at 512 x 1024 x 1024 tiles runs [., 2048] x [64, 2048, 1024] forward
# in 3.97 ms (70% of peak) and forward + backward in 13.1 ms (64%), ragged_dot
# in 5.06 and 18.0 (55%, 46%); megablox at its default 128^3 tiles takes 50.5 ms.
_MEGABLOX_TILE_M = 512
_MEGABLOX_TILES_KN = (1024, 512, 256, 128)

# Called once per TRACED dispatch with (impl, E, k, rows): the volunteer counts
# them (swarm.moe_dispatch), as ops/attention.py's observer does for the cores.
_dispatch_observer = None


def set_dispatch_observer(fn) -> None:
    global _dispatch_observer
    _dispatch_observer = fn


def _megablox_tiling(m: int, k: int, n: int) -> Optional[Tuple[int, int, int]]:
    """Tile sizes for megablox at this shape, or None where it cannot take it
    (its k and n tiles must divide the shape; rows may be ragged)."""
    tk = next((t for t in _MEGABLOX_TILES_KN if k % t == 0), None)
    tn = next((t for t in _MEGABLOX_TILES_KN if n % t == 0), None)
    if tk is None or tn is None or m < _MEGABLOX_TILE_M:
        return None
    return (_MEGABLOX_TILE_M, tk, tn)


def grouped_matmul_impl(m: int, k: int, n: int) -> str:
    """The grouped matmul a ``[m, k] x [E, k, n]`` product traced now takes.
    The one seam a test or an experiment patches to take the other."""
    # On several chips the expert stacks are sharded over ``ep``: ragged_dot
    # is an XLA op that GSPMD partitions, a Mosaic kernel is not
    # (ops/attention.py has the same rule for its kernel).
    if not tpu_backend() or jax.device_count() > 1 or _megablox_tiling(m, k, n) is None:
        return "ragged_dot"
    return "megablox"


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``[M, K] x [E, K, N] -> [M, N]``: rows ``sum(group_sizes[:e]) ..`` of
    ``lhs`` times ``rhs[e]``. ``group_sizes`` (int32 ``[E]``) sums to M."""
    m, k, n = lhs.shape[0], rhs.shape[1], rhs.shape[2]
    if grouped_matmul_impl(m, k, n) == "megablox":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        # interpreted only where a test has patched the choice off a TPU
        return gmm(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=_megablox_tiling(m, k, n), interpret=not tpu_backend(),
        )
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


@jax.custom_vjp
def _permute_rows(a: jax.Array, perm: jax.Array, inv: jax.Array) -> jax.Array:
    """``a[perm]`` for a permutation ``perm`` whose inverse is ``inv``."""
    return a[perm]


def _permute_fwd(a, perm, inv):
    return a[perm], (perm, inv)


def _permute_bwd(res, g):
    perm, inv = res
    return g[inv], None, None


_permute_rows.defvjp(_permute_fwd, _permute_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_tokens(x: jax.Array, order: jax.Array, inv: jax.Array, k: int) -> jax.Array:
    """``[S, d] -> [S k, d]``: sorted assignment ``i`` is token ``order[i] // k``."""
    return x[order // k]


def _rows_fwd(x, order, inv, k):
    return x[order // k], (inv,)


def _rows_bwd(k, res, g):
    (inv,) = res
    return g[inv].reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


def sort_by_expert(
    top_idx: jax.Array, n_experts: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``top_idx`` [S, k] -> (order, inv, group_sizes, experts): ``order`` lists
    the S k assignments (numbered token-major: ``s * k + choice``) by expert,
    stably; ``inv`` is its inverse; ``group_sizes`` [E] counts each expert's;
    ``experts`` [S k] is the expert of each sorted row (the sort's own keys)."""
    flat = top_idx.reshape(-1)
    experts, order = jax.lax.sort_key_val(flat, jnp.arange(flat.shape[0], dtype=jnp.int32))
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=jnp.int32))
    group_sizes = jnp.sum(
        (flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)[None, :]).astype(jnp.int32), axis=0
    )
    return order, inv, group_sizes, experts


def rows_not_computed(experts: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """How many of the S k sorted rows no grouped matmul computes with the
    expert the router chose (``experts`` [S k], sorted), read off what the
    kernel is handed: it multiplies row ``i`` by expert ``e`` where
    ``sum(group_sizes[:e]) <= i < sum(group_sizes[:e + 1])`` and by none beyond
    the groups' end. 0 here; a capacity that clipped ``group_sizes``, or a sort
    out of step with them, would show. One compare of S k x E (a gather or a
    binary search over the rows costs the step a millisecond on the chip)."""
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(experts.shape[0], dtype=ends.dtype)
    computed_by = jnp.sum((rows[:, None] >= ends[None, :]).astype(jnp.int32), axis=1)
    return jnp.sum((computed_by != experts).astype(jnp.int32))


def dropless_swiglu_experts(
    x: jax.Array,          # [S, d] tokens, compute dtype
    top_idx: jax.Array,    # [S, k] int32 expert of each choice
    top_gates: jax.Array,  # [S, k] float32 weight of each choice
    w_gate: jax.Array,     # [E, d, f]
    w_up: jax.Array,       # [E, d, f]
    w_down: jax.Array,     # [E, f, d]
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``y[s] = sum_i top_gates[s, i] * down_e(silu(gate_e x[s]) * up_e x[s])``
    with ``e = top_idx[s, i]``; returns (y [S, d], group_sizes [E], the
    assignments not computed: ``rows_not_computed``)."""
    s, d = x.shape
    k = top_idx.shape[1]
    e = w_gate.shape[0]
    dtype = x.dtype
    if _dispatch_observer is not None:
        _dispatch_observer(grouped_matmul_impl(s * k, d, w_gate.shape[2]), e, k, s * k)
    order, inv, group_sizes, experts = sort_by_expert(top_idx, e)
    rows = _rows_of_tokens(x, order, inv, k)                       # [S k, d]
    gate = grouped_matmul(rows, w_gate.astype(dtype), group_sizes)
    up = grouped_matmul(rows, w_up.astype(dtype), group_sizes)
    out = grouped_matmul(jax.nn.silu(gate) * up, w_down.astype(dtype), group_sizes)
    out = _permute_rows(out, inv, order).reshape(s, k, d)          # back to token-major
    y = jnp.einsum("skd,sk->sd", out, top_gates.astype(dtype))
    return y, group_sizes, rows_not_computed(experts, group_sizes)
