"""Flash attention as Pallas TPU kernels (forward + one fused backward).

The reference's hot path is a CUDA ``train_step`` (BASELINE.json:5); its TPU
equivalent for the transformer zoo is attention that never materialises the
[Tq, Tk] score matrix in HBM. Forward is a block-wise online-softmax kernel;
backward recomputes the probabilities from the saved logsumexp and produces
dq, dk and dv in ONE kernel, wired up through ``jax.custom_vjp``.

Design (speeds: PERF.md, Findings of PR 27, measured on a TPU v5e):

- **One head's whole sequence is resident in VMEM** and the kernels loop
  over its blocks themselves. The forward's grid is (batch, head, q-block)
  with the head's K and V as one block (fetched once per head: the block
  index does not change between q-blocks); the backward's grid is (batch,
  head, k-block) with the head's q, dO, lse and delta resident. Grid steps
  are hundreds, not the tens of thousands a 128 x 128 grid makes at
  T=1,024, and a step's cost is its arithmetic. ``choose_blocks`` picks the
  block sizes from (Tq, Tk, D, dtype) and refuses shapes whose resident
  set does not fit VMEM (the router then keeps the XLA core).
- **Causal blocks that are wholly masked are never visited** (the in-kernel
  loops end at the diagonal), and blocks wholly below the diagonal run a
  body with no mask arithmetic at all; only the blocks the diagonal crosses
  (and a ragged last block) pay for ``iota``/compare/select.
- **Softmax statistics are rows, not 128-lane broadcasts.** lse and delta
  live in HBM as ``[B, H, nq, 1, bq]`` (one row per q-block). The backward
  computes transposed scores ``k @ q^T`` so that a row broadcasts along
  sublanes, which is free; the forward turns its column into a row once per
  q-block.
- **Grouped key/value heads** (``k.shape[1]`` divides ``q.shape[1]``): query
  head ``h`` reads K/V head ``h // group`` through the block index map, so a
  group's K and V are fetched once while its query heads follow each other.
  The backward writes dk and dv per QUERY head and the caller sums each
  group's (one reduce over [B, Hkv, group, T, D]); with equal head counts the
  program is what it was.
- **A window** (``window=w``: query i sees keys j with i - w < j <= i) moves
  the START of the in-kernel loops as the diagonal moves their end: blocks
  wholly outside the band are never visited, blocks wholly inside run the
  unmasked body, and only the blocks an edge crosses pay for the mask. Where
  the edges fall corner to corner through the tiles (square blocks that the
  window and the sequence are whole numbers of: both cells' shapes;
  ``strip_form``, PR 73) an edge tile is not computed whole: it runs as strips
  of ``WINDOW_STRIP`` rows over the columns a strip keeps, and where the
  window is ONE block (every visited tile an edge tile, two for one block's
  worth of pairs) a strip's keys are one slab of window + strip rows of the
  resident head, scored by one product under a plain softmax
  (``_fwd_slabs`` / ``_bwd_slabs``). Any other windowed call (an unaligned
  window, a padded sequence) keeps the masked whole tiles. ``window_tiles``
  counts what either computes over the band. The
  windowed calls carry their own kernel names (``dvc_flash_win_fwd`` /
  ``dvc_flash_win_bwd``) so that a device trace tells them from the full ones.
- **A block-diffusion mask** (``block_diffusion=bd``, ``models/sdar_moe.py``):
  the rows are a sequence's clean copy then its noised copy, ``[x_0 ; x_t]``,
  L rows each, positions 0..L-1 twice, in blocks of ``bd`` positions. A clean
  row sees the clean blocks up to and including its own, a noised row the
  clean blocks strictly before its own and the noised rows of its own block,
  in both directions; nothing clean sees anything noised. Neither causal nor a
  band, so the loops' bounds are its own (``_bd_fwd_bounds`` /
  ``_bd_bwd_bounds``): a query block of the clean half visits the clean key
  blocks up to its diagonal, one of the noised half the clean key blocks before
  its own blocks' start and the noised tile (two where a tile's edge falls
  inside it) its diagonal crosses; the noised keys a clean row would meet under
  a causal mask over the 2L rows, a quarter of that mask's pairs, are never
  visited, and only the tiles an edge crosses pay for the mask. Kernel names
  ``dvc_flash_bd_fwd`` / ``dvc_flash_bd_bwd``.
- **The value head has its own width** (``v.shape[-1]``, latent attention's 128
  under keys of 128 + 64): the v, o, dO and dv blocks and the output's
  accumulator take it, the scores and dq / dk the key width. With equal widths
  the program is what it was.
- **The projections' own layout** (``flash_attention_merged``, PR 59): where a
  head is whole 128-lane tiles, block ``h`` of the last axis of ``[B, T, H * D]``
  IS head ``h``, so the same two kernels read q, k, v (and dO) where the
  projections leave them and write o, dq, dk, dv where the next product reads
  them: ``_head_spec`` gives either index, the kernel bodies see ``[rows, D]``
  both ways. No transpose exists around the call. What stood around it by
  head is done without leaving the layout: delta by a small kernel that sums
  each head along its own lanes (``dvc_attn_delta``; XLA's reduce would first
  re-tile both arrays by head), a group's dk and dv written as ``[B, group, T,
  Hkv * D]`` so that the group's sum is over a leading axis.
- **A head narrower than a tile is part of a block** (``heads_a_block``, PR 66):
  where 128 is whole heads (two of 64), the value head is as wide, no
  key/value head is shared and the (shard's) heads are whole blocks, a block
  ``[rows, 128]`` of ``[B, T, H * D]`` holds its heads side by side and the
  grid is ``(batch, H / 2, blocks)``. A grid step runs each head in turn: the
  SAME one-head body, on the same scratch, with its own running maximum,
  denominator and accumulators, and nothing is sliced: the other heads' lanes
  are zeroed in q (forward) or in k and v (backward), so a product that
  contracts the 128 lanes takes this head's alone (a contraction 128 deep
  costs the MXU what one of 64 padded to 128 costs by head), a product that
  keeps them (o, dk, dv) is right on this head's lanes and is chosen by lane
  when the block is written (``ops/lanes.of_head`` / ``by_head``, shared with
  the SSD kernels since PR 49), and dq falls on its own lanes of the one
  accumulator.
  o, dq, dk and dv leave as lane-dense blocks; lse and delta stay rows by head,
  ``[B, H, nq, 1, bq]``, a block's heads a grid step. A row of a by-head
  ``[B, H, T, 64]`` array takes 128 lanes in HBM and in every DMA; here none
  does. Plain causal or full attention only: grouped heads, the rotary turn, a
  window or the block-diffusion mask at D < 128 keep the by-head entry
  (``ops/attention.merged_in_place``). With one head a block the kernels are
  traced to the text they were. Static lane slices of every load and store
  (``_delta_kernel``'s way) compile and agree too, and are slower on the chip
  (``experiments/attention_head64_sweep.py`` carries that form and times both;
  PERF.md, Findings of PR 66).
- **The rotary turn by a lane roll** ("half" pairs, ``_turn``): ``x * cos +
  roll(x) * sin`` in float32 on a ``[rows, D]`` tile, tables ``[T, D]`` made
  once a call (``rotary_tables``: ones and zeros on the lanes a partial rotary
  passes through, the scale folded in), rounded to the input dtype where
  ``ops/attention.rope`` rounds. Each element is turned once a pass: the
  forward kernel turns the q block it has taken up (its grid visits a block
  once); k, and in the backward the resident q and its dq, take ONE
  merged-layout pass each beside the kernels (``dvc_rotary`` /
  ``dvc_rotary_back``: bfloat16 in and out, never an array D/2 wide, never
  float32 in HBM), because the kernel that holds them resident would turn them
  at every visit, or hold whole float32 tables: 16 MiB at T=16,384, where the
  backward already stands at 61.0 of its 64 MiB (PERF.md, Findings of PR 59).
- **Matmuls run in the INPUT dtype** with f32 accumulation, so bf16 inputs
  hit the MXU at its bf16 rate. Scores, statistics and accumulators are
  f32; the probabilities (and ds) are rounded to the input dtype before
  their matmuls, exactly as the XLA core rounds them (ops/attention.py).

On non-TPU backends the kernels run in interpret mode, so the same code
path is unit-testable on the CPU mesh (tests/conftest.py forces
JAX_PLATFORMS=cpu). Numerics are validated against ops/attention.py's
plain-XLA core in tests/test_pallas_attention.py.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedvolunteercomputing_tpu.ops.lanes import by_head, lanes_of, of_head
from distributedvolunteercomputing_tpu.utils.jaxenv import tpu_backend

NEG_INF = -1e30
LANES = 128
# What one kernel may hold in VMEM. A v5e core has 128 MiB; the compiler's
# default scoped limit is 16 MiB, so every call states its own.
VMEM_BUDGET_BYTES = 64 * 1024 * 1024
# The largest block a sequence is cut into. Measured, PR 27, TPU v5e
# (PERF.md, block sweep): at T=1,024 one 1,024 x 1,024 block a head (the
# forward is then a plain softmax: no running statistics to rescale) and
# 512 x 512 blocks (a quarter of the square skipped as masked) are within 3%
# of each other forward + backward, and everything smaller loses to the
# per-block cost of the running statistics; at T=2,048 blocks of 1,024 win.
# A window keeps its blocks as wide as itself for the same reason (PR 33's
# sweep, ``choose_blocks``) and cuts only the ROWS of a grid step into strips
# where an edge crosses a tile (``WINDOW_STRIP``, PR 73).
PREFERRED_BLOCK = 1024
# The names ``_fa_fwd`` gives the kernel's output and its log-sum-exp rows:
# what a rematerialised layer keeps of a call (models/common.remat_layer).
KEPT_NAMES = ("attention_out", "attention_lse")


def _interpret_default() -> bool:
    return not tpu_backend()


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(tq: int, tk: int, d: int, dtype, block_q: int, block_k: int, turned: bool = False) -> int:
    """Upper estimate of the backward kernel's VMEM (the larger of the two):
    the resident head (q, dO, dq, double-buffered; the f32 dq accumulator),
    the streamed k/v/dk/dv blocks and their accumulators, the statistics'
    rows (a [1, bq] row fills 8 sublanes) and the score-shaped f32
    temporaries (s, p, dp, ds and two rounded copies). The head dim pads to
    128 lanes in VMEM. A call that turns its q (``turned``) adds the forward's
    two float32 table blocks, double-buffered, on top: the backward is handed
    a turned q and takes no table, so this bounds both kernels."""
    item = jnp.dtype(dtype).itemsize
    dl = _round_up(d, LANES)
    tq_p, bk = _round_up(tq, block_q), min(block_k, _round_up(tk, 8))
    resident = 3 * 2 * tq_p * dl * item + tq_p * dl * 4
    streamed = 4 * 2 * bk * dl * item + 2 * bk * dl * 4
    stats = 2 * 2 * 8 * tq_p * 4
    tiles = 6 * block_q * bk * 4
    tables = 2 * 2 * block_q * dl * 4 if turned else 0
    return resident + streamed + stats + tiles + tables


# The largest block under a block-diffusion mask. A noised query block's
# diagonal tile holds block x bd kept pairs of its block x block, so with n
# blocks a half the loops visit n^2 + 2n tiles where a causal mask over the 2n
# would visit 2n^2 + n: 0.667 of them at n = 4 (1,024 at L = 4,096), 0.588 at
# n = 8 (512), 0.545 at n = 16 (256).
BD_BLOCK = 512
# The rows of a strip: where a window's edges fall corner to corner through the
# tiles (``strip_form``), the tiles an edge crosses run as strips of so many
# query rows (key rows in the backward) over the columns the strip keeps, so a
# window one block wide computes (window + strip) / window of its band where
# two whole tiles compute 2.0, and an edge tile of a wider window costs
# (1 + strip / block) / 2 of itself. Fewer rows compute fewer pairs and run
# shallower products (a strip's rows are what streams past each 128 x 128 tile
# of the other operand). Measured, PR 73, TPU v5e, forward / backward ms
# (experiments/laguna_attention_sweep.py; PERF.md, Findings of PR 73): window
# 512 at T=8,192, 512 x 512, 64 heads over 8: whole tiles 15.71 / 22.66,
# strips of 128 10.11 / 20.03, of 256 8.68 / 18.19, of 512 (a slab of the two
# tiles under a plain softmax) 7.69 / 20.26; window 4,096 at T=16,384, 1,024 x
# 1,024, 28 heads over 4: whole tiles 15.36 / 30.44, 128 15.77 / 26.81, 256
# 14.93 / 26.84, 512 14.90 / 27.91.
WINDOW_STRIP = 256


def choose_blocks(
    tq: int, tk: int, d: int, dtype, window: Optional[int] = None, turned: bool = False,
    block_diffusion: Optional[int] = None,
) -> Optional[Tuple[int, int]]:
    """(block_q, block_k) for a shape, or None where the kernel cannot hold
    one head in VMEM (``turned``: with the rotary tables' blocks of a call that
    turns its q). With a ``window`` the blocks are no larger than the
    window where the sequence allows it (under a ``block_diffusion`` mask no
    larger than ``BD_BLOCK``): every block a query block visits is
    then crossed by an edge, and still smaller BLOCKS lost to the per-block
    cost of the running statistics and the longer grid.
    Measured, PR 33, TPU v5e, window 512 at T=8,192, 64 heads over 8, forward
    + backward as masked whole tiles (experiments/laguna_attention_sweep.py):
    512 x 512 38.4 ms, 256 x 512 44.2, 1,024 x 1,024 49.4, 256 x 256 53.8,
    128 x 128 85.2. That is why the blocks stay as wide as the window; the two
    blocks visited for one block's worth of pairs are since PR 73 not computed
    whole: inside a grid step the rows run as strips (``WINDOW_STRIP``,
    ``strip_form``: 25.3 ms at the same 512 x 512).

    Mosaic wants a block's last two dims to be multiples of the dtype's
    tile ((8, 128) f32, (16, 128) bf16) or the whole dim; the statistics'
    row puts block_q on the lane axis. So a sequence that is a multiple of
    128 takes the largest of 1,024, 512, 256, 128 that divides it, and any
    other sequence is one block (padded to the sublane tile) up to 1,024 and
    padded to whole 1,024-blocks beyond."""

    largest = PREFERRED_BLOCK
    if window is not None:
        largest = max(128, min(PREFERRED_BLOCK, window))
    if block_diffusion is not None:
        largest = BD_BLOCK

    def pick(t: int) -> int:
        for b in (PREFERRED_BLOCK, 512, 256, 128):
            if b <= largest and t % b == 0:
                return b
        return _round_up(t, 16) if t <= PREFERRED_BLOCK else PREFERRED_BLOCK

    bq, bk = pick(tq), pick(tk)
    if vmem_bytes(tq, tk, d, dtype, bq, bk, turned) > VMEM_BUDGET_BYTES:
        return None
    return bq, bk


def _pad_seq(x: jax.Array, block: int, axis: int = 2) -> jax.Array:
    pad = (-x.shape[axis]) % block
    if pad == 0:
        return x
    return jnp.pad(x, [(0, pad if a == axis else 0) for a in range(x.ndim)])


def _dims(q: jax.Array, k: jax.Array, v: jax.Array, heads: Optional[Tuple[int, int]]):
    """(B, H, Hkv, Tq, Tk, D, Dv) of a call by head, ``[B, H, T, D]`` (``heads``
    None), or merged, ``[B, T, H * D]`` with ``heads`` = (H, Hkv)."""
    if heads is None:
        b, h, tq, d = q.shape
        return b, h, k.shape[1], tq, k.shape[2], d, v.shape[3]
    b, tq, _ = q.shape
    h, h_kv = heads
    return b, h, h_kv, tq, k.shape[1], q.shape[2] // h, v.shape[2] // h_kv


def _head_spec(merged: bool, rows: int, width: int, where) -> pl.BlockSpec:
    """``rows`` x ``width`` of one head as a block of an array by head,
    ``[B, H, T, D]``, or merged, ``[B, T, H * D]``: a head whose width is
    whole lanes is block ``head`` of the merged array's last axis, so the
    kernels read a projection's result, and write what the output projection
    reads, where they lie. ``where(i, j, g)`` gives (batch, head, row block)."""
    if merged:
        def index(i, j, g):
            b, h, r = where(i, j, g)
            return b, r, h

        return pl.BlockSpec((None, rows, width), index)
    return pl.BlockSpec((None, None, rows, width), lambda i, j, g: (*where(i, j, g), 0))


def heads_a_block(d: int, dv: int, h: int, h_kv: int) -> int:
    """How many heads one block of the merged layout holds, or 0 where the
    kernels cannot read ``[B, T, H * D]`` in place: one where a head is whole
    128-lane tiles; where it is narrower, as many as fill a tile (two of 64),
    if a tile is whole heads, the value head is as wide, no key/value head is
    shared and the (shard's) heads are whole blocks."""
    if d % LANES == 0 and dv % LANES == 0:
        return 1
    per = LANES // d
    return per if LANES % d == 0 and dv == d and h == h_kv and h % per == 0 else 0


def _dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """dot_general with f32 accumulation, operands kept in THEIR dtype —
    sub-f32 inputs hit the MXU at native rate (see module docstring)."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


def _compiler_params(interpret: bool, semantics, vmem: int):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=int(min(max(2 * vmem, 32 * 1024 * 1024), 100 * 1024 * 1024)),
    )


def _loop(lo, hi, body) -> None:
    """``for j in range(lo, hi): body(j)`` over refs; bounds may be traced."""
    jax.lax.fori_loop(lo, hi, lambda j, c: (body(j), c)[1], 0)


def _mask_scores(s, q0, kpos: jax.Array, q_axis: int, *, causal: bool, tk_valid: int, ragged: bool,
                 window: Optional[int] = None, block_diffusion: Optional[int] = None):
    """The scores of a masked block with NEG_INF where they do not count: key
    positions ``kpos`` (an iota the shape of the block) outside the valid
    keys, under causal masking after their query (queries run from ``q0``
    along ``q_axis``), and under a window ``window`` or more keys before it;
    under a ``block_diffusion`` mask, what ``bd_keep`` drops.
    The valid-key compare is emitted only where padded keys exist
    (``ragged``)."""
    keep = kpos < tk_valid if ragged else None
    if block_diffusion is not None:
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, kpos.shape, q_axis)
        kept = bd_keep(qpos, kpos, tk_valid // 2, block_diffusion)
        keep = kept if keep is None else keep & kept
    if causal:
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, kpos.shape, q_axis)
        keep = (kpos <= qpos) if keep is None else keep & (kpos <= qpos)
        if window is not None:
            keep = keep & (kpos > qpos - window)
    return s if keep is None else jnp.where(keep, s, NEG_INF)


# ---------------------------------------------------------------------------
# the block-diffusion mask: rows [x_0 ; x_t], ``half`` each, blocks of ``bd``
# ---------------------------------------------------------------------------


def bd_keep(qpos, kpos, half: int, bd: int):
    """Whether query row ``qpos`` sees key row ``kpos`` (int32 arrays of one
    shape, rows of ``[x_0 ; x_t]``: clean rows ``0..half-1``, noised rows
    ``half..2 half - 1``, a row's position its index within its half, its block
    ``position // bd``): clean -> clean iff the key's block is not after the
    query's; noised -> clean iff it is strictly before; noised -> noised iff the
    two are one block; clean -> noised never."""
    if bd & (bd - 1) == 0:  # a shift where the block is a power of two: the chip has no vector divide
        shift = bd.bit_length() - 1
        block = lambda x: jax.lax.shift_right_arithmetic(x, jnp.int32(shift))  # noqa: E731
    else:
        block = lambda x: x // bd  # noqa: E731
    q_clean, k_clean = qpos < half, kpos < half
    qb = block(jnp.where(q_clean, qpos, qpos - half))
    kb = block(jnp.where(k_clean, kpos, kpos - half))
    same = kb == qb
    return (k_clean & ((kb < qb) | (q_clean & same))) | (~k_clean & ~q_clean & same)


def _bd_fwd_bounds(iq, block_q: int, block_k: int, n_k: int, half: int, bd: int, xp=jnp):
    """The key blocks query block ``iq`` visits under the block-diffusion mask:
    ``(a, b, c, d)`` for ``[0, a)`` with no mask arithmetic (clean keys every
    row of the block sees), ``[a, b)`` masked (clean keys some row sees) and
    ``[c, d)`` masked (the noised keys of the blocks its noised rows lie in).
    Exact where a half is whole tiles, a superset otherwise (a tile that holds
    rows of both halves visits what either needs; the mask is exact anywhere).
    ``xp``: ``jnp`` on a traced ``iq``, ``numpy`` to count (``bd_tiles``)."""
    t = 2 * half
    start = lambda x: x // bd * bd  # noqa: E731  the first position of x's block
    r0 = iq * block_q
    r1 = xp.minimum(r0 + block_q, t) - 1
    has_clean, has_noised = r0 < half, r1 >= half
    c1 = xp.minimum(r1, half - 1)       # the block's last clean row
    n0 = xp.maximum(r0, half) - half    # its first and last noised positions
    n1 = r1 - half
    seen = xp.maximum(xp.where(has_clean, start(c1) + bd, 0), xp.where(has_noised, start(n1), 0))
    by_all = xp.minimum(xp.where(has_clean, start(r0) + bd, half), xp.where(has_noised, start(n0), half))
    seen = xp.minimum(seen, half)
    a = xp.minimum(by_all, seen) // block_k
    b = (seen + block_k - 1) // block_k
    c = xp.maximum(b, (half + start(n0)) // block_k)
    d = xp.where(has_noised, (xp.minimum(half + start(n1) + bd, t) + block_k - 1) // block_k, c)
    return a, b, c, xp.minimum(xp.maximum(d, c), n_k)


def _bd_bwd_bounds(ik, block_q: int, block_k: int, n_q: int, half: int, bd: int, xp=jnp):
    """The query blocks key block ``ik`` is visited from, the mirror of
    ``_bd_fwd_bounds``: ``(s1, e1, e2, s3, e3, e4)`` for ``[s1, e1)`` masked,
    ``[e1, e2)`` unmasked, ``[s3, e3)`` masked, ``[e3, e4)`` unmasked. A key
    block of clean keys: the clean rows from its first key's block on (masked
    until every row's block is at or after its last key's), then, past the
    noised rows that see none of it, the noised rows of later blocks. A key
    block of noised keys: the noised rows of its own blocks, masked. One that
    holds both (a half that is not whole tiles): every block from its first
    clean key's on, masked."""
    t = 2 * half
    start = lambda x: x // bd * bd  # noqa: E731
    ceil = lambda x: (x + block_q - 1) // block_q  # noqa: E731
    k0 = ik * block_k
    k1 = xp.minimum(k0 + block_k, t) - 1
    clean, noised = k0 + block_k <= half, k0 >= half
    kc1 = xp.minimum(k1, half - 1)
    t1 = start(k0) // block_q
    t3 = half // block_q  # the first query block that holds a noised row
    u1 = xp.clip(ceil(start(kc1)), t1, t3)
    # where a query block holds rows of both halves it is visited masked, with all after it
    t4 = (half + start(k0) + bd) // block_q if half % block_q == 0 else t3
    t4 = xp.minimum(t4, n_q)
    t5 = xp.clip(ceil(half + start(kc1) + bd), t4, n_q)
    n0, n1 = xp.maximum(k0, half) - half, k1 - half
    s3n = (half + start(n0)) // block_q
    e3n = xp.minimum(ceil(half + start(n1) + bd), n_q)
    s1 = xp.where(noised, 0, t1)
    e1 = xp.where(clean, u1, xp.where(noised, 0, n_q))
    e2 = xp.where(clean, t3, e1)
    s3 = xp.where(clean, t4, xp.where(noised, s3n, n_q))
    e3 = xp.where(clean, t5, xp.where(noised, xp.maximum(e3n, s3n), n_q))
    e4 = xp.where(clean, n_q, e3)
    return s1, e1, e2, s3, e3, e4


def bd_tiles(t: int, bd: int, block_q: int, block_k: int) -> dict:
    """Tiles the two kernels' loops visit over ``t`` rows (``[x_0 ; x_t]``)
    under the block-diffusion mask, and what a causal mask over the same rows
    makes them visit: ``{"fwd", "bwd", "causal_fwd", "causal_bwd"}``, counted
    from the kernels' own bounds."""
    import numpy as np

    half = t // 2
    n_q, n_k = -(-t // block_q), -(-t // block_k)
    iq, ik = np.arange(n_q), np.arange(n_k)
    _, b, c, d = _bd_fwd_bounds(iq, block_q, block_k, n_k, half, bd, np)
    s1, _, e2, s3, _, e4 = _bd_bwd_bounds(ik, block_q, block_k, n_q, half, bd, np)
    return {
        "fwd": int(np.sum(b + d - c)), "bwd": int(np.sum(e2 - s1 + e4 - s3)),
        "causal_fwd": int(np.sum(np.minimum(n_k, ((iq + 1) * block_q - 1) // block_k + 1))),
        "causal_bwd": int(np.sum(n_q - (ik * block_k) // block_q)),
    }


# ---------------------------------------------------------------------------
# a window: the tiles the loops visit, and the strips the edge tiles run as
# ---------------------------------------------------------------------------


def strip_form(t: int, window: Optional[int], block_q: int, block_k: int) -> Optional[str]:
    """How a windowed call over ``t`` rows runs the tiles an edge crosses, read
    off its shape: ``"slab"`` or ``"edge"`` where the edges fall corner to
    corner through the tiles (square blocks of whole strips that the window and
    the sequence are whole numbers of, more than one window of rows), None
    (masked whole tiles) anywhere else. ``"slab"``, the window one block: a
    strip's keys, its part of the tile before the diagonal one and of the
    diagonal one, are ONE slab of window + strip rows, scored by one product
    under a plain softmax. ``"edge"``, the window several blocks: the far tile
    and the diagonal tile each run as strips over the columns a strip keeps,
    inside the running statistics that the whole tiles between them use."""
    if (window is None or block_q != block_k or block_q % WINDOW_STRIP or window % block_k
            or t % block_q or t <= window):
        return None
    return "slab" if window == block_k else "edge"


def _win_fwd_bounds(iq, block_q: int, block_k: int, n_k: int, tk_valid: int, window: int, xp=jnp):
    """The key blocks query block ``iq`` visits under a window: ``(lo, a, b,
    end)`` for ``[lo, a)`` masked (the band's far edge: from the first block
    that holds a key some row sees to the first every row sees all of), ``[a,
    b)`` with no mask arithmetic and ``[b, end)`` masked (the diagonal, a ragged
    last block). ``xp``: ``jnp`` on a traced ``iq``, ``numpy`` to count."""
    row0, row1 = iq * block_q, (iq + 1) * block_q - 1
    n_full = xp.minimum(tk_valid // block_k, row0 // block_k)
    n_end = xp.minimum(n_k, row1 // block_k + 1)
    lo = xp.maximum(row0 - window + 1, 0) // block_k
    inside = xp.maximum(row1 - window + block_k, 0) // block_k  # ceil((row1 - w + 1) / bk)
    a = xp.clip(inside, lo, n_end)
    return lo, a, xp.clip(n_full, a, n_end), n_end


def _win_bwd_bounds(ik, block_q: int, block_k: int, n_q: int, n_k: int, tk_valid: int, window: int, xp=jnp):
    """The query blocks key block ``ik`` is visited from under a window, the
    mirror of ``_win_fwd_bounds``: ``(first, a, b, last)`` for ``[first, a)``
    masked (the diagonal; every block where the key block is a ragged last
    one), ``[a, b)`` unmasked (every row sees every key of it) and ``[b,
    last)`` masked (up to the last block that holds a row seeing its last key)."""
    key0, key1 = ik * block_k, (ik + 1) * block_k - 1
    first = key0 // block_q
    n_masked_end = xp.minimum(n_q, (key1 + block_q - 1) // block_q)
    if tk_valid % block_k:
        n_masked_end = xp.where(ik == n_k - 1, n_q, n_masked_end)
    last = xp.minimum(n_q, (key1 + window - 1) // block_q + 1)
    a = xp.minimum(n_masked_end, last)
    return first, a, xp.clip((key0 + window) // block_q, a, last), last


def window_tiles(t: int, window: int, block_q: int, block_k: int) -> dict:
    """Pairs (query, key) the two kernels compute over ``t`` rows under a
    window at these blocks, counted from the kernels' own bounds and strips,
    and pairs inside the band: ``{"fwd", "bwd", "band", "form"}``. A whole
    tile counts block_q x block_k, a strip its rows times the columns it takes."""
    import numpy as np

    n_q, n_k = -(-t // block_q), -(-t // block_k)
    lo, a, b, end = _win_fwd_bounds(np.arange(n_q), block_q, block_k, n_k, t, window, np)
    first, c, d, last = _win_bwd_bounds(np.arange(n_k), block_q, block_k, n_q, n_k, t, window, np)
    tile, form = block_q * block_k, strip_form(t, window, block_q, block_k)
    fwd, bwd = int(np.sum(end - lo)) * tile, int(np.sum(last - first)) * tile
    if form == "slab":  # every strip, clamped at the sequence's ends or not, takes one slab
        fwd = bwd = t * (window + WINDOW_STRIP)
    elif form == "edge":  # strip i of an edge tile takes (i + 1) strips' worth of columns
        m = block_q // WINDOW_STRIP
        saved = tile - WINDOW_STRIP * WINDOW_STRIP * m * (m + 1) // 2
        fwd -= int(np.sum(a - lo + end - b)) * saved
        bwd -= int(np.sum(c - first + last - d)) * saved
    ar = np.arange(t)
    return {"fwd": fwd, "bwd": bwd, "band": int(np.sum(np.minimum(ar + 1, window))), "form": form or "tiles"}


# ---------------------------------------------------------------------------
# the rotary turn ("half" layout: a frequency pairs lanes i and i + R / 2)
# ---------------------------------------------------------------------------


def rotary_tables(
    t: int, d: int, base: float = 10000.0, rotary_dim: Optional[int] = None,
    inv_freq: Optional[jax.Array] = None, scale: float = 1.0,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin), each ``[T, D]`` float32, of ``ops/attention.rope``'s "half"
    layout with the same ``base``, ``rotary_dim``, ``inv_freq``, ``scale`` and
    ``positions`` ([T]; the row index where none are given),
    laid out for ``_turn``: a lane's cosine, and its partner's sine with the
    sign the lane takes it with (minus on the first half of the rotated lanes);
    ones and zeros on the lanes a partial rotary passes through. The values are
    ``rope``'s own (same float32 products), made once a call by XLA."""
    r = d if rotary_dim is None else rotary_dim
    freqs = base ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r) if inv_freq is None else inv_freq
    if positions is None:
        positions = jnp.arange(t)
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [T, R/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    rest = jnp.ones((t, d - r), jnp.float32)
    return (
        jnp.concatenate([cos, cos, rest], axis=-1),
        jnp.concatenate([-sin, sin, 0.0 * rest], axis=-1),
    )


def _turn(x: jax.Array, cos: jax.Array, sin: jax.Array, rotary_dim: int, back: bool = False):
    """One head's rows ``x`` [rows, D] float32 turned by the tables' rows:
    ``x * cos + partner(x) * sin``, the partner reached by a roll along the
    lanes (no array D/2 wide). With R = D one roll by D/2 serves both halves;
    a partial rotary's first half looks R/2 up and its second R/2 down (the
    lanes past R meet a sine of 0). ``back`` is the transpose, which is the
    turn the other way (times the tables' scale): a cotangent's way home."""
    d, half = x.shape[-1], rotary_dim // 2
    if rotary_dim == d:
        partner = pltpu.roll(x, half, 1)
    else:
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        partner = jnp.where(lane < half, pltpu.roll(x, d - half, 1), pltpu.roll(x, half, 1))
    return x * cos - partner * sin if back else x * cos + partner * sin


def _turn_kernel(x_ref, cos_ref, sin_ref, o_ref, *, d, rotary_dim, back):
    cos, sin = cos_ref[...], sin_ref[...]
    for h in range(x_ref.shape[-1] // d):  # the block's heads side by side, each on its own lanes
        x = x_ref[:, h * d:(h + 1) * d].astype(jnp.float32)
        o_ref[:, h * d:(h + 1) * d] = _turn(x, cos, sin, rotary_dim, back).astype(o_ref.dtype)


# Heads a block of the passes beside the kernels: 8 x 128 lanes x 512 rows of
# bfloat16 is 1 MiB in and 1 MiB out a grid step, rows of 2 KiB in HBM.
_TURN_HEADS, _TURN_ROWS = 8, 512


def _heads_a_block(h: int, width: int = LANES) -> int:
    """The most heads of ``width`` lanes, up to ``_TURN_HEADS``, that divide
    ``h`` and fill whole 128-lane tiles."""
    return max(n for n in range(1, _TURN_HEADS + 1) if h % n == 0 and n * width % LANES == 0)


def _turn_merged(x: jax.Array, cos: jax.Array, sin: jax.Array, rotary_dim: int, back: bool,
                 interpret: Optional[bool] = None) -> jax.Array:
    return _turn_pass(x, cos, sin, rotary_dim, back, _interpreted(interpret))


@functools.partial(jax.jit, static_argnames=("rotary_dim", "back", "interpret"))
def _turn_pass(x: jax.Array, cos: jax.Array, sin: jax.Array, rotary_dim: int, back: bool, interpret: bool) -> jax.Array:
    """``x`` [B, T, H * D] with every head turned (or turned ``back``), as ONE
    pass in the merged layout: the input's dtype in and out, float32 on the
    tile, rounded once. The tables' rows are fetched once a row block (the
    heads are the innermost grid axis). Jitted so that a step's passes of one
    shape (a layer's k forward and recomputed, every layer of a kind) are
    traced and lowered once: start-up is part of what a donor pays."""
    b, t, hd = x.shape
    d = cos.shape[-1]
    h = hd // d
    per = _heads_a_block(h)
    rows = min(_TURN_ROWS, _round_up(t, 16))
    xp, cosp, sinp = _pad_seq(x, rows, 1), _pad_seq(cos, rows, 0), _pad_seq(sin, rows, 0)
    block = pl.BlockSpec((None, rows, per * d), lambda i, r, j: (i, r, j))
    table = pl.BlockSpec((rows, d), lambda i, r, j: (r, 0))
    out = pl.pallas_call(
        functools.partial(_turn_kernel, d=d, rotary_dim=rotary_dim, back=back),
        grid=(b, xp.shape[1] // rows, h // per),
        in_specs=[block, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="dvc_rotary_back" if back else "dvc_rotary",
    )(xp, cosp, sinp)
    return out[:, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def rotary_merged(x: jax.Array, cos: jax.Array, sin: jax.Array, rotary_dim: int,
                  interpret: Optional[bool] = None) -> jax.Array:
    """``ops/attention.rope`` ("half" layout) of ``x`` [B, T, H * D] where the
    projection left it, by ``rotary_tables``' tables: one pass, and one pass
    back for its cotangent."""
    return _turn_merged(x, cos, sin, rotary_dim, False, interpret)


def _rotary_fwd(x, cos, sin, rotary_dim, interpret):
    return _turn_merged(x, cos, sin, rotary_dim, False, interpret), (cos, sin)


def _rotary_bwd(rotary_dim, interpret, tables, g):
    cos, sin = tables
    return _turn_merged(g, cos, sin, rotary_dim, True, interpret), jnp.zeros_like(cos), jnp.zeros_like(sin)


rotary_merged.defvjp(_rotary_fwd, _rotary_bwd)


def _kernel_name(which: str, window: Optional[int], block_diffusion: Optional[int]) -> str:
    """A device trace tells the calls apart by name: full, windowed, block-diffusion."""
    kind = "bd_" if block_diffusion is not None else "" if window is None else "win_"
    return f"dvc_flash_{kind}{which}"


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _band(s, rel, off, window: int):
    """The scores of a strip against its slab with NEG_INF outside the band:
    ``rel`` a score's key index less its query index (an iota difference, made
    once a grid step), ``off`` the first query's position less the first key's."""
    return jnp.where((rel <= off) & (rel > off - window), s, NEG_INF)


def _fwd_slabs(q_ref, k_ref, v_ref, cos_ref, sin_ref, o_ref, lse_ref, lse_scr, *,
               scale, row0, block_q, window, rotary_dim):
    """A q block under a window one block wide (``strip_form`` "slab"): each
    strip of WINDOW_STRIP rows sees the window + strip keys that end with its
    own last row, all of them in the resident head, so its scores are ONE
    product over that slab and its softmax a plain one: no running maximum, no
    rescale, and none of the two tiles' pairs outside the slab are computed. In
    the first q block the slab starts at key 0 and the positions do the rest.
    Each strip is a body of its own in the kernel's text (two a block), so that
    one strip's products run beside the other's softmax: a ``fori_loop`` over
    them read 8.68 ms where this reads 7.18 (PR 73, the sweep of
    ``WINDOW_STRIP``'s comment), and the step's executable is 0.5 MB larger
    for it (and 1.5 MB smaller than with the whole tiles)."""
    strip, width = WINDOW_STRIP, window + WINDOW_STRIP
    rel = (jax.lax.broadcasted_iota(jnp.int32, (strip, width), 1)
           - jax.lax.broadcasted_iota(jnp.int32, (strip, width), 0))
    for r in range(0, block_q, strip):
        q = q_ref[r:r + strip, :]
        if rotary_dim is not None:  # turned on the tile, as the whole block is
            q = _turn(q.astype(jnp.float32), cos_ref[r:r + strip, :], sin_ref[r:r + strip, :],
                      rotary_dim).astype(q.dtype)
        start = pl.multiple_of(jnp.maximum(row0 + r - window, 0), strip)
        s = _band(_dot(q, k_ref[pl.ds(start, width), :], _NT) * scale, rel, row0 + r - start, window)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        out = _dot(p.astype(v_ref.dtype), v_ref[pl.ds(start, width), :], _NN) / l
        o_ref[r:r + strip, :] = out.astype(o_ref.dtype)
        lse_scr[r:r + strip, :] = jnp.broadcast_to(m + jnp.log(l), (strip, LANES))
    lse_ref[...] = jnp.transpose(lse_scr[...])[0:1, :]  # a column here, a row in HBM, as ``_fwd_kernel``'s


def _fwd_kernel(
    q_ref, k_ref, v_ref, *rest,
    scale, causal, block_q, block_k, tk_valid, n_k, window=None, rotary_dim=None,
    block_diffusion=None, head_dim=None, strips=None,
):
    cos_ref = sin_ref = None
    if rotary_dim is not None:  # this q block's rows of the rotary tables, [bq, D] float32
        cos_ref, sin_ref, *rest = rest
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    iq = pl.program_id(2)
    if strips == "slab":
        _fwd_slabs(q_ref, k_ref, v_ref, cos_ref, sin_ref, o_ref, lse_ref, m_scr,
                   scale=scale, row0=iq * block_q, block_q=block_q, window=window, rotary_dim=rotary_dim)
        return
    _masked = functools.partial(
        _mask_scores, causal=causal, tk_valid=tk_valid, ragged=tk_valid % block_k != 0,
        window=window, block_diffusion=block_diffusion,
    )

    def head(take_q):
        """One head's pass over its key blocks: its rows of o (float32) and a
        thunk for its statistics row. ``take_q()``: the q block [bq, width] in
        the input dtype, taken after the scratch is reset."""
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q = take_q()
        if rotary_dim is not None:
            # A q block is taken up once a pass, so its pairs are turned here, on the
            # tile, and rounded where ``ops/attention.rope`` rounds them.
            q = _turn(q.astype(jnp.float32), cos_ref[...], sin_ref[...], rotary_dim).astype(q.dtype)

        def step(jk, masked: bool, rows=..., lo: int = 0, width: int = block_k):
            """Key block ``jk`` into the running statistics: the whole q block against the
            whole tile, or a strip of it (``rows``, a static slice) against the ``width``
            columns from ``lo`` of the tile that the strip keeps. (The whole tile's
            operations are traced as they were before there were strips: every other
            caller's kernel keeps its text and its compile-cache key.)"""
            whole = rows is ...
            first = (lambda: jk * block_k) if whole else (lambda: jk * block_k + lo)
            start = pl.multiple_of(first(), block_k if whole else WINDOW_STRIP)
            kblk = k_ref[pl.ds(start, width), :]
            vblk = v_ref[pl.ds(start, width), :]
            s = _dot(q if whole else q[rows], kblk, _NT) * scale  # f32 [bq, bk]
            if masked:
                col = first() + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = _masked(s, iq * block_q if whole else iq * block_q + rows.start, col, 0)
            m_prev = m_scr[rows]  # [bq, LANES], every lane the same
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new[:, 0:1])
            corr = jnp.exp(m_prev - m_new)
            l_scr[rows] = l_scr[rows] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_scr[rows] = m_new
            acc_scr[rows] = acc_scr[rows] * corr[:, 0:1] + _dot(p.astype(vblk.dtype), vblk, _NN)

        def edge(jk, far: bool):
            """A tile a window's edge crosses corner to corner, as strips: a strip of the
            diagonal tile keeps the tile's columns up to its own rows', one of the far
            tile the columns from its own rows' on."""
            for r in range(0, block_q, WINDOW_STRIP):
                step(jk, True, slice(r, r + WINDOW_STRIP), r if far else 0, block_k - r if far else r + WINDOW_STRIP)

        # Blocks wholly visible (and wholly inside the valid keys) need no mask;
        # the ones the diagonal crosses, or a ragged last one, do; the rest of a
        # causal row's blocks are never visited.
        n_full = tk_valid // block_k
        n_end = n_k
        if causal:
            n_full = jnp.minimum(n_full, (iq * block_q) // block_k)
            n_end = jnp.minimum(n_k, ((iq + 1) * block_q - 1) // block_k + 1)
        if block_diffusion is not None:
            a, b, c, d = _bd_fwd_bounds(iq, block_q, block_k, n_k, tk_valid // 2, block_diffusion)
            _loop(0, a, lambda jk: step(jk, False))
            _loop(a, b, lambda jk: step(jk, True))
            _loop(c, d, lambda jk: step(jk, True))
        elif window is None:
            _loop(0, n_full, lambda jk: step(jk, False))
            _loop(n_full, n_end, lambda jk: step(jk, True))
        else:
            lo, a, b, n_end = _win_fwd_bounds(iq, block_q, block_k, n_k, tk_valid, window)
            _loop(lo, a, (lambda jk: edge(jk, True)) if strips else (lambda jk: step(jk, True)))
            _loop(a, b, lambda jk: step(jk, False))
            _loop(b, n_end, (lambda jk: edge(jk, False)) if strips else (lambda jk: step(jk, True)))

        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # The statistic is a column here and a row in HBM: one transpose per
        # q-block instead of a 128-lane broadcast written for every row.
        return acc_scr[...] / l_safe[:, 0:1], lambda: jnp.transpose(m_scr[...] + jnp.log(l_safe))[0:1, :]

    if head_dim is None:  # the block is one head
        out, lse = head(lambda: q_ref[...])
        o_ref[...] = out.astype(o_ref.dtype)
        lse_ref[...] = lse()
        return
    # The block is heads of ``head_dim`` lanes side by side (two of 64), each run in turn
    # on the same scratch with its own statistics, the block's o written lane-dense.
    # Nothing is sliced: the other heads' lanes are zeroed in q, so the scores are this head's
    # alone; p @ v is right on its lanes (v is read whole) and chosen by lane below.
    outs, rows = [], []
    for h in range(q_ref.shape[-1] // head_dim):
        out, lse = head(lambda: of_head(q_ref[...], lanes_of(q_ref), h, head_dim))
        outs.append(out)
        rows.append(lse())
    o_ref[...] = by_head(outs, lanes_of(o_ref), head_dim).astype(o_ref.dtype)
    lse_ref[...] = jnp.stack(rows)


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
    bq: int, bk: int, interpret: bool, window: Optional[int] = None,
    heads: Optional[Tuple[int, int]] = None, tables=None, rotary_dim: Optional[int] = None,
    block_diffusion: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(out [B, H, Tq, Dv], lse [B, H, nq, 1, bq] over the padded rows). With
    ``heads`` = (H, Hkv) q, k, v and out are merged, ``[B, T, H * D]`` (a head
    narrower than a tile: ``heads_a_block`` heads a block); with ``tables``
    (cos, sin ``[Tq, D]`` of ``rotary_tables``) the kernel turns q."""
    merged, seq = heads is not None, 2 if heads is None else 1
    b, h, h_kv, tq, tk, d, dv = _dims(q, k, v, heads)  # dv: the value head's own width, o's and the accumulator's
    group = h // h_kv  # query heads per key/value head
    per = heads_a_block(d, dv, h, h_kv) if merged else 1  # heads a block: the grid's second axis counts blocks
    scale = 1.0 / (d ** 0.5)
    qp, kp, vp = _pad_seq(q, bq, seq), _pad_seq(k, bk, seq), _pad_seq(v, bk, seq)
    tq_p, tk_p = qp.shape[seq], kp.shape[seq]
    n_q, n_k = tq_p // bq, tk_p // bk

    def at_q(i, j, iq):
        return i, j, iq

    def at_kv(i, j, iq):  # a group's query heads read one key/value head
        return i, (j if group == 1 else j // group), 0

    qspec, kvspec = _head_spec(merged, bq, per * d, at_q), _head_spec(merged, tk_p, per * d, at_kv)
    ospec, vspec = qspec, kvspec
    if dv != d:  # a value head narrower (or wider) than the key head: v and o in blocks of its width
        ospec, vspec = _head_spec(merged, bq, dv, at_q), _head_spec(merged, tk_p, dv, at_kv)
    turned = []
    if tables is not None:
        rows = pl.BlockSpec((bq, d), lambda i, j, iq: (iq, 0))
        turned = [(_pad_seq(t, bq, 0), rows) for t in tables]
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk, tk_valid=tk, n_k=n_k, window=window,
            rotary_dim=None if tables is None else rotary_dim,
            block_diffusion=block_diffusion, head_dim=None if per == 1 else d,
            strips=strip_form(tq, window, bq, bk),
        ),
        grid=(b, h // per, n_q),
        in_specs=[qspec, kvspec, vspec] + [spec for _, spec in turned],
        out_specs=[
            ospec,
            pl.BlockSpec((None, None if per == 1 else per, None, 1, bq), lambda i, j, iq: (i, j, iq, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tq_p, h * dv) if merged else (b, h, tq_p, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, n_q, 1, bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # running max
            pltpu.VMEM((bq, LANES), jnp.float32),  # running denominator
            pltpu.VMEM((bq, per * dv), jnp.float32),     # un-normalized output
        ],
        compiler_params=_compiler_params(
            interpret, ("parallel", "parallel", "parallel"),
            vmem_bytes(tq, tk, d, q.dtype, bq, bk, tables is not None),
        ),
        interpret=interpret,
        name=_kernel_name("fwd", window, block_diffusion),
    )(qp, kp, vp, *[t for t, _ in turned])
    return (out[:, :tq] if merged else out[:, :, :tq]), lse


# ---------------------------------------------------------------------------
# backward: one kernel per k-block, the head's q / dO / statistics resident
# ---------------------------------------------------------------------------


def _delta_kernel(do_ref, o_ref, delta_ref, *, dv):
    for h in range(do_ref.shape[-1] // dv):  # the block's heads side by side, each on its own lanes
        prod = do_ref[:, h * dv:(h + 1) * dv].astype(jnp.float32) * o_ref[:, h * dv:(h + 1) * dv].astype(jnp.float32)
        col = jnp.sum(prod, axis=1, keepdims=True)  # [bq, 1]: a row in HBM, as the forward's lse
        delta_ref[h] = jnp.transpose(jnp.broadcast_to(col, (col.shape[0], LANES)))[0:1, :]


@functools.partial(jax.jit, static_argnames=("h", "bq", "interpret"))
def _delta_merged(do: jax.Array, out: jax.Array, h: int, bq: int, interpret: bool) -> jax.Array:
    """delta_i = sum_d dO_i O_i of merged [B, T, H * Dv] arrays (T whole
    q-blocks) as the backward's rows [B, H, nq, 1, bq]: each head summed along
    its own lanes, where XLA's reduce would first re-tile both arrays by head."""
    b, t, hd = do.shape
    dv = hd // h
    per = _heads_a_block(h, dv)
    block = pl.BlockSpec((None, bq, per * dv), lambda i, r, j: (i, r, j))
    return pl.pallas_call(
        functools.partial(_delta_kernel, dv=dv),
        grid=(b, t // bq, h // per),
        in_specs=[block, block],
        out_specs=pl.BlockSpec((None, per, None, 1, bq), lambda i, r, j: (i, j, r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t // bq, 1, bq), jnp.float32),
        compiler_params=_compiler_params(
            interpret, ("parallel", "parallel", "parallel"), 2 * 2 * bq * per * dv * 4),
        interpret=interpret,
        name="dvc_attn_delta",
    )(do, out)


def _bwd_slabs(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dq_scr, *,
               scale, key0, block_k, rows, window):
    """A key block under a window one block wide (``strip_form`` "slab"), the
    mirror of ``_fwd_slabs``: a strip of WINDOW_STRIP keys is seen by the window
    + strip query rows that start with its own first, all of them in the
    resident head, so its transposed scores, dp and ds are each ONE product over
    that slab and its dk and dv are whole after one visit (written, not
    accumulated). The statistics come as rows of a strip, ``[rows / strip, 1,
    strip]``: a slab's are so many of them side by side. In the last key block
    the slab ends with the last row and the positions do the rest."""
    strip, width = WINDOW_STRIP, window + WINDOW_STRIP
    rel = (jax.lax.broadcasted_iota(jnp.int32, (strip, width), 0)
           - jax.lax.broadcasted_iota(jnp.int32, (strip, width), 1))  # key less query: the scores are transposed
    for c in range(0, block_k, strip):
        kblk, vblk = k_ref[c:c + strip, :], v_ref[c:c + strip, :]
        at = jnp.minimum((key0 + c) // strip, (rows - width) // strip)  # the slab's first strip of rows
        start = pl.multiple_of(at * strip, strip)
        qslab, doslab = q_ref[pl.ds(start, width), :], do_ref[pl.ds(start, width), :]
        stat = lambda ref: jnp.concatenate([ref[at + j] for j in range(width // strip)], axis=1)  # noqa: E731,B023
        s_t = _band(_dot(kblk, qslab, _NT) * scale, rel, start - key0 - c, window)
        p_t = jnp.exp(s_t - stat(lse_ref))  # f32 [strip, width]
        dv_ref[c:c + strip, :] = _dot(p_t.astype(doslab.dtype), doslab, _NN).astype(dv_ref.dtype)
        ds_t = (p_t * (_dot(vblk, doslab, _NT) - stat(delta_ref))).astype(qslab.dtype)
        dk_ref[c:c + strip, :] = (_dot(ds_t, qslab, _NN) * scale).astype(dk_ref.dtype)
        dq_scr[pl.ds(start, width), :] += _dot(ds_t, kblk, _TN)


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_scr, dk_scr, dv_scr,
    *, scale, causal, block_q, block_k, tk_valid, n_q, n_k, window=None, block_diffusion=None,
    head_dim=None, strips=None,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    _masked = functools.partial(
        _mask_scores, causal=causal, tk_valid=tk_valid, ragged=tk_valid % block_k != 0,
        window=window, block_diffusion=block_diffusion,
    )

    def head(take_kv, row=lambda ref, iq: ref[iq]):
        """One head's pass over the query blocks that see this key block: its
        dk and dv left in the scratch (unscaled), its share added to
        ``dq_scr``. ``take_kv()``: the key and value block, [bk, width] each;
        ``row(ref, iq)``: the head's row of a statistic for query block ``iq``."""
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        kblk, vblk = take_kv()  # [bk, D]

        def step(iq, masked: bool, keys=..., lo: int = 0, width: int = block_q):
            """Query block ``iq`` into dk, dv and dq: the whole key block against the
            whole tile, or a strip of its keys (``keys``, a static slice) against the
            ``width`` query rows from ``lo`` of the tile that see the strip."""
            whole = keys is ...
            first = (lambda: iq * block_q) if whole else (lambda: iq * block_q + lo)
            start = pl.multiple_of(first(), block_q if whole else WINDOW_STRIP)
            qblk = q_ref[pl.ds(start, width), :]
            doblk = do_ref[pl.ds(start, width), :]
            kstrip, vstrip = (kblk, vblk) if whole else (kblk[keys], vblk[keys])
            # Transposed scores [bk, bq]: the statistics are rows [1, bq] and
            # broadcast along sublanes; dv and dk need no transposed operand.
            s_t = _dot(kstrip, qblk, _NT) * scale
            if masked:
                kpos = (ik * block_k if whole else ik * block_k + keys.start) + jax.lax.broadcasted_iota(
                    jnp.int32, s_t.shape, 0)
                s_t = _masked(s_t, first(), kpos, 1)
            # a strip takes its columns of a row off the ref (a windowed block is one head)
            stat = (lambda ref: row(ref, iq)) if whole else (lambda ref: ref[iq, :, lo:lo + width])
            p_t = jnp.exp(s_t - stat(lse_ref))  # f32 [bk, bq]
            dv_scr[keys] += _dot(p_t.astype(doblk.dtype), doblk, _NN)
            dp_t = _dot(vstrip, doblk, _NT)
            ds_t = (p_t * (dp_t - stat(delta_ref))).astype(qblk.dtype)
            # dk and dq accumulate unscaled; the scale is applied once at the end.
            dk_scr[keys] += _dot(ds_t, qblk, _NN)
            dq_scr[pl.ds(start, width), :] += _dot(ds_t, kstrip, _TN)

        def edge(iq, far: bool):
            """A tile a window's edge crosses corner to corner, as strips of keys: a strip
            of the diagonal tile is seen by the tile's rows from its own on, one of the
            far tile by the rows up to its own."""
            for c in range(0, block_k, WINDOW_STRIP):
                step(iq, True, slice(c, c + WINDOW_STRIP), 0 if far else c, c + WINDOW_STRIP if far else block_q - c)

        # Padded q rows need no mask: their dO and delta are zero, so they add
        # nothing. Padded keys (a ragged last block) must not receive a share of
        # a row's probability, so every step of that block is masked.
        first, n_masked_end = 0, 0
        if causal:
            first = (ik * block_k) // block_q
            n_masked_end = jnp.minimum(n_q, ((ik + 1) * block_k + block_q - 2) // block_q)
        if tk_valid % block_k:
            n_masked_end = jnp.where(ik == n_k - 1, n_q, n_masked_end)
        if block_diffusion is not None:
            s1, e1, e2, s3, e3, e4 = _bd_bwd_bounds(ik, block_q, block_k, n_q, tk_valid // 2, block_diffusion)
            _loop(s1, e1, lambda iq: step(iq, True))
            _loop(e1, e2, lambda iq: step(iq, False))
            _loop(s3, e3, lambda iq: step(iq, True))
            _loop(e3, e4, lambda iq: step(iq, False))
        elif window is None:
            _loop(first, n_masked_end, lambda iq: step(iq, True))
            _loop(n_masked_end, n_q, lambda iq: step(iq, False))
        else:
            first, a, b, last = _win_bwd_bounds(ik, block_q, block_k, n_q, n_k, tk_valid, window)
            _loop(first, a, (lambda iq: edge(iq, False)) if strips else (lambda iq: step(iq, True)))
            _loop(a, b, lambda iq: step(iq, False))
            _loop(b, last, (lambda iq: edge(iq, True)) if strips else (lambda iq: step(iq, True)))

    if strips == "slab":
        _bwd_slabs(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dq_scr,
                   scale=scale, key0=ik * block_k, block_k=block_k, rows=n_q * block_q, window=window)
    elif head_dim is None:  # the block is one head
        head(lambda: (k_ref[...], v_ref[...]))
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)
    else:
        # The block is heads of ``head_dim`` lanes side by side, each run in turn with its own
        # rows of lse and delta; dq, dk and dv are written lane-dense. Nothing is sliced: the
        # other heads' lanes are zeroed in k and v, so the scores and dp are this head's and its
        # dq lands on its own lanes of the one accumulator; dk and dv are right on its lanes
        # (q and dO are read whole) and chosen by lane below.
        dks, dvs, lane = [], [], lanes_of(k_ref)
        for h in range(k_ref.shape[-1] // head_dim):
            head(lambda: (of_head(k_ref[...], lane, h, head_dim), of_head(v_ref[...], lane, h, head_dim)),
                 lambda ref, iq: ref[h, iq])
            dks.append(dk_scr[...])
            dvs.append(dv_scr[...])
        dk_ref[...] = (by_head(dks, lane, head_dim) * scale).astype(dk_ref.dtype)
        dv_ref[...] = by_head(dvs, lane, head_dim).astype(dv_ref.dtype)

    @pl.when(ik == n_k - 1)
    def finalize():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_backward(causal: bool, bq: int, bk: int, interpret: bool, residuals, g,
                    window: Optional[int] = None, heads: Optional[Tuple[int, int]] = None,
                    block_diffusion: Optional[int] = None):
    """(dq, dk, dv) in the layout of q, k, v: by head, or merged with ``heads``
    = (H, Hkv). The q of ``residuals`` is the one the scores were taken of (a
    rotary call hands its q turned: this kernel takes no table)."""
    q, k, v, out, lse = residuals
    do = g
    merged, seq = heads is not None, 2 if heads is None else 1
    b, h, h_kv, tq, tk, d, dv = _dims(q, k, v, heads)
    group = h // h_kv
    per = heads_a_block(d, dv, h, h_kv) if merged else 1  # heads a block, as the forward
    scale = 1.0 / (d ** 0.5)

    qp, kp, vp, dop = _pad_seq(q, bq, seq), _pad_seq(k, bk, seq), _pad_seq(v, bk, seq), _pad_seq(do, bq, seq)
    tq_p, tk_p = qp.shape[seq], kp.shape[seq]
    n_q, n_k = tq_p // bq, tk_p // bk
    # delta_i = sum_d dO_i O_i — the softmax-jacobian diagonal term.
    if merged:
        delta = _delta_merged(dop, _pad_seq(out, bq, seq), h, bq, interpret)
    else:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, tq_p - tq))).reshape(b, h, n_q, 1, bq)

    def at_head(i, j, ik):
        return i, j, 0

    def at_block(i, j, ik):
        return i, j, ik

    def at_kv(i, j, ik):  # a group's query heads read one K/V head and each writes its own dk, dv
        return i, (j if group == 1 else j // group), ik

    head = _head_spec(merged, tq_p, per * d, at_head)
    strips = strip_form(tq, window, bq, bk)
    stat = (n_q, 1, bq)
    if strips == "slab":  # a slab's statistics are whole rows of a strip, side by side (``_bwd_slabs``)
        stat = (tq_p // WINDOW_STRIP, 1, WINDOW_STRIP)
        lse, delta = lse.reshape(b, h, *stat), delta.reshape(b, h, *stat)
    rows = pl.BlockSpec((None, None if per == 1 else per, *stat), lambda i, j, ik: (i, j, 0, 0, 0))
    kblock, kv_in = _head_spec(merged, bk, per * d, at_block), _head_spec(merged, bk, per * d, at_kv)
    vhead, vblock, v_in = head, kblock, kv_in
    if dv != d:  # do, v and dv in blocks of the value head's width
        vhead = _head_spec(merged, tq_p, dv, at_head)
        vblock, v_in = _head_spec(merged, bk, dv, at_block), _head_spec(merged, bk, dv, at_kv)
    by_member = merged and group > 1
    if by_member:
        # A group's dk and dv as [B, group, T, Hkv * D]: the group's sum is then over a
        # leading axis and leaves the merged layout (a sum over heads that lie side by
        # side on the lanes would have to re-tile the whole array first).
        def member(width):
            return pl.BlockSpec((None, None, bk, width), lambda i, j, ik: (i, j % group, ik, j // group))

        kblock, vblock = member(d), member(dv)
    dq, dk, dv_ = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk, tk_valid=tk, n_q=n_q, n_k=n_k, window=window,
            block_diffusion=block_diffusion, head_dim=None if per == 1 else d, strips=strips,
        ),
        grid=(b, h // per, n_k),
        in_specs=[head, kv_in, v_in, vhead, rows, rows],
        out_specs=[head, kblock, vblock],
        out_shape=[
            jax.ShapeDtypeStruct((b, tq_p, h * d) if merged else (b, h, tq_p, d), q.dtype),
            jax.ShapeDtypeStruct(
                (b, group, tk_p, h_kv * d) if by_member else (b, tk_p, h * d) if merged else (b, h, tk_p, d),
                k.dtype),
            jax.ShapeDtypeStruct(
                (b, group, tk_p, h_kv * dv) if by_member else (b, tk_p, h * dv) if merged else (b, h, tk_p, dv),
                v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq_p, per * d), jnp.float32),
            pltpu.VMEM((bk, per * d), jnp.float32),
            pltpu.VMEM((bk, per * dv), jnp.float32),
        ],
        compiler_params=_compiler_params(
            # dq accumulates across the k-blocks of a head: that dim is
            # sequential.
            interpret, ("parallel", "parallel", "arbitrary"),
            vmem_bytes(tq, tk, d, q.dtype, bq, bk),
        ),
        interpret=interpret,
        name=_kernel_name("bwd", window, block_diffusion),
    )(qp, kp, vp, dop, lse, delta)
    if merged:
        if by_member:
            dk, dv_ = (jnp.sum(a, axis=1, dtype=jnp.float32).astype(a.dtype) for a in (dk, dv_))
        return dq[:, :tq], dk[:, :tk], dv_[:, :tk]
    dk, dv_ = dk[:, :, :tk], dv_[:, :, :tk]
    if group > 1:
        dk, dv_ = (
            jnp.sum(a.reshape(b, h_kv, group, tk, a.shape[-1]), axis=2, dtype=jnp.float32).astype(a.dtype)
            for a in (dk, dv_)
        )
    return dq[:, :, :tq], dk, dv_


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _resolve(q, k, block_q, block_k, interpret, causal=True, window=None,
             heads: Optional[Tuple[int, int]] = None, turned: bool = False,
             block_diffusion: Optional[int] = None) -> Tuple[int, int, bool]:
    """The call's block sizes and mode: explicit blocks are clipped to the
    (tile-rounded) sequence, missing ones come from ``choose_blocks``."""
    _, h, h_kv, tq, tk, d, _ = _dims(q, k, k, heads)
    if h % h_kv:
        raise ValueError(f"{h_kv} key/value heads do not divide {h} query heads")
    if window is not None and (not causal or tq != tk or window < 1):
        raise ValueError("a window needs causal attention over a square sequence")
    if block_diffusion is not None and (
            causal or window is not None or tq != tk or block_diffusion < 1 or tq % (2 * block_diffusion)):
        raise ValueError("a block-diffusion mask is over [x_0 ; x_t], two halves of whole blocks, and is its own mask")
    if block_q is None or block_k is None:
        chosen = choose_blocks(tq, tk, d, q.dtype, window, turned, block_diffusion)
        if chosen is None:
            raise ValueError(
                f"flash attention keeps one head in VMEM; Tq={tq}, Tk={tk}, D={d} "
                f"needs more than {VMEM_BUDGET_BYTES} bytes"
            )
        block_q = chosen[0] if block_q is None else block_q
        block_k = chosen[1] if block_k is None else block_k
    bq = min(block_q, _round_up(tq, 8))
    bk = min(block_k, _round_up(tk, 8))
    return bq, bk, _interpret_default() if interpret is None else interpret


def kept_bytes(q: jax.Array, k: jax.Array, window: Optional[int] = None,
               v: Optional[jax.Array] = None, heads: Optional[Tuple[int, int]] = None,
               block_diffusion: Optional[int] = None) -> int:
    """Bytes of the two residuals named by ``KEPT_NAMES`` for one call at
    ``choose_blocks``' blocks, as the chip lays them out: the output with its
    head dim padded to whole lanes where it is by head (a D=64 output takes a
    D=128 one's room: a kept stack ``bf16[L,B,H,T,64]`` is tiled (8, 128)) and
    lane-dense where it is merged (``bf16[L,B,T,H*D]``: two heads of 64 fill a
    tile), and the f32 log-sum-exp rows of whole q-blocks."""
    bq, _, _ = _resolve(q, k, None, None, False, block_diffusion is None, window, heads,
                        block_diffusion=block_diffusion)
    b, h, _, tq, _, d, dv = _dims(q, k, k if v is None else v, heads)
    if v is not None:  # the output is as wide as the value head
        d = dv
    if heads is None:  # by head a row of the output is whole lanes, whatever the head
        d = _round_up(d, LANES)
    return b * h * (tq * d * jnp.dtype(q.dtype).itemsize + 4 * _round_up(tq, bq))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, Hkv, Tk, D], Hkv dividing H
    v: jax.Array,  # [B, Hkv, Tk, Dv]: the value head may be another width than the key head; o is as wide
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    block_diffusion: Optional[int] = None,
) -> jax.Array:
    """Drop-in for ops.attention.attention_core (no additive mask support).

    Block sizes default to ``choose_blocks`` of the shape. Causal masking is
    top-left aligned: row i attends keys 0..i. For Tq != Tk this differs
    from attention_core's bottom-right alignment — the router in
    ops/attention.py only sends square causal shapes here. Query head ``h``
    reads key/value head ``h // (H / Hkv)``. With ``window`` (causal, square)
    row i attends keys ``i - window < j <= i``. With ``block_diffusion`` (not
    causal, square, rows ``[x_0 ; x_t]``) row i attends what ``bd_keep`` keeps.
    """
    bq, bk, interp = _resolve(q, k, block_q, block_k, interpret, causal, window, block_diffusion=block_diffusion)
    return _flash_forward(q, k, v, causal, bq, bk, interp, window, block_diffusion=block_diffusion)[0]


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret, window, block_diffusion):
    bq, bk, interp = _resolve(q, k, block_q, block_k, interpret, causal, window, block_diffusion=block_diffusion)
    out, lse = _flash_forward(q, k, v, causal, bq, bk, interp, window, block_diffusion=block_diffusion)
    # Named so that a checkpoint's policy can keep them (KEPT_NAMES): with the
    # kernel's two results saved, a rematerialised layer's recomputed forward
    # has no use for the kernel and its call there is dead code.
    out, lse = checkpoint_name(out, KEPT_NAMES[0]), checkpoint_name(lse, KEPT_NAMES[1])
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, window, block_diffusion, residuals, g):
    q, k = residuals[0], residuals[1]
    bq, bk, interp = _resolve(q, k, block_q, block_k, interpret, causal, window, block_diffusion=block_diffusion)
    return _flash_backward(causal, bq, bk, interp, residuals, g, window, block_diffusion=block_diffusion)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def flash_attention_merged(
    q: jax.Array,  # [B, T, H * D] as the projection made it (``heads_a_block`` > 0); turned here if ``cos`` is given
    k: jax.Array,  # [B, T, Hkv * D], Hkv dividing H; already turned (``rotary_merged``)
    v: jax.Array,  # [B, T, Hkv * Dv]: D and Dv whole lanes, or D = Dv a whole part of a tile and Hkv = H
    cos: Optional[jax.Array],  # ``rotary_tables`` [T, D] float32, or None: q is scored as it is
    sin: Optional[jax.Array],
    heads: Tuple[int, int],  # (H, Hkv)
    causal: bool = False,
    window: Optional[int] = None,
    rotary_dim: Optional[int] = None,
    interpret: Optional[bool] = None,
    block_diffusion: Optional[int] = None,
) -> jax.Array:
    """``flash_attention`` on the projections' own layout, giving [B, T, H * Dv]
    (what the output projection reads): the same two kernels, a head being
    block ``h`` of the last axis (or two heads of 64 a block: no rotary there).
    The forward turns each q block on the tile it
    has taken up; the backward, whose kernel holds a head's q resident and
    visits it from every key block, is handed q turned by one pass outside
    (``_turn_merged``) and its dq takes one pass back."""
    return _fam_forward(q, k, v, cos, sin, heads, causal, window, rotary_dim, _interpreted(interpret),
                        block_diffusion)[0]


def _interpreted(interpret: Optional[bool]) -> bool:
    return _interpret_default() if interpret is None else interpret


# Both halves are jitted: a model's layers of one kind (Laguna's three sliding
# layers, its two full ones) call them with equal shapes, and each is traced
# and lowered once a step program instead of once a layer.
@functools.partial(
    jax.jit, static_argnames=("heads", "causal", "window", "rotary_dim", "interpret", "block_diffusion"))
def _fam_forward(q, k, v, cos, sin, heads, causal, window, rotary_dim, interpret, block_diffusion=None):
    bq, bk, _ = _resolve(q, k, None, None, interpret, causal, window, heads, cos is not None, block_diffusion)
    tables = None if cos is None else (cos, sin)
    return _flash_forward(q, k, v, causal, bq, bk, interpret, window, heads, tables, rotary_dim, block_diffusion)


@functools.partial(
    jax.jit, static_argnames=("heads", "causal", "window", "rotary_dim", "interpret", "block_diffusion"))
def _fam_backward(q, k, v, out, lse, cos, sin, g, heads, causal, window, rotary_dim, interpret,
                  block_diffusion=None):
    bq, bk, _ = _resolve(q, k, None, None, interpret, causal, window, heads, cos is not None, block_diffusion)
    if cos is not None:
        q = _turn_pass(q, cos, sin, rotary_dim, False, interpret)
    dq, dk, dv = _flash_backward(
        causal, bq, bk, interpret, (q, k, v, out, lse), g, window, heads, block_diffusion)
    if cos is not None:
        dq = _turn_pass(dq, cos, sin, rotary_dim, True, interpret)
    return dq, dk, dv


def _fam_fwd(q, k, v, cos, sin, heads, causal, window, rotary_dim, interpret, block_diffusion):
    out, lse = _fam_forward(q, k, v, cos, sin, heads, causal, window, rotary_dim, _interpreted(interpret),
                            block_diffusion)
    out, lse = checkpoint_name(out, KEPT_NAMES[0]), checkpoint_name(lse, KEPT_NAMES[1])  # as ``_fa_fwd``
    return out, (q, k, v, out, lse, cos, sin)


def _fam_bwd(heads, causal, window, rotary_dim, interpret, block_diffusion, residuals, g):
    cos, sin = residuals[5:]
    grads = _fam_backward(*residuals, g, heads, causal, window, rotary_dim, _interpreted(interpret),
                          block_diffusion)
    return (*grads, None, None) if cos is None else (*grads, jnp.zeros_like(cos), jnp.zeros_like(sin))


flash_attention_merged.defvjp(_fam_fwd, _fam_bwd)
