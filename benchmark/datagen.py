"""Seeded inputs: the token file a volunteer trains on, and the stub peer's tree.

The token stream is a NumPy copy of the program's successor-map task
(``training/data.py:53-73``): each token has four likely successors given by
fixed affine maps (90% one of them, 10% uniform), so next-token prediction is
learnable at any vocabulary size. The copy lives here so that a PR which
changes the program's generator cannot change what the benchmark feeds it.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Tuple

import numpy as np

# next = (tok * mult + off) % vocab — the program's constants, copied.
SUCC_MULT = (3, 5, 7, 11)
SUCC_OFF = (13, 101, 997, 4099)
LIKELY_P = 0.9


def token_rows(seed: int, rows: int, length: int, vocab: int) -> np.ndarray:
    """``[rows, length]`` int32 tokens of the successor-map stream."""
    rng = np.random.default_rng([int(seed), 0x70CE])
    mult = np.asarray(SUCC_MULT, np.int64)
    off = np.asarray([o % vocab for o in SUCC_OFF], np.int64)
    out = np.empty((rows, length), np.int64)
    tok = rng.integers(0, vocab, rows)
    out[:, 0] = tok
    for t in range(1, length):
        c = rng.integers(0, len(SUCC_MULT), rows)
        likely = (tok * mult[c] + off[c]) % vocab
        uniform = rng.integers(0, vocab, rows)
        tok = np.where(rng.random(rows) < LIKELY_P, likely, uniform)
        out[:, t] = tok
    return out.astype(np.int32)


def lm_arrays(seed: int, rows: int, seq_len: int, vocab: int) -> Dict[str, np.ndarray]:
    """``tokens`` (the first ``seq_len`` of each row) and ``targets`` (the
    last ``seq_len``): the ``.npz`` schema ``npz_batch_iter`` expects."""
    toks = token_rows(seed, rows, seq_len + 1, vocab)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def write_token_file(path: str, seed: int, rows: int, seq_len: int, vocab: int) -> str:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.npz"
    np.savez(tmp, **lm_arrays(seed, rows, seq_len, vocab))
    os.replace(tmp, path)
    return path


def seeded_leaf(shape: Tuple[int, ...], seed: int, index: int, scale: float) -> np.ndarray:
    """Leaf ``index`` of a seeded tree: float32 normals, ``scale`` wide, from
    a stream of its own, so one leaf can be drawn without the others."""
    rng = np.random.default_rng([int(seed), 0x57B, int(index)])
    return rng.standard_normal(shape, dtype=np.float32) * np.float32(scale)


def seeded_tree(shape_tree: Any, seed: int, scale: float) -> Any:
    """A host tree shaped like ``shape_tree`` (leaves with ``.shape``), leaves
    in ``jax.tree_util`` order drawn by ``seeded_leaves``."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(shape_tree)
    return jax.tree_util.tree_unflatten(
        treedef,
        [seeded_leaf(tuple(x.shape), seed, i, scale) for i, x in enumerate(leaves)],
    )


def peer_seed(run_seed: int, peer_index: int) -> int:
    """The seed of stub peer ``peer_index``'s first contribution."""
    return int(run_seed) * 1009 + 17 + int(peer_index)
