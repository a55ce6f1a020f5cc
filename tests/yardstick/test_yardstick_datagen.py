"""Seeded inputs: the same seed gives the same bits, another seed other bits."""

import numpy as np
import pytest

from benchmark import datagen


def test_token_stream_is_bit_identical_per_seed_and_differs_across_seeds():
    a = datagen.lm_arrays(7, 32, 64, 50257)
    b = datagen.lm_arrays(7, 32, 64, 50257)
    c = datagen.lm_arrays(8, 32, 64, 50257)
    for key in ("tokens", "targets"):
        assert a[key].dtype == np.int32 and a[key].shape == (32, 64)
        assert np.array_equal(a[key], b[key])
        assert not np.array_equal(a[key], c[key])
    assert np.array_equal(a["tokens"][:, 1:], a["targets"][:, :-1])
    assert a["tokens"].min() >= 0 and a["targets"].max() < 50257


@pytest.mark.parametrize("vocab", [512, 50257])
def test_token_stream_follows_the_successor_maps(vocab):
    """About 90% of the next tokens are one of the four affine successors."""
    rows = datagen.token_rows(3, 64, 65, vocab).astype(np.int64)
    cur, nxt = rows[:, :-1], rows[:, 1:]
    likely = np.zeros(cur.shape, bool)
    for mult, off in zip(datagen.SUCC_MULT, datagen.SUCC_OFF):
        likely |= nxt == (cur * mult + off) % vocab
    assert 0.86 < likely.mean() < 0.94


def test_token_file_has_the_schema_the_volunteer_reads(tmp_path):
    path = datagen.write_token_file(str(tmp_path / "d" / "tokens.npz"), 5, 16, 32, 512)
    with np.load(path) as data:
        assert set(data) == {"tokens", "targets"}
        assert data["tokens"].shape == data["targets"].shape == (16, 32)


def test_stub_peer_tree_is_seeded_leaf_by_leaf():
    shapes = {"b": np.empty((5,)), "a": {"w": np.empty((4, 3)), "v": np.empty((2,))}}
    t1 = datagen.seeded_tree(shapes, 11, 0.02)
    t2 = datagen.seeded_tree(shapes, 11, 0.02)
    t3 = datagen.seeded_tree(shapes, 12, 0.02)
    assert t1["a"]["w"].dtype == np.float32 and t1["a"]["w"].shape == (4, 3)
    for x, y, z in zip(*(_leaves(t) for t in (t1, t2, t3))):
        assert np.array_equal(x, y) and not np.array_equal(x, z)
    # leaf i can be drawn alone (jax's order: a.v, a.w, b), which is how the
    # runner checks a round without holding the peer's whole tree
    assert np.array_equal(t1["a"]["w"], datagen.seeded_leaf((4, 3), 11, 1, 0.02))
    assert np.array_equal(t1["b"], datagen.seeded_leaf((5,), 11, 2, 0.02))
    assert datagen.peer_seed(1, 0) != datagen.peer_seed(2, 0) != datagen.peer_seed(2, 1)


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)
