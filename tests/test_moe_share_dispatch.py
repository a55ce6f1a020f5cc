"""The share path of ops/moe_dispatch.py as a dataflow (PR 36), at a tiny size
on the CPU and whatever model calls it: how many grouped matmuls a chunk is
(two forward: gate-up and down; five backward), that the router's weight meets
``hidden`` [rows, f] and never a [rows, d] buffer, and every gradient,
the weight's own among them, against a plain per-token float32 sum over the
held experts. tests/test_laguna.py and tests/test_smallthinker.py hold the
path to each model's dense form; this file holds it to its shape."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.interpreters import partial_eval as pe

from distributedvolunteercomputing_tpu.ops import moe_dispatch

S, D, F, E, K, OFFSET, HELD = 48, 16, 8, 16, 4, 4, 4
ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
# the chunk's rows over the even share (48): chunks of 24 rows, of which the
# routes below need three, and one of 144
SLACKS = {"several": 0.5, "one": 3.0}


def inputs(f=F):
    """Tokens, routes and held stacks: token 0 chooses no held expert, token 1
    all four of them (a run of k rows), the others as the draw has it (52 held
    assignments of 192)."""
    ks = jax.random.split(jax.random.PRNGKey(36), 6)
    x = jax.random.normal(ks[0], (S, D))
    stacks = [jax.random.normal(kk, shape) * 0.3
              for kk, shape in zip(ks[1:4], ((HELD, D, f), (HELD, D, f), (HELD, f, D)))]
    idx = jnp.argsort(jax.random.uniform(ks[4], (S, E)), axis=1)[:, :K].astype(jnp.int32)
    idx = idx.at[0].set(jnp.asarray([0, 1, 9, 13])).at[1].set(jnp.asarray([7, 4, 6, 5]))
    gates = jax.nn.softmax(jax.random.normal(ks[5], (S, K)), axis=1)
    return x, idx, gates, stacks


def per_token_sum(x, idx, gates, w_gate, w_up, w_down, act):
    """``y[s] = sum_i gates[s, i] * down_e(act(gate_e x[s]) * up_e x[s])`` over
    the choices whose expert ``e = idx[s, i]`` is held: every (token, choice)
    pair with its own expert's matrices, nothing sorted, nothing grouped."""
    local = idx - OFFSET
    held = (local >= 0) & (local < HELD)
    e = jnp.clip(local, 0, HELD - 1)
    with jax.default_matmul_precision("highest"):
        hidden = ACTS[act](jnp.einsum("sd,skdf->skf", x, w_gate[e])) * jnp.einsum("sd,skdf->skf", x, w_up[e])
        out = jnp.einsum("skf,skfd->skd", hidden, w_down[e])
    return jnp.einsum("skd,sk->sd", out, jnp.where(held, gates, 0.0))


def live_equations(jaxpr, path=()):
    """(enclosing primitives, equation) for every equation of ``jaxpr`` that
    something reads, the bodies of its loops and calls cleaned likewise: what
    a compiler keeps of it."""
    jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    for eqn in jaxpr.eqns:
        yield path, eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from live_equations(sub, path + (eqn.primitive.name,))


@pytest.mark.parametrize("chunks", list(SLACKS))
@pytest.mark.parametrize("act", list(ACTS))
def test_a_chunk_is_two_grouped_products_forward_and_five_backward(act, chunks):
    """In the gradient's jaxpr with dead code removed: the forward chunk loop
    (inside the ``custom_vjp`` call) holds the gate-up and the down product;
    the backward loop gate-up again, the cotangent of ``hidden``, and the
    three by the weights and the rows: the down product forward is dead there,
    since the router's weight is applied in front of it and nothing reads its
    result. Twelve before PR 36 (3 + 9). No multiply in either loop has a
    [rows, d] result: the gathered gates scale ``hidden`` [rows, f]."""
    x, idx, gates, stacks = inputs()
    slack = SLACKS[chunks]
    rows = moe_dispatch.share_rows_bound(S, K, HELD, E, slack)

    def loss(x, gates, *stacks):
        return jnp.sum(jnp.sin(moe_dispatch.share_glu_experts(
            x, idx, gates, *stacks, OFFSET, E, act=act, slack=slack)[0]))

    moved = moe_dispatch.share_glu_experts(x, idx, gates, *stacks, OFFSET, E, act=act, slack=slack)[3]
    assert int(moved) // rows == {"several": 3, "one": 1}[chunks]
    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(x, gates, *stacks)
    in_loops = [(path, eqn) for path, eqn in live_equations(closed.jaxpr) if "while" in path]
    products = collections.Counter(
        "forward" if "custom_vjp_call" in path[:path.index("while")] else "backward"
        for path, eqn in in_loops if eqn.primitive.name.startswith("ragged_dot"))
    assert products == {"forward": 2, "backward": 5}, products
    multiplies = [eqn for _, eqn in in_loops if eqn.primitive.name == "mul"]
    assert any(eqn.outvars[0].aval.shape == (rows, F) for eqn in multiplies)
    assert not any(eqn.outvars[0].aval.shape == (rows, D) for eqn in multiplies)
    # and nothing copies a [rows, d] buffer to pad it: the only concatenate in a loop is of row indices
    assert all(eqn.outvars[0].aval.ndim == 1 for _, eqn in in_loops if eqn.primitive.name == "concatenate")


@pytest.mark.parametrize("chunks", list(SLACKS))
@pytest.mark.parametrize("act", list(ACTS))
def test_every_gradient_is_the_per_token_sums(act, chunks):
    """The gradient with respect to the tokens, the router's weights and the
    three stacks against ``per_token_sum``'s, in float32. The weight's
    cotangent, computed from ``hidden`` and its cotangent since PR 36, on its
    own: for a held choice the probe's product with that expert's output for
    the token, for any other choice exactly zero."""
    x, idx, gates, stacks = inputs()
    probe = jax.random.normal(jax.random.PRNGKey(7), (S, D))

    def share(x, gates, *stacks):
        return moe_dispatch.share_glu_experts(
            x, idx, gates, *stacks, OFFSET, E, act=act, slack=SLACKS[chunks])[0]

    want_y = per_token_sum(x, idx, gates, *stacks, act)
    np.testing.assert_allclose(np.asarray(share(x, gates, *stacks)), np.asarray(want_y), rtol=2e-5, atol=2e-5)
    assert not np.asarray(want_y[0]).any() and np.asarray(want_y[1]).any()
    got = jax.jit(jax.grad(lambda *a: jnp.sum(share(*a) * probe), argnums=(0, 1, 2, 3, 4)))(x, gates, *stacks)
    want = jax.jit(jax.grad(lambda x, gates, *w: jnp.sum(per_token_sum(x, idx, gates, *w, act) * probe),
                            argnums=(0, 1, 2, 3, 4)))(x, gates, *stacks)
    for name, a, b in zip(("x", "top_gates", "w_gate", "w_up", "w_down"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5, err_msg=name)
    held = np.asarray((idx >= OFFSET) & (idx < OFFSET + HELD))
    assert not held[0].any() and held[1].all()
    d_gates = np.asarray(got[1])
    assert not d_gates[~held].any() and np.all(d_gates[held] != 0)
    # each choice's expert output alone, at weight 1: the weight's cotangent is its product with the probe
    alone = jnp.stack([jnp.sum(per_token_sum(x, idx, jnp.tile(jnp.eye(K)[i], (S, 1)), *stacks, act) * probe, axis=1)
                       for i in range(K)], axis=1)
    np.testing.assert_allclose(d_gates, np.asarray(alone), rtol=2e-4, atol=2e-5)
    assert not np.asarray(got[0][0]).any()  # a token with no held row takes no gradient from this share


# -- the grouped products end at the held rows (PR 46) ----------------------------------


def dense_groups(lhs, rhs, sizes):
    """Group by group, ``lhs[start:end] @ rhs[e]`` in float32; the rows past
    the sizes' sum are left zero."""
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    start = 0
    for e, n in enumerate(np.asarray(sizes)):
        out[start:start + n] = np.asarray(lhs[start:start + n], np.float32) @ np.asarray(rhs[e], np.float32)
        start += n
    return out


@pytest.mark.parametrize("sizes", [(300, 0, 200, 100), (0, 0, 0, 0), (512, 512, 500, 12)],
                         ids=["ends_inside_the_second_tile", "holds_nothing", "sums_to_every_row"])
@pytest.mark.parametrize("impl", ["ragged_dot", "megablox"])
def test_rows_past_the_sizes_sum_are_neither_read_nor_summed(impl, sizes, monkeypatch):
    """``grouped_matmul`` with sizes that sum to less than M, the rows of
    ``lhs`` past the sum (and of the result's cotangent) set to NaN and Inf:
    the rows inside the groups, the rows' cotangent inside the groups and the
    whole stack's cotangent equal the dense product group by group, on
    ``ragged_dot`` and on megablox (interpreted here: three row tiles of 512,
    of which the sizes end inside the second, visit none, or fill all)."""
    m, k, n = 1536, 128, 128
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda *a: impl)
    ks = jax.random.split(jax.random.PRNGKey(46), 3)
    sizes = jnp.asarray(sizes, jnp.int32)
    total = int(sizes.sum())
    inside = (jnp.arange(m) < total)[:, None]
    poison = jnp.where(jnp.arange(m)[:, None] % 2 == 0, jnp.nan, jnp.inf)
    lhs = jax.random.normal(ks[0], (m, k))
    rhs = jax.random.normal(ks[1], (len(sizes), k, n)) * 0.1
    cot = jax.random.normal(ks[2], (m, n))
    out, pull = jax.vjp(lambda a, b: moe_dispatch.grouped_matmul(a, b, sizes),
                        jnp.where(inside, lhs, poison), rhs)
    d_lhs, d_rhs = pull(jnp.where(inside, cot, poison))
    np.testing.assert_allclose(np.asarray(out)[:total], dense_groups(lhs, rhs, sizes)[:total], rtol=1e-4, atol=1e-4)
    want_lhs = dense_groups(cot, jnp.swapaxes(rhs, 1, 2), sizes)
    np.testing.assert_allclose(np.asarray(d_lhs)[:total], want_lhs[:total], rtol=1e-4, atol=1e-4)
    ends = np.concatenate([[0], np.cumsum(np.asarray(sizes))])
    want_rhs = np.stack([np.asarray(lhs[a:b], np.float32).T @ np.asarray(cot[a:b], np.float32)
                         for a, b in zip(ends[:-1], ends[1:])])
    np.testing.assert_allclose(np.asarray(d_rhs), want_rhs, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("lo,want", [(0, (5, 0, 3, 0)), (8, (0, 0, 6, 2)), (16, (0, 0, 0, 1)), (24, (0, 0, 0, 0))],
                         ids=["first_chunk", "a_group_over_both_edges", "last_chunk", "past_the_held_rows"])
def test_the_sizes_handed_are_the_held_rows_of_the_window(lo, want):
    """``_handed_sizes`` over sorted assignments ``lo .. lo + 8`` of held
    groups of 5, 0, 9 and 3: each held expert's rows inside the window (the
    third group lies over the first chunk's end and the second's), summing to
    the held rows there (8, 8, 1, 0), never topped up to the window's 8."""
    group_sizes = jnp.asarray([5, 0, 9, 3], jnp.int32)
    got = np.asarray(moe_dispatch._handed_sizes(group_sizes, jnp.int32(lo), 8))
    keys = np.repeat(np.arange(5), [5, 0, 9, 3, 15])  # the sorted keys, 4 for those not held
    assert tuple(got) == want == tuple(np.bincount(keys[lo:lo + 8], minlength=5)[:4])
    assert got.sum() == max(0, min(17 - lo, 8))


def grouped_matmul_that_leaves_rows_unwritten(lhs, rhs, sizes):
    """Megablox as this dispatch must take it, on ``ragged_dot``: it reads no
    row outside the groups, forward or backward, and every row past the
    sizes' sum of what it returns (the result, and the rows' cotangent) holds
    NaN, as memory nobody wrote may."""

    def inside(a):
        return (jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None]

    def product(a, b):
        return jax.lax.ragged_dot(jnp.where(inside(a), a, 0.0), b, sizes)

    @jax.custom_vjp
    def unwritten(a, b):
        return jnp.where(inside(a), product(a, b), jnp.nan)

    def backward(res, cot):
        d_a, d_b = jax.vjp(product, *res)[1](jnp.where(inside(cot), cot, 0.0))
        return jnp.where(inside(d_a), d_a, jnp.nan), d_b

    unwritten.defvjp(lambda a, b: (unwritten(a, b), (a, b)), backward)
    return unwritten(lhs, rhs)


def routes_of(fill):
    """(routes [S, K], slack) for a chunk filled as ``fill`` says."""
    _, idx, _, _ = inputs()
    if fill == "nothing_held":       # every choice on an expert this share does not hold
        return jnp.where((idx >= OFFSET) & (idx < OFFSET + HELD), idx + HELD, idx) % E, 3.0
    if fill == "to_the_last_row":    # one held choice a token: 48 held rows in one chunk of 48
        others = jnp.asarray([0, 1, 9], jnp.int32)
        return jnp.concatenate([OFFSET + jnp.arange(S, dtype=jnp.int32)[:, None] % HELD,
                                jnp.tile(others, (S, 1))], axis=1), 1.0
    return idx, SLACKS[fill]


FILLS = ["one", "several", "nothing_held", "to_the_last_row"]


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("act", list(ACTS))
def test_rows_the_products_leave_unwritten_reach_no_result(act, fill, monkeypatch):
    """``share_glu_experts`` over grouped products that write NaN into every
    row past the sizes' sum: ``y`` and the gradients of the tokens, the
    router's weights and the three stacks are finite and the per-token sums',
    with one chunk a third full, three chunks of which the last holds 4 rows
    of 24, a share that is sent nothing, and a chunk filled to its last row."""
    x, _, gates, stacks = inputs()
    idx, slack = routes_of(fill)
    held = np.asarray((idx >= OFFSET) & (idx < OFFSET + HELD))
    rows = moe_dispatch.share_rows_bound(S, K, HELD, E, slack)
    assert held.sum() == {"one": 52, "several": 52, "nothing_held": 0, "to_the_last_row": rows}[fill]
    monkeypatch.setattr(moe_dispatch, "grouped_matmul", grouped_matmul_that_leaves_rows_unwritten)
    probe = jax.random.normal(jax.random.PRNGKey(7), (S, D))

    def share(x, gates, *stacks):
        return moe_dispatch.share_glu_experts(x, idx, gates, *stacks, OFFSET, E, act=act, slack=slack)

    y, _, dropped, moved, zeros = share(x, gates, *stacks)
    assert int(dropped) == 0 and int(moved) == rows * max(1, -(-int(held.sum()) // rows))
    np.testing.assert_allclose(np.asarray(y), np.asarray(per_token_sum(x, idx, gates, *stacks, act)),
                               rtol=2e-5, atol=2e-5)
    if act == "relu":  # counted over the held rows only, whatever the others hold
        gate = jnp.einsum("sd,skdf->skf", x, stacks[0][jnp.clip(idx - OFFSET, 0, HELD - 1)])
        assert int(zeros) == int(jnp.sum((gate <= 0) & held[:, :, None]))
    got = jax.jit(jax.grad(lambda *a: jnp.sum(share(*a)[0] * probe), argnums=(0, 1, 2, 3, 4)))(x, gates, *stacks)
    want = jax.jit(jax.grad(lambda x, gates, *w: jnp.sum(per_token_sum(x, idx, gates, *w, act) * probe),
                            argnums=(0, 1, 2, 3, 4)))(x, gates, *stacks)
    for name, a, b in zip(("x", "top_gates", "w_gate", "w_up", "w_down"), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("fill", FILLS)
def test_an_assignment_that_is_not_this_shares_takes_a_weight_gradient_of_exactly_zero(fill, monkeypatch):
    """The router's weight of a choice on an expert held elsewhere: its row is
    past the sizes' sum, so ``sum(hidden * d hidden)`` over it is NaN here;
    the cotangent that leaves the dispatch for it is 0.0 to the bit (a select
    on ``valid``, not a product with it), and non-zero for every held one."""
    x, _, gates, stacks = inputs()
    idx, slack = routes_of(fill)
    held = np.asarray((idx >= OFFSET) & (idx < OFFSET + HELD))
    monkeypatch.setattr(moe_dispatch, "grouped_matmul", grouped_matmul_that_leaves_rows_unwritten)
    d_gates = np.asarray(jax.grad(lambda g: jnp.sum(jnp.sin(moe_dispatch.share_glu_experts(
        x, idx, g, *stacks, OFFSET, E, slack=slack)[0])))(gates))
    assert np.array_equal(d_gates[~held], np.zeros_like(d_gates[~held]))
    assert np.all(np.isfinite(d_gates)) and np.all(d_gates[held] != 0)


# -- a token's run summed by one block-local 0/1 product (PR 38) ------------------------

# rows R, k, and the runs the case is about as (first position in token order,
# rows); the positions before each are filled with other tokens' runs of 0 .. k
# rows, and at least one row at the end is not held
RUNS = {
    "one_tile_of_8": (8, 4, [(1, 4)]),
    "ends_on_the_second_tiles_first_row": (16, 4, [(1, 4), (5, 4)]),         # k - 1 = 3 rows behind the edge
    "one_row_behind_the_edge": (16, 4, [(7, 4), (11, 2)]),
    "k_of_8_over_the_edge": (16, 8, [(1, 8)]),                               # 7 rows behind, ends on row 8
    "three_tiles_of_8": (24, 4, [(6, 3), (9, 4), (13, 4)]),                  # both edges crossed
    "a_run_ahead_of_each_edge": (24, 3, [(5, 3), (8, 1), (14, 2), (16, 3)]),  # runs that end and begin at an edge
    "one_tile_of_128": (128, 6, [(0, 6), (120, 6)]),
    "two_tiles_of_128": (256, 6, [(100, 6), (123, 6), (245, 5)]),            # 123 .. 128: k - 1 behind, ends on row 128
    "two_tiles_of_128_k_of_8": (256, 8, [(121, 8)]),
    # Qwen3-Next's ten a token: nine rows behind the edge, more than one sublane tile of carry (PR 67)
    "two_tiles_of_128_k_of_10": (256, 10, [(119, 10), (200, 10)]),
    "three_tiles_of_128": (384, 6, [(127, 2), (252, 6), (300, 1)]),
}


def chunk_of(case, seed=38):
    """(tok [R], valid [R], n_tokens, k) for ``RUNS[case]``: held
    rows of tokens in the counts the case asks for, every third token with no
    row at all, in a random order among rows that are not held, whose token
    numbers are drawn from the same range (a sum that took them would show)."""
    r, k, forced = RUNS[case]
    rng = np.random.default_rng(seed)
    counts, pos, fill = [], 0, 0
    for start, length in forced:
        while pos < start:
            n = min((k, 0, 1, 2, 0, k - 1)[fill % 6], start - pos)
            counts.append(n)
            pos, fill = pos + n, fill + 1
        counts.append(length)
        pos += length
    counts += [0, 1, 0]
    assert sum(counts) < r and max(counts) <= k
    held = np.repeat(np.arange(len(counts)), counts)
    tok = np.concatenate([held, rng.integers(0, len(counts), size=r - len(held))])
    valid = np.arange(r) < len(held)
    order = rng.permutation(r)
    return jnp.asarray(tok[order], jnp.int32), jnp.asarray(valid[order]), len(counts), k


def plain_sum(rows, tok, valid, n_tokens):
    """Every token's float32 sum over its held rows, row by row."""
    out = np.zeros((n_tokens, rows.shape[1]), np.float32)
    for row, t, v in zip(np.asarray(rows, np.float32), np.asarray(tok), np.asarray(valid)):
        if v:
            out[t] += row
    return out


@pytest.mark.parametrize("case", list(RUNS))
def test_a_tokens_run_is_summed_whole_across_tile_edges(case):
    """``_combine`` against the plain sum: a run that ends on a tile's first
    row with k - 1 rows behind the edge, one with a single row behind it, runs
    of exactly k, tokens with no run, rows that are not held between the held
    ones, with the product's tile equal to the test's sublane tile (8: one,
    two and three tiles) and to the chip's (128). The case says where its runs
    lie; that they do is checked on the sorted order itself."""
    tok, valid, n_tokens, k = chunk_of(case)
    r, _, forced = RUNS[case]
    where = (tok, valid, *moe_dispatch._token_runs(tok, valid, n_tokens))
    tok_sorted = np.asarray(where[3])
    for start, length in forced:
        assert len(set(tok_sorted[start:start + length])) == 1 and tok_sorted[start] < n_tokens
        assert tok_sorted[start - 1] != tok_sorted[start] != tok_sorted[start + length] or start == 0
    rows = jax.random.normal(jax.random.PRNGKey(38), (r, D))
    got = moe_dispatch._combine(rows, where, k)
    want = plain_sum(rows, tok, valid, n_tokens)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    empty = np.bincount(np.asarray(tok)[np.asarray(valid)], minlength=n_tokens) == 0
    assert empty.any() and not np.asarray(got)[empty].any()
    # in the compute dtype the run is accumulated in float32 and rounded once
    low = moe_dispatch._combine(rows.astype(jnp.bfloat16), where, k)
    want_low = plain_sum(rows.astype(jnp.bfloat16), tok, valid, n_tokens)
    np.testing.assert_allclose(np.asarray(low, np.float32), want_low, rtol=2 ** -8, atol=1e-6)


def test_a_run_longer_than_the_carry_is_refused():
    tok, valid, n_tokens, _ = chunk_of("one_tile_of_8")
    where = (tok, valid, *moe_dispatch._token_runs(tok, valid, n_tokens))
    with pytest.raises(ValueError, match="carried"):
        moe_dispatch._combine(jnp.zeros((8, D)), where, 10)


@pytest.mark.parametrize("case", ["ends_on_the_second_tiles_first_row", "three_tiles_of_8", "two_tiles_of_128"])
def test_spread_and_combine_are_each_others_transpose_without_a_scatter_add(case):
    """``jax.vjp`` of ``_spread`` is ``_combine`` on the cotangent and the
    reverse (held to each function itself and, as an adjoint pair on the held
    rows, to the inner products: ``_spread`` zeroes no row since PR 46, and a
    row that is not held is in no group of the products between the two), and
    neither derivative's jaxpr holds a scatter-add: the product that sums a
    run is its own transpose's partner as the shifted adds were."""
    tok, valid, n_tokens, k = chunk_of(case)
    r = tok.shape[0]
    where = (tok, valid, *moe_dispatch._token_runs(tok, valid, n_tokens))
    kx, kg = jax.random.split(jax.random.PRNGKey(3))
    x, g = jax.random.normal(kx, (n_tokens, D)), jax.random.normal(kg, (r, D))

    def pull_spread(x, g):
        return jax.vjp(lambda x: moe_dispatch._spread(x, where, k), x)[1](g)[0]

    def pull_combine(g, x):
        return jax.vjp(lambda g: moe_dispatch._combine(g, where, k), g)[1](x)[0]

    np.testing.assert_allclose(np.asarray(pull_spread(x, g)), np.asarray(moe_dispatch._combine(g, where, k)),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pull_combine(g, x)), np.asarray(moe_dispatch._spread(x, where, k)),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(jnp.sum(moe_dispatch._spread(x, where, k) * jnp.where(valid[:, None], g, 0.0))),
                               float(jnp.sum(x * moe_dispatch._combine(g, where, k))), rtol=1e-5)
    # rows that are not held may hold anything: no sum takes them
    poisoned = jnp.where(valid[:, None], g, jnp.where(jnp.arange(r)[:, None] % 2 == 0, jnp.nan, jnp.inf))
    np.testing.assert_array_equal(np.asarray(moe_dispatch._combine(poisoned, where, k)),
                                  np.asarray(moe_dispatch._combine(g, where, k)))
    for pull, args in ((pull_spread, (x, g)), (pull_combine, (g, x))):
        names = {eqn.primitive.name for _, eqn in live_equations(jax.make_jaxpr(pull)(*args).jaxpr)}
        assert not any("scatter" in name for name in names), names
        assert "gather" in names


@pytest.mark.parametrize("chunks", list(SLACKS))
@pytest.mark.parametrize("act", list(ACTS))
def test_a_chunk_sums_its_runs_in_one_product_forward_and_one_backward(act, chunks):
    """In the gradient's jaxpr with dead code removed, each chunk loop holds
    ONE ``dot_general`` whose result is the ``[rows, d]`` buffer (as its
    ``[rows / T, T, d]`` tiles): ``_combine`` forward on the down product's
    result, and backward on the rows' cotangent, where it is ``_spread``'s
    transpose. No ``pad``, no slice and no add of a buffer that size: the
    three rounds of shifted slice, pad and add before PR 38 are gone, and the
    carry over a tile's edge is added inside the product's own expression."""
    x, idx, gates, stacks = inputs(f=12)  # so that [rows, 2 f] is not taken for [rows, d]
    slack = SLACKS[chunks]
    rows = moe_dispatch.share_rows_bound(S, K, HELD, E, slack)

    def loss(x, gates, *stacks):
        return jnp.sum(jnp.sin(moe_dispatch.share_glu_experts(
            x, idx, gates, *stacks, OFFSET, E, act=act, slack=slack)[0]))

    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(x, gates, *stacks)
    in_loops = [(path, eqn) for path, eqn in live_equations(closed.jaxpr) if "while" in path]

    def tiles_of_the_buffer(eqn):
        shape = eqn.outvars[0].aval.shape
        return len(shape) == 3 and shape[-1] == D and shape[0] * shape[1] == rows

    def shifted_rows(eqn):
        shape = eqn.outvars[0].aval.shape
        return len(shape) == 2 and shape[1] == D and rows - 8 <= shape[0] <= rows

    products = collections.Counter(
        "forward" if "custom_vjp_call" in path[:path.index("while")] else "backward"
        for path, eqn in in_loops if eqn.primitive.name == "dot_general" and tiles_of_the_buffer(eqn))
    assert products == {"forward": 1, "backward": 1}, products
    moved = collections.Counter(eqn.primitive.name for _, eqn in in_loops if shifted_rows(eqn))
    assert not {"pad", "slice", "concatenate", "add", "add_any", "dynamic_update_slice"} & set(moved), moved
    # the one add of that size is the carry's, on the product's float32 tiles, once a loop
    assert sum(eqn.primitive.name == "add" and tiles_of_the_buffer(eqn) for _, eqn in in_loops) == 2
