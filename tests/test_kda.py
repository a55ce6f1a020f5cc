"""ops/kda.py (Kimi Delta Attention's recurrence, a delta rule with a decay by
channel, as a chunked scan with its own backward) at a tiny size on the CPU:
the scan against the recurrence TOKEN BY TOKEN, values and every gradient, and
against the formulation it had until PR 55 (the streams normed, folded, summed
and laid out by chunk as whole passes around the scan)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedvolunteercomputing_tpu.ops import kda

NAMES = ("q", "k", "v", "g", "beta")


def unit(x):
    """Each head's vector at length 1, as ``kda`` norms the q and k it is handed."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + kda.L2_EPS)


def recurrence(q, k, v, g, beta):
    """A head's q at length 1 / sqrt(K) and k at length 1, then the three steps
    a token, float32: decay the state by channel, the delta along the new key,
    the rank-one update; ``o_t = S_t^T q_t``."""
    z, t, h, dk = q.shape
    q, k = unit(q.astype(jnp.float32)) * dk ** -0.5, unit(k.astype(jnp.float32))

    def token(s, now):
        q_t, k_t, v_t, g_t, b_t = now
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("zhkv,zhk->zhv", s, k_t))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("zhkv,zhk->zhv", s, q_t)

    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((z, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def scan_inputs(seed=0, z=2, t=40, h=3, dk=8, dv=16, decay=0.3, dtype=jnp.float32):
    """Seeded streams and a probe for the output: keys and queries of no
    particular length (the scan norms them), a log decay a channel from nearly
    none (1e-3 a token) to ``decay`` a token."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = (1.7 * jax.random.normal(k[0], (z, t, h, dk))).astype(dtype)
    key = (0.6 * jax.random.normal(k[1], (z, t, h, dk))).astype(dtype)
    v = jax.random.normal(k[2], (z, t, h, dv)).astype(dtype)
    g = -jnp.exp(jax.random.uniform(k[3], (z, t, h, dk), jnp.float32, jnp.log(1e-3), jnp.log(decay)))
    beta = jax.random.uniform(k[4], (z, t, h), jnp.float32, 0.05, 0.95)
    return (q, key, v, g, beta), jax.random.normal(k[5], (z, t, h, dv)).astype(dtype)


@functools.lru_cache(maxsize=4)   # ``recurrence``, asked for by every test, stays; a test's own lambda goes with it
def _value_and_grads(fn):
    def both(args, probe):
        return fn(*args), jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * probe.astype(jnp.float32)),
                                   argnums=tuple(range(len(args))))(*args)

    return jax.jit(both)


def value_and_grads(fn, args, probe):
    """``fn``'s value and its gradient against ``probe``, as ONE program a
    function (the token-by-token ``recurrence`` is compiled once a shape for
    the whole file; evaluated eagerly it is hundreds of small programs a test)."""
    return _value_and_grads(fn)(args, probe)


def close(got, want, tol, what):
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0,
                               atol=tol * scale, err_msg=what)


@pytest.mark.parametrize("heads, chunk", [(3, 16), (8, 16), (3, 2), (3, 8), (3, 32), (3, 64)],
                         ids=["2.5_chunks_of_16", "eight_heads", "chunks_of_2", "chunks_of_8", "chunks_of_32", "one_chunk_of_64"])
def test_the_scan_is_the_recurrence_token_by_token(heads, chunk):
    """A sequence of 40 in chunks of every size the levels allow (2.5 chunks of
    16; one level; a sequence inside one padded chunk): values and the gradients
    of q, k, v, g and beta, float32, against the recurrence differentiated by JAX."""
    args, probe = scan_inputs(h=heads)
    want, want_grads = value_and_grads(recurrence, args, probe)
    got, grads = value_and_grads(lambda *a: kda.kda(*a, chunk=chunk)[0], args, probe)
    assert got.shape == want.shape and got.dtype == jnp.float32
    close(got, want, 2e-6, "o")
    for name, a, b in zip(NAMES, grads, want_grads):
        close(a, b, 5e-6, f"d {name}")


def test_a_chunk_whose_summed_decay_is_below_minus_100_holds_no_inf_or_nan():
    """THE TRAP: ``exp(G_i - G_j)`` as ``(k e^G)(k e^-G)^T`` over a chunk overflows
    float32 once a chunk's summed log decay passes -88. Here every channel decays
    by 2 to 8 a token, a chunk of 16 sums to -32 .. -128 and the sequence's 40
    tokens to -320: the scan agrees with the recurrence and holds no inf or nan,
    values and every gradient."""
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    (q, key, v, _, beta), probe = scan_inputs(seed=1)
    g = -jax.random.uniform(k[0], q.shape, jnp.float32, 2.0, 8.0)
    args = (q, key, v, g, beta)
    _, sums = kda.kda_with_sums(*args, chunk=16)
    assert float(jnp.min(sums)) < -100.0
    want, want_grads = value_and_grads(recurrence, args, probe)
    got, grads = value_and_grads(lambda *a: kda.kda(*a, chunk=16)[0], args, probe)
    for a in (got, *grads):
        assert bool(jnp.all(jnp.isfinite(a)))
    close(got, want, 2e-6, "o")
    for name, a, b in zip(NAMES, grads, want_grads):
        close(a, b, 5e-6, f"d {name}")


def test_the_exponent_that_does_not_factor_is_the_one_this_module_avoids():
    """What the level-by-level exponent is for: over one chunk of summed decay
    below -100 the factored form ``(k e^G)(k e^-G)^T`` is inf times zero."""
    g = jnp.cumsum(-jnp.full((16, 8), 7.0), axis=0)
    assert float(g[-1, 0]) < -100.0
    with np.errstate(over="ignore", invalid="ignore"):
        factored = (jnp.ones((16, 8)) * jnp.exp(g)) @ (jnp.ones((16, 8)) * jnp.exp(-g)).T
    assert not bool(jnp.all(jnp.isfinite(factored)))
    row, col = kda._grid(16)
    lower = sum(kda._within(jnp.ones((16, 8)), jnp.ones((16, 8)), jnp.ones((16, 8)), kda._level_decays(g))[0])
    direct = jnp.where(row > col, jnp.sum(jnp.exp(g[:, None] - g[None, :]) * (row > col)[..., None], axis=-1), 0.0)
    assert bool(jnp.all(jnp.isfinite(lower)))
    np.testing.assert_allclose(np.asarray(lower), np.asarray(direct), rtol=1e-5, atol=1e-30)


def test_in_bfloat16_the_scan_stays_within_rounding_of_the_float32_recurrence():
    """The compute dtype of a chip: every product's operands are rounded to
    bfloat16 and summed in float32, the state stays float32; ``o`` and the
    streams' cotangents come back in bfloat16 (the backward scan stacks them so),
    the decay's in float32."""
    args, probe = scan_inputs(dtype=jnp.bfloat16)
    got, grads = value_and_grads(lambda *a: kda.kda(*a, chunk=16)[0], args, probe)
    assert [a.dtype for a in (got, *grads)] == [jnp.bfloat16] * 4 + [jnp.float32] * 2
    want, want_grads = value_and_grads(recurrence, tuple(a.astype(jnp.float32) for a in args), probe.astype(jnp.float32))
    close(got, want, 3e-2, "o")
    for name, a, b in zip(NAMES, grads, want_grads):
        close(a, b, 5e-2, f"d {name}")


@pytest.mark.parametrize("tokens", [48, 40], ids=["whole_chunks", "a_padded_tail"])
@pytest.mark.parametrize("dtype, o_tol, grad_tol", [(jnp.float32, 1e-5, 1e-5), (jnp.bfloat16, 3e-2, 5e-2)],
                         ids=["float32", "bfloat16"])
def test_the_scan_on_raw_streams_is_the_formulation_it_replaced(dtype, o_tol, grad_tol, tokens):
    """``kda`` takes its chunks out of the raw streams in place and norms, folds
    and sums on the chunk it holds. Held to PR 52's formulation
    (``experiments/kda_sweep.parent_kda``: whole-stream l2 norms, beta's fold and
    the running sum by chunk as passes that JAX differentiates, then a scan over
    chunks stacked ``[nc, Z, ..]``): ``o``, the chunks' sums and the gradient of
    EVERY input, at two sequences (the second one's slices) and with a tail that
    is no whole chunk (which the parent's entry padded as this one does)."""
    from experiments.kda_sweep import parent_kda

    chunk = 16
    args, probe = scan_inputs(t=tokens, dtype=dtype)
    pad = (-tokens) % chunk

    def parent(*streams):
        padded = tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in streams)
        return parent_kda(*padded, chunk)[:, :tokens]

    want, want_grads = value_and_grads(parent, args, probe)
    got, grads = value_and_grads(lambda *a: kda.kda(*a, chunk=chunk)[0], args, probe)
    assert got.dtype == want.dtype == dtype and [a.dtype for a in grads] == [b.dtype for b in want_grads]
    close(got, want, o_tol, "o")
    for name, a, b in zip(NAMES, grads, want_grads):
        close(a, b, grad_tol, f"d {name}")
    sums = kda.kda_with_sums(*args, chunk=chunk)[1]
    g = jnp.pad(args[3], ((0, 0), (0, pad), (0, 0), (0, 0)))
    assert sums.shape == (2, (tokens + pad) // chunk, 3, 8) and sums.dtype == jnp.float32
    close(sums, jnp.sum(g.reshape(2, -1, chunk, 3, 8), axis=2), 1e-6, "the chunks' sums")
    assert not np.any(np.asarray(jax.grad(lambda g: jnp.sum(kda.kda_with_sums(*args[:3], g, args[4], chunk=chunk)[1]))(args[3])))


def test_a_sequence_that_is_no_whole_number_of_chunks_is_padded_with_tokens_that_do_nothing():
    """40 tokens in chunks of 16 are the first 40 of 48: the padding neither
    decays nor writes, and a token changes nothing before it."""
    args, _ = scan_inputs()
    padded = tuple(jnp.pad(a, ((0, 0), (0, 8)) + ((0, 0),) * (a.ndim - 2)) for a in args)
    short = kda.kda(*args, chunk=16)[0]
    np.testing.assert_allclose(np.asarray(short), np.asarray(kda.kda(*padded, chunk=16)[0][:, :40]),
                               rtol=1e-6, atol=1e-7)
    moved = tuple(a.at[:, 30].add(0.1) if i < 3 else a for i, a in enumerate(args))
    np.testing.assert_array_equal(np.asarray(kda.kda(*moved, chunk=16)[0][:, :30]),
                                  np.asarray(short[:, :30]))


def test_carry_share_counts_the_boundaries_whose_slowest_channel_still_counts():
    """Of the (sequence, head, chunk after the first) triples, those whose
    chunk-summed decay at the channel that decays LEAST is over 1e-3."""
    (q, k, v, _, beta), _ = scan_inputs(t=48)
    g = jnp.full(q.shape, -1.0).at[:, :, 0, 0].set(-0.01)       # head 0 keeps one slow channel; the others forget
    _, sums = kda.kda_with_sums(q, k, v, g, beta, chunk=16)
    assert sums.shape == (2, 3, 3, 8)
    np.testing.assert_allclose(np.asarray(sums[0, 0, 0, :2]), [-0.16, -16.0], rtol=1e-5)
    assert float(kda.carry_share(sums)) == pytest.approx(1 / 3)
    assert float(kda.kda(q, k, v, g, beta, chunk=16)[1]) == pytest.approx(1 / 3)
    counters = kda.scan_counters(sums)
    assert float(counters["decay_min"]) == pytest.approx(-16.0) and set(counters) == {"carry_share", "decay_min"}
    assert float(kda.carry_share(sums[:, :1])) == 0.0               # one chunk: no boundary
    assert kda.CHUNK == 64 and kda.CARRY_FLOOR == 1e-3


def test_a_chunk_that_is_no_power_of_two_is_refused():
    args, _ = scan_inputs()
    with pytest.raises(ValueError, match="power of two"):
        kda.kda(*args, chunk=24)


def test_a_chunk_of_keys_that_resemble_each_other_is_solved_without_cancellation():
    """Keys that share most of their direction, beta near 1 and hardly any
    decay: ``L`` is nearly all 0.9 under the diagonal, its powers reach 1e9
    within a chunk of 32 and the product of ``I + (-L)^(2^k)`` cancels them in
    float32 (its error here is of the order of the values); the inverse by
    blocks is forward substitution and keeps float32's precision."""
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    z, t, h, dk, dv = 1, 64, 2, 8, 8
    key = unit(jnp.ones((z, t, h, dk)) + 0.2 * jax.random.normal(k[0], (z, t, h, dk)))
    q = unit(jax.random.normal(k[1], (z, t, h, dk))) * dk ** -0.5
    v = jax.random.normal(k[2], (z, t, h, dv))
    g = jnp.full((z, t, h, dk), -1e-3)
    beta = jnp.full((z, t, h), 0.95)
    args, probe = (q, key, v, g, beta), jax.random.normal(k[3], (z, t, h, dv))
    want, want_grads = value_and_grads(recurrence, args, probe)
    got, grads = value_and_grads(lambda *a: kda.kda(*a, chunk=32)[0], args, probe)
    close(got, want, 2e-5, "o")
    for name, a, b in zip(NAMES, grads, want_grads):
        close(a, b, 1e-4, f"d {name}")
    # what the shorter product would have summed
    levels, _ = kda._within(q[0, :32, 0], key[0, :32, 0], 0.95 * key[0, :32, 0], kda._level_decays(jnp.cumsum(g[0, :32, 0], 0)))
    lower = sum(levels)
    power, largest = lower, 0.0
    for _ in range(30):
        power = power @ lower
        largest = max(largest, float(jnp.max(jnp.abs(power))))
    assert largest > 1e6
