"""ops/gdn.py (the gated delta rule under ONE decay a head, several value heads
a key head, as a chunked scan with its own backward) at a tiny size on the CPU:
the scalar form against the recurrence TOKEN BY TOKEN and against ``ops/kda.kda``
fed the same decay broadcast over a head's channels and q, k repeated to the
value heads, values and every gradient; decays strong enough that a chunk's sum
passes -88; a sequence that is no whole number of chunks; what the form never
makes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedvolunteercomputing_tpu.ops import gdn, kda
from tests.test_kda import close, recurrence, value_and_grads

NAMES = ("qkv", "g", "beta")
HK, HV, DK, DV = 2, 4, 8, 16


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def streams(seed=0, z=2, t=40, lo=1e-3, hi=0.3, dtype=jnp.float32):
    """Seeded streams as the mixer holds them ([z, t, 2 Hk K + Hv V], g and beta
    [z, t, Hv]: a log decay a value head from ``lo`` to ``hi`` a token) and a probe for the output."""
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    qkv = (1.3 * jax.random.normal(k[0], (z, t, 2 * HK * DK + HV * DV))).astype(dtype)
    g = -jnp.exp(jax.random.uniform(k[1], (z, t, HV), jnp.float32, jnp.log(lo), jnp.log(hi)))
    beta = jax.random.uniform(k[2], (z, t, HV), jnp.float32, 0.05, 0.95)
    return (qkv, g, beta), jax.random.normal(k[3], (z, t, HV * DV)).astype(dtype)


def by_head(qkv, g, beta):
    """The streams as ``ops/kda.kda`` and the recurrence take them: q and k
    REPEATED to the value heads along the head axis (value head j reads key head
    j // 2), the decay broadcast over a head's key channels."""
    z, t, _ = qkv.shape
    r, kd = HV // HK, HK * DK
    q = jnp.repeat(qkv[..., :kd].reshape(z, t, HK, DK), r, axis=2)
    k = jnp.repeat(qkv[..., kd:2 * kd].reshape(z, t, HK, DK), r, axis=2)
    v = qkv[..., 2 * kd:].reshape(z, t, HV, DV)
    return q, k, v, jnp.broadcast_to(g[..., None], (z, t, HV, DK)), beta


def scalar_form(chunk):
    return lambda qkv, g, beta: gdn.gdn_with_sums(qkv, g, beta, HK, HV, DK, chunk)[0]


def per_channel_form(chunk):
    return lambda *a: kda.kda(*by_head(*a), chunk=chunk)[0].reshape(a[0].shape[0], a[0].shape[1], -1)


def token_by_token(*a):
    return recurrence(*by_head(*a)).reshape(a[0].shape[0], a[0].shape[1], -1)


@pytest.mark.parametrize("chunk", [16, 2, 8, 64], ids=["2.5_chunks_of_16", "chunks_of_2", "chunks_of_8", "one_chunk_of_64"])
def test_the_scalar_form_is_the_recurrence_token_by_token(chunk):
    """A sequence of 40 (two chunks of 16 and a half: a padded tail), float32:
    values and the gradients of the ONE qkv stream, g and beta against the
    recurrence differentiated by JAX over q and k repeated to four value heads."""
    args, probe = streams()
    want, want_grads = value_and_grads(token_by_token, args, probe)
    got, grads = value_and_grads(scalar_form(chunk), args, probe)
    assert got.shape == want.shape and got.dtype == jnp.float32
    close(got, want, 2e-6, "o")
    for name, a, b in zip(NAMES, grads, want_grads):
        close(a, b, 5e-6, f"d {name}")


@pytest.mark.parametrize("tokens, chunk, lo, hi", [(48, 16, 1e-3, 0.3), (128, 64, 0.7, 4.0), (100, 64, 0.7, 4.0)],
                         ids=["mild", "a_chunk_sums_below_minus_88", "strong_with_a_padded_tail"])
def test_the_scalar_form_is_the_per_channel_form_fed_a_broadcast_decay(tokens, chunk, lo, hi):
    """``ops/kda.kda`` with the decay broadcast over a head's 8 channels and q, k
    repeated to 4 heads computes the same function: forward and every gradient,
    float32, to 1e-5 relative, also where every whole chunk of 64 sums to -100 ..
    -130 (past float32's -88: ``exp(G_i - G_j)`` of a difference never sees it)."""
    args, probe = streams(seed=1, t=tokens, lo=lo, hi=hi)
    sums = gdn.gdn_with_sums(*args, HK, HV, DK, chunk)[1]
    assert sums.shape == (2, -(-tokens // chunk), HV) and (hi < 1.0 or float(jnp.max(sums[:, 0])) < -88.0)
    want, want_grads = value_and_grads(per_channel_form(chunk), args, probe)
    got, grads = value_and_grads(scalar_form(chunk), args, probe)
    for a in (got, *grads):
        assert bool(jnp.all(jnp.isfinite(a)))
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))  # noqa: E731
    assert rel(got, want) < 1e-5
    for name, a, b in zip(NAMES, grads, want_grads):
        assert rel(a, b) < 1e-5, (name, rel(a, b))
    # the per-channel form's chunk sums, a head's channels all equal, are this form's one number a head
    np.testing.assert_allclose(np.asarray(kda.kda_with_sums(*by_head(*args), chunk=chunk)[1][..., 0]),
                               np.asarray(sums), rtol=1e-6)


def test_value_head_j_reads_key_head_j_over_two_and_not_j_modulo_the_key_heads():
    args, probe = streams(seed=2)
    got = scalar_form(16)(*args)
    z, t, _ = args[0].shape
    q, k, v, g, beta = by_head(*args)
    tiled = lambda a: jnp.tile(a[:, :, ::HV // HK], (1, 1, HV // HK, 1))  # noqa: E731 — key head j % Hk
    wrong = recurrence(tiled(q), tiled(k), v, g, beta).reshape(z, t, -1)
    close(got, token_by_token(*args), 2e-6, "o")
    assert float(jnp.max(jnp.abs(got - wrong))) > 1e-2


def test_in_bfloat16_the_scalar_form_stays_within_rounding_of_the_float32_recurrence():
    args, probe = streams(seed=3, t=48)
    want, want_grads = value_and_grads(token_by_token, args, probe)
    low = (args[0].astype(jnp.bfloat16), args[1], args[2])
    got, grads = value_and_grads(scalar_form(16), low, probe.astype(jnp.bfloat16))
    assert got.dtype == jnp.bfloat16 and grads[0].dtype == jnp.bfloat16 and grads[1].dtype == jnp.float32
    close(got, want, 3e-2, "o")
    for name, a, b in zip(NAMES, grads, want_grads):
        close(a, b, 6e-2, f"d {name}")


def test_a_sequence_that_is_no_whole_number_of_chunks_is_padded_with_tokens_that_do_nothing():
    (qkv, g, beta), _ = streams()
    pad = lambda a: jnp.pad(a, ((0, 0), (0, 8), (0, 0)))  # noqa: E731
    short = scalar_form(16)(qkv, g, beta)
    np.testing.assert_allclose(np.asarray(short), np.asarray(scalar_form(16)(pad(qkv), pad(g), pad(beta))[:, :40]),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(scalar_form(16)(qkv.at[:, 30].add(0.1), g, beta)[:, :30]),
                                  np.asarray(short[:, :30]))


def test_the_counters_are_kdas_of_a_head_whose_one_decay_is_its_slowest_channels():
    (qkv, _, beta), _ = streams(t=48)
    g = jnp.full((2, 48, HV), -1.0).at[:, :, 0].set(-0.01)       # value head 0 is slow; the others forget
    sums = gdn.gdn_with_sums(qkv, g, beta, HK, HV, DK, 16)[1]
    np.testing.assert_allclose(np.asarray(sums[0, 0, :2]), [-0.16, -16.0], rtol=1e-5)
    counters = gdn.scan_counters(sums)
    assert set(counters) == {"carry_share", "decay_min"}
    assert float(counters["carry_share"]) == pytest.approx(1 / 4) and float(counters["decay_min"]) == pytest.approx(-16.0)
    assert gdn.CHUNK == kda.CHUNK == 64 and gdn.FORM == "scalar_decay_xla"


def test_heads_that_do_not_divide_and_a_chunk_that_is_no_power_of_two_are_refused():
    (qkv, g, beta), _ = streams()
    with pytest.raises(ValueError, match="power of two"):
        gdn.gdn_with_sums(qkv, g, beta, HK, HV, DK, 48)
    with pytest.raises(ValueError, match="do not divide"):
        gdn.gdn_with_sums(qkv, g[..., :3], beta[..., :3], HK, 3, DK, 16)


def _shapes(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.update((tuple(v.aval.shape), str(v.aval.dtype)) for v in eqn.outvars if hasattr(v.aval, "shape"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, found)
    return found


def test_nothing_of_a_streams_size_is_at_the_value_heads_count_but_v_and_o():
    """In the gradient's whole jaxpr (the loops' bodies included) no array is q
    or k repeated to the value heads at a stream's size ([z, T, Hv K] or by
    head), no decay is by channel ([z, T, Hv, K] float32), and the in-chunk
    ``k k^T`` and ``q k^T`` are made at the KEY heads' count: two products a
    chunk of [z, Hk, C, C] forward, none of [z, Hv, C, K] x [z, Hv, K, C]."""
    (qkv, g, beta), probe = streams(t=64)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(scalar_form(16)(*a) * probe), argnums=(0, 1, 2)))(qkv, g, beta)
    shapes = {s for s, _ in _shapes(jaxpr.jaxpr, set())}
    z, t = 2, 64
    assert (z, t, HV * DK) not in shapes and (z, t, HV, DK) not in shapes and (z, HV, t, DK) not in shapes
    assert (z, t, 2 * HK * DK + HV * DV) in shapes and (z, t, HV * DV) in shapes
    text = str(jax.make_jaxpr(lambda *a: scalar_form(16)(*a))(qkv, g, beta))
    # the forward step's two unscaled in-chunk products: [C, K] x [C, K]^T under the two vmaps (sequences, key heads)
    assert text.count(f"f32[{z},{HK},16,16] = dot_general") == 2
    assert f"f32[{z},{HV},16,16] = dot_general" not in text


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _level_products_by_scan(jaxpr, c):
    """For each scan that carries the value heads' states, in the program's order: how many products of two [C, C] matrices its body holds."""
    carries_states = lambda e: e.primitive.name == "scan" and any(  # noqa: E731
        tuple(v.aval.shape) == (2, HV, DV, DK) for v in e.outvars[:e.params["num_carry"]])
    return [sum(p.primitive.name == "dot_general" and all(tuple(v.aval.shape)[-2:] == (c, c) for v in p.invars)
                for sub in jax.core.jaxprs_in_params(e.params) for p in _eqns(sub))
            for e in _eqns(jaxpr) if carries_states(e)]


def test_the_backward_loop_takes_the_inverse_over_and_makes_none():
    """What shows that the mechanism engaged, in the traced program: a chunk's
    inverse (levels of two products of two [C, C] matrices each) is made by the
    FORWARD loop alone and handed to the backward as every chunk's T by chunk;
    the backward loop's body holds no product of two [C, C] matrices."""
    (qkv, g, beta), probe = streams(t=64)
    c = 4                   # chunks of 4 (no head's width, 8 or 16, is a chunk's): one level of two products past the first
    assert _level_products_by_scan(jax.make_jaxpr(scalar_form(c))(qkv, g, beta).jaxpr, c) == [2]
    grad = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(scalar_form(c)(*a) * probe), argnums=(0, 1, 2)))(qkv, g, beta)
    assert _level_products_by_scan(grad.jaxpr, c) == [2, 0]
    # the backward scan's xs: the chunks' numbers, the states that entered and T [nc, z, Hk, R, C, C]
    backward = [e for e in _eqns(grad.jaxpr) if e.primitive.name == "scan" and e.params["reverse"]]
    assert len(backward) == 1 and (16, 2, HK, HV // HK, c, c) in [tuple(v.aval.shape) for v in backward[0].invars]


@pytest.mark.parametrize("tokens, dtype, tol", [(96, jnp.float32, (2e-6, 5e-6)), (72, jnp.float32, (2e-6, 5e-6)),
                                                (80, jnp.bfloat16, (3e-2, 6e-2))],
                         ids=["six_chunks", "four_chunks_and_a_half", "five_chunks_in_bfloat16"])
def test_over_several_chunks_with_the_carried_state_alive_the_form_is_the_recurrence(tokens, dtype, tol):
    """Four chunks of 16 and more, the state carried across every boundary
    (``carry_share`` 1 at these decays), whole and with a padded tail, float32 at
    the tolerances of the 2.5-chunk case and bfloat16 at those of the 3-chunk
    one: ``o`` and the three gradients against the float32 recurrence token by
    token, so that the T a chunk's backward takes over from its forward meets the
    cotangent the reverse loop carries at every boundary."""
    args, probe = streams(seed=4, t=tokens)
    assert float(gdn.scan_counters(gdn.gdn_with_sums(*args, HK, HV, DK, 16)[1])["carry_share"]) == 1.0
    want, want_grads = value_and_grads(token_by_token, args, probe)
    got, grads = value_and_grads(scalar_form(16), (args[0].astype(dtype), args[1], args[2]), probe.astype(dtype))
    assert got.dtype == dtype and grads[0].dtype == dtype and grads[1].dtype == jnp.float32
    close(got, want, tol[0], "o")
    for name, a, b in zip(NAMES, grads, want_grads):
        close(a, b, tol[1], f"d {name}")


@pytest.mark.parametrize("lo, hi, dtype, tol", [(1e-3, 0.3, jnp.float32, 1e-5), (0.7, 4.0, jnp.float32, 1e-5),
                                                (1e-3, 0.3, jnp.bfloat16, 2e-2)],
                         ids=["mild", "a_chunk_sums_below_minus_88", "bfloat16"])
def test_what_the_forward_hands_the_backward_is_the_inverse_of_one_plus_l(lo, hi, dtype, tol):
    """Every chunk's T [nc, z, Hk, R, C, C] as ``_scan_fwd`` keeps it for the
    backward, against ``numpy.linalg.inv(I + L)`` of ``L`` built from the
    definition in float64 (k normed, the decay a difference of running sums),
    three chunks of 16: float32 at mild and at strong decays, and in bfloat16
    (the levels' products round their operands) to bfloat16's rounding."""
    (qkv, g, beta), _ = streams(seed=6, t=48, lo=lo, hi=hi, dtype=dtype)
    kept = gdn._scan_fwd(qkv, g, beta, HK, HV, DK, 16)[2]
    assert kept[0].shape == (3, 2, HV, DV, DK) and kept[1].shape == (3, 2, HK, HV // HK, 16, 16) and kept[1].dtype == dtype
    k = np.asarray(qkv[..., HK * DK:2 * HK * DK].astype(jnp.float32), np.float64).reshape(2, 3, 16, HK, DK)
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + kda.L2_EPS)
    gc = np.cumsum(np.asarray(g, np.float64).reshape(2, 3, 16, HV), axis=2)
    for z, n, h in [(0, 0, 0), (1, 2, 3), (0, 1, 2)]:
        kk, gg, bb = k[z, n, :, h // 2], gc[z, n, :, h], np.asarray(beta, np.float64).reshape(2, 3, 16, HV)[z, n, :, h]
        lower = np.tril(bb[:, None] * (kk @ kk.T) * np.exp(np.minimum(gg[:, None] - gg[None, :], 0.0)), -1)
        want = np.linalg.inv(np.eye(16) + lower)
        np.testing.assert_allclose(np.asarray(kept[1][n, z, h // 2, h % 2], np.float64), want, rtol=0,
                                   atol=tol * np.abs(want).max(), err_msg=str((z, n, h)))
