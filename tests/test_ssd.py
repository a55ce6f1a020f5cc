"""ops/ssd.py (the Mamba-2 recurrence as a chunked scan with its own backward)
and ops/short_conv.causal_conv (the one-stream convolution with a bias and an
activation), at a tiny size on the CPU: both forms of each against the plain
arithmetic they must equal, values and every gradient, float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedvolunteercomputing_tpu.ops import short_conv, ssd

ARGS = ("x", "dt", "a_log", "b", "c", "d")


def ssd_sequential(x, dt, a_log, b, c, d):
    """The recurrence one position at a time: what the chunked forms must equal. Float32."""
    z, t, h, p = x.shape
    g = b.shape[2]
    r = h // g
    a = -jnp.exp(a_log.astype(jnp.float32))
    bh, ch = jnp.repeat(b, r, axis=2), jnp.repeat(c, r, axis=2)             # [Z, T, H, N]

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = jnp.exp(dt_t * a)[..., None, None] * s + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return s, jnp.einsum("zhpn,zhn->zhp", s, c_t) + d[:, None] * x_t

    xs = tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0) for v in (x, dt, bh, ch))
    _, y = jax.lax.scan(step, jnp.zeros((z, h, p, b.shape[3]), jnp.float32), xs)
    return jnp.moveaxis(y, 0, 1)


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def scan_inputs(seed=0, z=2, t=40, h=4, p=8, g=2, n=16, dtype=jnp.float32):
    """Seeded inputs of the recurrence and a probe for its output: two groups
    of two heads, decays from a head that forgets within a chunk to one that
    carries across the whole sequence."""
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (z, t, h, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (z, t, h)) - 1.0)
    a_log = jnp.log(jnp.asarray([0.05, 0.5, 2.0, 8.0])[:h])
    b = jax.random.normal(k[2], (z, t, g, n)).astype(dtype)
    c = jax.random.normal(k[3], (z, t, g, n)).astype(dtype)
    d = jax.random.normal(k[4], (h,))
    return (x, dt, a_log, b, c, d), jax.random.normal(k[5], (z, t, h, p))


# a chunk shorter than the sequence, one that does not divide it (40 = 2.5 x 16), one longer than it
@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("form", [ssd.PLAIN, ssd.INTERPRET])
def test_the_chunked_scan_is_the_recurrence_position_by_position(form, chunk):
    """Values and all six gradients (x', dt, A_log, B, C, D) of both forms, the
    hand-written backward that carries dS among them, against JAX's
    differentiation of the recurrence one position at a time: 1e-5."""
    args, probe = scan_inputs()
    want = ssd_sequential(*args)
    got, _ = ssd.ssd(*args, chunk=chunk, form=form)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5 * scale, rtol=1e-5)
    g_got = jax.grad(lambda *a: jnp.sum(ssd.ssd(*a, chunk=chunk, form=form)[0] * probe), argnums=tuple(range(6)))(*args)
    g_want = jax.grad(lambda *a: jnp.sum(ssd_sequential(*a) * probe), argnums=tuple(range(6)))(*args)
    for name, a, b in zip(ARGS, g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5 * float(jnp.max(jnp.abs(b))), rtol=1e-5,
                                   err_msg=name)


def test_one_chunks_backward_is_the_transpose_of_its_forward():
    """``_chunk_bwd`` against ``jax.vjp`` of ``_chunk_fwd``, with a state coming
    in and a cotangent of the state going out: the five cotangents."""
    k = jax.random.split(jax.random.PRNGKey(3), 8)
    z, g, r, q, p, n = 2, 2, 3, 8, 4, 16
    s = jax.random.normal(k[0], (z, g, r, p, n))
    xd = jax.random.normal(k[1], (z, g, r, q, p))
    cum = jnp.cumsum(-jax.nn.softplus(jax.random.normal(k[2], (z, g, r, q))), axis=-1)
    b, c = jax.random.normal(k[3], (z, g, q, n)), jax.random.normal(k[4], (z, g, q, n))
    dy, ds = jax.random.normal(k[5], (z, g, r, q, p)), jax.random.normal(k[6], (z, g, r, p, n))
    _, pull = jax.vjp(ssd._chunk_fwd, s, xd, cum, b, c)
    ds_prev, dxd, dcum, db, dc = pull((dy, ds))
    got = ssd._chunk_bwd(ds, s, xd, cum, b, c, dy)
    for name, a, w in zip(("dxd", "dcum", "db", "dc", "ds_prev"), got, (dxd, dcum, db, dc, ds_prev)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=2e-5, atol=2e-5, err_msg=name)


def test_the_kernels_in_the_compute_dtype_are_the_plain_form_in_it():
    """bfloat16 streams: the interpreted kernels and the plain form round in
    the same places (operands in the compute dtype, decays and state float32)."""
    args, probe = scan_inputs(dtype=jnp.bfloat16)
    plain, kernel = (ssd.ssd(*args, chunk=16, form=f)[0].astype(jnp.float32) for f in (ssd.PLAIN, ssd.INTERPRET))
    assert plain.dtype == jnp.float32 and ssd.ssd(*args, chunk=16, form=ssd.PLAIN)[0].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(plain), rtol=2e-2, atol=2e-2)
    exact = ssd_sequential(*args)
    assert float(jnp.max(jnp.abs(plain - exact))) < 0.05 * float(jnp.max(jnp.abs(exact)))


def test_carry_share_counts_the_boundaries_a_state_survives():
    """Heads whose whole-chunk decay is over ``CARRY_FLOOR``, over the chunks
    after the first: a hand count from dt and A."""
    (x, dt, a_log, b, c, d), _ = scan_inputs(t=48)
    dt = jnp.full_like(dt, 0.1)
    _, share = ssd.ssd(x, dt, a_log, b, c, d, chunk=16)
    # exp(-16 x 0.1 x A): A = 0.05, 0.5, 2 survive (0.92, 0.45, 0.041), A = 8 does not (2.8e-6)
    assert float(share) == pytest.approx(0.75)
    assert float(ssd.ssd(x, dt, a_log, b, c, d, chunk=64)[1]) == 0.0        # one chunk: no boundary
    padded = ssd.ssd(x[:, :40], dt[:, :40], a_log, b[:, :40], c[:, :40], d, chunk=16)[1]
    # the half chunk at the end decays half as far: A = 8 survives it (exp(-6.4) = 1.7e-3): 7 of 8
    assert float(padded) == pytest.approx(7 / 8)
    assert jax.grad(lambda v: ssd.ssd(x, v, a_log, b, c, d, chunk=16)[1])(dt).max() == 0.0


def test_which_shapes_the_kernels_take():
    assert ssd.kernel_takes(64, 8, 64, 128, 128)                    # the published mixer
    assert not ssd.kernel_takes(4, 2, 8, 16, 16)                    # the tests' size: the plain form
    assert not ssd.kernel_takes(64, 16, 64, 128, 128) and not ssd.kernel_takes(64, 8, 64, 64, 128)
    assert ssd.choose_form(64, 8, 64, 128, 128) == ssd.PLAIN        # no TPU here
    assert ssd.CHUNK == 128 and ssd.CARRY_FLOOR == 1e-3


# -- the convolution -----------------------------------------------------------------


def conv_inputs(t=96, d=128, taps=4):
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    return (jax.random.normal(k[0], (2, t, d)), jax.random.normal(k[1], (taps, d)),
            jax.random.normal(k[2], (d,))), jax.random.normal(k[3], (2, t, d))


def by_position(u, w, bias):
    """``silu(sum_j w[j] u_{t - (K - 1 - j)} + bias)`` in numpy, a position at a time."""
    u, w, bias = (np.asarray(a, np.float64) for a in (u, w, bias))
    out = np.zeros_like(u)
    k = w.shape[0]
    for t in range(u.shape[1]):
        for j in range(k):
            if t - (k - 1 - j) >= 0:
                out[:, t] += w[j] * u[:, t - (k - 1 - j)]
    out += bias
    return out / (1 + np.exp(-out))


@pytest.mark.parametrize("taps", [4, 3, 2])
def test_causal_conv_both_forms_are_the_sum_over_taps(taps):
    (u, w, bias), probe = conv_inputs(taps=taps)
    want = by_position(u, w, bias)
    plain = short_conv.causal_conv_xla(u, w, bias)
    kernel = short_conv.causal_conv_kernel(u, w, bias, 32, True)       # three blocks: both edges
    np.testing.assert_allclose(np.asarray(plain), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(kernel), want, rtol=1e-5, atol=1e-5)
    g_plain = jax.grad(lambda *a: jnp.sum(short_conv.causal_conv_xla(*a) * probe), argnums=(0, 1, 2))(u, w, bias)
    g_kernel = jax.grad(lambda *a: jnp.sum(short_conv.causal_conv_kernel(*a, 32, True) * probe),
                        argnums=(0, 1, 2))(u, w, bias)
    for name, a, b in zip(("u", "taps", "bias"), g_kernel, g_plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5 * float(jnp.max(jnp.abs(b))),
                                   err_msg=name)


def test_causal_conv_chooses_the_plain_form_off_the_chip_and_the_gated_kernels_block():
    (u, w, bias), _ = conv_inputs()
    assert short_conv.choose_block(8192, 6144, 4) == short_conv.BLOCK_T      # the published mixer's stream
    assert short_conv.choose_block(40, 64, 4) is None                        # the tests' model: the plain form
    assert np.array_equal(np.asarray(short_conv.causal_conv(u, w, bias)),
                          np.asarray(short_conv.causal_conv_xla(u, w, bias)))
    # a token changes nothing before it
    moved = short_conv.causal_conv_xla(u.at[:, 50].add(1.0), w, bias)
    assert np.array_equal(np.asarray(moved[:, :50]), np.asarray(short_conv.causal_conv_xla(u, w, bias)[:, :50]))
