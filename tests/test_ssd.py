"""ops/ssd.py (the Mamba-2 recurrence as a chunked scan with its own backward)
and ops/short_conv.causal_conv (the one-stream convolution with a bias and an
activation), at a tiny size on the CPU: both forms of each against the plain
arithmetic they must equal, values and every gradient, float32."""

import functools

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


@jax.jit
def sequential_grads(probe, *args):
    """The recurrence's six gradients against ``probe``, one program for every case that shares a shape."""
    return jax.grad(lambda *a: jnp.sum(ssd_sequential(*a) * probe), argnums=tuple(range(6)))(*args)


def side_by_side(x, b, c):
    """``xbc`` [Z, T, H P + 2 G N]: x' by head, then B and C by group, in one array's lanes."""
    z, t = x.shape[:2]
    return jnp.concatenate([a.reshape(z, t, -1) for a in (x, b, c)], axis=-1)


def scan(x, dt, a_log, b, c, d, **kw):
    """``ssd.ssd`` as the mixer calls it: x', B and C side by side in ONE array
    (what the convolution leaves), ``y`` back in ``x``'s shape."""
    y, share = ssd.ssd(side_by_side(x, b, c), dt, a_log, d, b.shape[2], b.shape[3], **kw)
    return y.reshape(x.shape), share


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
    want = jax.jit(ssd_sequential)(*args)
    got, _ = scan(*args, chunk=chunk, form=form)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5 * scale, rtol=1e-5)
    g_got = jax.jit(jax.grad(lambda *a: jnp.sum(scan(*a, chunk=chunk, form=form)[0] * probe), argnums=tuple(range(6))))(*args)
    g_want = sequential_grads(probe, *args)
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
    ds_prev, dxd, dcum, db, dc = jax.jit(lambda *a: jax.vjp(ssd._chunk_fwd, *a)[1]((dy, ds)))(s, xd, cum, b, c)
    got = jax.jit(ssd._chunk_bwd)(ds, s, xd, cum, b, c, dy)
    for name, a, w in zip(("dxd", "dcum", "db", "dc", "ds_prev"), got, (dxd, dcum, db, dc, ds_prev)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=2e-5, atol=2e-5, err_msg=name)


def test_the_kernels_in_the_compute_dtype_are_the_plain_form_in_it():
    """bfloat16 streams: the interpreted kernels and the plain form round in
    the same places (operands in the compute dtype, decays and state float32)."""
    args, probe = scan_inputs(dtype=jnp.bfloat16)
    plain, kernel = (scan(*args, chunk=16, form=f)[0].astype(jnp.float32) for f in (ssd.PLAIN, ssd.INTERPRET))
    assert plain.dtype == jnp.float32 and scan(*args, chunk=16, form=ssd.PLAIN)[0].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(plain), rtol=2e-2, atol=2e-2)
    exact = ssd_sequential(*args)
    assert float(jnp.max(jnp.abs(plain - exact))) < 0.05 * float(jnp.max(jnp.abs(exact)))


def test_carry_share_counts_the_boundaries_a_state_survives():
    """Heads whose whole-chunk decay is over ``CARRY_FLOOR``, over the chunks
    after the first: a hand count from dt and A."""
    (x, dt, a_log, b, c, d), _ = scan_inputs(t=48)
    dt = jnp.full_like(dt, 0.1)
    _, share = scan(x, dt, a_log, b, c, d, chunk=16)
    # exp(-16 x 0.1 x A): A = 0.05, 0.5, 2 survive (0.92, 0.45, 0.041), A = 8 does not (2.8e-6)
    assert float(share) == pytest.approx(0.75)
    assert float(scan(x, dt, a_log, b, c, d, chunk=64)[1]) == 0.0        # one chunk: no boundary
    padded = scan(x[:, :40], dt[:, :40], a_log, b[:, :40], c[:, :40], d, chunk=16)[1]
    # the half chunk at the end decays half as far: A = 8 survives it (exp(-6.4) = 1.7e-3): 7 of 8
    assert float(padded) == pytest.approx(7 / 8)
    assert jax.grad(lambda v: scan(x, v, a_log, b, c, d, chunk=16)[1])(dt).max() == 0.0


def packed(seed=0, groups=2, dtype=jnp.float32, t=40):
    """The mixer's own operands: ``xbc`` [Z, T, H P + 2 G N] as the convolution
    leaves it (x', B, C side by side), dt, A_log, D; and a probe for ``y``."""
    (x, dt, a_log, b, c, d), probe = scan_inputs(seed, t=t, g=groups, dtype=dtype)
    return (side_by_side(x, b, c), dt, a_log, d), probe.reshape(*x.shape[:2], -1).astype(dtype), (groups, b.shape[3])


# four heads of 8 in one group (four heads a lane tile) and in two (two a tile); 40 positions = 2.5 chunks of 16
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("groups", [1, 2])
def test_the_token_major_kernels_are_the_plain_form(groups, dtype):
    """The kernels (interpreted) read x', B and C out of ONE ``xbc`` array, scale
    by dt and add the skip themselves; the plain form splits it and works by
    head. Values and the gradients of ``xbc`` (x', B and C in their lanes), dt,
    A_log and D, the same arithmetic: 1e-5 in float32; in bfloat16 the kernels
    round ``y`` once, after the skip, and ``dx'`` once, after both its terms."""
    args, probe, (g, n) = packed(groups=groups, dtype=dtype)
    assert ssd.heads_a_tile(4 // groups, 8) == 4 // groups
    tol = 1e-5 if dtype == jnp.float32 else 2e-2

    @functools.partial(jax.jit, static_argnums=0)
    def run(form):
        y, vjp = jax.vjp(lambda *a: ssd.ssd(*a, g, n, chunk=16, form=form)[0], *args)
        return (y, *vjp(probe))

    got, want = run(ssd.INTERPRET), run(ssd.PLAIN)
    assert got[0].dtype == dtype and got[0].shape == probe.shape and got[1].shape == args[0].shape
    lanes = {"x'": slice(0, 32), "B": slice(32, 32 + g * n), "C": slice(32 + g * n, None)}
    for name, a, b in zip(("y", "d_xbc", "d_dt", "d_a_log", "d_D"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        for part, at in (lanes.items() if name == "d_xbc" else [("", slice(None))]):
            np.testing.assert_allclose(a[..., at], b[..., at], atol=tol * float(np.max(np.abs(b[..., at]))), rtol=tol,
                                       err_msg=f"{name} {part}")


def _eqns_outside_kernels(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, a ``pallas_call`` taken whole."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns_outside_kernels(sub)


def test_nothing_of_a_streams_size_runs_outside_the_kernels():
    """The pin that keeps the passes from coming back: in the kernel form the
    jaxpr of ``ssd`` holds, outside its ``pallas_call``, no operation at all over
    an array of x's size (no transpose, no multiply by dt, no skip, no split or
    reshape of ``xbc``); its gradient's holds two, the updates in place that
    put dB and dC into their lanes of the array the kernel wrote dx' into (G N
    lanes each: no concatenation moves the H P lanes of dx' again)."""
    args, probe, (g, n) = packed(t=32)
    stream = probe.size

    def big(jaxpr):
        found = [e for e in _eqns_outside_kernels(jaxpr.jaxpr)
                 if any(getattr(v.aval, "size", 0) >= stream for v in (*e.invars, *e.outvars))]
        return sorted(e.primitive.name for e in found)

    def scan(*a):
        return ssd.ssd(*a, g, n, chunk=16, form=ssd.INTERPRET)[0]

    assert big(jax.make_jaxpr(scan)(*args)) == ["custom_vjp_call", "pallas_call"]
    backward = big(jax.make_jaxpr(lambda dy, *a: jax.vjp(scan, *a)[1](dy))(probe, *args))
    assert backward == ["dynamic_update_slice"] * 2 + ["pallas_call"] * 2, backward
    # the plain form, the same entry: there the passes are (what the kernels took over)
    plain = big(jax.make_jaxpr(lambda *a: ssd.ssd(*a, g, n, chunk=16, form=ssd.PLAIN)[0])(*args))
    assert "transpose" in plain and "mul" in plain and "split" in plain


@pytest.mark.parametrize("shape, takes", [
    ((64, 8, 64, 128, 128), True),      # the published mixer: eight heads a group, two a lane tile
    ((64, 8, 128, 128, 128), True),     # a head a tile
    ((64, 8, 256, 128, 128), True),     # a head two tiles
    ((32, 2, 32, 128, 256), True),      # four heads a tile, sixteen a group; chunks of 256
    ((64, 8, 64, 256, 128), True),      # a state of two lane tiles: x' is sixteen of B's blocks into xbc
    ((4, 2, 8, 16, 16), False),         # the tests' size: the plain form
    ((64, 16, 64, 128, 128), False),    # four heads a group: half a sublane tile of cum
    ((64, 8, 64, 64, 128), False),      # a state of half a lane tile
    ((64, 8, 64, 128, 64), False),      # a chunk of half a lane tile
    ((64, 8, 96, 128, 128), False),     # a head of 96 lanes: neither a whole number of tiles nor of heads a tile
    ((64, 8, 24, 128, 128), False),     # 192 lanes a group: a tile and a half
    ((24, 3, 16, 256, 128), False),     # 384 lanes of x': B's first block is no whole number of blocks of 256 into xbc
    ((60, 8, 64, 128, 128), False),     # heads that do not divide into the groups
])
def test_which_shapes_the_kernels_take(shape, takes):
    assert ssd.kernel_takes(*shape) == takes
    assert ssd.choose_form(*shape) == ssd.PLAIN                     # no TPU here


def test_the_scans_constants():
    assert ssd.CHUNK == 128 and ssd.CARRY_FLOOR == 1e-3
    assert ssd.heads_a_tile(8, 64) == 2 and ssd.heads_a_tile(8, 128) == 1 and ssd.heads_a_tile(2, 8) == 2


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
    g_plain = jax.jit(jax.grad(lambda *a: jnp.sum(short_conv.causal_conv_xla(*a) * probe), argnums=(0, 1, 2)))(u, w, bias)
    g_kernel = jax.jit(jax.grad(lambda *a: jnp.sum(short_conv.causal_conv_kernel(*a, 32, True) * probe),
                                argnums=(0, 1, 2)))(u, w, bias)
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
