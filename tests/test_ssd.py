"""The chunked state-space scan and the short convolution (kernels/ssd.py)
against the recurrence they stand for, written here as a plain loop over
positions: at the published starting values (A = 1 .. H, dt in [0.001,
0.1]) and at the benchmark configuration's draw (A_log std 8, dt_bias std
1: heads that forget within a chunk beside heads that carry state over
many), over more than two chunks, forward and gradient; with the fault
planted (states not passed between chunks) failing the same comparison."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.kernels import ssd

B, T, H, P, G, N, CHUNK = 2, 40, 4, 8, 2, 16, 8


def recurrence(x, dt, a, b, c):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t . C_t, a
    position a step in float32; S . C a multiply and a sum (on a TPU no
    matrix unit rounds it). Shapes as ``ssd.scan`` takes them."""
    bsz, _t, h, p = x.shape
    g, n = b.shape[2:]
    per_head = lambda v: jnp.repeat(v, h // g, axis=2)
    b, c = per_head(b), per_head(c)

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.sum(s * c_t[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((bsz, h, p, n), jnp.float32), tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)




def _inputs(kind):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    b = jax.random.normal(ks[1], (B, T, G, N))
    c = jax.random.normal(ks[2], (B, T, G, N))
    raw = jax.random.normal(ks[3], (B, T, H))
    if kind == "published":
        a_log = jnp.log(jnp.arange(1.0, H + 1))
        dt0 = jnp.exp(jax.random.uniform(ks[4], (H,))
                      * (np.log(0.1) - np.log(0.001)) + np.log(0.001))
        dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
        raw = 0.1 * raw
    else:  # the configuration's draw, heads of both regimes by hand
        a_log = jnp.array([-9.0, -3.0, 0.5, 28.0])  # |A| to 1.4e12
        dt_bias = jax.random.normal(ks[5], (H,))
    return x, raw, dt_bias, a_log, b, c


def _through(scan, args):
    x, raw, dt_bias, a_log, b, c = args
    dt = jax.nn.softplus(raw + dt_bias)
    return scan(x, dt, -jnp.exp(a_log), b, c)


@pytest.mark.parametrize("kind", ["published", "draw"])
def test_chunked_scan_is_therecurrence_forward_and_gradient(kind):
    args = _inputs(kind)
    chunked = lambda *a: ssd.scan(*a, chunk=CHUNK)
    faulty = lambda *a: ssd.scan(*a, chunk=CHUNK, pass_states=False)
    want = _through(recurrence, args)
    got = _through(chunked, args)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=1e-4)
    # the fault: every chunk starts from nought. The first chunk agrees,
    # the later ones do not, by far more than the comparison allows
    bad = _through(faulty, args)
    np.testing.assert_allclose(bad[:, :CHUNK], want[:, :CHUNK],
                               atol=2e-5 * scale, rtol=1e-4)
    assert float(jnp.max(jnp.abs(bad - want))) > 1e-2 * scale

    weights = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    loss = lambda scan: lambda *a: jnp.sum(_through(scan, a) * weights)
    grads = lambda scan: jax.grad(loss(scan), argnums=range(6))(*args)
    want_g, got_g, bad_g = grads(recurrence), grads(chunked), grads(faulty)
    for name, w, g, f in zip(("x", "dt", "dt_bias", "A_log", "B", "C"),
                             want_g, got_g, bad_g):
        norm = float(jnp.linalg.norm(w))
        assert float(jnp.linalg.norm(g - w)) < 1e-4 * norm, name
        assert float(jnp.linalg.norm(f - w)) > 1e-2 * norm, name


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused():
    x, raw, dt_bias, a_log, b, c = _inputs("published")
    with pytest.raises(ValueError, match="whole number of chunks"):
        ssd.scan(x, jax.nn.softplus(raw), -jnp.exp(a_log), b, c, chunk=16)


def test_bf16_operands_accumulate_in_float32():
    args = _inputs("draw")
    want = _through(recurrence, args)
    x, raw, dt_bias, a_log, b, c = args
    half = lambda v: v.astype(jnp.bfloat16)
    got = _through(lambda *a: ssd.scan(*a, chunk=CHUNK),
                   (half(x), raw, dt_bias, a_log, half(b), half(c)))
    assert got.dtype == jnp.bfloat16
    err = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert 1e-4 < err < 2e-2  # bf16's rounding, not float32's and no more


def test_carry_share_counts_the_chunks_that_hand_state_on():
    dt = jnp.full((1, 4 * CHUNK, 3), 0.1)
    # whole-chunk decays exp(8 x 0.1 x A): 0.92, 0.10 - a hair, 3e-4
    a = -jnp.array([0.1, np.log(10.0) / 0.8 + 1e-3, 10.0])
    assert float(ssd.carry_share(dt, a, chunk=CHUNK)) == pytest.approx(1 / 3)


def test_the_convolution_is_the_plain_loop_at_the_sequences_start():
    k, channels = 4, 6
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, channels))
    w = jax.random.normal(jax.random.PRNGKey(1), (channels, k))
    bias = jax.random.normal(jax.random.PRNGKey(2), (channels,))
    got = np.asarray(ssd.causal_conv(x, w, bias))
    xn, wn = np.asarray(x), np.asarray(w)
    for t in range(10):
        want = np.asarray(bias).copy()
        for tap in range(k):
            src = t - (k - 1) + tap
            if src >= 0:  # nought before the start
                want = want + wn[:, tap] * xn[:, src]
        np.testing.assert_allclose(got[:, t], want, rtol=1e-5, atol=1e-6)
    # each channel alone, and no position sees a later one
    moved = x.at[:, 5:, 0].add(1.0)
    diff = np.asarray(ssd.causal_conv(moved, w, bias)) - got
    assert np.all(diff[:, :5] == 0) and np.all(diff[:, :, 1:] == 0)
    assert np.any(diff[:, 5:, 0] != 0)
