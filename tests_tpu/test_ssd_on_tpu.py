"""The chunked state-space scan (kernels/ssd.py) compiled ON the TPU chip.

tests/test_ssd.py checks the chunked form against the recurrence on the CPU
in float32; this is the hardware half, at the benchmark configuration's
sizes a head (H = 64 heads of P = 64, 8 groups, state N = 128, chunks of
128) over T = 1024, eight chunks: bf16 operands as the model gives them
against the literal recurrence in float32 (a multiply and a sum, no matrix
unit), heads that forget within a chunk beside heads that carry state over
all eight; with the fault planted (states not passed) failing; and timed."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from mpi_operator_tpu.kernels import ssd
from tests.test_ssd import recurrence

B, T, H, P, G, N, CHUNK = 2, 1024, 64, 64, 8, 128, 128


def _inputs():
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    b = jax.random.normal(ks[1], (B, T, G, N)) * N ** -0.5
    c = jax.random.normal(ks[2], (B, T, G, N))
    # the configuration's draw: A_log std 8, dt_bias std 1
    a = -jnp.exp(8.0 * jax.random.normal(ks[3], (H,)))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (B, T, H))
                         + jax.random.normal(ks[5], (H,)))
    return x, dt, a, b, c


def test_chunked_scan_in_bf16_is_the_float32recurrence_on_the_chip():
    x, dt, a, b, c = _inputs()
    share = float(ssd.carry_share(dt, a, chunk=CHUNK))
    assert 0.15 < share < 0.6  # both regimes among the 64 heads
    half = lambda v: v.astype(jnp.bfloat16)
    rounded = lambda v: half(v).astype(jnp.float32)
    want = jax.jit(recurrence)(rounded(x), dt, a, rounded(b), rounded(c))
    chunked = jax.jit(lambda *v: ssd.scan(*v, chunk=CHUNK))
    got = chunked(half(x), dt, a, half(b), half(c)).astype(jnp.float32)
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    err = float(jnp.sqrt(jnp.mean((got - want) ** 2))) / scale
    assert np.isfinite(err) and err < 2e-2, err  # bf16's rounding
    faulty = jax.jit(lambda *v: ssd.scan(*v, chunk=CHUNK, pass_states=False))
    bad = faulty(half(x), dt, a, half(b), half(c)).astype(jnp.float32)
    bad_err = float(jnp.sqrt(jnp.mean((bad - want) ** 2))) / scale
    assert bad_err > 10 * err, (err, bad_err)
    # float32 operands: the same products at the matrix unit's default
    # precision, the decays and the states float32 throughout
    got32 = chunked(rounded(x), dt, a, rounded(b), rounded(c))
    err32 = float(jnp.sqrt(jnp.mean((got32 - want) ** 2))) / scale
    assert err32 < 2e-2, err32

    jax.block_until_ready(got)
    t0 = time.perf_counter()
    for _ in range(10):
        out = chunked(half(x), dt, a, half(b), half(c))
    jax.block_until_ready(out)
    print(f"chunked scan forward, {B} x {T}: "
          f"{(time.perf_counter() - t0) * 100:.2f} ms; rms error {err:.2e} "
          f"(states not passed: {bad_err:.2e}), carry share {share:.2f}")
