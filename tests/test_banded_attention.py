"""The three flash kernels with a window (``flash_fwd``, ``flash_dq``,
``flash_dkv``), in interpret mode so that the CPU runs the kernel bodies,
against a dense banded mask: T not a multiple of the tile, the window both
under and over a tile, grouped-query heads. And the band's tile algebra
against the mask it stands for."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

from mpi_operator_tpu.kernels.flash_attention import (
    chunked_reference,
    flash_attention,
)
from mpi_operator_tpu.parallel.ring_attention import dense_attention

# the package exports the function under the module's name
fa = importlib.import_module("mpi_operator_tpu.kernels.flash_attention")


def _qkv(key, b=1, t=80, h=2, hkv=1, d=16):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, t, h, d), jnp.float32),
            jax.random.normal(kk, (b, t, hkv, d), jnp.float32),
            jax.random.normal(kv, (b, t, hkv, d), jnp.float32))


def _banded_by_hand(q, k, v, window):
    """Softmax over exactly the keys j with i - window < j <= i."""
    b, t, h, d = q.shape
    g = h // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * d ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = jnp.where((j <= i) & (j > i - window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)


# T = 80 with 32-wide tiles: two whole tiles and a ragged third
@pytest.mark.parametrize("window", [1, 7, 32, 33, 50, 80, 200])
def test_forward_matches_a_dense_banded_mask(window):
    q, k, v = _qkv(jax.random.PRNGKey(window))
    want = _banded_by_hand(q, k, v, window)
    got = flash_attention(q, k, v, causal=True, window=window, block_q=32,
                          block_k=32, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    for other in (
        dense_attention(q, k, v, causal=True, scale=16 ** -0.5,
                        window=window),
        chunked_reference(q, k, v, window=window, block_q=32),
        flash_attention(q, k, v, causal=True, window=window),  # off-TPU auto
    ):
        np.testing.assert_allclose(other, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window,bq,bk", [
    (7, 32, 32), (33, 32, 32), (50, 32, 16), (20, 16, 32), (80, 32, 32)])
def test_three_gradients_match_a_dense_banded_mask(window, bq, bk):
    q, k, v = _qkv(jax.random.PRNGKey(100 + window))
    cot = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.float32)

    def through(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * cot),
                        argnums=(0, 1, 2))(q, k, v)

    want = through(lambda q, k, v: _banded_by_hand(q, k, v, window))
    got = through(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=bq, block_k=bk,
        interpret=True))
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("window,bq,bk", [
    (1, 8, 8), (5, 8, 8), (8, 8, 8), (9, 8, 8), (20, 8, 16), (20, 16, 8),
    (1000, 8, 8)])
def test_tile_algebra_agrees_with_the_mask(window, bq, bk):
    """A tile is open iff some (query, key) pair in it is seen; the clamps
    name the first and last open tile."""
    t = 64
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & (j > i - window)
    n_q, n_k = t // bq, t // bk
    for qi in range(n_q):
        open_k = [ki for ki in range(n_k)
                  if seen[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk].any()]
        says = [ki for ki in range(n_k)
                if bool(fa._tile_open(qi, ki, bq, bk, True, window))]
        assert says == open_k
        clamped = {int(fa._k_tile_clamp(qi, ki, bq, bk, True, window))
                   for ki in range(n_k)}
        assert clamped == set(open_k)
    for ki in range(n_k):
        open_q = [qi for qi in range(n_q)
                  if seen[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk].any()]
        clamped = {min(int(fa._q_tile_clamp(ki, qi, bq, bk, True, window)),
                       n_q - 1) for qi in range(n_q)}
        assert clamped == set(open_q)


def test_a_window_needs_causal_attention():
    q, k, v = _qkv(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0)
