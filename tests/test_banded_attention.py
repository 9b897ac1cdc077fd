"""The three flash kernels with a window (``flash_fwd``, ``flash_dq``,
``flash_dkv``), in interpret mode so that the CPU runs the kernel bodies,
against a dense banded mask: T not a multiple of the tile, the window both
under and over a tile, grouped-query heads, the tile the window picks. And
the band walk's tile algebra against the mask it stands for, and the grid
and tile each call builds."""

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


def _walks_visit_the_opened_tiles(t, window, bq, bk):
    """Each band walk's live steps visit exactly the tiles in which some
    (query, key) pair is seen, each once and in order; a dead step repeats
    a live tile's index (so its DMA is elided); and the walk is no longer
    than the most tiles one row or column of tiles opens."""
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & (j > i - window)
    n_q, n_k = -(-t // bq), -(-t // bk)
    opened = np.array([[seen[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk].any()
                        for ki in range(n_k)] for qi in range(n_q)])
    for walk, n_outer, n_inner, by_outer in (
            (fa._band_k_walk, n_q, n_k, opened),
            (fa._band_q_walk, n_k, n_q, opened.T)):
        steps = fa._band_steps(walk, n_outer, n_inner, bq, bk, window, n_inner)
        assert steps == by_outer.sum(axis=1).max()
        tile, live = (np.asarray(a) for a in walk(
            np.arange(n_outer)[:, None], np.arange(steps)[None, :],
            bq, bk, window, n_inner))
        for outer in range(n_outer):
            assert tile[outer][live[outer]].tolist() == \
                np.flatnonzero(by_outer[outer]).tolist()
            assert set(tile[outer].tolist()) == set(
                tile[outer][live[outer]].tolist())


@pytest.mark.parametrize("window,bq,bk", [
    (1, 8, 8), (5, 8, 8), (8, 8, 8), (9, 8, 8), (20, 8, 16), (20, 16, 8),
    (1000, 8, 8)])
def test_tile_algebra_agrees_with_the_mask(window, bq, bk):
    """T = 64, whole tiles."""
    _walks_visit_the_opened_tiles(64, window, bq, bk)


@pytest.mark.parametrize("window,bq,bk", [
    (1, 8, 8), (7, 16, 16), (16, 16, 16), (33, 32, 32), (50, 32, 16),
    (20, 16, 32), (79, 8, 24), (80, 32, 8), (200, 32, 32)])
def test_tile_algebra_agrees_with_the_mask_at_a_ragged_length(window, bq, bk):
    """T = 80, the last tile cut short."""
    _walks_visit_the_opened_tiles(80, window, bq, bk)


@pytest.mark.parametrize("window,tile,steps,tiles_1024", [
    (512, 512, 2, 7.75), (1024, 512, 3, 11.25),
    (512, 1024, 2, 15), (1024, 1024, 2, 15)])
def test_the_band_walk_at_the_cells_length(window, tile, steps, tiles_1024):
    """At the window cells' 8192 positions, by arithmetic alone: a tile is
    open iff its first key is no later than its last query and its last key
    lies in its first query's window. Both walks visit exactly those tiles;
    a 512-key window in 512² tiles computes 7.75 tiles of 1024² a row of
    heads (15 in 1024² tiles), a 1024-key window 11.25."""
    t = 8192
    n = t // tile
    qi, ki = np.arange(n)[:, None], np.arange(n)[None, :]
    opened = (ki * tile <= (qi + 1) * tile - 1) & (
        (ki + 1) * tile - 1 > qi * tile - window)
    k_steps = fa._band_steps(fa._band_k_walk, n, n, tile, tile, window, n)
    q_steps = fa._band_steps(fa._band_q_walk, n, n, tile, tile, window, n)
    assert k_steps == q_steps == steps
    k_tile, k_live = (np.asarray(a) for a in fa._band_k_walk(
        qi, np.arange(steps)[None, :], tile, tile, window, n))
    q_tile, q_live = (np.asarray(a) for a in fa._band_q_walk(
        np.arange(n)[:, None], np.arange(steps)[None, :], tile, tile,
        window, n))
    by_k_walk = np.zeros((n, n), bool)
    by_q_walk = np.zeros((n, n), bool)
    for row in range(n):
        by_k_walk[row, k_tile[row][k_live[row]]] = True
        by_q_walk[q_tile[row][q_live[row]], row] = True
    assert (by_k_walk == opened).all() and (by_q_walk == opened).all()
    assert k_live.sum() == q_live.sum() == opened.sum()
    assert opened.sum() * tile ** 2 / 1024 ** 2 == tiles_1024


@pytest.mark.parametrize("window,t,tile", [
    (512, 8192, 512), (1024, 8192, 512), (1023, 8192, 512),
    (2048, 8192, 1024), (4096, 8192, 1024), (1, 8192, 512),
    (512, 300, 300), (4096, 700, 700)])
def test_the_tile_a_window_picks(window, t, tile):
    """Half the window, at least 512 and at most 1024, never past T."""
    assert fa._band_tile(window, t) == tile


def _pallas_calls(jaxpr):
    """(name, grid, the sequence axis of each block) of every pallas_call."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            gm = eqn.params["grid_mapping"]
            out.append((eqn.params["name"], tuple(gm.grid), {
                getattr(bm.block_shape[2], "block_size", bm.block_shape[2])
                for bm in gm.block_mappings}))
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                out += _pallas_calls(sub)
    return out


@pytest.mark.parametrize("window,grid,tile", [
    (None, (1, 2, 2, 2), 1024), (512, (1, 2, 4, 2), 512),
    (1024, (1, 2, 4, 3), 512), (2048, (1, 2, 2, 2), 1024)])
def test_the_grid_and_tile_each_call_builds(window, grid, tile):
    """Without a window the three kernels keep the (b, h, n_qb, n_kb) grid
    in 1024² tiles; with one, the grid's last axis is the band walk's steps
    in the tile the window picks (at T 2048: a 2048-key window's walk is
    every tile there is)."""
    q, k, v = _qkv(jax.random.PRNGKey(0), t=2048)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=window, interpret=True)),
        argnums=(0, 1, 2)))(q, k, v)
    calls = _pallas_calls(jaxpr.jaxpr)
    assert [c[0] for c in calls] == ["flash_fwd", "flash_dq", "flash_dkv"]
    assert all(c[1:] == (grid, {tile}) for c in calls)


# T = 1300: two whole 512² tiles and a ragged third
@pytest.mark.parametrize("window", [512, 1024, 700])
def test_forward_and_gradients_at_the_band_tile(window):
    """The tile the window picks (512 for each of these), with the window
    one tile, two tiles and no whole number of tiles wide: the forward and
    the three gradients in interpret mode against the dense banded mask."""
    q, k, v = _qkv(jax.random.PRNGKey(window), t=1300)
    cot = jax.random.normal(jax.random.PRNGKey(6), q.shape, jnp.float32)

    def through(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return out, vjp(cot)

    want = through(lambda q, k, v: _banded_by_hand(q, k, v, window))
    got = through(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=True))
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-5)
    for name, a, b in zip("qkv", got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


def test_a_window_needs_causal_attention():
    q, k, v = _qkv(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0)


@pytest.mark.parametrize("heads", [6, 8])
def test_groups_of_six_and_eight_under_a_window_of_512(heads):
    """The gated-heads configuration's attention: 48 or 64 query heads over
    8 KV heads (groups 6 and 8) and a window of 512 keys, here one KV head's
    group over 1024 positions in tiles of 256 (the window two tiles wide):
    the forward and the three gradients against the dense banded mask."""
    q, k, v = _qkv(jax.random.PRNGKey(heads), t=1024, h=heads, hkv=1)
    cot = jax.random.normal(jax.random.PRNGKey(3), q.shape, jnp.float32)

    def through(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * cot),
            argnums=(0, 1, 2))(q, k, v)

    want = through(lambda q, k, v: _banded_by_hand(q, k, v, 512))
    got = through(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=512, block_q=256, block_k=256,
        interpret=True))
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for name, a, b in zip("qkv", got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")
