"""kernels/row_map.py: a map over the first rows of a buffer, its kernel
under the Pallas interpreter, with the routed feed-forward's four bodies
(parallel/moe.py). The interpreter starts a result as NaN, so a row the
kernel did not write reads NaN here (on the chip: whatever the buffer held).
The chip's half is tests_tpu/test_moe_on_tpu.py; the cell's shapes compile
in tests/test_grouped_matmul.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.kernels import row_map
from mpi_operator_tpu.parallel import moe

R, W = 2048, 256
TILE = 512
BF16, F32 = jnp.bfloat16, jnp.float32


def _buffer(i, width=W, dtype=BF16):
    return jax.random.normal(jax.random.PRNGKey(i), (R, width), dtype)


N = 384  # rows of the array that ``t`` gathers from


def _buffers():
    """Three buffers and a number a row; ``t`` is ``d`` as the pair it is
    gathered from, a shorter array and each row's place in it."""
    source = jax.random.normal(jax.random.PRNGKey(5), (N, W), BF16)
    row_of = jax.random.randint(jax.random.PRNGKey(6), (R,), 0, N)
    return dict(g=_buffer(1), u=_buffer(2), d=source[row_of],
                w=_buffer(4, 1, F32)[:, 0], t=(source, row_of))


def _in_numpy(name, g, u, d, w, t=None):
    """Each map's expression in ``jax.numpy`` over every row, float32
    inside: what the layer computed before the passes were bounded."""
    g32, u32, d32 = (a.astype(F32) for a in (g, u, d))
    if name == "moe_silu_up":
        return [jax.nn.silu(g32) * u32]
    if name == "moe_silu_up_t":  # autodiff's, of the line above
        return list(jax.vjp(lambda a, b: jax.nn.silu(a) * b, g32, u32)[1](d32))
    if name == "moe_add":
        return [g32 + u32]
    # moe_combine_t, its first operand a buffer or gathered into one
    return [d32 * w[:, None], jnp.sum(d32 * u32, axis=-1)]


MAPS = {
    "moe_silu_up": (moe._silu_up, "gu", ((W, BF16),)),
    "moe_silu_up_t": (moe._silu_up_t, "dgu", ((W, BF16), (W, BF16))),
    "moe_add": (moe._add, "gu", ((W, BF16),)),
    "moe_combine_t": (moe._combine_t, "duw", ((W, BF16), (None, F32))),
    "moe_combine_t, gathering": (moe._combine_t, "tuw",
                                 ((W, BF16), (None, F32))),
}


@pytest.mark.parametrize("rows", [0, 1, TILE - 1, TILE, TILE + 1, R])
@pytest.mark.parametrize("name", sorted(MAPS))
def test_a_map_is_its_expression_on_the_held_rows_and_stops_there(name, rows):
    body, takes, outs = MAPS[name]
    buffers = _buffers()
    assert row_map.row_tile(R) == TILE
    worked = -(-rows // TILE) * TILE
    assert int(row_map.rows_worked(rows, R)) == worked
    got = row_map.row_map(body, [buffers[t] for t in takes], outs,
                          jnp.int32(rows), name=name.split(",")[0],
                          interpret=True)
    want = _in_numpy(name, **buffers)
    assert len(got) == len(want) == len(outs)
    for a, b, (width, dtype) in zip(got, want, outs):
        assert a.shape == ((R, width) if width else (R,))
        assert a.dtype == dtype
        a = np.asarray(a, np.float32)
        # one rounding of a float32 result to the buffer's dtype
        np.testing.assert_allclose(
            a[:worked], np.asarray(b.astype(dtype), np.float32)[:worked],
            rtol=2 ** -7 if dtype == BF16 else 1e-5, atol=1e-6)
        assert np.all(np.isnan(a[worked:]))  # never written


@pytest.mark.parametrize("name", sorted(MAPS))
def test_off_the_kernel_a_map_is_its_body_over_every_row(name):
    body, takes, outs = MAPS[name]
    buffers = _buffers()
    got = row_map.row_map(body, [buffers[t] for t in takes], outs, 5,
                          name=name.split(",")[0])
    for a, b, (width, dtype) in zip(got, _in_numpy(name, **buffers), outs):
        assert a.shape == b.shape and a.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b.astype(dtype), np.float32),
            rtol=2 ** -7 if dtype == BF16 else 1e-5, atol=1e-6)


def test_a_map_is_float32_inside_and_rounds_once():
    """bf16 buffers, and no bf16 arithmetic: every operation of the kernel's
    body but the loads' and stores' converts is float32."""
    closed = jax.make_jaxpr(lambda g, u: row_map.row_map(
        moe._silu_up, (g, u), ((W, BF16),), 7, name="moe_silu_up",
        interpret=True))(_buffer(1), _buffer(2))

    def arithmetic(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("mul", "logistic", "exp", "div", "add"):
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from arithmetic(sub)

    seen = [e for e in arithmetic(closed.jaxpr)
            if e.outvars[0].aval.shape[-1:] == (W,)]
    assert seen and all(e.outvars[0].aval.dtype == F32 for e in seen)


def test_shapes_the_kernel_takes():
    assert row_map.mappable(131072, 2304, 896) and row_map.mappable(1152, 128)
    assert not row_map.mappable(131072, 100) and not row_map.mappable(144, 128)
    assert row_map.row_tile(131072) == 512 and row_map.row_tile(1152) == 128


def test_a_row_of_bf16_goes_through_words_as_it_was():
    """What the kernel gathers from: two bf16 columns a uint32 word, taken
    apart again to the float32 values they were, bit for bit."""
    a = _buffer(7).at[0, :4].set(
        jnp.array([0.0, -0.0, jnp.inf, -1e-30], BF16))
    words = row_map._words(a)
    assert words.shape == (R, W // 2) and words.dtype == jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(row_map._unworded(words)), np.asarray(a, np.float32))


def test_where_the_source_cannot_be_held_the_rows_are_gathered_before():
    wide = jax.ShapeDtypeStruct((16384, 2304), BF16)
    assert row_map._gathers_inside(wide)  # the cell's: 75 MB
    for shape, dtype in (((65536, 2304), BF16), ((16384, 896), BF16),
                         ((16384, 2304), F32)):
        assert not row_map._gathers_inside(jax.ShapeDtypeStruct(shape, dtype))
