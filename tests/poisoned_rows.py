"""The routed feed-forward with every row past the held ones poisoned.

What ``parallel/moe.py`` promises of its assignment buffers is that no
result depends on a row past the held ones. On the chip such a row holds
whatever the buffer held, which a test cannot choose; here it holds NaN:
``patched`` puts NaN into every row from H on of every operand of a grouped
product and of a row-order pass before the pass runs, and into every row
the pass does not own of what it gives (a grouped product owns the rows
``[0, H)``, a ``kernels.row_map`` the rows of the tiles it visits), going
forward and coming back. Used by tests/test_moe.py (the kernels under the
Pallas interpreter) and tests_tpu/test_moe_on_tpu.py (compiled).
``the_layers_maps`` is the layer's four row-order passes as
``kernels.row_map`` takes them, for the tests that compile and time them at
the benchmark cell's shapes."""

import contextlib

import jax
import jax.numpy as jnp
import pytest

from mpi_operator_tpu.kernels import row_map
from mpi_operator_tpu.kernels.grouped_matmul import grouped_matmul
from mpi_operator_tpu.parallel import moe


@jax.custom_vjp
def poison(a, rows):
    """``a`` with NaN in every row from ``rows`` on; its cotangent too."""
    here = (jnp.arange(a.shape[0]) < rows).reshape(-1, *[1] * (a.ndim - 1))
    return jnp.where(here, a, jnp.nan)


poison.defvjp(lambda a, rows: (poison(a, rows), rows),
              lambda rows, d_a: (poison(d_a, rows), None))


def the_layers_maps(three_wide, a_row, tokens, row_of):
    """{name: (body, operands, outs)}: ``three_wide`` three buffers ``[R,
    W]`` (or their shapes), ``a_row`` a number a row, ``tokens`` ``[N, W]``
    what the combine's transpose gathers its first operand from by
    ``row_of``."""
    a, b, c = three_wide
    wide = (a.shape[1], a.dtype)
    return {
        "moe_silu_up": (moe._silu_up, (a, b), (wide,)),
        "moe_silu_up_t": (moe._silu_up_t, (a, b, c), (wide, wide)),
        "moe_add": (moe._add, (a, b), (wide,)),
        "moe_combine_t": (moe._combine_t, ((tokens, row_of), b, a_row),
                          (wide, (None, jnp.float32))),
    }


@contextlib.contextmanager
def patched(interpret: bool):
    """``moe`` on the bounded path whatever the backend and the shapes'
    share of the experts, its kernels interpreted or compiled, every buffer
    poisoned around every pass."""
    real_map = row_map.row_map

    def grouped(xs, w, sizes, precision):
        rows = jnp.sum(sizes)
        return poison(grouped_matmul(poison(xs, rows), w, sizes,
                                     interpret=interpret), rows)

    def mapped(body, operands, outs, rows, *, name, interpret):
        # a gathered operand's source is in its own order: not a buffer
        results = real_map(
            body, [a if isinstance(a, tuple) else poison(a, rows)
                   for a in operands], outs, rows, name=name,
            interpret=interpret)
        worked = row_map.rows_worked(rows, results[0].shape[0])
        return [poison(a, worked) for a in results]

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "_grouped", grouped)
        patch.setattr(moe, "_row_passes", lambda *shapes: interpret)
        patch.setattr(row_map, "row_map", mapped)
        yield
