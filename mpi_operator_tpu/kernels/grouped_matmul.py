"""Grouped matrix product (forward and both transposes) as Pallas TPU kernels.

``grouped_matmul(xs [R, K], w [G, K, N], group_sizes [G]) -> [R, N]``: the
rows of ``xs`` are sorted by group, group g owns ``group_sizes[g]`` rows
after those of the groups before it, and each group's rows meet that
group's matrix. It is what ``lax.ragged_dot`` computes; the routed
feed-forward (parallel/moe.py) makes its three expert products with it.
Written after ``jax.experimental.pallas.ops.tpu.megablox`` (Apache-2.0),
with only what that layer uses.

Three kernels, named in the device trace:

- ``moe_gmm``: ``xs[rows of g] @ w[g]``;
- ``moe_gmm_dx``: ``d_out[rows of g] @ w[g].T``, the same kernel reading
  ``w`` through a transposing contraction (no transposed copy in HBM);
- ``moe_gmm_dw``: ``xs[rows of g].T @ d_out[rows of g]`` for every g.

The grid runs over *visits*: one for every (row tile, group) pair that
shares a row, in row order. A tile inside one group is visited once; a tile
that a boundary cuts is visited once a group, consecutively, with the other
groups' rows masked (and, in the first two kernels, the chunks of 128 rows
that hold none of the group's passed over). Group offsets, each visit's group and row tile, and
the number of visits are scalar prefetch, and the grid's extent is that
number: rows past the last group are in no visited tile but the one the
last boundary cuts, so they are never read or written (what ``out`` holds
there is whatever the buffer held).

Numerics: operands in their own dtype (bf16 from the caller), every
contraction accumulated in float32 (the MXU's accumulator, and a float32
VMEM scratch across grid steps), results cast once to the operands' dtype:
what ``lax.ragged_dot`` and its transposes give for bf16 operands.

Tiles come from the shapes: ``tm`` the largest power of two up to 512 that
divides R; ``tk`` and ``tn`` the largest multiples of 128 that divide K and
N and fit the VMEM budget double-buffered (whole widths for experts 896
and 2304 wide: a product is then ~80 grid steps of MXU work, not the ~8,000
of 128 x 128 tiles). A width that is no multiple of 128 (experts 1856 wide)
is taken whole, as the one block that may end off a lane tile: one equal to
the array's extent.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What the three kernels may hold in VMEM (blocks double-buffered, the
# float32 accumulator and one float32 product beside it). A v5e core has
# 128 MiB; Mosaic's default scoped limit of 16 MiB is what stops whole-width
# tiles, so each call states its own.
_VMEM_BUDGET = 48 << 20
_VMEM_LIMIT = 64 << 20
_MAX_TM = 512
_LANE = 128
_MIN_TM = 16  # a packed bf16 tile's rows
_ROW_CHUNK = 128  # rows of a tile that one product inside a kernel takes


def _row_tile(r: int) -> int:
    """The largest power of two <= 512 that divides r."""
    return min(r & -r, _MAX_TM)


def _lane_tiles(width: int):
    """Multiples of 128 that divide ``width``, largest first; of a width
    that is no multiple of 128, the whole width alone."""
    if width % _LANE:
        return [width]
    return [d * _LANE for d in range(width // _LANE, 0, -1)
            if width % (d * _LANE) == 0]


def _gmm_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    blocks = tm * tk + tk * tn + tm * tn
    return 2 * blocks * itemsize + 2 * tm * tn * 4


def _dw_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    blocks = tm * tk + tm * tn + tk * tn
    return 2 * blocks * itemsize + 2 * tk * tn * 4


def _tiles(r: int, k: int, n: int, itemsize: int, footprint):
    """(tm, tk, tn): whole widths where they fit, else the widest ``tk``
    (what is contracted stays in one step) and then the widest ``tn``."""
    tm = _row_tile(r)
    for tk in _lane_tiles(k):
        for tn in _lane_tiles(n):
            if footprint(tm, tk, tn, itemsize) <= _VMEM_BUDGET:
                return tm, tk, tn
    raise ValueError(f"no tile of a {k} x {n} product fits the VMEM budget")


def whole_or_tiled(width: int) -> bool:
    """A width a block can span: a multiple of 128 (tiled), or one past 128
    in whole packed sublanes (a single whole-width block)."""
    return width % _LANE == 0 or (width > _LANE and width % _MIN_TM == 0)


def tileable(r: int, k: int, n: int) -> bool:
    """Whether the kernels take these shapes as they are (no padding):
    widths in whole packed sublanes (a multiple of 128 is tiled, another
    is one whole-width block), and tiles that fit the VMEM budget."""
    if r % _MIN_TM or not (whole_or_tiled(k) and whole_or_tiled(n)):
        return False
    try:
        _tiles(r, k, n, 2, _gmm_bytes)
        _tiles(r, k, n, 2, _dw_bytes)
    except ValueError:
        return False
    return True


def _visits(group_sizes, r: int, tm: int, *, empty_groups: bool):
    """(offsets [G + 1], group of each visit, row tile of each visit, number
    of visits). The two lists have room for the most there can be, R / tm
    tiles and a boundary inside a tile for every group but the first; past
    the number of visits they repeat the last one. With ``empty_groups`` a
    group with no row is visited once all the same (its result is written:
    zeros)."""
    g = group_sizes.shape[0]
    n_tiles = r // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = jnp.minimum(starts // tm, n_tiles - 1)
    count = jnp.where(sizes > 0, (ends + tm - 1) // tm - first,
                      1 if empty_groups else 0)
    until = jnp.cumsum(count)
    total = until[-1]
    visit = jnp.minimum(jnp.arange(n_tiles + g - 1, dtype=jnp.int32),
                        jnp.maximum(total - 1, 0))
    group = jnp.minimum(
        jnp.searchsorted(until, visit, side="right").astype(jnp.int32), g - 1)
    tile = first[group] + visit - (until[group] - count[group])
    return offsets, group, jnp.clip(tile, 0, n_tiles - 1), total


def _mine(offsets_ref, group, first_row, rows: int):
    """[rows, 1]: which of the rows from ``first_row`` on are the group's."""
    ids = first_row + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return jnp.logical_and(ids >= offsets_ref[group],
                           ids < offsets_ref[group + 1])


def _gmm_kernel(offsets_ref, group_ref, tile_ref, x_ref, w_ref, o_ref,
                *scratch, tm: int, tiles_k: int, transpose_w: bool):
    """Grid (n tiles, visits, k tiles). x [tm, tk]; w [tk, tn], or [tn, tk]
    contracted over its second dimension; o [tm, tn], revisited while
    consecutive visits share the row tile. The tile is worked through in
    chunks of rows by a loop (the compiler unrolls a product, not a loop:
    a quarter of the code), and a chunk with no row of this visit's group,
    as most of a tile that a boundary cuts, is passed over."""
    v = pl.program_id(1)
    ki = pl.program_id(2)
    group = group_ref[v]
    tr = min(tm, _ROW_CHUNK)
    dims = (((1,), (1 if transpose_w else 0,)), ((), ()))

    def chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * tr, tr), tr)
        first_row = tile_ref[v] * tm + c * tr

        def emit(acc):
            mine = _mine(offsets_ref, group, first_row, tr)
            o_ref[rows, :] = jnp.where(
                mine, acc, o_ref[rows, :].astype(jnp.float32)
            ).astype(o_ref.dtype)

        @pl.when(jnp.logical_and(first_row < offsets_ref[group + 1],
                                 first_row + tr > offsets_ref[group]))
        def _some_rows_are_the_groups():
            part = lax.dot_general(x_ref[rows, :], w_ref[...], dims,
                                   preferred_element_type=jnp.float32)
            if tiles_k == 1:
                emit(part)
                return
            acc_ref, = scratch

            @pl.when(ki == 0)
            def _first():
                acc_ref[rows, :] = part

            @pl.when(ki > 0)
            def _fold():
                acc_ref[rows, :] += part

            @pl.when(ki == tiles_k - 1)
            def _emit():
                emit(acc_ref[rows, :])

        return carry

    lax.fori_loop(0, tm // tr, chunk, 0)


# The two launchers are jitted so that a step's many products of one shape
# (a layer makes twelve, forward, replay and transposes) are traced once a
# process and lowered once a program: a kernel's body is Python to trace, and
# unjitted the routed cell's 48 calls added seconds to every start.
@functools.partial(jax.jit,
                   static_argnames=("transpose_w", "interpret", "name"))
def _gmm(x, w, group_sizes, *, transpose_w: bool, interpret: bool, name: str):
    """x [R, K] by group against w [G, K, N] (or, transposed, [G, N, K])."""
    r, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    tm, tk, tn = _tiles(r, k, n, x.dtype.itemsize, _gmm_bytes)
    tiles_k, tiles_n = k // tk, n // tn
    offsets, group, tile, total = _visits(group_sizes, r, tm,
                                          empty_groups=False)
    if transpose_w:
        w_spec = pl.BlockSpec(
            (None, tn, tk), lambda ni, v, ki, off, grp, til: (grp[v], ni, ki))
    else:
        w_spec = pl.BlockSpec(
            (None, tk, tn), lambda ni, v, ki, off, grp, til: (grp[v], ki, ni))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k,
                          transpose_w=transpose_w),
        out_shape=jax.ShapeDtypeStruct((r, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda ni, v, ki, off, grp, til: (til[v], ki)),
                w_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda ni, v, ki, off, grp, til: (til[v], ni)),
            grid=(tiles_n, total, tiles_k),
            scratch_shapes=([] if tiles_k == 1
                            else [pltpu.VMEM((tm, tn), jnp.float32)]),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * r * k * n, transcendentals=0,
            bytes_accessed=(r * k + r * n + w.size) * x.dtype.itemsize),
        interpret=interpret,
        name=name,
    )(offsets, group, tile, x, w)


def _dw_kernel(offsets_ref, group_ref, tile_ref, x_ref, dy_ref, o_ref,
               acc_ref, *, tm: int):
    """Grid (n tiles, k tiles, visits). x [tm, tk], dy [tm, tn], o [tk, tn]:
    one group's, written when the next visit is another group's."""
    v = pl.program_id(2)
    last_visit = pl.num_programs(2) - 1
    group = group_ref[v]
    first = jnp.logical_or(v == 0, group_ref[jnp.maximum(v - 1, 0)] != group)
    last = jnp.logical_or(
        v == last_visit, group_ref[jnp.minimum(v + 1, last_visit)] != group)
    # a select on both, in every visit (it hides behind the product, and one
    # product in the body is half the code): the other rows may hold anything
    mine = _mine(offsets_ref, group, tile_ref[v] * tm, tm)
    part = lax.dot_general(
        jnp.where(mine, x_ref[...], 0), jnp.where(mine, dy_ref[...], 0),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(first)
    def _first():
        acc_ref[...] = part

    @pl.when(jnp.logical_not(first))
    def _fold():
        acc_ref[...] += part

    @pl.when(last)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _gmm_dw(x, dy, group_sizes, *, interpret: bool, name: str):
    """x [R, K], dy [R, N] -> [G, K, N]: x[rows of g].T @ dy[rows of g]."""
    r, k = x.shape
    n = dy.shape[1]
    g = group_sizes.shape[0]
    tm, tk, tn = _tiles(r, k, n, x.dtype.itemsize, _dw_bytes)
    offsets, group, tile, total = _visits(group_sizes, r, tm,
                                          empty_groups=True)
    return pl.pallas_call(
        functools.partial(_dw_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((g, k, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda ni, ki, v, off, grp, til: (til[v], ki)),
                pl.BlockSpec((tm, tn),
                             lambda ni, ki, v, off, grp, til: (til[v], ni)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda ni, ki, v, off, grp, til: (grp[v], ki, ni)),
            grid=(n // tn, k // tk, total),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * r * k * n, transcendentals=0,
            bytes_accessed=(r * k + r * n + g * k * n) * x.dtype.itemsize),
        interpret=interpret,
        name=name,
    )(offsets, group, tile, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(xs, w, group_sizes, interpret):
    return _gmm(xs, w, group_sizes, transpose_w=False, interpret=interpret,
                name="moe_gmm")


def _grouped_fwd(xs, w, group_sizes, interpret):
    return (_grouped(xs, w, group_sizes, interpret), (xs, w, group_sizes))


def _grouped_bwd(interpret, res, d_out):
    xs, w, group_sizes = res  # one dtype, and the cotangent's: the result's
    d_xs = _gmm(d_out, w, group_sizes, transpose_w=True, interpret=interpret,
                name="moe_gmm_dx")
    d_w = _gmm_dw(xs, d_out, group_sizes, interpret=interpret,
                  name="moe_gmm_dw")
    return d_xs, d_w, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(xs, w, group_sizes, *, interpret: Optional[bool] = None):
    """xs [R, K] sorted by group, w [G, K, N], group_sizes [G] (their sum at
    most R) -> [R, N] in ``xs``'s dtype; rows past the last group are left
    as the buffer held them. Differentiable in ``xs`` and ``w``.

    ``interpret=None`` runs the kernels on a TPU where the shapes tile
    (:func:`tileable`) and ``lax.ragged_dot`` anywhere
    else (on the CPU a masked dense product); ``interpret=True`` reaches
    the kernels' bodies off the TPU, for their tests."""
    r, k = xs.shape
    if interpret is None:
        if jax.default_backend() != "tpu" or not tileable(r, k, w.shape[2]):
            return lax.ragged_dot(xs, w, group_sizes)
        interpret = False
    return _grouped(xs, w.astype(xs.dtype), group_sizes, interpret)
