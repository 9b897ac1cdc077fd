"""A weighted sum of each token's rows of an assignment buffer, as one Pallas
TPU kernel that reads the held rows only.

``token_sum(src, pos, plan, c)``: ``src`` is ``[R, D]``, the routed
feed-forward's assignment buffer (parallel/moe.py): sorted by held expert,
the absent experts' rows last, rows past the held ones undefined (they may
be NaN); ``pos`` [N, k] is each assignment's row. The result is
``[N, D]``,

    out[t] = sum over the held j of  c[t, j] * src[pos[t, j]]

summed in float32 and rounded once to ``src``'s dtype; ``c`` None is a
weight of 1. It is the combine's weighted sum (``src`` the experts' rows,
``c`` the router's weights) and the dispatch's transpose (``src`` the
rows' cotangents, no weight).

What makes the held rows few and easy to find: the sort that builds the
buffer is stable, so inside one held group the rows are in token order,
and a tile of ``TILE`` (128) consecutive tokens owns one contiguous range of
each group's rows. :func:`plan` takes those ranges from a histogram of the
groups by token tile; summed over tiles and groups they hold every held
row once.

The kernel's grid runs over the token tiles. A tile's ranges are copied
from ``src`` (left in HBM) into VMEM by DMA, one after the other, each from
the 16-row line at or before its start (the bf16 tile; a copy of one row is
refused) and in pieces of 16, 32, 64 or 128 rows; the next tile's copies run
while this one is summed. Where each assignment's row landed among the
copies is found in the kernel, from its ``pos`` and the tile's ranges: an
assignment whose row lies in no range (an absent expert's, past the held
rows) has none; so nothing of the size of ``pos`` is made for the kernel or
kept for the backward. ``pos`` and ``c`` enter as lines of 128 tokens,
``[k, N]``, the layout the compiler gives them, and are turned into
columns in VMEM: as ``[N, k]`` blocks each would be copied into a layout
that pads its k numbers a token to 128 (8.4 MB at 16,384 tokens, live from
the forward to the backward). The rows copied but not in a range (the
lines' heads and tails, rows past the held ones, what an earlier tile
left) are set to zero by a select before any product, so a NaN there
reaches nothing. The sum is a product on the MXU by chunks of 256 copied
rows:
``S [tile, 256] @ rows [256, D]``, ``S[t, v] = c`` where copied row v is
token t's row. A weight goes in as the three bf16 numbers whose sum it is (a
float32's 24 bits), three ``S`` one above the other in one product, so that
every product is exact and the MXU adds in float32. Without weights ``S``
is 0/1 and takes one product. (Measured on a v5e at the routed cells'
shapes: chunks of 256 rows take 0.86-1.03 times the time of chunks of 128;
gathering each token's rows one by one as uint32 words in VMEM, as
kernels/row_map.py gathers, 1.03-2.0 times the time of the product.)

``rows_read`` of the plan is what the copies move: the rows of the aligned
ranges. A line that two ranges share is copied for each, so with nearly
every assignment held it may pass ``R``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_operator_tpu.kernels.grouped_matmul import _LANE
from mpi_operator_tpu.kernels.row_map import _turned

_VMEM = 128 << 20  # a v5e core's
_SLACK = 16 << 20  # beside the blocks: the products' values
_LINE = 16  # a bf16 tile's rows: where a copy may start
_CHUNK = 256  # copied rows a product takes
TILE = _LANE  # tokens a grid step sums
_PIECES = 4  # a range's copies are 16, 32, 64 and 128 rows long, or a sum


class Plan(NamedTuple):
    """Where each token tile's rows are. ``ranges`` [tiles * groups]: the
    range's first row in ``src`` times 256 plus its length (a word, read by
    the kernel from SMEM); ``rows_read``: the rows the copies move (a
    scalar)."""
    ranges: jax.Array
    rows_read: jax.Array


def takes(n: int, d: int, dtype) -> bool:
    """Whether the kernel takes ``src`` of this width and dtype for ``n``
    tokens as they are: bf16 rows a whole number of lane tiles wide, whole
    tiles of tokens."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and d % _LANE == 0
            and n % TILE == 0)


def plan(group, n_groups: int) -> Plan:
    """From ``group`` [N, k], each assignment's held group (``n_groups``
    where its expert is absent)."""
    n, k = group.shape
    ids = jnp.arange(n_groups, dtype=jnp.int32)
    mine = group[..., None] == ids  # [N, k, G]
    count = jnp.sum(mine.reshape(n // TILE, TILE * k, n_groups), axis=1,
                    dtype=jnp.int32)  # [tiles, G]
    sizes = jnp.sum(count, axis=0)
    lo = (jnp.cumsum(sizes) - sizes)[None, :] + (
        jnp.cumsum(count, axis=0) - count)
    first = lo // _LINE * _LINE
    copied = jnp.where(count > 0,
                       (lo + count - first + _LINE - 1) // _LINE * _LINE, 0)
    return Plan(ranges=(lo * 256 + count).reshape(-1),
                rows_read=jnp.sum(copied))


def _range(ranges_ref, tile_i, g, n_groups: int):
    """Tile ``tile_i``'s range of group ``g``: its first row and length,
    the line its copy starts at and the lines it copies."""
    word = ranges_ref[tile_i * n_groups + g]
    lo, count = word >> 8, word & 255  # shifts and masks: few to trace
    first = lo & -_LINE
    lines = jnp.where(count > 0, (lo + count - first + _LINE - 1) >> 4, 0)
    return lo, count, first, lines


def _copies(ranges_ref, src_ref, buf_ref, sem_ref, tile_i, slot, *,
            n_groups: int, wait: bool):
    """Start (or wait for) the copies of tile ``tile_i``'s ranges into
    ``buf_ref[slot]``, one after the other; the rows copied."""
    def group(g, at):
        _, _, first, lines = _range(ranges_ref, tile_i, g, n_groups)

        def copy(offset, rows):
            dma = pltpu.make_async_copy(
                src_ref.at[pl.ds(pl.multiple_of(first + offset, _LINE), rows)],
                buf_ref.at[slot, pl.ds(pl.multiple_of(at + offset, _LINE),
                                       rows)],
                sem_ref.at[slot])
            dma.wait() if wait else dma.start()

        longest = _LINE << (_PIECES - 1)

        def long(_, offset):
            copy(offset, longest)
            return offset + longest

        done = lax.fori_loop(0, lines >> (_PIECES - 1), long, 0)
        for p in reversed(range(_PIECES - 1)):
            rows = _LINE << p
            big = (lines >> p) & 1 == 1
            pl.when(big)(functools.partial(copy, done, rows))
            done = done + jnp.where(big, rows, 0)
        return at + lines * _LINE

    return lax.fori_loop(0, n_groups, group, jnp.int32(0))


def _placed(ranges_ref, pos, tile_i, n_groups: int):
    """[k, tile]: the row of tile ``tile_i``'s copies that holds each
    assignment, from its row ``pos`` [k, tile] in ``src``; -1 where no range
    holds it."""
    def group(g, carry):
        vrow, at = carry
        lo, count, first, lines = _range(ranges_ref, tile_i, g, n_groups)
        vrow = jnp.where((pos >= lo) & (pos < lo + count), pos - first + at,
                         vrow)
        return vrow, at + lines * _LINE

    return lax.fori_loop(0, n_groups, group,
                         (jnp.full(pos.shape, -1, jnp.int32), jnp.int32(0)))[0]


def _kernel(ranges_ref, src_ref, pos_ref, *refs, n_groups: int, k: int,
            weighted: bool, chunks: int):
    """Grid (token tiles,). ``pos_ref`` is the tile's rows in ``src`` as
    [8, tile], one line of 128 tokens an assignment (k of them, -1 after).
    ``refs``: the weights' block the same way where ``weighted``, the
    result's block [tile, D], then the copies' two buffers [2, rows, D],
    the float32 sum [tile, D], the copied row of each assignment as a
    column [tile, 8] and the weights the same way, and two DMA
    semaphores."""
    if weighted:
        c_ref, out_ref, buf_ref, acc_ref, col_ref, c_col_ref, sem_ref = refs
    else:
        c_ref, c_col_ref = None, None
        out_ref, buf_ref, acc_ref, col_ref, sem_ref = refs
    i = pl.program_id(0)
    tiles = pl.num_programs(0)
    slot = i & 1
    copies = functools.partial(_copies, ranges_ref, src_ref, buf_ref, sem_ref,
                               n_groups=n_groups)

    def start(t, carry):  # tiles 0 and 1 at the first step, then the next
        copies(t, t & 1, wait=False)
        return carry

    lax.fori_loop(jnp.where(i == 0, 0, i + 1), jnp.minimum(i + 2, tiles),
                  start, 0)

    # lines of tokens, as HBM holds them without padding, turned into the
    # columns that a product's rows are picked by (one transpose a tile)
    col_ref[...] = _placed(ranges_ref, pos_ref[...], i, n_groups).T
    if weighted:
        c_col_ref[...] = c_ref[...].T
    rows = copies(i, slot, wait=True)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk(q, carry):
        @pl.when(q * _CHUNK < rows)
        def _some_rows():
            at = pl.multiple_of(q * _CHUNK, _CHUNK)
            cols = at + lax.broadcasted_iota(jnp.int32, (1, _CHUNK), 1)
            hits = [col_ref[:, j:j + 1] == cols for j in range(k)]
            s = sum(jnp.where(h, c_col_ref[:, j:j + 1] if weighted else 1.0,
                              0.0)
                    for j, h in enumerate(hits))  # [tile, 256] float32
            # a copied row no token reads: zero before the product
            line = jnp.max(jnp.where(functools.reduce(jnp.logical_or, hits),
                                     1.0, 0.0), axis=0, keepdims=True)
            read = jnp.concatenate(
                [_turned(line[:, h:h + _LANE])
                 for h in range(0, _CHUNK, _LANE)], axis=0)  # [256, 1]
            block = buf_ref[slot, pl.ds(at, _CHUNK), :]
            block = jnp.where(read > 0, block, jnp.zeros_like(block))
            pieces = []
            for _ in range(3 if weighted else 1):
                piece = s.astype(block.dtype)
                pieces.append(piece)
                s = s - piece.astype(jnp.float32)
            # the pieces one above the other: the block enters the MXU once
            out = jnp.dot(jnp.concatenate(pieces, axis=0), block,
                          preferred_element_type=jnp.float32)
            tile = acc_ref.shape[0]
            acc_ref[...] += sum(out[p * tile:(p + 1) * tile]
                                for p in range(len(pieces)))
        return carry

    lax.fori_loop(0, chunks, chunk, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def token_sum(src, pos, plan_: Plan, c=None, *, interpret: bool = False):
    """``[N, D]``: each token's rows ``pos`` [N, k] of ``src`` [R, D], where
    ``plan_`` holds them, times ``c`` [N, k] (float32; None: 1), summed.
    ``interpret=True`` runs the kernel's body under the Pallas interpreter,
    for the tests. ``pos`` and ``c`` go in as [8, N] (k rows, then -1 and
    0): lines of tokens, as the compiler lays out [N, k] itself, where an
    [N, k] block would be padded from k numbers a token to 128."""
    ranges = plan_.ranges
    d = src.shape[1]
    n, k = pos.shape
    tile = TILE
    n_groups = ranges.shape[0] // (n // tile)
    # a tile's copies: its held assignments and two lines a range at most
    most = tile * min(k, n_groups) + 2 * _LINE * n_groups
    chunks = -(-most // _CHUNK)
    weighted = c is not None
    lines = -(-k // 8) * 8  # a whole sublane tile of assignments
    block = pl.BlockSpec((lines, tile), lambda i, ranges: (0, i))
    itemsize = src.dtype.itemsize
    # bytes of VMEM: the copies' two buffers, the sum, the result's blocks
    held = (2 * chunks * _CHUNK * d * itemsize + tile * d * 4
            + 2 * tile * d * itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, n_groups=n_groups, k=k,
                          weighted=weighted, chunks=chunks),
        out_shape=jax.ShapeDtypeStruct((n, d), src.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), block,
                      *[block] * weighted],
            out_specs=pl.BlockSpec((tile, d), lambda i, ranges: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunks * _CHUNK, d), src.dtype),
                pltpu.VMEM((tile, d), jnp.float32),
                pltpu.VMEM((tile, lines), jnp.int32),
                *[pltpu.VMEM((tile, lines), jnp.float32)] * weighted,
                pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(held + _SLACK, _VMEM)),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * min(k, n_groups) * _CHUNK * d, transcendentals=0,
            bytes_accessed=(n * min(k, n_groups) + n) * d * itemsize),
        interpret=interpret,
        name="moe_token_sum",
    )(ranges, src, _lines(pos, lines, -1, jnp.int32),
      *([_lines(c, lines, 0, jnp.float32)] if weighted else []))


def _lines(a, lines: int, fill, dtype):
    """[N, k] as [lines, N], the rows past k ``fill``."""
    return jnp.pad(a.T.astype(dtype), ((0, lines - a.shape[1]), (0, 0)),
                   constant_values=fill)
