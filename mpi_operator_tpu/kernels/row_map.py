"""A map over the first rows of a buffer, as one Pallas TPU kernel.

``row_map(body, operands, outs, rows)``: the operands are ``[R, W]`` buffers
of which only the rows ``[0, rows)`` matter (the routed feed-forward's
assignment buffers, parallel/moe.py: sorted by held expert, the absent
experts' rows last), ``rows`` a scalar on the device. ``body`` takes the
operands' rows as float32 arrays and gives each result's rows, float32 too;
a result is rounded once to its own dtype. What a row gives depends on that
row alone.

The grid runs over the row tiles below ``cdiv(rows, tile)`` and no further:
its extent is that number, read on the device as the grouped products read
theirs (kernels/grouped_matmul.py). A result's rows from
``cdiv(rows, tile) * tile`` on are in no visited tile, so they are never
written (they hold whatever the buffer held), and no operand's are read. The
rows between ``rows`` and that tile's end are computed from whatever the
operands hold there. ``rows = R`` visits every tile: the worst case computes
what a map over the whole buffer computes.

Three kinds of operand, all seen by ``body`` as float32 ``[rows, W]``:

- a buffer ``[R, W]``, W a multiple of 16 (a block is the whole width, so
  it may end off a lane tile): a block is ``tile`` rows (the
  grouped products' own ``_row_tile``) by the whole width, worked through
  in chunks of 128 rows by a loop (the compiler unrolls an array's
  operations, not a loop);
- a number a row (a row's weight, a sum over its columns): a float32 ``[R]``
  vector, a ``[rows, 1]`` column to ``body``. The kernel holds it whole, as
  ``[R / 128, 128]``: a chunk's 128 numbers are one line of it, turned into
  a column and back by the transpose unit. (As ``[R, 1]`` blocks Mosaic
  wants it in a tiled layout that pads every number to 512 bytes: two
  copies of 64 MB around the call at the cell's R.)
- rows gathered from a shorter array, ``(source [N, W], row_of [R])``: row r
  is ``source[row_of[r]]``, for the visited rows only. The kernel holds
  ``source`` whole in VMEM (a v5e core has 128 MiB) and ``row_of`` in SMEM
  and copies a chunk's rows one by one. A row of a bf16 array is half of a
  packed sublane, which cannot be addressed alone; so ``source`` goes in as
  uint32 words, a word the bits of column c beside those of column
  c + W / 2, and a chunk is taken apart again after the copy. Where that
  does not apply (another dtype, W / 2 no multiple of 128, a source too
  large for VMEM) the rows are gathered over all R beforehand.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_operator_tpu.kernels.grouped_matmul import _LANE
from mpi_operator_tpu.kernels.grouped_matmul import _row_tile as row_tile
from mpi_operator_tpu.kernels.grouped_matmul import whole_or_tiled

_VMEM = 128 << 20  # a v5e core's
_SOURCE_LIMIT = 96 << 20  # what a gathered source may take of it
_SLACK = 16 << 20  # beside the blocks: the body's own values
_COPIES = 8  # rows copied a step of the gather's loop


def rows_worked(rows, r: int):
    """How many of a buffer's ``r`` rows a map bounded by ``rows`` visits."""
    tile = row_tile(r)
    return (rows + tile - 1) // tile * tile


def mappable(r: int, *widths: int) -> bool:
    """Whether the kernel takes buffers of these shapes as they are: rows
    in lines of 128, widths (each a whole block) in packed sublanes."""
    return r % _LANE == 0 and all(whole_or_tiled(w) for w in widths)


def _gathers_inside(source) -> bool:
    n, width = source.shape
    return (source.dtype == jnp.bfloat16 and width % (2 * _LANE) == 0
            and n % 8 == 0 and n * width * 2 <= _SOURCE_LIMIT)


def _words(a):
    """bf16 ``[N, W]`` as uint32 ``[N, W / 2]``: a word's low half the bits
    of column c, its high half those of column c + W / 2 (a bf16 is the
    high half of the float32 it converts to)."""
    half = a.shape[1] // 2
    bits = lambda x: lax.bitcast_convert_type(
        x.astype(jnp.float32), jnp.uint32)
    return (bits(a[:, :half]) >> 16) | (
        bits(a[:, half:]) & jnp.uint32(0xFFFF0000))


def _unworded(words):
    """uint32 ``[rows, W / 2]`` -> the float32 ``[rows, W]`` it was made of."""
    as_f32 = lambda x: lax.bitcast_convert_type(x, jnp.float32)
    return jnp.concatenate(
        [as_f32(words << 16), as_f32(words & jnp.uint32(0xFFFF0000))], axis=1)


def _turned(a):
    """A line of 128 numbers ``[1, 128]`` as a column ``[128, 1]``, or a
    column as a line."""
    square = jnp.broadcast_to(a, (_LANE, _LANE)).T
    return square[:, :1] if a.shape[0] == 1 else square[:1, :]


def _kernel(*refs, body: Callable, n_in: int, kinds: Sequence[str],
            tile: int):
    """Grid (visited row tiles,). ``refs``: ``row_of`` first where an
    operand is gathered, then one for each of ``kinds`` (the ``n_in``
    operands' and then the results'), then the gathered chunk's scratch. A
    ``buffer``'s ref is its block ``[tile, W]``, a ``vector``'s the whole
    ``[R / 128, 128]``, a ``gathered`` one's the whole source in words."""
    row_of = chunk_words = None
    if "gathered" in kinds:
        row_of, *refs, chunk_words = refs
    marked = list(zip(refs, kinds))
    lines = tile // _LANE
    first_line = pl.program_id(0) * lines

    def gathered(ref, first_row):
        def copies(step, carry):
            at = pl.multiple_of(step * _COPIES, _COPIES)
            for u in range(_COPIES):
                chunk_words[pl.ds(at + u, 1), :] = ref[
                    pl.ds(row_of[first_row + at + u], 1), :]
            return carry

        lax.fori_loop(0, _LANE // _COPIES, copies, 0)
        return _unworded(chunk_words[...])

    def chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * _LANE, _LANE), _LANE)
        line = pl.ds(first_line + c, 1)

        def value(ref, kind):
            if kind == "vector":
                return _turned(ref[line, :])
            if kind == "gathered":
                return gathered(ref, (first_line + c) * _LANE)
            return ref[rows, :].astype(jnp.float32)

        results = body(*(value(ref, kind) for ref, kind in marked[:n_in]))
        for (ref, kind), result in zip(marked[n_in:], results):
            if kind == "vector":
                ref[line, :] = _turned(result)
            else:
                ref[rows, :] = result.astype(ref.dtype)
        return carry

    lax.fori_loop(0, lines, chunk, 0)


@functools.partial(jax.jit,
                   static_argnames=("body", "outs", "name", "interpret"))
def _launch(rows, row_of, *operands, body, outs, name: str, interpret: bool):
    """``operands``: each a buffer ``[R, W]`` or a vector ``[R]``, but the
    first where ``row_of`` is given: the source ``[N, W / 2]`` in words."""
    gathers = row_of is not None
    r = (row_of if gathers else operands[0]).shape[0]
    tile = row_tile(r)
    folded = (r // _LANE, _LANE)  # a vector, a line of 128 numbers a row
    kinds = (*("gathered" if gathers and i == 0
               else "vector" if a.ndim == 1 else "buffer"
               for i, a in enumerate(operands)),
             *("buffer" if width else "vector" for width, _ in outs))
    block = lambda width: pl.BlockSpec((tile, width), lambda i, *_: (i, 0))
    whole = pl.BlockSpec(folded, lambda i, *_: (0, 0))
    spec = {"buffer": lambda a: block(a.shape[1]), "vector": lambda a: whole,
            "gathered": lambda a: pl.BlockSpec(memory_space=pltpu.VMEM)}
    # bytes of VMEM: a block and a vector twice (the pipeline's two), the
    # source once
    held = sum({"buffer": 2 * tile * a.shape[-1], "vector": 2 * r,
                "gathered": a.size}[kind] * a.dtype.itemsize
               for a, kind in zip(operands, kinds))
    held += sum(2 * (tile * width if width else r) * dtype.itemsize
                for width, dtype in outs)
    moved = sum(a.size * a.dtype.itemsize for a in operands) + sum(
        r * (width or 1) * dtype.itemsize for width, dtype in outs)
    results = pl.pallas_call(
        functools.partial(_kernel, body=body, n_in=len(operands), kinds=kinds,
                          tile=tile),
        out_shape=[jax.ShapeDtypeStruct((r, width) if width else folded, dtype)
                   for width, dtype in outs],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(gathers),
            grid=((rows.astype(jnp.int32) + tile - 1) // tile,),
            in_specs=[spec[kind](a) for a, kind in zip(operands, kinds)],
            out_specs=[block(width) if width else whole for width, _ in outs],
            scratch_shapes=[pltpu.VMEM((_LANE, operands[0].shape[1]),
                                       jnp.uint32)] * gathers),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(held + _SLACK, _VMEM)),
        cost_estimate=pl.CostEstimate(
            flops=8 * max(a.size for a in operands), transcendentals=0,
            bytes_accessed=moved),
        interpret=interpret,
        name=name,
    )(*([row_of] if gathers else []),
      *(a.reshape(folded) if kind == "vector" else a
        for a, kind in zip(operands, kinds)))
    return [a if width else a.reshape(r)
            for a, (width, _) in zip(results, outs)]


def row_map(body: Callable, operands: Sequence, outs: Sequence, rows, *,
            name: str, interpret: Optional[bool] = None):
    """``body`` over the rows ``[0, rows)`` of ``operands`` (each a buffer
    ``[R, W]`` or a float32 vector ``[R]``; the first may be a pair
    ``(source [N, W], row_of [R])`` that stands for ``source[row_of]``) ->
    one array for each ``(width, dtype)`` of ``outs``: ``[R, width]``, or
    ``[R]`` float32 where ``width`` is None. Rows past the last visited tile
    are left as the buffer held them. ``body`` must be one function object a
    call site (it is a static argument of the jitted launcher: a new lambda
    a call is a new trace).

    ``interpret=None`` is no kernel: ``body`` over every row in
    ``jax.numpy``, which is what the kernel computes at ``rows = R``;
    ``False`` the kernel compiled for the TPU, ``True`` its body under the
    Pallas interpreter, for the tests."""
    first, *rest = operands
    row_of = None
    if isinstance(first, tuple):
        source, row_of = first
        if interpret is not None and _gathers_inside(source):
            first, row_of = _words(source), row_of.astype(jnp.int32)
        else:  # in bounds, and said so: no fill after the gather
            first, row_of = jnp.take(source, row_of, axis=0, mode="clip"), None
    flat = [first, *rest]
    if interpret is None:
        results = body(*(a.astype(jnp.float32).reshape(a.shape[0], -1)
                         for a in flat))
        return [result.astype(dtype) if width else result[:, 0].astype(dtype)
                for result, (width, dtype) in zip(results, outs)]
    outs = tuple((width and int(width), jnp.dtype(dtype))
                 for width, dtype in outs)
    return _launch(jnp.asarray(rows), row_of, *flat, body=body, outs=outs,
                   name=name, interpret=interpret)
