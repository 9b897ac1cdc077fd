"""The Mamba-2 layer's two memory-bound passes around the scan
(models/mamba2.py): the short causal convolution with its silu, and the
gate with its grouped RMS norm.

    conv_silu(x, w, bias)[t, c] = silu(bias[c]
                                       + sum_k w[c, k] x[t - (K - 1) + k, c])
    gate_norm(y, z, scale)      = g rsqrt(mean over the group of g^2 + eps)
                                  scale,              g = y silu(z)

``x`` [B, T, C] with nought before a sequence's start (a row of the batch
sees nothing of the row before it); a group is ``C / groups`` adjacent
channels. Both are a handful of float32 operations a number over arrays of
hundreds of megabytes: what they cost is their traffic. So ``x`` and ``z``
may be columns of a wider array (``first``: the layer's ``z``, ``x``,
``B``, ``C`` lie side by side in its input projection's result), read
where they lie: a slice in front of a kernel is a copy, and so is a split
behind one, which three calls for ``x``, ``B`` and ``C`` do not need.

Two implementations of each, chosen by what the call can observe
(``interpret=None``: no flag, no variable; the rule ``ssd.scan`` has):

- **Pallas TPU kernels with a ``jax.custom_vjp``**, on a TPU where the
  widths are whole lane tiles (:func:`conv_tileable`, :func:`gate_tileable`).
  In the device trace ``ssm_conv_fwd`` / ``ssm_conv_bwd`` and
  ``ssm_gate_fwd`` / ``ssm_gate_bwd``: one read of each operand and one
  write of each result a pass, bf16 (the operands' dtype) in HBM, float32
  inside VMEM, each result rounded once. A visit is a tile of positions by
  a tile of channels, worked through in chunks of rows by a loop (the
  compiler unrolls an array's operations, not a loop). The cotangent of a
  wider operand is nought in its other columns (a pad the compiler fuses
  into whatever adds the columns' cotangents up).

  The convolution's forward reads its tile and the 16 positions before it
  (a second, small block of the same array: the last K - 1 of them are the
  halo, nought in a row's first tile), forms the K taps, the bias and the
  silu. Its backward reads ``dy`` and ``x`` alone, with ``x``'s halo on both
  sides and ``dy``'s after the tile: it rebuilds the pre-activation, forms
  ``d_pre = dy silu'(pre)`` over its positions and the K - 1 after them,
  writes ``dx[t] = sum_k w[k] d_pre[t + K - 1 - k]`` and a tile's sums for
  ``dw`` and ``dbias``, float32, which are added up outside. The layer's
  checkpoint keeps nothing of it: the replay runs the forward again.

  The gated norm's tile is whole groups wide, so a group's mean square is a
  sum along the lanes of the tile and nothing is reshaped to ``[.., groups,
  C / groups]`` in HBM. Its backward rebuilds the gate and the group's
  ``rsqrt`` from ``y`` and ``z``, writes ``dy`` and ``dz`` and a tile's
  sums for ``dscale``.

  A Pallas call has no SPMD partitioning rule: on a mesh of more than one
  device the kernels run under ``shard_map`` with the rows over (data,
  fsdp) and every channel on each device; ``w``, ``bias`` and ``scale``
  enter whole and their gradients are summed over the rows' axes. Where a
  ``tensor`` axis would split the channels the ``jax.numpy`` forms run.
- **The ``jax.numpy`` passes the compiler lowers**, autodiff's backward
  (``ssd.causal_conv`` with ``jax.nn.silu``, :func:`_gate_norm_passes`):
  everywhere else, the CPU suite included. They are the tests' yardstick.

Numerics, both: every product and sum is float32, ``dw``, ``dbias`` and
``dscale`` are float32 sums; the kernels' differ in the order of those sums
alone.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from mpi_operator_tpu.kernels import ssd
from mpi_operator_tpu.kernels.ssd import _LANE, _nbytes

_SUBLANES = 8  # a float32 tile's rows: what a halo is held in
_HALO = 16  # rows of a halo's block: a packed bf16 tile
# A visit's tile, from the chip (PERF.md section 6, PR 37; one layer's pass
# at 2 x 8192, bf16): the convolution 512 positions by 512 channels (by 1024
# the backward's float32 scratch passes Mosaic's 16 MiB; by 256 the forward
# is a fifth slower), the gated norm every channel of as many rows as make a
# block 1 MiB (128 in bf16: five blocks, twice each, are 10 MiB), both
# worked 32 rows a step (64: the convolution's backward a tenth slower;
# 16: the norm's forward two thirds slower)
_CONV_ROWS = 512
_CONV_LANES = 512
_GATE_BLOCK = 1 << 20  # bytes
_GATE_LANES = 4096  # in whole groups
_CHUNK = 32


def _tile(n: int, most: int) -> int:
    """The largest power of two that divides ``n``, ``most`` at most."""
    return min(n & -n, most)


def conv_tileable(t: int, c: int, k: int, first: int = 0) -> bool:
    """Whether the convolution's kernels take these shapes: channels, and
    the columns before them, in whole lane tiles, positions in whole packed
    tiles, a halo that fits a float32 tile."""
    return (not (c % _LANE or first % _LANE or t % _HALO)
            and 1 < k <= _SUBLANES + 1)


def gate_tileable(rows: int, c: int, groups: int, first: int = 0) -> bool:
    """Whether the gated norm's kernels take these shapes: a group in whole
    lane tiles, the columns before ``z``'s in whole groups, rows in whole
    packed tiles."""
    return not (c % groups or (c // groups) % _LANE or first % (c // groups)
                or rows % _HALO)


def _gate_norm_passes(y, z, scale, groups: int, eps: float):
    """``y silu(z)``, then RMS-normalised over each group of channels."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = g.reshape(*g.shape[:-1], groups, -1)
    grouped = grouped * lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return (grouped.reshape(g.shape) * scale).astype(y.dtype)


def _chunks(tile: int, body):
    """``body(rows)`` over a tile's chunks of rows."""
    step = min(_CHUNK, tile)

    def chunk(c, carry):
        body(pl.ds(pl.multiple_of(c * step, step), step))
        return carry

    lax.fori_loop(0, tile // step, chunk, 0)


def _taps(window, rows: int, k: int):
    """What each of the K taps reads for ``rows`` positions, oldest first,
    from a window that starts ``_SUBLANES`` positions before the first of
    them: ``rows`` rows from row 8 - (K - 1) + tap. Off a tile's edge that
    is a turn of the whole window (a slice there costs a tenth more on the
    chip)."""
    return [_from(window, _SUBLANES - (k - 1) + tap, rows) for tap in range(k)]


def _from(window, at: int, rows: int):
    if at % _SUBLANES == 0:
        return window[at:at + rows]
    return pltpu.roll(window, window.shape[0] - at, axis=0)[:rows]


def _pre(taps, w_ref, b_ref):
    """The pre-activation: bias first, then the taps from the oldest, as
    ``ssd.causal_conv`` adds them."""
    pre = b_ref[...]
    for tap, x in enumerate(taps):
        pre = pre + x * w_ref[tap:tap + 1, :]
    return pre


def _conv_fwd_kernel(x_ref, before_ref, w_ref, b_ref, y_ref, xe_ref, *,
                     k: int, halo: bool):
    """Grid (row, tile of positions, tile of channels). x [Tt, Ct];
    ``before`` [16, Ct], the positions before the tile (any, where it is the
    row's first); w [K, Ct] and bias [1, Ct] float32. Result y [Tt, Ct].
    Scratch: the tile in float32 behind its halo, [8 + Tt, Ct]."""
    f32 = jnp.float32
    tile = x_ref.shape[0]
    before = before_ref[...].astype(f32)[_HALO - _SUBLANES:]
    keep = pl.program_id(1) > 0 if halo else False
    xe_ref[:_SUBLANES] = jnp.where(keep, before, 0.0)
    xe_ref[_SUBLANES:] = x_ref[...].astype(f32)

    def chunk(rows):
        window = xe_ref[pl.ds(rows.start, rows.size + _SUBLANES), :]
        pre = _pre(_taps(window, rows.size, k), w_ref, b_ref)
        y_ref[rows, :] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)

    _chunks(tile, chunk)


def _conv_bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                     w_ref, b_ref, dx_ref, sums_ref, xe_ref, de_ref, *,
                     k: int, halo: bool):
    """The same grid. Beside the forward's operands: ``after`` [16, Ct],
    x's positions after the tile, and dy [Tt, Ct] with its own ``after``
    (nought past a row's end). Results: dx [Tt, Ct]; the tile's sums
    [8 or 16, Ct] float32, row ``k`` the tap's ``dw`` and row K ``dbias``.
    Scratch: x in float32 between its halos, [8 + Tt + 8, Ct], and
    ``d_pre`` of the tile and of the 8 positions after it, [Tt + 8, Ct]."""
    f32 = jnp.float32
    tile = x_ref.shape[0]
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    before = before_ref[...].astype(f32)[_HALO - _SUBLANES:]
    after = after_ref[...].astype(f32)[:_SUBLANES]
    dy_after = dy_after_ref[...].astype(f32)[:_SUBLANES]
    if halo:
        before = jnp.where(first, 0.0, before)
        dy_after = jnp.where(last, 0.0, dy_after)
    else:
        before, dy_after = jnp.zeros_like(before), jnp.zeros_like(dy_after)
    xe_ref[:_SUBLANES] = before
    xe_ref[_SUBLANES:_SUBLANES + tile] = x_ref[...].astype(f32)
    xe_ref[_SUBLANES + tile:] = after
    sums_ref[...] = jnp.zeros_like(sums_ref)

    def d_pre(taps, dy):
        pre = _pre(taps, w_ref, b_ref)
        s = jax.nn.sigmoid(pre)
        return dy * (s * (1.0 + pre * (1.0 - s)))  # dy silu'(pre)

    def sums(rows):
        taps = _taps(xe_ref[pl.ds(rows.start, rows.size + _SUBLANES), :],
                     rows.size, k)
        d = d_pre(taps, dy_ref[rows, :].astype(f32))
        de_ref[rows, :] = d
        for tap, x in enumerate(taps):
            sums_ref[tap:tap + 1, :] += jnp.sum(d * x, axis=0, keepdims=True)
        sums_ref[k:k + 1, :] += jnp.sum(d, axis=0, keepdims=True)

    _chunks(tile, sums)
    # the K - 1 positions after the tile hand their d_pre back into it
    de_ref[tile:] = d_pre(_taps(xe_ref[tile:], _SUBLANES, k), dy_after)

    def transpose(rows):
        window = de_ref[pl.ds(rows.start, rows.size + _SUBLANES), :]
        dx = window[:rows.size] * w_ref[k - 1:k, :]
        for ahead in range(1, k):
            dx = dx + (_from(window, ahead, rows.size)
                       * w_ref[k - 1 - ahead:k - ahead, :])
        dx_ref[rows, :] = dx.astype(dx_ref.dtype)

    _chunks(tile, transpose)


def _conv_specs(t: int, c: int, first: int = 0):
    """(grid of a row's tiles, block specs of a visit (row, positions,
    channels) by the first column of the array they cut: a tile, the 16
    positions before it and after it (the nearest whole block at a row's
    ends); ``rows`` numbers a channel)."""
    tile, lanes = _tile(t, _CONV_ROWS), _tile(math.gcd(c, first), _CONV_LANES)
    per, blocks = tile // _HALO, t // _HALO

    def cut(first):
        at = first // lanes
        return dict(
            tile=pl.BlockSpec((None, tile, lanes),
                              lambda i, p, j: (i, p, at + j)),
            before=pl.BlockSpec(
                (None, _HALO, lanes),
                lambda i, p, j: (i, jnp.maximum(p * per - 1, 0), at + j)),
            after=pl.BlockSpec(
                (None, _HALO, lanes), lambda i, p, j: (
                    i, jnp.minimum((p + 1) * per, blocks - 1), at + j)))

    return (t // tile, c // lanes), dict(
        x=cut(first), y=cut(0),
        channel=lambda rows: pl.BlockSpec(
            (rows, lanes), lambda i, p, j: (0, j)),
        tile=tile, lanes=lanes)


def _parallel(axes: int):
    return pltpu.CompilerParams(dimension_semantics=("parallel",) * axes)


# jitted, as the scan's (kernels/ssd.py): a step's calls of one shape (four
# layers: forward, replay and backward) are traced once a process
@functools.partial(jax.jit, static_argnames=("first", "halo", "interpret"))
def _ssm_conv_fwd(x, w, bias, *, first: int, halo: bool, interpret: bool):
    """x [B, T, >= first + C], w [K, C] and bias [1, C] float32 -> silu(conv
    + bias) of x's columns from ``first``, [B, T, C] of x's dtype."""
    bsz, t, _ = x.shape
    k, c = w.shape
    tiles, s = _conv_specs(t, c, first)
    y = jax.ShapeDtypeStruct((bsz, t, c), x.dtype)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, k=k, halo=halo),
        out_shape=y,
        grid=(bsz, *tiles),
        in_specs=[s["x"]["tile"], s["x"]["before"], s["channel"](k),
                  s["channel"](1)],
        out_specs=s["y"]["tile"],
        scratch_shapes=[
            pltpu.VMEM((_SUBLANES + s["tile"], s["lanes"]), jnp.float32)],
        compiler_params=_parallel(3),
        cost_estimate=pl.CostEstimate(
            flops=(2 * k + 4) * y.size, transcendentals=y.size,
            bytes_accessed=_nbytes(y, y, w, bias)),
        interpret=interpret,
        name="ssm_conv_fwd",
    )(x, x, w, bias)


@functools.partial(jax.jit, static_argnames=("first", "halo", "interpret"))
def _ssm_conv_bwd(x, w, bias, dy, *, first: int, halo: bool,
                  interpret: bool):
    """The forward's operands and dy [B, T, C] -> (dx as dy; the tiles' sums
    [B, tiles, 8 or 16, C] float32: rows 0 .. K - 1 ``dw``'s, row K
    ``dbias``'s)."""
    bsz, t, c = dy.shape
    k = w.shape[0]
    tiles, s = _conv_specs(t, c, first)
    sums = -(-(k + 1) // _SUBLANES) * _SUBLANES
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, k=k, halo=halo),
        out_shape=[jax.ShapeDtypeStruct(dy.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, tiles[0], sums, c),
                                        jnp.float32)],
        grid=(bsz, *tiles),
        in_specs=[s["x"]["tile"], s["x"]["before"], s["x"]["after"],
                  s["y"]["tile"], s["y"]["after"],
                  s["channel"](k), s["channel"](1)],
        out_specs=[s["y"]["tile"],
                   pl.BlockSpec((None, None, sums, s["lanes"]),
                                lambda i, p, j: (i, p, 0, j))],
        scratch_shapes=[
            pltpu.VMEM((2 * _SUBLANES + s["tile"], s["lanes"]), jnp.float32),
            pltpu.VMEM((_SUBLANES + s["tile"], s["lanes"]), jnp.float32)],
        compiler_params=_parallel(3),
        cost_estimate=pl.CostEstimate(
            flops=(6 * k + 10) * dy.size, transcendentals=dy.size,
            bytes_accessed=_nbytes(dy, dy, dy, w, bias)),
        interpret=interpret,
        name="ssm_conv_bwd",
    )(x, x, x, dy, dy, w, bias)


def _by_channel(w, bias):
    """w [C, K], bias [C] as the kernels read them: [K, C] and [1, C]
    float32, a channel a lane."""
    return w.T.astype(jnp.float32), bias[None].astype(jnp.float32)


def _beside(d, like, first: int):
    """A cotangent of ``like``'s columns from ``first`` as one of all of
    them: nought in the others (where they are the same, ``d`` itself)."""
    after = like.shape[-1] - first - d.shape[-1]
    if not (first or after):
        return d
    return jnp.pad(d, [(0, 0)] * (d.ndim - 1) + [(first, after)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_kernels(x, w, bias, first, halo, interpret):
    """x [B, T, >= first + C], w [C, K], bias [C] -> silu(conv + bias) of
    x's columns from ``first``, [B, T, C]."""
    return _ssm_conv_fwd(x, *_by_channel(w, bias), first=first, halo=halo,
                         interpret=interpret)


def _conv_kernels_fwd(x, w, bias, first, halo, interpret):
    return _conv_kernels(x, w, bias, first, halo, interpret), (x, w, bias)


def _conv_kernels_bwd(first, halo, interpret, res, dy):
    x, w, bias = res
    k = w.shape[1]
    dx, sums = _ssm_conv_bwd(x, *_by_channel(w, bias), dy, first=first,
                             halo=halo, interpret=interpret)
    sums = jnp.sum(sums, axis=(0, 1))
    return (_beside(dx, x, first), sums[:k].T.astype(w.dtype),
            sums[k].astype(bias.dtype))


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


def _gate(y_ref, z_ref, rows, lanes):
    """A group's chunk: y, z, sigmoid(z) and ``y silu(z)`` in float32."""
    f32 = jnp.float32
    y, z = y_ref[rows, lanes].astype(f32), z_ref[rows, lanes].astype(f32)
    s = jax.nn.sigmoid(z)
    return y, z, s, y * (z * s)


def _gate_fwd_kernel(y_ref, z_ref, scale_ref, o_ref, *, group: int,
                     eps: float):
    """Grid (tile of rows, tile of whole groups). y, z [Rt, Ct]; scale
    [1, Ct] float32. Result [Rt, Ct]."""
    tile, width = y_ref.shape

    def chunk(rows):
        for at in range(0, width, group):
            lanes = slice(at, at + group)
            *_, g = _gate(y_ref, z_ref, rows, lanes)
            g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
            o_ref[rows, lanes] = (g * scale_ref[:, lanes]).astype(o_ref.dtype)

    _chunks(tile, chunk)


def _gate_bwd_kernel(y_ref, z_ref, scale_ref, do_ref, dy_ref, dz_ref,
                     dscale_ref, *, group: int, eps: float):
    """The same grid. Beside the forward's operands: do [Rt, Ct]. Results:
    dy, dz [Rt, Ct]; the tile's sum for ``dscale`` [1, Ct] float32.

    With ``n = g r`` (``r`` the group's rsqrt): ``out = n scale``, so
    ``dscale = sum do n`` and, ``dn = do scale``, ``dg = r (dn - n mean(dn
    n))``; then ``dy = dg silu(z)`` and ``dz = dg y silu'(z)``."""
    f32 = jnp.float32
    tile, width = y_ref.shape
    dscale_ref[...] = jnp.zeros_like(dscale_ref)

    def chunk(rows):
        for at in range(0, width, group):
            lanes = slice(at, at + group)
            y, z, s, g = _gate(y_ref, z_ref, rows, lanes)
            r = lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
            n = g * r
            do = do_ref[rows, lanes].astype(f32)
            dscale_ref[:, lanes] += jnp.sum(do * n, axis=0, keepdims=True)
            dn = do * scale_ref[:, lanes]
            dg = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
            dy_ref[rows, lanes] = (dg * (z * s)).astype(dy_ref.dtype)
            dz_ref[rows, lanes] = (
                dg * y * (s * (1.0 + z * (1.0 - s)))).astype(dz_ref.dtype)

    _chunks(tile, chunk)


def _gate_specs(rows: int, c: int, groups: int, first: int, itemsize: int):
    """(grid; a visit's block of y's kind, of z's (whose columns start at
    ``first``), of scale's, of a tile's sums; the lanes of a group)."""
    group = c // groups
    # whole groups side by side, as many as divide the channels and the
    # columns before z's
    lanes = group * _tile(math.gcd(groups, first // group),
                          max(_GATE_LANES // group, 1))
    tile = _tile(rows, max(_GATE_BLOCK // (lanes * itemsize), _HALO))
    at = first // lanes
    return ((rows // tile, c // lanes),
            pl.BlockSpec((tile, lanes), lambda i, j: (i, j)),
            pl.BlockSpec((tile, lanes), lambda i, j: (i, at + j)),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j)),
            pl.BlockSpec((None, 1, lanes), lambda i, j: (i, 0, j)), group)


_GATE_STATICS = ("first", "groups", "eps", "interpret")


@functools.partial(jax.jit, static_argnames=_GATE_STATICS)
def _ssm_gate_fwd(y, z, scale, *, first: int, groups: int, eps: float,
                  interpret: bool):
    """y [R, C], z [R, >= first + C], scale [1, C] float32 -> the rows of y
    gated by z's columns from ``first`` and normed, as y."""
    grid, wide, gate, per_channel, _, group = _gate_specs(
        *y.shape, groups, first, y.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, group=group, eps=eps),
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        grid=grid,
        in_specs=[wide, gate, per_channel],
        out_specs=wide,
        compiler_params=_parallel(2),
        cost_estimate=pl.CostEstimate(
            flops=10 * y.size, transcendentals=y.size,
            bytes_accessed=_nbytes(y, y, y, scale)),
        interpret=interpret,
        name="ssm_gate_fwd",
    )(y, z, scale)


@functools.partial(jax.jit, static_argnames=_GATE_STATICS)
def _ssm_gate_bwd(y, z, scale, do, *, first: int, groups: int, eps: float,
                  interpret: bool):
    """The forward's operands and do as y -> (dy and dz as y; the row
    tiles' sums for ``dscale`` [tiles, 1, C] float32)."""
    grid, wide, gate, per_channel, sums, group = _gate_specs(
        *y.shape, groups, first, y.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_gate_bwd_kernel, group=group, eps=eps),
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(y.shape, z.dtype),
                   jax.ShapeDtypeStruct((grid[0], 1, y.shape[1]),
                                        jnp.float32)],
        grid=grid,
        in_specs=[wide, gate, per_channel, wide],
        out_specs=[wide, wide, sums],
        compiler_params=_parallel(2),
        cost_estimate=pl.CostEstimate(
            flops=30 * y.size, transcendentals=y.size,
            bytes_accessed=_nbytes(y, y, y, y, do, scale)),
        interpret=interpret,
        name="ssm_gate_bwd",
    )(y, z, scale, do)


def _rows_of(y, z, scale):
    """y [.., C], z [.., Cz], scale [C] as the kernels read them: [R, C],
    [R, Cz] and [1, C] float32."""
    return (y.reshape(-1, y.shape[-1]), z.reshape(-1, z.shape[-1]),
            scale[None].astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gate_kernels(y, z, scale, first, groups, eps, interpret):
    """y [.., C], z [.., >= first + C], scale [C] -> y gated by z's columns
    from ``first`` and normed, as y."""
    return _ssm_gate_fwd(*_rows_of(y, z, scale), first=first, groups=groups,
                         eps=eps, interpret=interpret).reshape(y.shape)


def _gate_kernels_fwd(y, z, scale, first, groups, eps, interpret):
    return (_gate_kernels(y, z, scale, first, groups, eps, interpret),
            (y, z, scale))


def _gate_kernels_bwd(first, groups, eps, interpret, res, do):
    y, z, scale = res
    dy, dz, dscale = _ssm_gate_bwd(
        *_rows_of(y, z, scale), do.reshape(-1, y.shape[-1]), first=first,
        groups=groups, eps=eps, interpret=interpret)
    return (dy.reshape(y.shape), _beside(dz.reshape(y.shape), z, first),
            jnp.sum(dscale, axis=(0, 1)).astype(scale.dtype))


_gate_kernels.defvjp(_gate_kernels_fwd, _gate_kernels_bwd)


def _over_rows(local, mesh, wide: int, whole: int):
    """``local`` (``wide`` operands [B, T, C], then ``whole`` ones a
    channel) as it runs on a mesh: under ``shard_map`` on more than one
    device, the rows over the mesh's ``data`` and ``fsdp`` axes, every
    other axis whole. check_vma=False: a pallas_call's results carry no
    varying-axes annotation (kernels/flash_attention.py); the whole
    operands get their cotangents summed over the rows' axes by the
    transpose."""
    if mesh is None or mesh.size == 1:
        return local
    rows = PartitionSpec(tuple(
        ax for ax in ("data", "fsdp") if ax in mesh.axis_names) or None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(rows,) * wide + (PartitionSpec(),) * whole,
        out_specs=rows, check_vma=False)


def _splits_channels(mesh) -> bool:
    return (mesh is not None and "tensor" in mesh.axis_names
            and mesh.shape["tensor"] > 1)


def conv_silu(x, w, bias, *, first: int = 0, halo: bool = True,
              interpret: Optional[bool] = None, mesh=None):
    """x [B, T, >= first + C], w [C, K], bias [C] -> ``silu(causal_conv(x) +
    bias)`` of x's columns ``first .. first + C``, [B, T, C] in ``x``'s
    dtype, float32 before the one cast. ``x`` may be wider than the
    convolved channels: a kernel reads them where they lie (the layer's
    ``x``, ``B``, ``C`` in its input projection's result), and a slice in
    front of a kernel would be a copy. ``halo=False`` is the fault a test
    plants in the kernels: every tile of positions starts from nought and
    hands nothing back.

    ``interpret=None`` runs the Pallas kernels on a TPU where the shapes
    tile (:func:`conv_tileable`) and no ``tensor`` axis of ``mesh`` splits
    the channels, and ``ssd.causal_conv`` with ``jax.nn.silu`` anywhere
    else; ``interpret=True`` reaches the kernels' bodies off the TPU, for
    their tests. On a ``mesh`` of more than one device the kernels run
    under ``shard_map``, the rows over ``data`` and ``fsdp``."""
    c, k = w.shape
    if interpret is None:
        if (jax.default_backend() != "tpu" or _splits_channels(mesh)
                or not conv_tileable(x.shape[1], c, k, first)):
            return jax.nn.silu(ssd.causal_conv(
                x[..., first:first + c], w, bias)).astype(x.dtype)
        interpret = False
    return _over_rows(
        lambda x, w, bias: _conv_kernels(x, w, bias, first, halo, interpret),
        mesh, 1, 2)(x, w, bias)


def gate_norm(y, z, scale, *, groups: int, eps: float, first: int = 0,
              interpret: Optional[bool] = None, mesh=None):
    """y [B, T, C], z [B, T, >= first + C], scale [C] -> ``y silu(z)`` with
    z's columns ``first .. first + C``, RMS-normalised over each of the
    ``groups`` groups of adjacent channels alone, times ``scale``, in
    ``y``'s dtype. ``z`` may be wider, the choice of implementation and the
    mesh are as :func:`conv_silu`'s (:func:`gate_tileable`)."""
    c = y.shape[-1]
    if interpret is None:
        if (jax.default_backend() != "tpu" or _splits_channels(mesh)
                or not gate_tileable(y.size // c, c, groups, first)):
            return _gate_norm_passes(y, z[..., first:first + c], scale,
                                     groups, eps)
        interpret = False
    return _over_rows(
        lambda y, z, scale: _gate_kernels(y, z, scale, first, groups, eps,
                                          interpret),
        mesh, 2, 1)(y, z, scale)
