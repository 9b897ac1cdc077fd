"""Mamba-2's state-space scan in its chunked form (SSD, arXiv:2405.21060),
and the short causal convolution in front of it as ``jax.numpy`` passes
(``causal_conv``: what runs off a TPU and the yardstick of the kernel that
runs on one, kernels/ssm_conv_gate.py).

The function, per head with a state ``S`` [P, N] that starts at nought:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t . C_t

``scan`` computes it a chunk of ``chunk`` positions at a time, in four
parts:

- inside a chunk the masked product ``(L o C B^T) (dt x)`` with
  ``L_ij = exp(sum_{j<l<=i} dt_l A)`` for ``j <= i`` and nought above the
  diagonal. The mask goes on before the ``exp``: the upper triangle's sums
  run backwards and are positive;
- each chunk's closing state ``sum_j exp(sum_{j<l<=last} dt_l A) dt_j
  x_j (x) B_j``;
- the pass of states from chunk to chunk: the state entering chunk c is
  ``sum_{z<c} exp(sum of the whole chunks z+1 .. c-1) closing_z``;
- the entering state's part of the output, ``exp(sum_{l<=i} dt_l A)
  C_i . S``.

Two implementations of the one function, chosen by what the call can
observe (``scan``'s ``interpret=None``: no flag, no variable):

- **Pallas TPU kernels with a ``jax.custom_vjp``**, on a TPU where
  ``chunk``, ``N`` and a group's ``R P`` lanes are multiples of 128 (and P
  of 16: :func:`tileable`). In the
  device trace: ``ssd_fwd`` (the forward; where it is differentiated it
  also writes each chunk's entering states, float32, for the backward to
  read) and ``ssd_bwd``. Both run a grid over (row, group, chunk) with the
  chunk axis sequential: a visit holds the chunk's ``x`` ``[Q, R P]``,
  ``B``, ``C`` ``[Q, N]`` and the group's log-decays in VMEM, makes
  ``C B^T`` once a group and each head's ``[Q, Q]`` decays from the
  cumulative sums, and steps the group's states ``[R P, N]`` (float32,
  VMEM scratch) in place: ``S <- exp(last) S + (x dt to_end)^T B``. A
  visit transposes ``x`` once and works a head as ``[P, Q]``: whole lane
  tiles where ``[Q, P]`` fills half of each, a number a position is a row
  and not a column, and the sums over P run down the sublanes. The
  backward walks the chunks in reverse with the states' cotangent in the
  same scratch, rebuilds a chunk's decays, and reduces ``d cum``, ``d dt``
  and ``d D`` to ``[Q]`` a head inside the kernel: nothing of size (row,
  chunk, head, Q, Q) reaches HBM in the forward, the replay or the
  backward. The layer's skip ``D x`` is added inside, in float32 before
  the one cast. Outside the kernels stay the floor, the cumulative sum of
  a chunk's log-decays and their layouts (``[B, G, R, T]`` and
  ``[B, G, T, R]``: passes over 4 MB), differentiated by autodiff, which
  is where ``d A`` comes from. The forward's ``y`` and states carry the
  names ``ssd_y`` and ``ssd_states`` (``checkpoint_name``): a layer's
  ``jax.checkpoint`` that keeps them replays no ``ssd_fwd``. A Pallas call
  has no SPMD partitioning rule, so on a mesh of more than one device
  (``scan``'s ``mesh``) the kernels run under ``shard_map``: the rows over
  (data, fsdp), the groups with their heads over tensor where it divides
  them, each device stepping its own states; ``A`` and ``D`` enter whole
  and their gradients are summed over the rows' axes.
- **``jax.numpy`` einsums the compiler lowers**, autodiff's backward
  (``_scan_products``): everywhere else, the CPU suite included. The pass of
  states is one product against a ``[chunks, chunks]`` matrix of decays. On
  a mesh they are the compiler's to partition.

Numerics, both: ``dt``, ``A``, the log-decays, their cumulative sums, every
``exp`` and the states are float32 (the products' pass of states at
``highest`` precision, since a default float32 product on a TPU rounds its
operands to bf16; the kernels' a sequential float32 update, never rounded
between chunks); the large products take their operands in ``x``'s dtype
(bf16 from the model, float32 from the tests) and accumulate in float32;
``y`` is cast once. The kernels' forward rounds where the einsums round
(``dt_j`` on the weights, ``x`` as it came); their backward needs no
``[Q, Q]`` cotangent, and makes the two sums over a decay's positions from
the rounded ``W`` and ``x`` of the forward's own product, so that they
cancel over a span as the einsums' float32 sums do.

A step's log-decay ``dt A`` is held at ``LOG_DECAY_FLOOR`` (-80) or above:
``exp(-80)`` is 1.8e-35, nought beside anything float32 keeps, so the result
is the recurrence's to the last bit that matters. Without the floor a head
whose ``|A| dt`` runs to 1e12 (the benchmark's draw has such heads) puts
1e14 into a chunk's cumulative sum, the difference of two of them loses
every digit of a short span, and one that comes out positive is an ``inf``
(seen on the chip, where the cumulative sum is a tree and not a loop: a
NaN loss from the first step). Every exponent is also held at nought or
below, which it is in exact arithmetic.

Heads share ``B`` and ``C`` in groups (head h reads group
``h // (H / G)``), so ``C B^T`` is made once a group.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

# a chunk whose decay over its whole length is above this carries state
# into the next one (the counter ``ssm.carry_share``)
CARRY_FLOOR = 0.1
# the least log-decay of one step: exp(-80) = 1.8e-35
LOG_DECAY_FLOOR = -80.0


def _chunked(a, chunk: int):
    """[B, T, ...] -> [B, T / chunk, chunk, ...]."""
    b, t = a.shape[:2]
    if t % chunk:
        raise ValueError(
            f"a sequence of {t} is no whole number of chunks of {chunk}")
    return a.reshape(b, t // chunk, chunk, *a.shape[2:])


def _below(n: int, strict: bool = False):
    """[n, n] mask: column j at or below (strictly below) row i."""
    i = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return j < i if strict else j <= i


def _scan_products(x, dt, a, b, c, *, chunk: int, pass_states: bool):
    """:func:`scan` as four ``jax.numpy`` products the compiler lowers, the
    backward pass autodiff's: what runs off a TPU, and on one where the
    shapes do not tile."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g  # heads a group
    dtype = x.dtype
    f32 = jnp.float32
    xc = _chunked(x, chunk).reshape(bsz, -1, chunk, g, r, p)
    bc, cc = _chunked(b, chunk), _chunked(c, chunk)
    dtc = _chunked(dt.astype(f32), chunk).reshape(bsz, -1, chunk, g, r)
    nc = xc.shape[1]
    # cum_i = sum_{l<=i} dt_l A, within the chunk: [B, C, Q, G, R], <= 0
    cum = jnp.cumsum(jnp.maximum(
        dtc * a.astype(f32).reshape(g, r), LOG_DECAY_FLOOR), axis=2)
    last = cum[:, :, -1]  # the whole chunk's: [B, C, G, R]

    # inside the chunk
    cb = jnp.einsum("bzign,bzjgn->bzgij", cc, bc,
                    preferred_element_type=f32)  # [B, C, G, Q, Q]
    rows = jnp.moveaxis(cum, 2, -1)  # [B, C, G, R, Q]
    span = rows[..., :, None] - rows[..., None, :]  # i, j
    decay = jnp.exp(jnp.where(_below(chunk), jnp.minimum(span, 0.0),
                              -jnp.inf))
    weights = cb[:, :, :, None] * decay * jnp.moveaxis(dtc, 2, -1)[..., None, :]
    y = jnp.einsum("bzgrij,bzjgrp->bzigrp", weights.astype(dtype), xc,
                   preferred_element_type=f32)

    if pass_states and nc > 1:
        # each chunk's closing state: [B, C, G, R, P, N]
        to_end = jnp.exp(jnp.minimum(last[:, :, None] - cum, 0.0)) * dtc
        closing = jnp.einsum(
            "bzjgrp,bzjgn->bzgrpn",
            (xc.astype(f32) * to_end[..., None]).astype(dtype), bc,
            preferred_element_type=f32)
        # the state entering chunk c: closing states of the chunks before
        # it, each decayed by the whole chunks in between
        whole = jnp.moveaxis(last, 1, -1)  # [B, G, R, C]
        through = jnp.cumsum(whole, axis=-1)
        # sum of the chunks z+1 .. c-1 = through[c-1] - through[z]
        between = (through - whole)[..., :, None] - through[..., None, :]
        carry = jnp.exp(jnp.where(_below(nc, strict=True),
                                  jnp.minimum(between, 0.0), -jnp.inf))
        entering = jnp.einsum("bgrcz,bzgrpn->bcgrpn", carry, closing,
                              precision=lax.Precision.HIGHEST)
        y = y + jnp.exp(jnp.minimum(cum, 0.0))[..., None] * jnp.einsum(
            "bzign,bzgrpn->bzigrp", cc, entering.astype(dtype),
            preferred_element_type=f32)
    return y.reshape(bsz, t, h, p).astype(dtype)


_LANE = 128
_NT = (((1,), (1,)), ((), ()))  # a . b^T
_TN = (((0,), (0,)), ((), ()))  # a^T . b


def tileable(chunk: int, n: int, r: int, p: int) -> bool:
    """Whether the kernels take these shapes: a chunk, the state and a
    group's ``R P`` channels in whole lane tiles, and a head's P rows of
    the transposed ``x`` in whole packed sublanes."""
    return not (chunk % _LANE or n % _LANE or (r * p) % _LANE or p % 16)


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _decays(cum, cumc_ref, dtc_ref, r: int, later):
    """Head ``r``'s decays transposed and times ``dt_j``, [Q (from j), Q (to
    i)] float32: ``exp(min(cum_i - cum_j, 0)) dt_j`` where ``j <= i`` and
    nought elsewhere; cum_i along the lanes (a row of ``cum`` [R, Q]),
    cum_j and dt_j down the sublanes (columns of ``cumc``, ``dtc`` [Q, R])."""
    return jnp.where(later, jnp.exp(jnp.minimum(
        cum[r:r + 1] - cumc_ref[:, r:r + 1], 0.0)) * dtc_ref[:, r:r + 1], 0.0)


def _later(q: int):
    """[Q, Q] mask: the lane's position i at or after the sublane's j."""
    return (lax.broadcasted_iota(jnp.int32, (q, q), 0)
            <= lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _over_chunk(cum, dt):
    """cum, dt [R, Q] -> (``exp(cum_i)`` and ``dt_j exp(min(last - cum_j,
    0))``, both [R, Q]: what an entering state has kept by position i, what
    position j's x hands to the chunk's end; ``last`` [R, 1])."""
    last = cum[:, cum.shape[1] - 1:]
    return (jnp.exp(jnp.minimum(cum, 0.0)),
            dt * jnp.exp(jnp.minimum(last - cum, 0.0)), last)


def _whole(last, r: int, n: int):
    """``exp(last)`` of head ``r`` as a row [1, n] (Mosaic broadcasts along
    sublanes or along lanes, not one element along both: the ``exp`` sits
    between the two)."""
    return jnp.exp(jnp.minimum(
        jnp.broadcast_to(last[r:r + 1], (1, n)), 0.0))


def _fwd_kernel(*refs, heads: int, p: int, pass_states: bool,
                save_states: bool):
    """Grid (row, group, chunk), the chunk axis sequential. Operands: x
    [Q, R P]; b, c [Q, N]; dt, cum [R, Q] and dtc, cumc [Q, R] float32 (the
    same numbers in both layouts); d [R, Q] (a head's ``D`` along its row).
    Results: y [Q, R P] and, with ``save_states``, the states as they enter
    the chunk. Scratch: the group's states [R P, N] float32 across the
    chunks.

    A visit works on ``x`` transposed, a head's [P, Q] (whole lane tiles,
    where [Q, P] fills half of each; what is one number a position is then
    a row, and a sum over P runs down the sublanes): ``y^T = x^T W^T +
    e (S C^T)`` with ``W^T = (B C^T) o decays^T dt_j`` made in place and
    rounded once, as the products form rounds it, and ``S <- exp(last) S +
    (x dt to_end)^T B``."""
    (x_ref, b_ref, c_ref, dt_ref, cum_ref, dtc_ref, cumc_ref, d_ref,
     y_ref) = refs[:9]
    dtype = x_ref.dtype
    f32 = jnp.float32
    q, n = b_ref.shape
    xt = x_ref[...].T  # [R P, Q]
    cum = cum_ref[...]
    if pass_states:
        s_ref = refs[-1]

        @pl.when(pl.program_id(2) == 0)
        def _from_nought():
            s_ref[...] = jnp.zeros_like(s_ref)

        if save_states:
            refs[9][...] = s_ref[...]
        entering = _dot(s_ref[...].astype(dtype), c_ref[...], _NT)  # [R P, Q]
        kept, to_end, last = _over_chunk(cum, dt_ref[...])
    cbt = _dot(b_ref[...], c_ref[...], _NT)  # [Q, Q]: B_j . C_i, once a group
    later = _later(q)
    ys, xs = [], []
    for r in range(heads):
        rows = slice(r * p, (r + 1) * p)
        y = _dot(xt[rows], (cbt * _decays(
            cum, cumc_ref, dtc_ref, r, later)).astype(dtype))
        x = xt[rows].astype(f32)
        if pass_states:
            y = y + kept[r:r + 1] * entering[rows]
            xs.append((x * to_end[r:r + 1]).astype(dtype))
        ys.append((y + d_ref[r:r + 1] * x).astype(y_ref.dtype))
    y_ref[...] = jnp.concatenate(ys, axis=0).T
    if pass_states:
        closing = _dot(jnp.concatenate(xs, axis=0), b_ref[...])  # [R P, N]
        for r in range(heads):
            rows = slice(r * p, (r + 1) * p)
            s_ref[rows] = _whole(last, r, n) * s_ref[rows] + closing[rows]


def _bwd_kernel(*refs, heads: int, p: int, pass_states: bool):
    """The same grid, the chunks from the last to the first. Beside the
    forward's operands: dy [Q, R P] and the states that entered the chunk
    [R P, N] float32. Results: dx [Q, R P]; db, dc [Q, N] (summed over the
    group's heads here); d dt and d cum [R, Q] float32 (summed over P and
    over the other position here); d d [R, Q], summed over the chunks (its
    block stays while they pass). Scratch: the cotangent of the states that
    leave the chunk, [R P, N] float32, stepped in reverse.

    With ``W = (C B^T o decays dt_j)``: ``y = W x + e (C . S)`` and ``S' =
    exp(last) S + (dt to_end x)^T B``. The sums over a decay's two positions
    never need the [Q, Q] cotangent: ``sum_j dW_ij W_ij`` is ``dy_i . (W
    x)_i`` and ``sum_i dW_ij W_ij`` is ``x_j . (W^T dy)_j``, so d cum_i takes
    ``dy_i . y_i`` whole and gives ``x_i . (W^T dy)_i`` back. Over a span of
    positions the two cancel, all but the pairs astride it, and only the
    same bilinear form of the same rounded operands cancels: both are made
    from the ``W`` and the ``x`` the forward's product took. The same sum
    over ``dt_j`` is the part of d dt_j that comes through ``W``."""
    (x_ref, b_ref, c_ref, dt_ref, cum_ref, dtc_ref, cumc_ref, d_ref,
     dy_ref) = refs[:9]
    at = 9
    if pass_states:
        s_ref = refs[at]
        at += 1
    dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, dd_ref = refs[at:at + 6]
    dtype = x_ref.dtype
    f32 = jnp.float32
    q, n = b_ref.shape
    first_visit = pl.program_id(2) == 0
    xt, dyt = x_ref[...].T, dy_ref[...].T  # [R P, Q]
    dt, cum = dt_ref[...], cum_ref[...]
    # 0 / 0 where a dt has underflowed: its W is nought, and so is its sum
    per_dt = jnp.where(dt > 0.0, 1.0 / dt, 0.0)

    @pl.when(first_visit)
    def _no_sum_yet():
        dd_ref[...] = jnp.zeros_like(dd_ref)

    if pass_states:
        ds_ref = refs[-1]

        @pl.when(first_visit)
        def _from_nought():
            ds_ref[...] = jnp.zeros_like(ds_ref)

        s = s_ref[...].astype(dtype)
        ds = ds_ref[...].astype(dtype)
        entering = _dot(s, c_ref[...], _NT)  # [R P, Q]: S . C_i
        leaving = _dot(ds, b_ref[...], _NT)  # [R P, Q]: dS' . B_j
        kept, to_end, last = _over_chunk(cum, dt)
        at_end = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    cbt = _dot(b_ref[...], c_ref[...], _NT)
    later = _later(q)
    dcbt = jnp.zeros((q, q), f32)
    dxs, xs, dyes = [], [], []
    for r in range(heads):
        rows = slice(r * p, (r + 1) * p)
        x, dy = xt[rows].astype(f32), dyt[rows].astype(f32)
        decays = _decays(cum, cumc_ref, dtc_ref, r, later)
        weights = (cbt * decays).astype(dtype)  # W^T: [Q from j, Q to i]
        dcbt = dcbt + _dot(xt[rows], dyt[rows], _TN) * decays
        y = _dot(xt[rows], weights)  # [P, Q to i]
        dx = _dot(dyt[rows], weights, _NT)  # [P, Q from j]: (W^T dy)^T
        through_w = jnp.sum(x * dx, axis=0, keepdims=True)  # x_j . (W^T dy)_j
        ddt = through_w * per_dt[r:r + 1]
        dcum = -through_w
        if pass_states:
            y = y + kept[r:r + 1] * entering[rows]
            xs.append((x * to_end[r:r + 1]).astype(dtype))
            dyes.append((dy * kept[r:r + 1]).astype(dtype))
            # d(last - cum_j) = dt_j to_end_j x_j . (dS' . B_j); d last is
            # their sum and the states' own decay
            d_span = to_end[r:r + 1] * jnp.sum(x * leaving[rows], axis=0,
                                               keepdims=True)
            d_last = jnp.sum(d_span, axis=1, keepdims=True) + jnp.sum(
                _whole(last, r, n) * jnp.sum(
                    ds_ref[rows] * s_ref[rows], axis=0, keepdims=True),
                axis=1, keepdims=True)
            dcum = dcum - d_span + jnp.where(at_end, d_last, 0.0)
            ddt = ddt + d_span * per_dt[r:r + 1]
            dx = dx + to_end[r:r + 1] * leaving[rows]
        ddt_ref[r:r + 1] = ddt
        dcum_ref[r:r + 1] = dcum + jnp.sum(dy * y, axis=0, keepdims=True)
        dd_ref[r:r + 1] += jnp.sum(dy * x, axis=0, keepdims=True)
        dxs.append((dx + d_ref[r:r + 1] * dy).astype(dx_ref.dtype))
    dx_ref[...] = jnp.concatenate(dxs, axis=0).T
    dcbt = dcbt.astype(dtype)
    db = _dot(dcbt, c_ref[...])
    dc = _dot(dcbt, b_ref[...], _TN)
    if pass_states:
        dye = jnp.concatenate(dyes, axis=0)  # [R P, Q]
        dc = dc + _dot(dye, s, _TN)
        db = db + _dot(jnp.concatenate(xs, axis=0), ds, _TN)
        entered = _dot(dye, c_ref[...])  # [R P, N]
        for r in range(heads):
            rows = slice(r * p, (r + 1) * p)
            ds_ref[rows] = _whole(last, r, n) * ds_ref[rows] + entered[rows]
    dc_ref[...] = dc.astype(dc_ref.dtype)
    db_ref[...] = db.astype(db_ref.dtype)


def _specs(q: int, lanes: int, n: int, r: int, chunk_of):
    """Block specs of a visit (row, group, chunk): [Q, R P] of x's kind,
    [Q, N] of B's, [R, Q] and [Q, R] of the log-decays', a group's [R, Q]
    that stays while the chunks pass, the states'; and ``operands``, those
    of what :func:`_operands` gives, in its order."""
    z = chunk_of
    s = dict(
        x=pl.BlockSpec((None, q, lanes), lambda i, g, c: (i, z(c), g)),
        bc=pl.BlockSpec((None, q, n), lambda i, g, c: (i, z(c), g)),
        row=pl.BlockSpec((None, None, r, q), lambda i, g, c: (i, g, 0, z(c))),
        col=pl.BlockSpec((None, None, q, r), lambda i, g, c: (i, g, z(c), 0)),
        d=pl.BlockSpec((None, r, q), lambda i, g, c: (g, 0, 0)),
        dd=pl.BlockSpec((None, None, r, q), lambda i, g, c: (i, g, 0, 0)),
        st=pl.BlockSpec((None, None, None, lanes, n),
                        lambda i, g, c: (i, g, z(c), 0, 0)))
    s["operands"] = [s[k] for k in ("x", "bc", "bc", "row", "row", "col",
                                    "col", "d")]
    return s


def _params():
    # no ``vmem_limit_bytes``: a visit's blocks double-buffered (x, dy, dx:
    # 128 KB each at the configuration's sizes; the entering states 256
    # KB), the states' scratch, the transposed copies and a few [R P, Q]
    # float32 products are ~4 MB, under Mosaic's default of 16 MiB
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _nbytes(*arrays) -> int:
    return sum(a.size * a.dtype.itemsize for a in arrays)


# jitted so that a step's scans of one shape (four layers: forward, replay
# and backward) are traced once a process: a kernel's body is Python
@functools.partial(jax.jit, static_argnames=(
    "chunk", "pass_states", "save_states", "interpret"))
def _ssd_fwd(x, b, c, dt, cum, dtc, cumc, d, *, chunk: int,
             pass_states: bool, save_states: bool, interpret: bool):
    """x [B, T, G R P], b and c [B, T, G N], dt and cum [B, G, R, T], dtc and
    cumc [B, G, T, R], d [G, R, chunk] -> y as x, and with ``save_states``
    the states that entered each chunk, [B, G, T / chunk, R P, N]
    float32."""
    bsz, t, width = x.shape
    g, r = dt.shape[1:3]
    n, lanes, nc = b.shape[2] // g, width // g, t // chunk
    s = _specs(chunk, lanes, n, r, lambda c: c)
    save_states = save_states and pass_states
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [s["x"]]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((bsz, g, nc, lanes, n),
                                              jnp.float32))
        out_specs.append(s["st"])
    visits = bsz * g * nc
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=r, p=lanes // r,
                          pass_states=pass_states, save_states=save_states),
        out_shape=out_shape,
        grid=(bsz, g, nc),
        in_specs=s["operands"],
        out_specs=out_specs,
        scratch_shapes=(
            [pltpu.VMEM((lanes, n), jnp.float32)] if pass_states else []),
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * visits * chunk * (chunk * n + chunk * lanes
                                        + 2 * n * lanes),
            transcendentals=visits * r * chunk * chunk,
            bytes_accessed=_nbytes(x, x, b, c, dt, cum, dtc, cumc)
            + (visits * n * lanes * 4 if save_states else 0)),
        interpret=interpret,
        name="ssd_fwd",
    )(x, b, c, dt, cum, dtc, cumc, d)
    return (out[0], out[1]) if save_states else (out[0], None)


@functools.partial(jax.jit, static_argnames=(
    "chunk", "pass_states", "interpret"))
def _ssd_bwd(x, b, c, dt, cum, dtc, cumc, d, dy, states, *, chunk: int,
             pass_states: bool, interpret: bool):
    """The forward's operands, dy as y and the saved states -> (dx, db, dc
    as x, b, c; d dt and d cum [B, G, R, T] float32; d d [B, G, R, chunk]
    float32)."""
    bsz, t, width = x.shape
    g, r = dt.shape[1:3]
    n, lanes, nc = b.shape[2] // g, width // g, t // chunk
    s = _specs(chunk, lanes, n, r, lambda c: nc - 1 - c)
    operands = [x, b, c, dt, cum, dtc, cumc, d, dy]
    in_specs = s["operands"] + [s["x"]]
    if pass_states:
        operands.append(states)
        in_specs.append(s["st"])
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                 jax.ShapeDtypeStruct(b.shape, b.dtype),
                 jax.ShapeDtypeStruct(c.shape, c.dtype),
                 jax.ShapeDtypeStruct(dt.shape, jnp.float32),
                 jax.ShapeDtypeStruct(cum.shape, jnp.float32),
                 jax.ShapeDtypeStruct((bsz, g, r, chunk), jnp.float32)]
    out_specs = [s["x"], s["bc"], s["bc"], s["row"], s["row"], s["dd"]]
    visits = bsz * g * nc
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=r, p=lanes // r,
                          pass_states=pass_states),
        out_shape=out_shape,
        grid=(bsz, g, nc),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=(
            [pltpu.VMEM((lanes, n), jnp.float32)] if pass_states else []),
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * visits * chunk * (3 * chunk * n + 3 * chunk * lanes
                                        + 5 * n * lanes),
            transcendentals=visits * r * chunk * chunk,
            bytes_accessed=_nbytes(x, x, x, b, b, c, c, dt, dt, cum, cum,
                                   dtc, cumc)
            + (visits * n * lanes * 4 if pass_states else 0)),
        interpret=interpret,
        name="ssd_bwd",
    )(*operands)


def _by_group(v, g: int):
    """[B, T, H] -> [B, G, T, R]: a group's columns together."""
    bsz, t, h = v.shape
    return jnp.moveaxis(v.reshape(bsz, t, g, h // g), 2, 1)


def _operands(x, dt, cum, b, c, d, chunk: int):
    """The layouts the kernels read: x, b, c with their heads' (groups')
    channels side by side; dt and cum with the positions along the lanes
    [B, G, R, T], and both once more as a group's columns [B, G, T, R]; a
    head's ``D`` along a row [G, R, chunk]."""
    bsz, t = x.shape[:2]
    g = b.shape[2]
    dtc, cumc = _by_group(dt, g), _by_group(cum, g)
    d = jnp.broadcast_to(
        d.astype(jnp.float32).reshape(g, -1, 1), (g, dt.shape[2] // g, chunk))
    return (x.reshape(bsz, t, -1), b.reshape(bsz, t, -1),
            c.reshape(bsz, t, -1), jnp.swapaxes(dtc, 2, 3),
            jnp.swapaxes(cumc, 2, 3), dtc, cumc, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan_kernels(x, dt, cum, b, c, d, chunk, pass_states, interpret):
    """x [B, T, H, P], dt and cum [B, T, H] float32 (cum the sum of the
    log-decays from its chunk's start), b and c [B, T, G, N], d [H] float32
    -> y as x."""
    y, _ = _ssd_fwd(*_operands(x, dt, cum, b, c, d, chunk), chunk=chunk,
                    pass_states=pass_states, save_states=False,
                    interpret=interpret)
    return y.reshape(x.shape)


def _scan_kernels_fwd(x, dt, cum, b, c, d, chunk, pass_states, interpret):
    y, states = _ssd_fwd(*_operands(x, dt, cum, b, c, d, chunk), chunk=chunk,
                         pass_states=pass_states, save_states=True,
                         interpret=interpret)
    # under a layer's ``jax.checkpoint`` these two may be kept by name
    # (models/llama.py does): the replay then runs no ``ssd_fwd``
    y = checkpoint_name(y, "ssd_y")
    if states is not None:
        states = checkpoint_name(states, "ssd_states")
    return y.reshape(x.shape), (x, dt, cum, b, c, d, states)


def _scan_kernels_bwd(chunk, pass_states, interpret, res, dy):
    x, dt, cum, b, c, d, states = res
    bsz, t = x.shape[:2]
    dx, db, dc, ddt, dcum, dd = _ssd_bwd(
        *_operands(x, dt, cum, b, c, d, chunk), dy.reshape(bsz, t, -1),
        states, chunk=chunk, pass_states=pass_states, interpret=interpret)
    by_head = lambda v: jnp.moveaxis(v, 3, 1).reshape(dt.shape)
    return (dx.reshape(x.shape), by_head(ddt), by_head(dcum),
            db.reshape(b.shape), dc.reshape(c.shape),
            jnp.sum(dd, axis=(0, 3)).reshape(d.shape).astype(d.dtype))


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def scan(x, dt, a, b, c, *, chunk: int, skip, pass_states: bool = True,
         interpret: Optional[bool] = None, mesh=None):
    """x [B, T, H, P], dt [B, T, H] (positive, float32), a [H] (negative,
    float32), b and c [B, T, G, N], skip [H] float32 (the layer's ``D``) ->
    ``y + D x`` [B, T, H, P] in ``x``'s dtype, the sum in float32 before the
    one cast. ``pass_states=False`` is the fault a test plants: every chunk
    starts from nought.

    ``interpret=None`` runs the Pallas kernels on a TPU where the shapes
    tile (:func:`tileable`) and the ``jax.numpy`` products anywhere else;
    ``interpret=True`` reaches the kernels' bodies off the TPU, for their
    tests. A Pallas call has no SPMD partitioning rule: on a ``mesh`` of
    more than one device the kernels run under ``shard_map``, the rows over
    the mesh's ``data`` and ``fsdp`` axes and the groups with their heads
    over ``tensor`` where it divides them (as ``flash_attention`` shards),
    every other axis whole. The products are the compiler's to
    partition."""
    t, h, p = x.shape[1:]
    g, n = b.shape[2:]
    if t % chunk:
        raise ValueError(
            f"a sequence of {t} is no whole number of chunks of {chunk}")
    f32 = jnp.float32
    if interpret is None:
        if jax.default_backend() != "tpu" or not tileable(
                chunk, n, h // g, p):
            y = _scan_products(x, dt, a, b, c, chunk=chunk,
                               pass_states=pass_states)
            return (y.astype(f32)
                    + skip[:, None] * x.astype(f32)).astype(x.dtype)
        interpret = False

    def local(x, dt, a, b, c, skip):
        dt = dt.astype(f32)
        # cum_i = sum_{l<=i} dt_l A from the chunk's start: [B, T, H], <= 0
        cum = jnp.cumsum(_chunked(jnp.maximum(
            dt * a.astype(f32), LOG_DECAY_FLOOR), chunk),
            axis=2).reshape(dt.shape)
        return _scan_kernels(x, dt, cum, b.astype(x.dtype),
                             c.astype(x.dtype), skip, chunk, pass_states,
                             interpret)

    if mesh is None or mesh.size == 1:
        return local(x, dt, a, b, c, skip)
    rows = tuple(
        ax for ax in ("data", "fsdp") if ax in mesh.axis_names) or None
    # a group and its heads stay on one device
    heads = "tensor" if ("tensor" in mesh.axis_names
                         and g % mesh.shape["tensor"] == 0) else None
    per_head = PartitionSpec(heads)
    wide = PartitionSpec(rows, None, heads, None)
    # check_vma=False: a pallas_call's results carry no varying-axes
    # annotation (kernels/flash_attention.py); a and skip, whole over the
    # rows' axes, get their cotangents summed over them by the transpose
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(wide, PartitionSpec(rows, None, heads), per_head, wide, wide,
                  per_head),
        out_specs=wide, check_vma=False)(x, dt, a, b, c, skip)


def carry_share(dt, a, *, chunk: int):
    """The share of (row, chunk, head) whose decay over the whole chunk,
    ``exp(sum dt A)``, is above :data:`CARRY_FLOOR`: how much of the scan
    hands state from one chunk to the next. A scalar, float32."""
    whole = jnp.sum(
        _chunked(dt.astype(jnp.float32), chunk) * a.astype(jnp.float32),
        axis=2)
    return jnp.mean((whole > jnp.log(CARRY_FLOOR)).astype(jnp.float32))


def causal_conv(x, w, bias):
    """Depthwise causal convolution along positions, each channel alone:
    ``y[t, c] = bias[c] + sum_k w[c, k] x[t - (K - 1) + k, c]`` with nought
    before the sequence's start. x [B, T, C], w [C, K], bias [C]. K shifted
    multiply-adds (K is 4: one fused pass over ``x``), in float32 and
    returned so: what follows it rounds. The ``jax.numpy`` form of
    ``ssm_conv_gate.conv_silu``, which calls it wherever its kernels do not
    run: ``x`` padded and widened in HBM, autodiff's backward a pass a
    tap."""
    k = w.shape[1]
    t = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), [(0, 0), (k - 1, 0), (0, 0)])
    y = bias.astype(jnp.float32)
    for i in range(k):
        y = y + padded[:, i:i + t] * w[:, i].astype(jnp.float32)
    return y
