"""Mamba-2's state-space scan in its chunked form (SSD, arXiv:2405.21060),
and the short causal convolution in front of it.

The function, per head with a state ``S`` [P, N] that starts at nought:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t . C_t

``scan`` computes it a chunk of ``chunk`` positions at a time, as four
products that the compiler lowers (``jax.numpy`` einsums: no Pallas kernel
yet; the backward pass is autodiff's through the same products):

- inside a chunk the masked product ``(L o C B^T) (dt x)`` with
  ``L_ij = exp(sum_{j<l<=i} dt_l A)`` for ``j <= i`` and nought above the
  diagonal. The mask goes on before the ``exp``: the upper triangle's sums
  run backwards and are positive;
- each chunk's closing state ``sum_j exp(sum_{j<l<=last} dt_l A) dt_j
  x_j (x) B_j``;
- the pass of states from chunk to chunk: the state entering chunk c is
  ``sum_{z<c} exp(sum of the whole chunks z+1 .. c-1) closing_z``, one
  product against a ``[chunks, chunks]`` matrix of decays;
- the entering state's part of the output, ``exp(sum_{l<=i} dt_l A)
  C_i . S``.

Numerics: ``dt``, ``A``, the decays, their cumulative sums and the states
are float32 (the pass of states at ``highest`` precision: a default float32
product on a TPU rounds its operands to bf16); the three large products
take their operands in ``x``'s dtype (bf16 from the model, float32 from the
tests) and accumulate in float32.

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

import jax
import jax.numpy as jnp
from jax import lax

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


def scan(x, dt, a, b, c, *, chunk: int, pass_states: bool = True):
    """x [B, T, H, P], dt [B, T, H] (positive, float32), a [H] (negative,
    float32), b and c [B, T, G, N] -> y [B, T, H, P] in ``x``'s dtype.
    ``pass_states=False`` is the fault a test plants: every chunk starts
    from nought."""
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
    returned so: what follows it rounds."""
    k = w.shape[1]
    t = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), [(0, 0), (k - 1, 0), (0, 0)])
    y = bias.astype(jnp.float32)
    for i in range(k):
        y = y + padded[:, i:i + t] * w[:, i].astype(jnp.float32)
    return y
