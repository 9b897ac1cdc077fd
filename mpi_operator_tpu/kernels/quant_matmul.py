"""Quantized matmul path: int8 / fp8 forward, full-precision backward.

ISSUE 16 tentpole (d): the llama FFN matmuls (w_gate/w_up/w_down — ~2/3 of
the model's FLOPs) can run on the MXU's low-precision throughput tiers.
This module is the config-gated seam: dynamic per-row/per-column absmax
quantization of activations and weights, the contraction itself in the
narrow dtype (``lax.dot_general`` with ``preferred_element_type`` so XLA
lowers to the int8/fp8 MXU path on hardware that has one — v5e int8 is
2x the bf16 peak, v6e adds native fp8), and dequantization folded into the
epilogue as a rank-1 outer-product scale.

Training stays stable because only the FORWARD contraction is quantized:
a ``custom_vjp`` routes the backward through plain full-precision matmuls
(the straight-through estimator — quantization noise is treated as
identity under differentiation). That is the standard QAT recipe; it keeps
the loss landscape intact while the forward eats the rounding error.

Honesty note (PERF.md round 16): on backends whose MXU has no narrow-dtype
tier the compiler upcasts and the path measures pure overhead — the config
flag defaults OFF, and the bench reports the flag it ran with.

Scaling granularity: activations per-row (each [.., K] vector gets its own
scale), weights per-column — the finest granularity expressible as a
rank-1 epilogue, so accuracy degrades per-token/per-feature rather than
per-tensor, with zero extra matmuls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# quantization grids: int8 symmetric [-127, 127] (dropping -128 keeps the
# grid symmetric so absmax scaling is unbiased); fp8 e4m3 saturates at 448
_QMAX = {"int8": 127.0, "fp8": 448.0}

_DIMS = (((1,), (0,)), ((), ()))  # plain [M,K] @ [K,N]


def _scale(a32: jnp.ndarray, axis: int, qmax: float) -> jnp.ndarray:
    """Per-slice absmax → multiply-by-scale dequant factor, floored so an
    all-zero row/column quantizes to zeros instead of dividing by zero."""
    m = jnp.max(jnp.abs(a32), axis=axis, keepdims=True)
    return jnp.maximum(m, 1e-12) / qmax


def _quantize(a32, scale, precision):
    if precision == "int8":
        return jnp.clip(jnp.round(a32 / scale), -127.0, 127.0).astype(jnp.int8)
    # rounded to e4m3's three mantissa bits by arithmetic before the cast.
    # A chip with no fp8 product (v5e) gets the operands widened again, and
    # the compiler drops a narrowing convert that a widening one follows: the
    # cast alone read as bf16 on every number there (PERF.md section 7, row
    # 18f). A reduce_precision it keeps. Five exponent bits, so that nothing
    # up to 448 overflows; the cast that follows is then exact but for
    # e4m3's subnormals, under 2**-6 / 448 of the largest.
    return lax.reduce_precision(
        a32 / scale, exponent_bits=5, mantissa_bits=3
    ).astype(jnp.float8_e4m3fn)


def _forward_2d(x: jnp.ndarray, w: jnp.ndarray, precision: str) -> jnp.ndarray:
    qmax = _QMAX[precision]
    x32 = x.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    sx = _scale(x32, -1, qmax)  # [M, 1] — per activation row
    sw = _scale(w32, 0, qmax)   # [1, N] — per weight column
    xq = _quantize(x32, sx, precision)
    wq = _quantize(w32, sw, precision)
    acc = lax.dot_general(
        xq, wq, _DIMS,
        preferred_element_type=(
            jnp.int32 if precision == "int8" else jnp.float32
        ),
    )
    return (acc.astype(jnp.float32) * sx * sw).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _quant_mm_2d(x, w, precision):
    return _forward_2d(x, w, precision)


def _quant_mm_fwd(x, w, precision):
    return _forward_2d(x, w, precision), (x, w)


def _quant_mm_bwd(precision, res, g):
    # straight-through: backward ignores the quantizer and differentiates
    # the underlying x @ w in full precision — gradient quality is what
    # keeps QAT training curves tracking the bf16 baseline
    x, w = res
    dx = (g @ w.T.astype(g.dtype)).astype(x.dtype)
    dw = (x.T.astype(g.dtype) @ g).astype(w.dtype)
    return dx, dw


_quant_mm_2d.defvjp(_quant_mm_fwd, _quant_mm_bwd)


def quant_matmul(
    x: jnp.ndarray, w: jnp.ndarray, *, precision: str = "int8"
) -> jnp.ndarray:
    """``x @ w`` with the contraction quantized to ``precision``.

    ``x``: [..., K] (leading dims flattened for the 2D kernel and restored
    after); ``w``: [K, N]. ``precision`` ∈ {"int8", "fp8", "bf16"} — "bf16"
    is the identity escape hatch so call sites can pass the config flag
    straight through."""
    if precision == "bf16":
        return x @ w
    if precision not in _QMAX:
        raise ValueError(
            f"precision={precision!r}; expected int8|fp8|bf16"
        )
    lead = x.shape[:-1]
    out = _quant_mm_2d(x.reshape(-1, x.shape[-1]), w, precision)
    return out.reshape(*lead, w.shape[-1])


# -- the grouped product (parallel/moe.py's expert matrices) -----------------

def _ragged_forward(x, w, group_sizes, precision):
    """Rows of group g times ``w[g]``, quantized as :func:`_forward_2d`
    does it: activations a row, weights a column of each expert; the
    contraction in the narrow dtype, the scales in the epilogue."""
    qmax = _QMAX[precision]
    x32 = x.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    sx = _scale(x32, -1, qmax)  # [M, 1]
    sw = _scale(w32, 1, qmax)   # [G, 1, N]
    acc = lax.ragged_dot(
        _quantize(x32, sx, precision), _quantize(w32, sw, precision),
        group_sizes,
        preferred_element_type=(
            jnp.int32 if precision == "int8" else jnp.float32
        ),
    )
    # each row's own expert's column scales; rows past the last group
    # belong to no expert and are never read
    group_of_row = jnp.repeat(
        jnp.arange(w.shape[0]), group_sizes, total_repeat_length=x.shape[0])
    return (acc.astype(jnp.float32) * sx * sw[group_of_row, 0]).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _quant_ragged(x, w, group_sizes, precision):
    return _ragged_forward(x, w, group_sizes, precision)


def _quant_ragged_fwd(x, w, group_sizes, precision):
    return _ragged_forward(x, w, group_sizes, precision), (x, w, group_sizes)


def _quant_ragged_bwd(precision, res, g):
    # straight-through, as _quant_mm_bwd: the plain grouped product's own
    # transposes
    x, w, group_sizes = res
    _, vjp = jax.vjp(lambda x, w: lax.ragged_dot(x, w, group_sizes), x, w)
    return (*vjp(g.astype(x.dtype)), None)


_quant_ragged.defvjp(_quant_ragged_fwd, _quant_ragged_bwd)


def quant_ragged_dot(x, w, group_sizes, *, precision: str = "int8"):
    """``lax.ragged_dot(x, w, group_sizes)`` with the contraction quantized
    to ``precision``: ``x`` [M, K] with its rows sorted by group, ``w``
    [G, K, N], ``group_sizes`` [G]. Forward in the narrow dtype, backward
    full precision (straight-through), like :func:`quant_matmul`."""
    if precision == "bf16":
        return lax.ragged_dot(x, w, group_sizes)
    if precision not in _QMAX:
        raise ValueError(
            f"precision={precision!r}; expected int8|fp8|bf16"
        )
    return _quant_ragged(x, w, group_sizes, precision)


def quant_error(x, w, *, precision: str = "int8") -> float:
    """Relative Frobenius error of the quantized product vs the f32 oracle
    — the number PERF.md quotes next to any MFU claim for this path."""
    exact = x.astype(jnp.float32) @ w.astype(jnp.float32)
    approx = quant_matmul(x, w, precision=precision).astype(jnp.float32)
    return float(
        jnp.linalg.norm(approx - exact) / jnp.maximum(jnp.linalg.norm(exact), 1e-12)
    )
