"""The Mamba-2 mixer (arXiv:2405.21060) as the decoder's state-space layer
kind (models/llama.py calls it on the normed activations and adds the
residual itself).

    [z | xBC | dt] = u . W_in          widths  H P | H P + 2 G N | H
    xBC = silu(causal_conv(xBC) + bias)    the short convolution, each
         channel alone over K positions (kernels/ssm_conv_gate.py:
         ``conv_silu``)
    x, B, C = split(xBC)               [T, H, P], [T, G, N], [T, G, N]
    dt = softplus(dt + dt_bias)   A = -exp(A_log)             float32
    y  = scan(x, dt, A, B, C) + D x    the chunked scan (kernels/ssd.py)
    g  = y silu(z), RMS-normalised over each of the G groups of channels
         alone, times a weight          (gate before the norm;
         kernels/ssm_conv_gate.py: ``gate_norm``)
    out = g . W_out

The two projections are the compiler's products. The three passes between
them each have two forms and choose between them themselves, from what the
call observes (no flag): on a TPU at shapes that tile, Pallas kernels with
hand-written backward passes (``ssm_conv_fwd`` / ``ssm_conv_bwd``,
``ssd_fwd`` / ``ssd_bwd``, ``ssm_gate_fwd`` / ``ssm_gate_bwd`` in the
trace: the convolution and the gated norm are memory-bound and read each
operand once, in bf16, where the compiler's passes widen, pad and reshape
in HBM; the scan keeps a chunk's decays in VMEM), under ``shard_map`` on a
mesh of more than one device; ``jax.numpy`` forms anywhere else, the CPU
suite included.

No bias but the convolution's. Scopes in the device trace, inside the
decoder's ``mamba``: ``ssm_in_proj``, ``ssm_conv``, ``ssm_scan``,
``ssm_gate_norm``, ``ssm_out_proj``. Counter: ``ssm.carry_share``, the share
of (row, chunk, head) whose whole-chunk decay exceeds 0.1, from the step's
own ``dt``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from mpi_operator_tpu.kernels import ssd, ssm_conv_gate

Params = Dict[str, Any]

CARRY_SHARE = "ssm.carry_share"


def widths(c):
    """(inner H P, convolved H P + 2 G N, projected 2 H P + 2 G N + H)."""
    inner = c.ssm_heads * c.ssm_head_dim
    conv = inner + 2 * c.ssm_groups * c.ssm_state
    return inner, conv, inner + conv + c.ssm_heads


def init(key, c) -> Params:
    """One layer's weights. Projections std fan_in**-0.5; ``A`` = 1 .. H and
    ``dt`` log-uniform in [0.001, 0.1] (``dt_bias`` its inverse softplus),
    ``D`` = 1: the published starting values."""
    inner, conv, proj = widths(c)
    ki, kc, kd, ko = jax.random.split(key, 4)
    h, d = c.ssm_heads, c.d_model
    dt = jnp.exp(jax.random.uniform(kd, (h,), jnp.float32)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return {
        "norm": {"scale": jnp.ones((d,), jnp.float32)},
        "in_proj": {"w": jax.random.normal(ki, (d, proj), jnp.float32)
                    * d ** -0.5},
        "conv": {"w": jax.random.normal(kc, (conv, c.conv_kernel),
                                        jnp.float32) * c.conv_kernel ** -0.5,
                 "b": jnp.zeros((conv,), jnp.float32)},
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "gate_norm": {"scale": jnp.ones((inner,), jnp.float32)},
        "out_proj": {"w": jax.random.normal(ko, (inner, d), jnp.float32)
                     * inner ** -0.5},
    }


def logical_axes() -> Params:
    return {
        "norm": {"scale": ("stats",)},
        "in_proj": {"w": ("embed", "mlp")},
        "conv": {"w": ("mlp", None), "b": ("mlp",)},
        "dt_bias": ("stats",), "A_log": ("stats",), "D": ("stats",),
        "gate_norm": {"scale": ("mlp",)},
        "out_proj": {"w": ("mlp", "embed")},
    }


def apply(c, lp: Params, u, *, mesh=None):
    """u [B, T, D], normed -> (the mixer's result [B, T, D], counters).
    ``mesh`` goes to the convolution, the scan and the gated norm, whose
    kernels the compiler cannot partition (kernels/ssd.py,
    kernels/ssm_conv_gate.py): the projections are the compiler's."""
    dt_ = u.dtype
    bsz, t, _ = u.shape
    h, p, g, n = c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state
    inner, conv, _ = widths(c)
    with jax.named_scope("ssm_in_proj"):
        zxbcdt = u @ lp["in_proj"]["w"].astype(dt_)
        dt = zxbcdt[..., inner + conv:]
    with jax.named_scope("ssm_conv"):
        # x, B and C each convolved where they lie in the projection's
        # result, to an array of its own: a slice in front of a kernel and
        # a split behind it would each be a copy (8 and 4 ms a step in
        # nemotron3nano.steady-8k: PERF.md section 6, PR 37)
        x, b, cm = (
            ssm_conv_gate.conv_silu(
                zxbcdt, lp["conv"]["w"][at:at + width],
                lp["conv"]["b"][at:at + width], first=inner + at, mesh=mesh)
            for at, width in ((0, inner), (inner, g * n),
                              (inner + g * n, g * n)))
    with jax.named_scope("ssm_scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        a = -jnp.exp(lp["A_log"].astype(jnp.float32))
        x = x.reshape(bsz, t, h, p)
        y = ssd.scan(x, dt, a, b.reshape(bsz, t, g, n),
                     cm.reshape(bsz, t, g, n), skip=lp["D"],
                     chunk=c.ssm_chunk, mesh=mesh)
        counters = {CARRY_SHARE: lax.stop_gradient(
            ssd.carry_share(dt, a, chunk=c.ssm_chunk))}
    with jax.named_scope("ssm_gate_norm"):
        # z where it lies: the projection's first columns
        gated = ssm_conv_gate.gate_norm(
            y.reshape(bsz, t, inner), zxbcdt, lp["gate_norm"]["scale"],
            groups=g, eps=c.norm_eps, mesh=mesh)
    with jax.named_scope("ssm_out_proj"):
        return gated @ lp["out_proj"]["w"].astype(dt_), counters
