"""Ring attention: exact attention over sequence shards via ICI ppermute.

The long-context capability (SURVEY.md §5.7 — absent from the reference,
required here). Sequence length T is sharded over the ``sequence`` mesh axis:
each device holds a [B, T/N, H, D] slice of Q, K, V. K/V blocks rotate around
the ring (one neighbour ``ppermute`` hop per step — bandwidth-optimal on an
ICI torus), and each device folds every visiting block into its local queries
with the online-softmax recurrence, so the full [T, T] score matrix is never
materialized and memory stays O(T/N · block).

This is the Liu et al. ring-attention scheme expressed as plain shard_map +
lax.scan: XLA overlaps each step's einsums with the next block's ppermute.
Causal jobs mask per-block: a visiting block strictly newer than the local
queries contributes nothing, same-index blocks get the triangular mask, older
blocks attend fully.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mpi_operator_tpu.runtime.topology import AXIS_SEQ

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/max() NaN-free
                  # for fully-masked blocks

# longest sequence for which the dense fallback may materialize [T, T]
# scores; past this the chunked lowering (kernels/flash_attention.py) is the
# only memory-sane non-ring path
DENSE_FALLBACK_MAX_T = 1024


def _scores(q, k, scale):
    """Attention scores with GQA grouping: q [B,Tq,H,D], k [B,Tk,Hkv,D] with
    H = Hkv·G (consecutive q heads share a kv head) → [B,H,Tq,Tk]. K/V are
    never expanded to H heads — the grouped einsum keeps K/V bytes at Hkv
    through the ring (4x less ICI traffic at Llama-3-8B's 32/8 ratio)."""
    b, t_q, h, d = q.shape
    h_kv = k.shape[2]
    g = h // h_kv
    q5 = q.reshape(b, t_q, h_kv, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k, preferred_element_type=jnp.float32)
    return s.reshape(b, h, t_q, k.shape[1]) * scale


def _weighted_v(p, v):
    """p [B,H,Tq,Tk] × v [B,Tk,Hkv,D] → [B,Tq,H,D] (grouped, see _scores)."""
    b, h, t_q, t_k = p.shape
    h_kv = v.shape[2]
    g = h // h_kv
    p5 = p.reshape(b, h_kv, g, t_q, t_k)
    pv = jnp.einsum("bhgqk,bkhd->bqhgd", p5, v.astype(p.dtype))
    return pv.reshape(b, t_q, h, v.shape[3])


def _block(q, k, v, bias, carry, scale):
    """Fold one K/V block into the online-softmax accumulator.

    carry = (o, m, l): o [B,Tq,H,D] unnormalized output, m [B,H,Tq] running
    max, l [B,H,Tq] running denominator.
    """
    o, m, l = carry
    s = _scores(q, k, scale)
    if bias is not None:
        s = s + bias
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = _weighted_v(p, v)
    o_new = o * jnp.transpose(corr, (0, 2, 1))[..., None] + pv
    return o_new, m_new, l_new


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool, scale: float):
    """Per-shard body (runs under shard_map). q,k,v: [B, T_local, H, D]."""
    from mpi_operator_tpu.parallel import collectives as c

    n = c.axis_size_static(axis_name)
    my_idx = lax.axis_index(axis_name)
    t_q, t_k = q.shape[1], k.shape[1]

    q32 = q.astype(jnp.float32)
    # Derive the accumulators from q so they inherit its varying-manual-axes
    # type (a plain jnp.zeros would be device-invariant and rejected as a
    # scan carry under shard_map).
    o0 = jnp.zeros_like(q32)
    m0 = jnp.transpose(q32[..., 0], (0, 2, 1)) * 0 + _NEG_INF
    l0 = jnp.zeros_like(m0)

    def bias_for(step_idx):
        if not causal:
            return None
        # After s hops, the resident block originated at (my_idx - s) mod n.
        # Future block: fully masked. Same block: triangular. Past: open.
        src = (my_idx - step_idx) % n
        q_pos = my_idx * t_q + jnp.arange(t_q)[:, None]
        k_pos = src * t_k + jnp.arange(t_k)[None, :]
        return jnp.where(q_pos >= k_pos, 0.0, _NEG_INF)[None, None]

    # Shift-then-consume: the resident block is folded first, then steps
    # 1..n-1 each hop K/V one neighbour and fold — no dead hop on the last
    # block (the rotation is left incomplete on purpose; K/V are consumed).
    acc0 = _block(q32, k, v, bias_for(0), (o0, m0, l0), scale)

    def step(carry, step_idx):
        o, m, l, k_blk, v_blk = carry
        k_blk = c.ring_shift(k_blk, axis_name, shift=1)
        v_blk = c.ring_shift(v_blk, axis_name, shift=1)
        o, m, l = _block(q32, k_blk, v_blk, bias_for(step_idx), (o, m, l), scale)
        return (o, m, l, k_blk, v_blk), None

    (o, m, l, _, _), _ = lax.scan(
        step, (*acc0, k, v), jnp.arange(1, n), length=n - 1
    )
    out = o / jnp.transpose(l, (0, 2, 1))[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    mesh: Mesh,
    *,
    axis_name: str = AXIS_SEQ,
    causal: bool = True,
    scale: Optional[float] = None,
    batch_spec: P = P(("data", "fsdp")),
    head_axis: Optional[str] = "tensor",
):
    """Exact multi-head attention with the sequence dim sharded over
    ``axis_name``. Shapes are the *global* q [B,T,H,D], k/v [B,T,Hkv,D] with
    H a multiple of Hkv (GQA; consecutive q heads share a kv head — pass
    Hkv=H for plain MHA). Sharding is handled internally via shard_map; K/V
    stay at Hkv heads through the ring.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    h_part = head_axis if head_axis in mesh.axis_names else None
    seq_part = axis_name if axis_name in mesh.axis_names else None
    b_axes = batch_spec[0] if len(batch_spec) else None
    if isinstance(b_axes, str):
        b_axes = (b_axes,)
    b_part = tuple(a for a in (b_axes or ()) if a in mesh.axis_names) or None
    spec = P(b_part, seq_part, h_part, None)
    fn = functools.partial(
        _ring_attention_local,
        axis_name=axis_name,
        causal=causal,
        scale=scale,
    )
    if seq_part is None:
        # No sequence axis in this mesh: single-shard attention, no ring.
        # Above the threshold the dense [T,T] score matrix is a production
        # OOM (8B-class sequence lengths), so route to the memory-bounded
        # chunked lowering; dense stays the small-case/test oracle.
        if q.shape[1] > DENSE_FALLBACK_MAX_T:
            from mpi_operator_tpu.kernels.flash_attention import (
                chunked_reference,
            )

            return chunked_reference(q, k, v, causal=causal, scale=scale)
        return dense_attention(q, k, v, causal=causal, scale=scale)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def dense_attention(q, k, v, *, causal: bool, scale: float, window=None):
    """Reference (and no-sequence-axis fallback) attention; also the oracle
    the tests compare ring attention against. GQA-aware like the ring path.
    ``window`` (causal only): query i sees key j iff i - window < j <= i."""
    s = _scores(q, k, scale)
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        if window is not None:
            mask = jnp.logical_and(
                mask, jnp.arange(t_q)[:, None] - jnp.arange(t_k)[None, :] < window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _weighted_v(p, v).astype(q.dtype)
