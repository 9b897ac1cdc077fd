"""Flash attention (forward + backward) as Pallas TPU kernels.

Blockwise online-softmax attention: every kernel streams fixed-size Q and
K/V tiles through a 4-D grid, so VMEM use is O(block·head_dim) regardless
of sequence length — the [T, T] score matrix never exists, no full-sequence
array is ever VMEM-resident (the first kernel generation held whole K/V per
program and died at T≈16k against the 16 MB scoped-VMEM limit), and
T is bounded only by HBM. GQA-aware: the kv head for a q head is derived in
the BlockSpec index maps (no K/V expansion in HBM).

Layout: [B, H, T, D] (heads-major — the kernel-friendly transpose of the
model's [B, T, H, D]; the wrapper handles it). Queries and keys share one
head size and values, and so the output and its cotangent, may have
another (latent attention's 192 / 128): every block, scratch and output is
sized by its own operand; a width that is no whole lane tile is one
whole-width block. bf16 operands on the MXU,
f32 accumulation in VMEM scratch that persists across the innermost grid
dimension; outputs are written on that dimension's final step.

Backward is FlashAttention-2-style: the forward additionally emits the
log-sum-exp rows, and the backward recomputes probabilities blockwise
on-chip to produce dq (grid over q tiles × streamed K/V) and dk/dv (grid
over k tiles × streamed Q) — neither direction round-trips a score block
through HBM. Profiling the Llama train step showed the previous
recompute-through-XLA backward was the single largest cost: ~330 ms/step
of HBM-bound score-block traffic on v5e.

Pallas custom calls have no SPMD partitioning rule, so on a sharded mesh the
kernel must run under shard_map; pass ``mesh`` and the wrapper shards batch
over (data, fsdp) and heads over tensor, running the kernel on local shards.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _window_kw(window):
    """The kernels' ``window`` keyword, only where there is one: without it
    the partials, and so the Mosaic bodies, are what they were."""
    return {} if window is None else {"window": int(window)}


def _pad_t(x, t_pad: int):
    """Zero-pad dim 2 (sequence) of [B,H,T,D]-like arrays up to t_pad."""
    t = x.shape[2]
    if t == t_pad:
        return x
    return jnp.pad(x, [(0, 0), (0, 0), (0, t_pad - t)] + [(0, 0)] * (x.ndim - 3))


# Causal tile-skip algebra, shared by the kernels' pl.when predicates and
# the BlockSpec index-map clamps (a clamped index repeats on skipped grid
# steps, so pallas elides the dead tiles' DMAs). The two sides MUST agree:
# a tile is computed iff ki * bk < (qi + 1) * bq ("diag open").


def _causal_open(qi, ki, bq: int, bk: int):
    """True iff k tile ki intersects the causal (lower-triangular) region
    of q tile qi — the kernels' compute-skip predicate."""
    return ki * bk < (qi + 1) * bq


def _causal_last_k_tile(qi, bq: int, bk: int):
    """Largest ki with _causal_open(qi, ki): ceil((qi+1)*bq / bk) - 1."""
    return ((qi + 1) * bq + bk - 1) // bk - 1


def _causal_first_q_tile(ki, bq: int, bk: int):
    """Smallest qi with _causal_open(qi, ki): (ki*bk) // bq."""
    return (ki * bk) // bq


# A window adds the band's other side: query i sees key j iff
# i - window < j <= i. A tile is then computed iff it is causally open AND
# (ki + 1) * bk + window - 1 > qi * bq ("band open"): the tile's last key
# lies inside the window of the tile's first query. The clamps below are
# the same inequality solved for ki and for qi.


def _band_open(qi, ki, bq: int, bk: int, window: int):
    """True iff k tile ki reaches into the window of q tile qi."""
    return (ki + 1) * bk + window - 1 > qi * bq


def _band_first_k_tile(qi, bq: int, bk: int, window: int):
    """Smallest ki with _band_open(qi, ki): the tile that holds the oldest
    key the tile's first query sees."""
    return jnp.maximum(qi * bq - window + 1, 0) // bk


def _band_last_q_tile(ki, bq: int, bk: int, window: int):
    """Largest qi with _band_open(qi, ki) (the caller clips it to the
    grid): the tile of the last query that still sees the tile's last key."""
    return ((ki + 1) * bk + window - 2) // bq


def _tile_open(qi, ki, bq: int, bk: int, causal: bool, window):
    """The kernels' compute-skip predicate; ``True`` (a Python bool) where
    nothing is skipped."""
    is_open = _causal_open(qi, ki, bq, bk) if causal else True
    if window is not None:
        is_open = jnp.logical_and(is_open, _band_open(qi, ki, bq, bk, window))
    return is_open


def _k_tile_clamp(qi, ki, bq: int, bk: int, causal: bool, window):
    """ki clamped to the tiles q tile qi uses: a clamped index repeats on
    skipped grid steps, so pallas elides their DMAs."""
    if causal:
        ki = jnp.minimum(ki, _causal_last_k_tile(qi, bq, bk))
    if window is not None:
        ki = jnp.maximum(ki, _band_first_k_tile(qi, bq, bk, window))
    return ki


def _q_tile_clamp(ki, qi, bq: int, bk: int, causal: bool, window):
    """qi clamped to the tiles k tile ki uses (dk/dv streams q tiles)."""
    if causal:
        qi = jnp.maximum(qi, _causal_first_q_tile(ki, bq, bk))
    if window is not None:
        qi = jnp.minimum(qi, _band_last_q_tile(ki, bq, bk, window))
    return qi


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
    block_q: int, block_k: int, n_kb: int, causal: bool, scale: float,
    t_real: int, window=None,
):
    """One grid step folds one (q-tile, k-tile) pair. Grid (b, h, qi, ki),
    ki innermost: the f32 scratch (acc, m, l) carries the online softmax
    across a q-tile's k sweep; o/lse are written on the sweep's last step.
    Refs: q [1,1,BQ,D], k [1,1,BK,D], v [1,1,BK,Dv], o [1,1,BQ,Dv],
    lse [1,1,BQ,1]."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: tiles strictly above the diagonal contribute nothing; skip
    # their compute (the matching index-map clamp elides their DMAs too).
    # A window skips the tiles wholly older than the band the same way.
    diag_open = _tile_open(qi, ki, block_q, block_k, causal, window)

    @pl.when(diag_open)
    def _fold():
        q = q_ref[0, 0]  # input dtype: full-rate MXU, f32 accumulate
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK] f32
        k_idx = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = k_idx < t_real  # edge tiles read past t: mask them
        if causal:
            q_idx = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            valid = jnp.logical_and(valid, q_idx >= k_idx)
            if window is not None:  # the two edge tiles of the band
                valid = jnp.logical_and(valid, q_idx - k_idx < window)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_prev * corr + jnp.sum(p, axis=-1)
        m_ref[:, 0] = m_new
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_kb - 1)
    def _emit():
        l = l_ref[:, 0]
        # fully-masked rows (q padding) have l == 0; emit 0, not NaN
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(safe[:, None]))


def _flash_fwd(
    q, k, v, *, causal: bool, scale: float, block_q: int, block_k: int,
    interpret: bool, window=None,
):
    """q [B,H,T,D], k [B,Hkv,T,D], v [B,Hkv,T,Dv] →
    (o [B,H,T,Dv], lse [B,H,Tq_pad,1])."""
    b, h, t, d = q.shape
    dv = v.shape[-1]
    h_kv = k.shape[1]
    g = h // h_kv
    bq = min(block_q, t)
    bk = min(block_k, t)
    n_qb = pl.cdiv(t, bq)
    n_kb = pl.cdiv(t, bk)
    grid = (b, h, n_qb, n_kb)

    # zero-pad to block multiples: an edge tile's OOB region is otherwise
    # undefined memory, and 0·NaN = NaN leaks through masked weights in the
    # PV product (zero weights do NOT neutralize NaN operands). Padding is
    # a no-op at production sizes; the score mask (t_real) keeps padded
    # keys from attending.
    q = _pad_t(q, n_qb * bq)
    k = _pad_t(k, n_kb * bk)
    v = _pad_t(v, n_kb * bk)

    kernel = functools.partial(
        _fwd_kernel, block_q=bq, block_k=bk, n_kb=n_kb, causal=causal,
        scale=scale, t_real=t, **_window_kw(window),
    )

    # causal: a k tile strictly above the diagonal is skipped by the kernel
    # (pl.when) — clamping its block index to the last USED tile makes the
    # index map repeat, so pallas elides the DMA too. ~2x less K/V traffic
    # at long T (the causally-dead half of the rectangle grid). A window
    # clamps from below too: only the band's tiles are ever fetched.
    def kv_index(bi, hi, qi, ki):
        return (bi, hi // g, _k_tile_clamp(qi, ki, bq, bk, causal, window), 0)

    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n_qb * bq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, n_qb * bq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return o[:, :, :t], lse


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *,
    block_q: int, block_k: int, n_kb: int, causal: bool, scale: float,
    t_real: int, window=None,
):
    """dq: grid (b, h, qi, ki) streams K/V tiles past each q tile,
    recomputing P on-chip from the saved LSE. Refs: q/dq [1,1,BQ,D],
    k [1,1,BK,D], v [1,1,BK,Dv], do [1,1,BQ,Dv], lse/delta [1,1,BQ,1]."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    diag_open = _tile_open(qi, ki, block_q, block_k, causal, window)

    @pl.when(diag_open)
    def _fold():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        k_idx = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = k_idx < t_real
        if causal:
            q_idx = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            valid = jnp.logical_and(valid, q_idx >= k_idx)
            if window is not None:  # the two edge tiles of the band
                valid = jnp.logical_and(valid, q_idx - k_idx < window)
        # p rows are already normalized: lse folds in the denominator
        p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta[:, None])).astype(k.dtype)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == n_kb - 1)
    def _emit():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref, *,
    block_q: int, block_k: int, n_qb: int, causal: bool, scale: float,
    t_real: int, window=None,
):
    """dk/dv: grid (b, h, ki, qi) streams Q/dO tiles past each k tile. GQA:
    outputs are per *q* head; the wrapper group-sums to kv heads. Refs:
    k/dk [1,1,BK,D], v/dv [1,1,BK,Dv], q [1,1,BQ,D], do [1,1,BQ,Dv],
    lse/delta [1,1,BQ,1]."""
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    diag_open = _tile_open(qi, ki, block_q, block_k, causal, window)

    @pl.when(diag_open)
    def _fold():
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]
        q_idx = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_idx = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = jnp.logical_and(q_idx < t_real, k_idx < t_real)
        if causal:
            valid = jnp.logical_and(valid, q_idx >= k_idx)
            if window is not None:  # the two edge tiles of the band
                valid = jnp.logical_and(valid, q_idx - k_idx < window)
        p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta[:, None])).astype(q.dtype)
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(qi == n_qb - 1)
    def _emit():
        dk_ref[0, 0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd(
    q, k, v, o, lse, do, *, causal: bool, scale: float, block_q: int,
    block_k: int, interpret: bool, window=None,
):
    """Pallas flash backward. q [B,H,T,D], k [B,Hkv,T,D], v [B,Hkv,T,Dv],
    o/do [B,H,T,Dv], lse [B,H,Tq_pad,1] → (dq, dk, dv) in input
    shapes/dtypes."""
    b, h, t, d = q.shape
    dv = v.shape[-1]
    h_kv = k.shape[1]
    g = h // h_kv
    bq = min(block_q, t)
    bk = min(block_k, t)
    n_qb = pl.cdiv(t, bq)
    n_kb = pl.cdiv(t, bk)

    # delta_i = dO_i · O_i — the rowwise residual term of d(softmax);
    # trailing singleton matches the lse layout. Everything zero-padded to
    # block multiples (see _flash_fwd: undefined OOB tile memory leaks NaN
    # through masked products).
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [B, H, T, 1]
    delta = _pad_t(delta, n_qb * bq)
    q_p = _pad_t(q, n_qb * bq)
    do_p = _pad_t(do, n_qb * bq)
    k_p = _pad_t(k, n_kb * bk)
    v_p = _pad_t(v, n_kb * bk)

    # causally-skipped tiles: clamp the index map so the DMA is elided too
    # (see the same trick in _flash_fwd)
    def kv_index(bi, hi, qi, ki):
        return (bi, hi // g, _k_tile_clamp(qi, ki, bq, bk, causal, window), 0)

    def q_index_dkv(bi, hi, ki, qi):
        qi = _q_tile_clamp(ki, qi, bq, bk, causal, window)
        # the band's last q tile may lie past the grid at the sequence's end
        return (bi, hi, jnp.minimum(qi, n_qb - 1) if window else qi, 0)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_q=bq, block_k=bk, n_kb=n_kb,
            causal=causal, scale=scale, t_real=t, **_window_kw(window),
        ),
        grid=(b, h, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, dv), kv_index),
            pl.BlockSpec((1, 1, bq, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, n_qb * bq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(q_p, k_p, v_p, do_p, lse, delta)[:, :, :t]

    # dk/dv per q-head (grid over k tiles, q innermost); kv grads group-sum
    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=bq, block_k=bk, n_qb=n_qb,
            causal=causal, scale=scale, t_real=t, **_window_kw(window),
        ),
        grid=(b, h, n_kb, n_qb),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_index_dkv),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, qi: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda bi, hi, ki, qi: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bq, dv), q_index_dkv),
            pl.BlockSpec((1, 1, bq, 1), q_index_dkv),
            pl.BlockSpec((1, 1, bq, 1), q_index_dkv),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
        # partials in the input dtype (f32 accumulation stays in scratch):
        # the per-q-head [B,H,T,D] pair is the backward's largest transient,
        # and the group-sum result is cast to k.dtype regardless
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n_kb * bk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, n_kb * bk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(q_p, k_p, v_p, do_p, lse, delta)

    dk = (
        dk_h[:, :, :t]
        .reshape(b, h_kv, g, t, d)
        .astype(jnp.float32)
        .sum(axis=2)
        .astype(k.dtype)
    )
    d_v = (
        dv_h[:, :, :t]
        .reshape(b, h_kv, g, t, dv)
        .astype(jnp.float32)
        .sum(axis=2)
        .astype(v.dtype)
    )
    return dq, dk, d_v


def _block_reference(q_blk, k, v, q_offset, *, causal: bool, scale: float,
                     window=None):
    """Attention for one q block against full K/V (heads-major, GQA-aware).
    q_blk [B,H,BQ,D], k [B,Hkv,T,D], v [B,Hkv,T,Dv], q_offset scalar start
    index; with ``window`` the band is a mask on positions."""
    b, h, bq, d = q_blk.shape
    h_kv = k.shape[1]
    g = h // h_kv
    q5 = q_blk.reshape(b, h_kv, g, bq, d).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q5, k.astype(jnp.float32)) * scale
    s = s.reshape(b, h, bq, k.shape[2])
    if causal:
        q_idx = q_offset + jnp.arange(bq)[:, None]
        k_idx = jnp.arange(k.shape[2])[None, :]
        seen = q_idx >= k_idx
        if window is not None:
            seen = jnp.logical_and(seen, q_idx - k_idx < window)
        s = jnp.where(seen[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p5 = p.reshape(b, h_kv, g, bq, k.shape[2])
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p5, v.astype(p.dtype))
    return o.reshape(b, h, bq, v.shape[-1]).astype(q_blk.dtype)


def _chunked_reference(q, k, v, *, causal: bool, scale: float, block_q: int,
                       window=None):
    """Memory-bounded XLA attention: lax.map over checkpointed q blocks, so
    its vjp stores only block inputs and recomputes scores blockwise —
    backward memory stays O(BQ·T) instead of [T,T]. The non-TPU fallback
    and the independent lowering the on-chip checks compare against."""
    b, h, t, d = q.shape
    bq = min(block_q, t)
    n = -(-t // bq)
    t_pad = n * bq
    q_p = _pad_t(q, t_pad)
    qr = q_p.reshape(b, h, n, bq, d).transpose(2, 0, 1, 3, 4)  # [n,B,H,BQ,D]
    offsets = jnp.arange(n) * bq

    blk = jax.checkpoint(
        lambda qb, off: _block_reference(
            qb, k, v, off, causal=causal, scale=scale, window=window)
    )
    out = jax.lax.map(lambda args: blk(*args), (qr, offsets))  # [n,B,H,BQ,D]
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, h, t_pad, v.shape[-1])
    return out[:, :, :t]


def _dense_reference(q, k, v, *, causal: bool, scale: float, window=None):
    """Unchunked XLA reference (numerics tests)."""
    return _block_reference(q, k, v, 0, causal=causal, scale=scale,
                            window=window)


def chunked_reference(q, k, v, *, causal: bool = True, scale=None,
                      block_q: int = 256, window=None):
    """The chunked XLA reference in *model* layout (q [B,T,H,D]) — the
    independent lowering that the on-hardware checks (tests_tpu/) compare
    the compiled kernel against: same math, same bound on memory."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _chunked_reference(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        scale=scale,
        block_q=block_q,
        window=window,
    ).transpose(0, 2, 1, 3)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def _flash(q, k, v, causal, scale, block_q, block_k, interpret, window):
    o, _ = _flash_fwd(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window,
    )
    return o


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   window):
    o, lse = _flash_fwd(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window,
    )
    # named so a rematted caller can elect to SAVE these residuals (o is
    # cheap to keep, recomputing it costs a full kernel pass) — see
    # models.llama.apply's save_only_these_names policy
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, window, res,
                   do):
    q, k, v, o, lse = res
    return _flash_bwd(
        q, k, v, o, lse, do, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window,
    )


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    mesh=None,
    batch_axes=("data", "fsdp"),
    head_axis: str = "tensor",
    layout: str = "bthd",
    window: Optional[int] = None,
):
    """Flash attention in model layout q [B,T,H,D], k [B,T,Hkv,D],
    v [B,T,Hkv,Dv] → [B,T,H,Dv] (Dv = D, or another head size for the
    values alone) — or,
    with ``layout="bhtd"``, directly in the kernel's heads-major layout
    (a caller that PRODUCES q/k/v heads-major skips the [B,T,H,D]↔[B,H,T,D]
    copies the wrapper otherwise pays on every call, ~3% of the llama step).

    With ``mesh``, runs under shard_map (batch over ``batch_axes``, heads
    over ``head_axis`` when divisible) — required for sharded inputs, since
    the pallas call is not SPMD-partitionable. ``interpret=None`` (auto)
    runs the real kernel on TPU and the exact chunked XLA reference on any
    other backend — never the Pallas interpreter; pass ``interpret=True``
    explicitly to exercise the kernel body off-TPU (kernel tests do).
    Differentiable (Pallas flash backward).

    ``window`` (with ``causal``): query i sees key j iff i - window < j <= i,
    ``window`` keys with its own. All three kernels skip the k tiles (dk/dv:
    the q tiles) on both sides of the band and mask inside its edge tiles."""
    if layout not in ("bthd", "bhtd"):
        raise ValueError(f"layout={layout!r}; expected bthd|bhtd")
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window!r} needs causal attention and at least one key")
    heads_major = layout == "bhtd"
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # interpret=None means "auto": the real kernel on TPU; elsewhere the
    # chunked XLA reference (same math, same memory bound) — NOT interpret
    # mode, which is orders of magnitude slower than XLA and only useful
    # when a test explicitly asks to exercise the kernel body.
    use_kernel = interpret is not None or jax.default_backend() == "tpu"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def local(q_, k_, v_):
        if heads_major:
            qt, kt, vt = q_, k_, v_
        else:
            qt = q_.transpose(0, 2, 1, 3)
            kt = k_.transpose(0, 2, 1, 3)
            vt = v_.transpose(0, 2, 1, 3)
        if use_kernel:
            o = _flash(qt, kt, vt, causal, scale, block_q, block_k, interpret,
                       window)
        else:
            o = _chunked_reference(
                qt, kt, vt, causal=causal, scale=scale, block_q=block_q,
                window=window,
            )
        return o if heads_major else o.transpose(0, 2, 1, 3)

    if mesh is None:
        return local(q, k, v)

    from jax.sharding import PartitionSpec as P

    b_part = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    h_dim = 1 if heads_major else 2
    h, h_kv = q.shape[h_dim], k.shape[h_dim]
    tp = mesh.shape.get(head_axis, 1) if head_axis in mesh.axis_names else 1
    # heads shard only when BOTH head counts divide: the GQA grouping must
    # stay aligned on every shard
    h_part = head_axis if (tp > 1 and h % tp == 0 and h_kv % tp == 0) else None
    spec = (
        P(b_part, h_part, None, None)
        if heads_major
        else P(b_part, None, h_part, None)
    )
    # check_vma=False: pallas_call's out_shape carries no varying-mesh-axes
    # annotation, so shard_map's vma checker rejects it; the specs above are
    # the full partitioning contract anyway.
    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
