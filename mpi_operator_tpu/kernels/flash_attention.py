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

A sliding window (query i sees the ``window`` keys up to its own) changes
the grids, not the arithmetic: their innermost axis walks only the tiles
the band opens, each step the band's first tile plus the step, where the
causal grids walk every tile and skip the dead ones. The window's tile is
square, half the window, at least 512 and at most 1024 (``_band_tile``):
a 512-key band then computes 1,024 keys a query, against 2,048 in the
1024² tiles every other call keeps.

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

from mpi_operator_tpu.kernels.sparse_select import (
    no_cotangent,
    select_tile,
    unpack,
    words_to_mask,
)

_NEG_INF = -1e30
_SEL_VMEM = 64 * 1024 * 1024  # the selected kernels' g-head blocks


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
# lies inside the window of the tile's first query. A windowed call's grid
# walks only the band: its innermost axis counts steps along it, not tiles,
# and each step's tile is the band's first plus the step. The two helpers
# below are the inequality solved for ki and for qi.


def _band_first_k_tile(qi, bq: int, bk: int, window: int):
    """Smallest ki with the band open: the tile that holds the oldest key
    the tile's first query sees."""
    return jnp.maximum(qi * bq - window + 1, 0) // bk


def _band_last_q_tile(ki, bq: int, bk: int, window: int):
    """Largest qi with the band open (the caller clips it to the grid): the
    tile of the last query that still sees the tile's last key."""
    return ((ki + 1) * bk + window - 2) // bq


def _band_k_walk(qi, step, bq: int, bk: int, window: int, n_kb: int):
    """(ki, live) at ``step`` of q tile qi's walk along the band (flash_fwd,
    flash_dq): from the band's first k tile to the causal diagonal or the
    sequence's last tile. A step past that is dead; its index repeats the
    last live tile, so pallas elides its DMA."""
    ki = _band_first_k_tile(qi, bq, bk, window) + step
    last = jnp.minimum(_causal_last_k_tile(qi, bq, bk), n_kb - 1)
    return jnp.minimum(ki, last), ki <= last


def _band_q_walk(ki, step, bq: int, bk: int, window: int, n_qb: int):
    """(qi, live) at ``step`` of k tile ki's walk along the band
    (flash_dkv): from the causal diagonal to the band's last q tile or the
    sequence's last tile; dead steps as in ``_band_k_walk``."""
    qi = _causal_first_q_tile(ki, bq, bk) + step
    last = jnp.minimum(_band_last_q_tile(ki, bq, bk, window), n_qb - 1)
    return jnp.minimum(qi, last), qi <= last


def _band_steps(walk, n_outer: int, n_inner: int, *tiling):
    """A band walk's grid length: the most live steps any outer tile has
    (the walk is live from its first step on). Static: computed from the
    tiling alone, also while the caller is being traced."""
    with jax.ensure_compile_time_eval():
        _, live = walk(jnp.arange(n_outer)[None, :],
                       jnp.arange(n_inner)[:, None], *tiling)
        return int(jnp.max(jnp.sum(live, axis=0)))


def _band_tile(window: int, t: int) -> int:
    """A windowed call's square tile: half the window, at least 512 and at
    most 1024 keys, and no longer than the sequence. A 512-key window then
    computes 1,024 keys a query where 1024² tiles computed 2,048, and a
    1024-key window 1,536 for 3,072."""
    return min(max(window // 2, 512), 1024, t)


def _k_sweep(bq: int, bk: int, n_qb: int, n_kb: int, causal: bool, window,
             g: int):
    """The innermost axis of flash_fwd's and flash_dq's grids: (its length,
    the K/V tiles' index map, the kernels' window keywords). Without a
    window every k tile, and the partials, and so the Mosaic bodies, are
    what they were; with one, the band walk's steps."""
    if window is None:
        def kv_index(bi, hi, qi, ki):
            return (bi, hi // g, _k_tile_clamp(qi, ki, bq, bk, causal), 0)
        return n_kb, kv_index, {}
    walk = (bq, bk, int(window), n_kb)
    steps = _band_steps(_band_k_walk, n_qb, n_kb, *walk)

    def kv_index(bi, hi, qi, step):
        return (bi, hi // g, _band_k_walk(qi, step, *walk)[0], 0)
    return steps, kv_index, {"window": int(window), "band_steps": steps}


def _q_sweep(bq: int, bk: int, n_qb: int, n_kb: int, causal: bool, window):
    """The innermost axis of flash_dkv's grid, as ``_k_sweep``: every q
    tile, or the band walk's steps."""
    if window is None:
        def q_index(bi, hi, ki, qi):
            return (bi, hi, _q_tile_clamp(ki, qi, bq, bk, causal), 0)
        return n_qb, q_index, {}
    walk = (bq, bk, int(window), n_qb)
    steps = _band_steps(_band_q_walk, n_kb, n_qb, *walk)

    def q_index(bi, hi, ki, step):
        return (bi, hi, _band_q_walk(ki, step, *walk)[0], 0)
    return steps, q_index, {"window": int(window), "band_steps": steps}


def _tile_open(qi, ki, bq: int, bk: int, causal: bool):
    """The full grid's compute-skip predicate; ``True`` (a Python bool)
    where nothing is skipped."""
    return _causal_open(qi, ki, bq, bk) if causal else True


def _k_tile_clamp(qi, ki, bq: int, bk: int, causal: bool):
    """ki clamped to the tiles q tile qi uses: a clamped index repeats on
    skipped grid steps, so pallas elides their DMAs."""
    if causal:
        ki = jnp.minimum(ki, _causal_last_k_tile(qi, bq, bk))
    return ki


def _q_tile_clamp(ki, qi, bq: int, bk: int, causal: bool):
    """qi clamped to the tiles k tile ki uses (dk/dv streams q tiles)."""
    if causal:
        qi = jnp.maximum(qi, _causal_first_q_tile(ki, bq, bk))
    return qi


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
    block_q: int, block_k: int, n_kb: int, causal: bool, scale: float,
    t_real: int, window=None, band_steps=None,
):
    """One grid step folds one (q-tile, k-tile) pair. Grid (b, h, qi, ki),
    ki innermost (with a window: (b, h, qi, step), the band's k tiles): the
    f32 scratch (acc, m, l) carries the online softmax across a q-tile's k
    sweep; o/lse are written on the sweep's last step.
    Refs: q [1,1,BQ,D], k [1,1,BK,D], v [1,1,BK,Dv], o [1,1,BQ,Dv],
    lse [1,1,BQ,1]."""
    qi = pl.program_id(2)
    step = pl.program_id(3)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if window is None:
        # causal: tiles strictly above the diagonal contribute nothing; skip
        # their compute (the matching index-map clamp elides their DMAs too)
        ki, n_steps = step, n_kb
        diag_open = _tile_open(qi, ki, block_q, block_k, causal)
    else:
        ki, diag_open = _band_k_walk(qi, step, block_q, block_k, window, n_kb)
        n_steps = band_steps

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

    @pl.when(step == n_steps - 1)
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
    # causal: a k tile strictly above the diagonal is skipped by the kernel
    # (pl.when) — clamping its block index to the last USED tile makes the
    # index map repeat, so pallas elides the DMA too. ~2x less K/V traffic
    # at long T (the causally-dead half of the rectangle grid). A window
    # walks the band's tiles alone.
    n_steps, kv_index, band_kw = _k_sweep(bq, bk, n_qb, n_kb, causal, window, g)
    grid = (b, h, n_qb, n_steps)

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
        scale=scale, t_real=t, **band_kw,
    )

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
    t_real: int, window=None, band_steps=None,
):
    """dq: grid (b, h, qi, ki) streams K/V tiles past each q tile (with a
    window, the band's: as the forward), recomputing P on-chip from the
    saved LSE. Refs: q/dq [1,1,BQ,D], k [1,1,BK,D], v [1,1,BK,Dv],
    do [1,1,BQ,Dv], lse/delta [1,1,BQ,1]."""
    qi = pl.program_id(2)
    step = pl.program_id(3)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if window is None:
        ki, n_steps = step, n_kb
        diag_open = _tile_open(qi, ki, block_q, block_k, causal)
    else:
        ki, diag_open = _band_k_walk(qi, step, block_q, block_k, window, n_kb)
        n_steps = band_steps

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

    @pl.when(step == n_steps - 1)
    def _emit():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref, *,
    block_q: int, block_k: int, n_qb: int, causal: bool, scale: float,
    t_real: int, window=None, band_steps=None,
):
    """dk/dv: grid (b, h, ki, qi) streams Q/dO tiles past each k tile (with
    a window, (b, h, ki, step): the band's q tiles). GQA: outputs are per
    *q* head; the wrapper group-sums to kv heads. Refs: k/dk [1,1,BK,D],
    v/dv [1,1,BK,Dv], q [1,1,BQ,D], do [1,1,BQ,Dv], lse/delta [1,1,BQ,1]."""
    ki = pl.program_id(2)
    step = pl.program_id(3)

    @pl.when(step == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    if window is None:
        qi, n_steps = step, n_qb
        diag_open = _tile_open(qi, ki, block_q, block_k, causal)
    else:
        qi, diag_open = _band_q_walk(ki, step, block_q, block_k, window, n_qb)
        n_steps = band_steps

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

    @pl.when(step == n_steps - 1)
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
    # (see the same trick in _flash_fwd); a window walks the band alone
    k_steps, kv_index, k_band_kw = _k_sweep(
        bq, bk, n_qb, n_kb, causal, window, g)
    q_steps, q_index_dkv, q_band_kw = _q_sweep(
        bq, bk, n_qb, n_kb, causal, window)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_q=bq, block_k=bk, n_kb=n_kb,
            causal=causal, scale=scale, t_real=t, **k_band_kw,
        ),
        grid=(b, h, n_qb, k_steps),
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
            causal=causal, scale=scale, t_real=t, **q_band_kw,
        ),
        grid=(b, h, n_kb, q_steps),
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


# A selection (kernels/sparse_select.py) gives each query its own keys, one
# selection for every head: the three kernels below are the causal ones with
# the selection's bits read a tile at a time beside K/V (or Q), in the
# selection's square tile, and a tile that holds no selected key passed over
# (its flag, prefetched to SMEM). Their grids run over KV heads: a step holds
# the KV head's g query heads, reads K/V and the tile's words once for all g,
# and builds the tile's mask once, as a bias (0 at a selected pair, _NEG_INF
# elsewhere) in VMEM that each head adds to its scores.
#
# The kernels trust the bits: a selected pair is causal (key <= query) and
# its key lies inside the sequence (key < T), padded query rows included.
# ``select`` guarantees it, since its kernel scores every non-causal or
# padded key INT_MIN, below any threshold it can reach, and its jax.numpy
# twin masks them out before the threshold; so the kernels compare no
# positions. While a row has met none of its keys its running maximum is
# _NEG_INF, and so are its masked scores: the forward then subtracts 0, not
# the maximum, so that their probabilities are nought. A row that meets no
# key at all (a tile's flag cleared) emits 0 and a log-sum-exp of _NEG_INF,
# which the backward kernels raise to -_NEG_INF for the same end.


def _sel_bias(bias_ref, words_ref, block: int):
    """Writes the tile's mask as a bias on the scores, an eighth of its rows
    (four bit planes) a loop step: as ``_heads``, the code of one chunk and
    not of the whole tile."""
    rows = block // 8

    def chunk(c, carry):
        bias_ref[pl.ds(pl.multiple_of(c * rows, rows), rows), :] = jnp.where(
            unpack(words_ref[0], rows, c * 4, block), 0.0, _NEG_INF)
        return carry

    jax.lax.fori_loop(0, 8, chunk, 0)


def _sel_lse(lse):
    """A row's log-sum-exp for exp(s - lse): a row with no key has none,
    and its probabilities are nought."""
    return jnp.where(lse > _NEG_INF / 2, lse, -_NEG_INF)


def _flag(flags_ref, bi, qi, ki, n: int):
    return flags_ref[(bi * n + qi) * n + ki] > 0


def _heads(g: int, body):
    """``body(h)`` for each of a step's g query heads, as one loop, so that
    the kernel's code (which the device holds in HBM) is one head's
    [block, ·] body and not g of them."""
    def step(h, carry):
        body(h)
        return carry

    jax.lax.fori_loop(0, g, step, 0)


def _fwd_sel_kernel(
    flags_ref, q_ref, k_ref, v_ref, words_ref, o_ref, lse_ref, acc_ref,
    m_ref, l_ref, bias_ref, *, block: int, n: int, g: int, scale: float,
):
    """The forward over a selection: grid (b, h_kv, qi, ki), ki innermost,
    the causally dead tiles skipped. Refs: q [1,g,block,D], k [1,1,block,D],
    v [1,1,block,Dv], words [1,block/32,block], o [1,g,block,Dv], lse
    [1,g,block,1]; scratch acc [g,block,Dv], m and l [g,block,1], the
    tile's bias [block,block]."""
    bi, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        def init(h):
            acc_ref[h] = jnp.zeros(acc_ref.shape[1:], jnp.float32)
            m_ref[h] = jnp.full(m_ref.shape[1:], _NEG_INF, jnp.float32)
            l_ref[h] = jnp.zeros(l_ref.shape[1:], jnp.float32)

        _heads(g, init)

    @pl.when(jnp.logical_and(_causal_open(qi, ki, block, block),
                             _flag(flags_ref, bi, qi, ki, n)))
    def _fold():
        _sel_bias(bias_ref, words_ref, block)
        k = k_ref[0, 0]
        v = v_ref[0, 0]

        def head(h):
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + bias_ref[...]
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - jnp.where(m_new > _NEG_INF / 2, m_new, 0.0))
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _heads(g, head)

    @pl.when(ki == n - 1)
    def _emit():
        def emit(h):
            l = l_ref[h]
            safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_ref[h] / safe).astype(o_ref.dtype)
            lse_ref[0, h] = m_ref[h] + jnp.log(safe)

        _heads(g, emit)


def _dq_sel_kernel(
    flags_ref, q_ref, k_ref, v_ref, words_ref, do_ref, lse_ref, delta_ref,
    dq_ref, acc_ref, bias_ref, *, block: int, n: int, g: int, scale: float,
):
    """dq over a selection: grid (b, h_kv, qi, ki), as the forward's.
    Refs as there, and do [1,g,block,Dv], lse/delta [1,g,block,1], dq
    [1,g,block,D]; scratch acc [g,block,D] and the tile's bias."""
    bi, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        def init(h):
            acc_ref[h] = jnp.zeros(acc_ref.shape[1:], jnp.float32)

        _heads(g, init)

    @pl.when(jnp.logical_and(_causal_open(qi, ki, block, block),
                             _flag(flags_ref, bi, qi, ki, n)))
    def _fold():
        _sel_bias(bias_ref, words_ref, block)
        k = k_ref[0, 0]
        v = v_ref[0, 0]

        def head(h):
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + bias_ref[...]
            p = jnp.exp(s - _sel_lse(lse_ref[0, h]))
            dp = jax.lax.dot_general(
                do_ref[0, h], v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, h])).astype(k.dtype)
            acc_ref[h] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _heads(g, head)

    @pl.when(ki == n - 1)
    def _emit():
        def emit(h):
            dq_ref[0, h] = (acc_ref[h] * scale).astype(dq_ref.dtype)

        _heads(g, emit)


def _dkv_sel_kernel(
    flags_ref, q_ref, k_ref, v_ref, words_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, bias_ref, *, block: int, n: int,
    g: int, scale: float,
):
    """dk/dv over a selection: grid (b, h_kv, ki, qi), qi innermost; the
    words are the (qi, ki) tile's. The g query heads' parts add into one
    accumulator a KV head. A padded query row adds nought: its dO and
    delta are zero. Refs as dq's, and dk [1,1,block,D], dv
    [1,1,block,Dv]; scratch dk_acc [block,D], dv_acc [block,Dv] and the
    tile's bias."""
    bi, ki, qi = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    @pl.when(jnp.logical_and(_causal_open(qi, ki, block, block),
                             _flag(flags_ref, bi, qi, ki, n)))
    def _fold():
        _sel_bias(bias_ref, words_ref, block)
        k = k_ref[0, 0]
        v = v_ref[0, 0]

        def head(h):
            q = q_ref[0, h]
            do = do_ref[0, h]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + bias_ref[...]
            p = jnp.exp(s - _sel_lse(lse_ref[0, h]))
            dv_acc_ref[...] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, h])).astype(q.dtype)
            dk_acc_ref[...] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _heads(g, head)

    @pl.when(qi == n - 1)
    def _emit():
        dk_ref[0, 0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _sel_specs(block: int, g: int, d: int, dv: int, q_major: bool):
    """BlockSpecs of (q, k, v, words, do, lse, delta) over a selection's
    grid: (b, h_kv, qi, ki) for flash_fwd and flash_dq, (b, h_kv, ki, qi)
    for flash_dkv (``q_major`` False); a q-side block holds the KV head's
    g query heads, and the causally dead tiles' indices are clamped to a
    live one, so that their DMAs are elided."""
    def ids(a, b_):
        qi, ki = (a, b_) if q_major else (b_, a)
        if q_major:
            ki = _k_tile_clamp(qi, ki, block, block, True)
        else:
            qi = _q_tile_clamp(ki, qi, block, block, True)
        return qi, ki

    def q_row(bi, hk, a, b_, f):
        return (bi, hk, ids(a, b_)[0], 0)

    def kv_row(bi, hk, a, b_, f):
        return (bi, hk, ids(a, b_)[1], 0)

    def words(bi, hk, a, b_, f):
        qi, ki = ids(a, b_)
        return (bi, qi, ki)

    return [pl.BlockSpec((1, g, block, d), q_row),
            pl.BlockSpec((1, 1, block, d), kv_row),
            pl.BlockSpec((1, 1, block, dv), kv_row),
            pl.BlockSpec((1, block // 32, block), words),
            pl.BlockSpec((1, g, block, dv), q_row),
            pl.BlockSpec((1, g, block, 1), q_row),
            pl.BlockSpec((1, g, block, 1), q_row)]


def _flash_sel_fwd(q, k, v, words, flags, *, scale: float, interpret: bool):
    """q [B,H,T,D], k [B,Hkv,T,D], v [B,Hkv,T,Dv], a selection's words and
    flags in its tile -> (o [B,H,T,Dv], lse [B,H,T_pad,1])."""
    b, h, t, d = q.shape
    dv = v.shape[-1]
    h_kv = k.shape[1]
    g = h // h_kv
    block = select_tile(t)
    n = pl.cdiv(t, block)
    q, k, v = (_pad_t(a, n * block) for a in (q, k, v))
    specs = _sel_specs(block, g, d, dv, q_major=True)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_sel_kernel, block=block, n=n, g=g,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h_kv, n, n),
            in_specs=specs[:4],
            out_specs=[specs[4], specs[5]],
            scratch_shapes=[pltpu.VMEM((g, block, dv), jnp.float32),
                            pltpu.VMEM((g, block, 1), jnp.float32),
                            pltpu.VMEM((g, block, 1), jnp.float32),
                            pltpu.VMEM((block, block), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, n * block, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, n * block, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_SEL_VMEM),
        interpret=interpret,
        name="sparse_flash_fwd",
    )(flags.reshape(-1), q, k, v, words)
    return o[:, :, :t], lse


def _flash_sel_bwd(q, k, v, words, flags, o, lse, do, *, scale: float,
                   interpret: bool):
    """dq, dk, dv over a selection (as _flash_bwd, causal)."""
    b, h, t, d = q.shape
    dv = v.shape[-1]
    h_kv = k.shape[1]
    g = h // h_kv
    block = select_tile(t)
    n = pl.cdiv(t, block)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    q_p, k_p, v_p, do_p, delta = (
        _pad_t(a, n * block) for a in (q, k, v, do, delta))
    operands = (flags.reshape(-1), q_p, k_p, v_p, words, do_p, lse, delta)
    params = pltpu.CompilerParams(vmem_limit_bytes=_SEL_VMEM)
    q_specs = _sel_specs(block, g, d, dv, q_major=True)
    dq = pl.pallas_call(
        functools.partial(_dq_sel_kernel, block=block, n=n, g=g,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h_kv, n, n), in_specs=q_specs,
            out_specs=pl.BlockSpec((1, g, block, d), q_specs[0].index_map),
            scratch_shapes=[pltpu.VMEM((g, block, d), jnp.float32),
                            pltpu.VMEM((block, block), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, n * block, d), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="sparse_flash_dq",
    )(*operands)[:, :, :t]
    k_specs = _sel_specs(block, g, d, dv, q_major=False)
    dk, d_v = pl.pallas_call(
        functools.partial(_dkv_sel_kernel, block=block, n=n, g=g,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h_kv, n, n), in_specs=k_specs,
            out_specs=[k_specs[1], k_specs[2]],
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                            pltpu.VMEM((block, dv), jnp.float32),
                            pltpu.VMEM((block, block), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h_kv, n * block, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h_kv, n * block, dv), v.dtype)],
        compiler_params=params,
        interpret=interpret,
        name="sparse_flash_dkv",
    )(*operands)
    return dq, dk[:, :, :t], d_v[:, :, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_sel(q, k, v, words, flags, scale, interpret):
    o, lse = _flash_sel_fwd(q, k, v, words, flags, scale=scale,
                            interpret=interpret)
    return o, lse[:, :, :q.shape[2], 0]


def _flash_sel_vjp_fwd(q, k, v, words, flags, scale, interpret):
    o, lse = _flash_sel_fwd(q, k, v, words, flags, scale=scale,
                            interpret=interpret)
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return (o, lse[:, :, :q.shape[2], 0]), (q, k, v, words, flags, o, lse)


def _flash_sel_vjp_bwd(scale, interpret, res, cotangents):
    # the log-sum-exp is handed out for a caller's stopped use: its
    # cotangent is dropped
    do, _ = cotangents
    q, k, v, words, flags, o, lse = res
    dq, dk, dv = _flash_sel_bwd(q, k, v, words, flags, o, lse, do,
                                scale=scale, interpret=interpret)
    return dq, dk, dv, no_cotangent(words), no_cotangent(flags)


_flash_sel.defvjp(_flash_sel_vjp_fwd, _flash_sel_vjp_bwd)


def _selected_reference(q, k, v, words, *, scale: float):
    """The chunked XLA reference over a selection, a selection tile of
    queries at a time: (o, each query's log-sum-exp over its keys, no
    gradient through it)."""
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    g = h // h_kv
    block = select_tile(t)
    n = -(-t // block)
    t_pad = n * block
    mask = words_to_mask(words, t_pad, block)[:, :, :t]
    qr = _pad_t(q, t_pad).reshape(b, h, n, block, d).transpose(2, 0, 1, 3, 4)
    mr = mask.reshape(b, n, block, t).transpose(1, 0, 2, 3)
    k_idx = jnp.arange(t)

    @jax.checkpoint
    def blk(args):
        i, q_blk, m_blk = args
        q_idx = i * block + jnp.arange(block)
        m_blk = m_blk & (q_idx[:, None] >= k_idx[None, :])
        s = jnp.einsum("bhgqd,bhkd->bhgqk",
                       q_blk.reshape(b, h_kv, g, block, d).astype(jnp.float32),
                       k.astype(jnp.float32)).reshape(b, h, block, t) * scale
        s = jnp.where(m_blk[:, None], s, _NEG_INF)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.where(m_blk[:, None], jnp.exp(s - lse[..., None]), 0.0)
        o = jnp.einsum("bhgqk,bhkd->bhgqd",
                       p.reshape(b, h_kv, g, block, t), v.astype(p.dtype))
        return (o.reshape(b, h, block, -1).astype(q.dtype),
                jax.lax.stop_gradient(lse))

    o, lse = jax.lax.map(blk, (jnp.arange(n), qr, mr))
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, h, t_pad, -1)[:, :, :t]
    return o, lse.transpose(1, 2, 0, 3).reshape(b, h, t_pad)[:, :, :t]


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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    mesh=None,
    batch_axes=("data", "fsdp"),
    head_axis: str = "tensor",
    layout: str = "bthd",
    window: Optional[int] = None,
    selection=None,
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

    ``block_q`` / ``block_k``: the tiles; ``None`` picks them, 1024 without
    a window and ``_band_tile`` with one.

    ``window`` (with ``causal``): query i sees key j iff i - window < j <= i,
    ``window`` keys with its own. The three kernels' grids then walk only
    the band: the innermost axis of flash_fwd's and flash_dq's grid counts
    the k tiles from the band's first (its oldest key) to the diagonal, and
    flash_dkv's the q tiles from the diagonal to the band's last, so no grid
    step is spent on a tile outside it; the kernels mask inside its edge
    tiles. The tile is then square and sized from the window: half of it,
    at least 512 and at most 1024 (``_band_tile``), so a band of 512 keys
    computes two 512² tiles a q tile where the full grid's 1024² computed
    two of four times the size.

    ``selection`` (a ``sparse_select.Selection``, with ``causal`` and no
    window): query i sees only its selected keys, one selection for every
    head. The kernels are then ``sparse_flash_fwd``, ``sparse_flash_dq``
    and ``sparse_flash_dkv``, in the selection's square tile, on grids over
    KV heads: each step takes a KV head's query heads, reads the tile's
    bits once beside its K/V tile and builds one mask for all of them, and
    a tile that holds no selected key is passed over. The kernels trust
    the bits to be causal and inside the sequence, as ``select`` makes
    them. The call returns ``(o, lse)``, lse [B,H,T]
    float32 each query's log-sum-exp over its keys, for a caller's stopped
    use (no gradient flows back through it). A call without a selection
    builds the kernels, grids and tiles it always has."""
    if selection is not None:
        return _selected_attention(
            q, k, v, selection, causal=causal, scale=scale,
            interpret=interpret, mesh=mesh, batch_axes=batch_axes,
            head_axis=head_axis, layout=layout, window=window,
            blocks=(block_q, block_k))
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
    tile = 1024 if window is None else _band_tile(
        window, q.shape[2 if heads_major else 1])
    bq = tile if block_q is None else block_q
    bk = tile if block_k is None else block_k

    def local(q_, k_, v_):
        if heads_major:
            qt, kt, vt = q_, k_, v_
        else:
            qt = q_.transpose(0, 2, 1, 3)
            kt = k_.transpose(0, 2, 1, 3)
            vt = v_.transpose(0, 2, 1, 3)
        if use_kernel:
            o = _flash(qt, kt, vt, causal, scale, bq, bk, interpret, window)
        else:
            # the reference's chunk bounds its memory; it is not a tile
            o = _chunked_reference(
                qt, kt, vt, causal=causal, scale=scale,
                block_q=1024 if block_q is None else block_q, window=window,
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


def _selected_attention(q, k, v, selection, *, causal, scale, interpret,
                        mesh, batch_axes, head_axis, layout, window, blocks):
    """flash_attention over a selection (see there)."""
    if not causal or window is not None or blocks != (None, None):
        raise ValueError("a selection is of causal keys, in its own tile: "
                         f"causal={causal}, window={window}, blocks={blocks}")
    if layout not in ("bthd", "bhtd"):
        raise ValueError(f"layout={layout!r}; expected bthd|bhtd")
    heads_major = layout == "bhtd"
    if scale is None:
        scale = q.shape[-1] ** -0.5
    use_kernel = interpret is not None or jax.default_backend() == "tpu"

    def local(q_, k_, v_, words, flags):
        qt, kt, vt = ((a if heads_major else a.transpose(0, 2, 1, 3))
                      for a in (q_, k_, v_))
        if use_kernel:
            o, lse = _flash_sel(qt, kt, vt, words, flags, scale,
                                bool(interpret))
        else:
            o, lse = _selected_reference(qt, kt, vt, words, scale=scale)
        return (o if heads_major else o.transpose(0, 2, 1, 3)), lse

    words, flags = selection.words, selection.flags
    if mesh is None:
        return local(q, k, v, words, flags)

    from jax.sharding import PartitionSpec as P

    b_part = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    h_dim = 1 if heads_major else 2
    tp = mesh.shape.get(head_axis, 1) if head_axis in mesh.axis_names else 1
    h_part = head_axis if (tp > 1 and q.shape[h_dim] % tp == 0
                           and k.shape[h_dim] % tp == 0) else None
    spec = (P(b_part, h_part, None, None) if heads_major
            else P(b_part, None, h_part, None))
    bits = P(b_part, None, None)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec, bits, bits),
        out_specs=(spec, P(b_part, h_part, None)), check_vma=False,
    )(q, k, v, words, flags)
