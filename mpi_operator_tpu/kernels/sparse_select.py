"""A learned top-k selection of keys, and the loss that trains it, as Pallas
TPU kernels with ``jax.numpy`` twins off the chip.

The indexer (DeepSeek-V3.2-Exp's lightning indexer) scores every causal
key ``s`` of a query ``t`` over its heads ``j``:

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])

A query keeps the keys whose score is at least its ``topk``-th largest over
``s <= t``; every causal key where ``t + 1 <= topk``. Ties at the threshold
are all kept. One selection a token serves every query head of the layer:
it is made once a layer and read by the three flash kernels
(``flash_attention(..., selection=...)``) tile by tile.

The threshold is exact and needs no sort: a score's float32 bits, turned
into an int32 that orders as the float does (``_order_image``), are
bisected from the top bit down, 32 counts of the row against a candidate;
the largest candidate that at least ``topk`` keys reach is the ``topk``-th
largest score. ``select`` works a tile of queries at a time, the tile's
causal scores held whole in VMEM, so that the 32 counts read VMEM and not
HBM.

The selection travels as bits, an eighth of a byte a (query, key) pair
(33.5 MB a layer at 16,384 tokens): ``words`` [B, n_q * tile / 32,
n_k * tile] int32, in the square tiles of ``select_tile``. Within a tile of
``tile`` queries, row ``r * tile / 32 + i`` of the tile's queries is bit
``r`` of word row ``i``: a tile's bits unpack into its rows with a copy of
the word rows a bit and a shift (``unpack``), no lane shuffle. ``flags``
[B, n_q, n_k] says which tiles hold a selected key: the kernels pass over
those that hold none.

The loss (the report's sparse stage):
``L = sum_t KL(p_t || softmax over S_t of I_t)``, ``p_t`` the main
attention's probabilities over the selected keys averaged over its query
heads: a stopped target, so that the indexer learns from ``L`` alone. Its
gradient with respect to ``I`` is ``softmax_S(I) - p`` on the selection and
nought elsewhere, known in the forward pass: ``index_kl`` computes the loss
and the gradients of ``qI``, ``kI`` and ``w`` in one kernel pass, which
recomputes each head's probabilities tile by tile from the attention's
saved log-sum-exp as the flash backward does (a blockwise pass through XLA
would move 32 x T^2 x 4 bytes, 34 GB a layer at 16,384).

Off the TPU, ``select`` and ``index_kl`` are ``jax.numpy`` in blocks of
queries (the threshold by ``lax.top_k``, the loss by autodiff); pass
``interpret=True`` to run the kernels' bodies there, as the tests do.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INT_MIN = -(2 ** 31)
_LANES = 128
_SELECT_ROWS = 256      # queries a step of the selection kernel
_SELECT_KEYS = 512      # keys a chunk of its loops
_KL_ROWS = 256          # the loss kernel's tile: queries
_KL_KEYS = 512          # and keys
_VMEM_LIMIT = 64 * 1024 * 1024


class Selection(NamedTuple):
    """A layer's selection: the bits, which tiles hold a selected key, each
    query's log-sum-exp of its scores over its selection (float32
    [B, T]), and how many keys it selected (float32 [B, T])."""

    words: jax.Array
    flags: jax.Array
    lse: jax.Array
    count: jax.Array


def select_tile(t: int) -> int:
    """The square tile of a selection and of the kernels that read it:
    1024 from 1,024 tokens on, else the power of two at or above ``t``,
    at least 256 (a tile's 32 bit planes are then whole sublane rows)."""
    if t >= 1024:
        return 1024
    return max(256, 1 << (t - 1).bit_length())


def _order_image(x):
    """float32 -> int32 that orders as the floats do (no NaN here); both
    zeros map to 0. Its own inverse on the bits."""
    x = jnp.where(x == 0.0, 0.0, x)
    b = lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _from_image(img):
    b = img ^ ((img >> 31) & 0x7FFFFFFF)
    return lax.bitcast_convert_type(b, jnp.float32)


def unpack(words, rows: int, first_plane, tile: int):
    """bool [rows, K] of word rows ``words`` [tile / 32, K]: the tile's
    queries of bit planes ``first_plane`` .. ``first_plane + rows * 32 /
    tile`` (the tile's rows ``first_plane * tile / 32`` on)."""
    g = tile // 32
    copies = jnp.concatenate([words] * (rows // g), axis=0)
    shift = first_plane + lax.broadcasted_iota(jnp.int32, copies.shape, 0) // g
    return (lax.shift_right_logical(copies, shift) & 1) == 1


def pack(mask, tile: int):
    """bool [B, T, T] -> words [B, n * tile / 32, n * tile] int32."""
    b, t, _ = mask.shape
    n = -(-t // tile)
    g = tile // 32
    m = jnp.pad(mask, [(0, 0), (0, n * tile - t), (0, n * tile - t)])
    m = m.reshape(b, n, 32, g, n * tile).astype(jnp.uint32)
    bits = jnp.sum(m << jnp.arange(32, dtype=jnp.uint32)[None, None, :,
                                                           None, None],
                   axis=2, dtype=jnp.uint32)
    return lax.bitcast_convert_type(bits, jnp.int32).reshape(
        b, n * g, n * tile)


def words_to_mask(words, t: int, tile: int):
    """The inverse of :func:`pack`: bool [B, T, T]."""
    b, rows, cols = words.shape
    g = tile // 32
    n = rows // g
    w = words.reshape(b, n, 1, g, cols)
    bits = lax.shift_right_logical(
        w, jnp.arange(32, dtype=jnp.int32)[None, None, :, None, None]) & 1
    return bits.reshape(b, n * tile, cols)[:, :t, :t] == 1


def tile_flags(words, tile: int):
    """int32 [B, n_q, n_k]: 1 where a tile holds a selected key."""
    b, rows, cols = words.shape
    g = tile // 32
    w = words.reshape(b, rows // g, g, cols // tile, tile)
    return jnp.any(w != 0, axis=(2, 4)).astype(jnp.int32)


def _use_kernel(interpret):
    return interpret is not None or jax.default_backend() == "tpu"


def _pad_axis(x, axis: int, size: int):
    if x.shape[axis] == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pads)


# -- the selection ------------------------------------------------------------

def _select_kernel(qi_ref, kt_ref, w_ref, words_ref, lse_ref, count_ref,
                   s_ref, *, heads: int, rows: int, keys: int, tile: int,
                   topk: int, t_real: int):
    """Grid (b, step): ``rows`` queries a step. Refs: qI [1,Hi,rows,Di],
    kI^T [1,Di,Tk] (whole), w [1,rows,Hi], words [1,tile/32,Tk] (the
    tile's, revisited by its tile / rows steps), lse/count [1,rows,1];
    scratch s [rows, Tk] int32, the rows' score images."""
    step = pl.program_id(1)
    q0 = step * rows
    per_tile = tile // rows
    g = tile // 32
    n_chunks = (q0 + rows + keys - 1) // keys  # the causal ones
    q_idx = q0 + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

    def scores(c, carry):
        cols = pl.ds(pl.multiple_of(c * keys, keys), keys)
        kt = kt_ref[0, :, cols]
        acc = jnp.zeros((rows, keys), jnp.float32)
        for j in range(heads):
            s = jnp.dot(qi_ref[0, j], kt, preferred_element_type=jnp.float32)
            acc = acc + w_ref[0, :, j:j + 1] * jnp.maximum(s, 0.0)
        k_idx = c * keys + lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
        valid = jnp.logical_and(k_idx <= q_idx, k_idx < t_real)
        s_ref[:, cols] = jnp.where(valid, _order_image(acc), _INT_MIN)
        return carry

    lax.fori_loop(0, n_chunks, scores, 0)

    def count_at_least(cand):
        def chunk(c, acc):
            for u in range(keys // _LANES):
                col = pl.ds(pl.multiple_of(c * keys + u * _LANES, _LANES),
                            _LANES)
                acc = acc + (s_ref[:, col] >= cand).astype(jnp.int32)
            return acc

        acc = lax.fori_loop(0, n_chunks, chunk,
                            jnp.zeros((rows, _LANES), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    need = jnp.minimum(jnp.minimum(q_idx + 1, t_real), topk)

    def bisect(i, ans):
        # bit 31 - i of the offset image; at i = 0, INT_MIN + 2**31 = 0
        cand = ans + lax.shift_left(jnp.int32(1), 31 - i)
        return jnp.where(count_at_least(cand) >= need, cand, ans)

    tau = lax.fori_loop(0, 32, bisect,
                        jnp.full((rows, 1), _INT_MIN, jnp.int32))

    def top(c, m):
        cols = pl.ds(pl.multiple_of(c * keys, keys), keys)
        return jnp.maximum(m, jnp.max(s_ref[:, cols], axis=1, keepdims=True))

    m = _from_image(lax.fori_loop(0, n_chunks, top,
                                  jnp.full((rows, 1), _INT_MIN, jnp.int32)))

    @pl.when(step % per_tile == 0)
    def _clear():
        words_ref[...] = jnp.zeros_like(words_ref)

    first_plane = (step % per_tile) * (rows // g)

    def emit(c, carry):
        l, n = carry
        cols = pl.ds(pl.multiple_of(c * keys, keys), keys)
        img = s_ref[:, cols]
        sel = img >= tau
        l = l + jnp.sum(jnp.where(sel, jnp.exp(_from_image(img) - m), 0.0),
                        axis=1, keepdims=True)
        n = n + jnp.sum(sel.astype(jnp.float32), axis=1, keepdims=True)
        bits = words_ref[0, :, cols]
        sel32 = sel.astype(jnp.int32)
        for p in range(rows // g):
            bits = bits | lax.shift_left(sel32[p * g:(p + 1) * g],
                                         first_plane + p)
        words_ref[0, :, cols] = bits
        return l, n

    zero = jnp.zeros((rows, 1), jnp.float32)
    l, n = lax.fori_loop(0, n_chunks, emit, (zero, zero))
    lse_ref[0] = jnp.where(n > 0, m + jnp.log(jnp.maximum(l, 1e-30)), 0.0)
    count_ref[0] = n


def _select_kernel_call(qi, kt, w, *, topk: int, tile: int, t: int,
                        interpret: bool):
    b, hi, t_pad, di = qi.shape
    rows = min(_SELECT_ROWS, tile)
    keys = min(_SELECT_KEYS, tile)
    g = tile // 32
    kernel = functools.partial(
        _select_kernel, heads=hi, rows=rows, keys=keys, tile=tile,
        topk=topk, t_real=t)
    per_tile = tile // rows
    words, lse, count = pl.pallas_call(
        kernel,
        grid=(b, t_pad // rows),
        in_specs=[
            pl.BlockSpec((1, hi, rows, di), lambda bi, s: (bi, 0, s, 0)),
            pl.BlockSpec((1, di, t_pad), lambda bi, s: (bi, 0, 0)),
            pl.BlockSpec((1, rows, hi), lambda bi, s: (bi, s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, g, t_pad), lambda bi, s: (bi, s // per_tile, 0)),
            pl.BlockSpec((1, rows, 1), lambda bi, s: (bi, s, 0)),
            pl.BlockSpec((1, rows, 1), lambda bi, s: (bi, s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t_pad // 32, t_pad), jnp.int32),
            jax.ShapeDtypeStruct((b, t_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, t_pad, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, t_pad), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="sparse_select",
    )(qi, kt, w)
    return words, lse[..., 0], count[..., 0]


def _scores_block(qi_blk, ki, w_blk):
    """I of a block of queries: qI [B,Hi,Q,Di], kI [B,T,Di], w [B,Q,Hi] ->
    float32 [B,Q,T]."""
    s = jnp.einsum("bhqd,bkd->bhqk", qi_blk, ki,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bqh,bhqk->bqk", w_blk.astype(jnp.float32),
                      jax.nn.relu(s))


def _select_xla(qi, ki, w, *, topk: int, tile: int, t: int):
    """The selection in ``jax.numpy``, a tile of queries at a time: the
    threshold is the ``topk``-th largest causal score (``lax.top_k``)."""
    b, hi, t_pad, di = qi.shape
    n = t_pad // tile
    ki_p = _pad_axis(ki, 1, t_pad)
    k_idx = jnp.arange(t_pad)

    def block(args):
        i, q_blk, w_blk = args
        s = _scores_block(q_blk, ki_p, w_blk)  # [B, tile, Tk]
        q_idx = i * tile + jnp.arange(tile)
        valid = (k_idx[None, :] <= q_idx[:, None]) & (k_idx[None, :] < t)
        s = jnp.where(valid, s, -jnp.inf)
        k = min(topk, t_pad)
        tau = lax.top_k(s, k)[0][..., k - 1:k]
        tau = jnp.where((q_idx + 1 <= topk)[:, None], -jnp.inf, tau)
        sel = valid & (s >= tau)
        lse = jax.nn.logsumexp(jnp.where(sel, s, -jnp.inf), axis=-1)
        count = jnp.sum(sel, axis=-1).astype(jnp.float32)
        return sel, jnp.where(count > 0, lse, 0.0), count

    qr = jnp.moveaxis(qi.reshape(b, hi, n, tile, di), 2, 0)
    wr = jnp.moveaxis(w.reshape(b, n, tile, hi), 1, 0)
    sel, lse, count = lax.map(block, (jnp.arange(n), qr, wr))
    sel = jnp.moveaxis(sel, 0, 1).reshape(b, t_pad, t_pad)
    return (pack(sel, tile),
            jnp.moveaxis(lse, 0, 1).reshape(b, t_pad),
            jnp.moveaxis(count, 0, 1).reshape(b, t_pad))


def select(qi, ki, w, *, topk: int, interpret: Optional[bool] = None,
           mesh=None, batch_axes=("data", "fsdp")) -> Selection:
    """The selection of every query. qI [B,Hi,T,Di] (heads-major), kI
    [B,T,Di], w [B,T,Hi] (the heads' weights, scale folded in). Nothing of
    it is differentiable. On the TPU the ``sparse_select`` kernel, else
    ``jax.numpy`` (``interpret=True``: the kernel's body, interpreted).
    With ``mesh``, under shard_map, batch over ``batch_axes``."""
    qi, ki, w = (lax.stop_gradient(a) for a in (qi, ki, w))

    def local(qi, ki, w):
        b, hi, t, di = qi.shape
        tile = select_tile(t)
        t_pad = -(-t // tile) * tile
        qi_p = _pad_axis(qi, 2, t_pad)
        w_p = _pad_axis(w.astype(jnp.float32), 1, t_pad)
        if _use_kernel(interpret):
            kt = _pad_axis(ki, 1, t_pad).transpose(0, 2, 1)
            words, lse, count = _select_kernel_call(
                qi_p, kt, w_p, topk=topk, tile=tile, t=t,
                interpret=bool(interpret))
        else:
            words, lse, count = _select_xla(qi_p, ki, w_p, topk=topk,
                                            tile=tile, t=t)
        from jax.ad_checkpoint import checkpoint_name

        # named so that a rematted caller can keep the bits (a 32nd of the
        # attention's scores) rather than select again in its replay
        words = checkpoint_name(words, "sparse_select_bits")
        flags = checkpoint_name(tile_flags(words, tile), "sparse_select_bits")
        return Selection(words, flags, lse[:, :t], count[:, :t])

    if mesh is None:
        return local(qi, ki, w)
    from jax.sharding import PartitionSpec as P

    b = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b, None, None, None), P(b, None, None), P(b, None, None)),
        out_specs=Selection(P(b, None, None), P(b, None, None), P(b, None),
                            P(b, None)),
        check_vma=False)(qi, ki, w)


# -- the indexer's loss ---------------------------------------------------------

def _kl_kernel(flags_ref, q_ref, k_ref, lse_ref, qi_ref, kt_ref, w_ref,
               lsei_ref, words_ref, kl_ref, dqi_ref, dw_ref, dkt_ref, *,
               heads: int, group: int, index_heads: int, rows: int,
               keys: int, tile: int, n_kt: int, scale: float, t_real: int):
    """Grid (b, qi, ki), ki innermost, every axis in order: dkI^T [1,Di,Tk]
    is one block for the whole grid of a row. Refs: q [1,H,rows,D], k
    [1,Hkv,keys,D], lse [1,H,rows,1], qI [1,Hi,rows,Di], kI^T
    [1,Di,keys], w [1,rows,Hi], lseI [1,rows,1], words [1,tile/32,keys];
    out kl [1,rows,1], dqI [1,Hi,rows,Di], dw [1,rows,Hi] (each a q tile's,
    over its k sweep), dkI^T."""
    bi, qt, kt_i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q0, k0 = qt * rows, kt_i * keys

    @pl.when(jnp.logical_and(qt == 0, kt_i == 0))
    def _clear_dk():
        dkt_ref[...] = jnp.zeros_like(dkt_ref)

    @pl.when(kt_i == 0)
    def _clear():
        kl_ref[...] = jnp.zeros_like(kl_ref)
        dqi_ref[...] = jnp.zeros_like(dqi_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    n_q_tiles = pl.num_programs(1) * rows // tile
    flag = flags_ref[(bi * n_q_tiles + q0 // tile) * n_kt + k0 // tile]
    live = jnp.logical_and(k0 < q0 + rows, flag > 0)

    @pl.when(live)
    def _fold():
        def head(h, acc):
            s = lax.dot_general(
                q_ref[0, h], k_ref[0, h // group], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            return acc + jnp.exp(s - lse_ref[0, h])

        p = lax.fori_loop(0, heads, head,
                          jnp.zeros((rows, keys), jnp.float32)) / heads
        kt = kt_ref[0]
        score = jnp.zeros((rows, keys), jnp.float32)
        for j in range(index_heads):
            s = jnp.dot(qi_ref[0, j], kt, preferred_element_type=jnp.float32)
            score = score + w_ref[0, :, j:j + 1] * jnp.maximum(s, 0.0)
        q_idx = q0 + lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
        k_idx = k0 + lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
        g = tile // 32
        sel = unpack(words_ref[0], rows, (q0 % tile) // g, tile)
        sel = sel & (k_idx <= q_idx) & (q_idx < t_real) & (k_idx < t_real)
        log_q = score - lsei_ref[0]
        p = jnp.where(sel, p, 0.0)
        kl_ref[0] += jnp.sum(
            jnp.where(jnp.logical_and(sel, p > 0),
                      p * (jnp.log(jnp.maximum(p, 1e-30)) - log_q), 0.0),
            axis=1, keepdims=True)
        d_score = jnp.where(sel, jnp.exp(log_q) - p, 0.0)
        for j in range(index_heads):
            qj = qi_ref[0, j]
            s = jnp.dot(qj, kt, preferred_element_type=jnp.float32)
            dw_ref[0, :, j:j + 1] += jnp.sum(
                d_score * jnp.maximum(s, 0.0), axis=1, keepdims=True)
            ds = jnp.where(s > 0, d_score * w_ref[0, :, j:j + 1], 0.0
                           ).astype(qj.dtype)
            dqi_ref[0, j] += lax.dot_general(
                ds, kt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            cols = pl.ds(pl.multiple_of(k0, keys), keys)
            dkt_ref[0, :, cols] += lax.dot_general(
                qj, ds, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def _kl_kernel_call(q, k, lse, qi, kt, w, lsei, words, flags, *, scale: float,
                    tile: int, t: int, interpret: bool):
    b, h, t_pad, d = q.shape
    hkv = k.shape[1]
    hi, di = qi.shape[1], qi.shape[3]
    rows = min(_KL_ROWS, tile)
    keys = min(_KL_KEYS, tile)
    n_kt = t_pad // tile
    g = tile // 32

    def k_tile(qt, ki):  # causally dead tiles repeat the last live one
        return jnp.minimum(ki, ((qt + 1) * rows - 1) // keys)

    kernel = functools.partial(
        _kl_kernel, heads=h, group=h // hkv, index_heads=hi, rows=rows,
        keys=keys, tile=tile, n_kt=n_kt, scale=scale, t_real=t)
    kl, dqi, dw, dkt = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t_pad // rows, t_pad // keys),
            in_specs=[
                pl.BlockSpec((1, h, rows, d),
                             lambda bi, qt, ki, f: (bi, 0, qt, 0)),
                pl.BlockSpec((1, hkv, keys, d),
                             lambda bi, qt, ki, f: (bi, 0, k_tile(qt, ki), 0)),
                pl.BlockSpec((1, h, rows, 1),
                             lambda bi, qt, ki, f: (bi, 0, qt, 0)),
                pl.BlockSpec((1, hi, rows, di),
                             lambda bi, qt, ki, f: (bi, 0, qt, 0)),
                pl.BlockSpec((1, di, keys),
                             lambda bi, qt, ki, f: (bi, 0, k_tile(qt, ki))),
                pl.BlockSpec((1, rows, hi), lambda bi, qt, ki, f: (bi, qt, 0)),
                pl.BlockSpec((1, rows, 1), lambda bi, qt, ki, f: (bi, qt, 0)),
                pl.BlockSpec((1, g, keys),
                             lambda bi, qt, ki, f: (
                                 bi, qt * rows // tile, k_tile(qt, ki))),
            ],
            out_specs=[
                pl.BlockSpec((1, rows, 1), lambda bi, qt, ki, f: (bi, qt, 0)),
                pl.BlockSpec((1, hi, rows, di),
                             lambda bi, qt, ki, f: (bi, 0, qt, 0)),
                pl.BlockSpec((1, rows, hi), lambda bi, qt, ki, f: (bi, qt, 0)),
                pl.BlockSpec((1, di, t_pad), lambda bi, qt, ki, f: (bi, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, t_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hi, t_pad, di), jnp.float32),
            jax.ShapeDtypeStruct((b, t_pad, hi), jnp.float32),
            jax.ShapeDtypeStruct((b, di, t_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="sparse_index_kl",
    )(flags.reshape(-1), q, k, lse, qi, kt, w, lsei, words)
    return jnp.sum(kl), dqi, dw, dkt.transpose(0, 2, 1)


def _kl_xla(qi, ki, w, q, k, lse, sel, *, scale: float):
    """The loss in ``jax.numpy``, a tile of queries at a time, its gradient
    by autodiff (the target stopped)."""
    b, hi, t, di = qi.shape
    h, hkv = q.shape[1], k.shape[1]
    tile = select_tile(t)
    t_pad = -(-t // tile) * tile
    n = t_pad // tile
    mask = words_to_mask(sel.words, t_pad, tile)
    padded = lambda x, axis: _pad_axis(x, axis, t_pad)
    ki_p = padded(ki, 1)
    k_p = padded(k, 2).astype(jnp.float32)
    k_idx = jnp.arange(t_pad)

    @jax.checkpoint
    def block(args):
        i, q_blk, lse_blk, qi_blk, w_blk, m_blk = args
        q_idx = i * tile + jnp.arange(tile)
        m_blk = m_blk & (k_idx[None, :] <= q_idx[:, None]) & (
            q_idx[:, None] < t) & (k_idx[None, :] < t)
        s = jnp.einsum("bhgqd,bhkd->bhgqk",
                       q_blk.astype(jnp.float32).reshape(
                           b, hkv, h // hkv, tile, -1), k_p)
        s = s.reshape(b, h, tile, t_pad) * scale
        p = jnp.exp(s - lse_blk[..., None])
        p = lax.stop_gradient(jnp.where(m_blk[:, None], p, 0.0).mean(axis=1))
        score = _scores_block(qi_blk, ki_p, w_blk)
        log_q = jax.nn.log_softmax(
            jnp.where(m_blk, score, -jnp.inf), axis=-1)
        return jnp.sum(jnp.where(m_blk & (p > 0),
                                 p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                      - jnp.where(m_blk, log_q, 0.0)), 0.0))

    split = lambda x, axis: jnp.moveaxis(
        padded(x, axis).reshape(*x.shape[:axis], n, tile,
                                *x.shape[axis + 1:]), axis, 0)
    parts = (jnp.arange(n), split(q, 2), split(lse, 2), split(qi, 2),
             split(w.astype(jnp.float32), 1),
             jnp.moveaxis(mask.reshape(b, n, tile, t_pad), 1, 0))
    return jnp.sum(lax.map(block, parts))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _kl(qi, ki, w, q, k, lse, sel, scale, interpret):
    return _kl_fwd(qi, ki, w, q, k, lse, sel, scale, interpret)[0]


def _kl_fwd(qi, ki, w, q, k, lse, sel, scale, interpret):
    b, hi, t, di = qi.shape
    tile = select_tile(t)
    t_pad = -(-t // tile) * tile
    padded = lambda x, axis: _pad_axis(x, axis, t_pad)
    loss, dqi, dw, dki = _kl_kernel_call(
        padded(q, 2), padded(k, 2), padded(lse, 2)[..., None],
        padded(qi, 2), padded(ki, 1).transpose(0, 2, 1),
        padded(w.astype(jnp.float32), 1), padded(sel.lse, 1)[..., None],
        sel.words, sel.flags, scale=scale, tile=tile, t=t,
        interpret=interpret)
    from jax.ad_checkpoint import checkpoint_name

    grads = tuple(checkpoint_name(a, "sparse_index_grads") for a in (
        dqi[:, :, :t].astype(qi.dtype), dki[:, :t].astype(ki.dtype),
        dw[:, :t].astype(w.dtype)))
    return loss, (grads, (q, k, lse, sel))


def no_cotangent(a):
    """The zero cotangent of an operand that takes no gradient."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        return jnp.zeros_like(a)
    return np.zeros(a.shape, jax.dtypes.float0)


def _kl_bwd(scale, interpret, res, g):
    grads, stopped = res
    return (*((g * d).astype(d.dtype) for d in grads),
            *jax.tree.map(no_cotangent, stopped))


_kl.defvjp(_kl_fwd, _kl_bwd)


def index_kl(qi, ki, w, q, k, lse, sel: Selection, *, scale: float,
             interpret: Optional[bool] = None, mesh=None,
             batch_axes=("data", "fsdp")):
    """``sum_t KL(p_t || softmax over S_t of I_t)`` over every query of the
    batch; differentiable in qI [B,Hi,T,Di], kI [B,T,Di] and w [B,T,Hi]
    alone. q [B,H,T,D], k [B,Hkv,T,D] and lse [B,H,T] (float32, each
    query's log-sum-exp over its selected keys) are the main attention's,
    taken as they are: ``p`` is a target. On the TPU the
    ``sparse_index_kl`` kernel (loss and gradients in one pass, the
    gradients kept, named ``sparse_index_grads``), else ``jax.numpy``
    and autodiff (``interpret=True``: the kernel's body, interpreted)."""
    stopped = [lax.stop_gradient(a) for a in (q, k, lse)]
    sel = jax.tree.map(lax.stop_gradient, sel)
    use_kernel = _use_kernel(interpret)

    def local(qi, ki, w, q, k, lse, sel):
        if use_kernel:
            return _kl(qi, ki, w, q, k, lse, sel, scale, bool(interpret))
        return _kl_xla(qi, ki, w, q, k, lse, sel, scale=scale)

    if mesh is None:
        return local(qi, ki, w, *stopped, sel)
    from jax.sharding import PartitionSpec as P

    b = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    sum_over = b
    spec4, spec3, spec2 = (P(b, None, None, None), P(b, None, None),
                           P(b, None))

    def summed(*args):
        total = local(*args)
        return lax.psum(total, sum_over) if sum_over else total

    return jax.shard_map(
        summed, mesh=mesh,
        in_specs=(spec4, spec3, spec3, spec4, spec4, spec3,
                  Selection(spec3, spec3, spec2, spec2)),
        out_specs=P(), check_vma=False)(qi, ki, w, *stopped, sel)
