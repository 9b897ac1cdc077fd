"""The learned selection and the flash kernels over it on the chip, at the
cell's shape (``keyevl2.long-16k``: 16,384 tokens, 32 query heads over 4 of
128, an indexer of 16 heads of 64 over one key head, a top 2048):

- the ``sparse_select`` kernel's selection against ``lax.top_k``'s set,
  exactly, on scores that are exact in float32 whatever adds them;
- ``sparse_flash_fwd`` and the three gradients of ``sparse_flash_dq`` /
  ``sparse_flash_dkv`` (bf16) against masked attention in float32 at
  ``highest``, a block of queries at a time;
- the three kernels' times, each alone, beside the unmasked
  ``flash_fwd`` / ``flash_dq`` / ``flash_dkv`` at the same shape (printed,
  for PERF.md);
- the ``sparse_index_kl`` kernel's loss and gradients against its
  ``jax.numpy`` twin, float32, at a quarter of the length;
- how many selected keys the program's bf16 indexer moves against the
  float32 one on the same weights, over eight blocks of 256 queries
  (printed, for PERF.md).

    python -m pytest tests_tpu/test_sparse_attention_on_tpu.py -q -s  # a TPU
"""

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mpi_operator_tpu.kernels import sparse_select as ss
from mpi_operator_tpu.kernels.flash_attention import flash_attention
from mpi_operator_tpu.models import llama

T, H, HKV, D = 16384, 32, 4, 128
HI, DI, TOPK = 16, 64, 2048
BLOCK = 512


def _eighths(key, shape, reach=4):
    return jax.random.randint(key, shape, -reach, reach + 1).astype(
        jnp.float32) / 8


def _reference_mask(qi, ki, w, first, rows):
    """The plain selection of queries first .. first + rows (``first`` may
    be traced): lax.top_k's threshold over every causal key, ties kept."""
    score = jnp.einsum(
        "bqh,bhqk->bqk", lax.dynamic_slice_in_dim(w, first, rows, 1),
        jax.nn.relu(jnp.einsum(
            "bhqd,bkd->bhqk", lax.dynamic_slice_in_dim(qi, first, rows, 2),
            ki, precision=lax.Precision.HIGHEST)),
        precision=lax.Precision.HIGHEST)
    q_idx = first + jnp.arange(rows)
    causal = jnp.arange(T)[None, :] <= q_idx[:, None]
    score = jnp.where(causal, score, -jnp.inf)
    tau = lax.top_k(score, TOPK)[0][..., TOPK - 1:]
    tau = jnp.where((q_idx < TOPK)[:, None], -jnp.inf, tau)
    return causal & (score >= tau)


def _mask_rows(sel, first, rows):
    """The selection's bits of queries first .. first + rows, unpacked
    (``first`` may be traced; a multiple of ``rows``, which divides the
    selection's tile)."""
    tile = ss.select_tile(T)
    g = tile // 32
    words = lax.dynamic_slice_in_dim(sel.words, first // tile * g, g, 1)
    plane = (first % tile) // g
    return jax.vmap(lambda wd: ss.unpack(wd, rows, plane, tile))(words)


def test_the_selection_is_top_k_s_set_at_the_cells_shape():
    keys = jax.random.split(jax.random.PRNGKey(44), 3)
    qi = _eighths(keys[0], (1, HI, T, DI))
    ki = _eighths(keys[1], (1, T, DI))
    w = _eighths(keys[2], (1, T, HI))
    sel = jax.jit(lambda a, b, c: ss.select(a, b, c, topk=TOPK))(qi, ki, w)
    counts = np.asarray(sel.count[0])
    assert (counts[:TOPK] == np.arange(1, TOPK + 1)).all()
    assert (counts[TOPK:] >= TOPK).all()
    check = jax.jit(lambda qi, ki, w, sel, first: jnp.sum(
        _reference_mask(qi, ki, w, first, 256)
        != _mask_rows(sel, first, 256)))
    for first in (0, 1792, 2048, 6400, T - 256):
        assert int(check(qi, ki, w, sel, first)) == 0, first
    share = float(jnp.sum(sel.count)) / (T * (T + 1) / 2)
    print(f"selected share {share:.4f}; tiles open "
          f"{float(jnp.mean(sel.flags[0][jnp.tril(jnp.ones((16, 16), bool))])):.3f}")
    assert abs(share - 31_458_304 / 134_225_920) < 0.01


def _chunked_masked(q, k, v, sel):
    """Masked attention in float32 at highest, a block of queries at a
    time, recomputed in the backward pass."""
    g = H // HKV
    kk = jnp.repeat(k.astype(jnp.float32), g, axis=1)
    vv = jnp.repeat(v.astype(jnp.float32), g, axis=1)

    @jax.checkpoint
    def block(args):
        first, qb = args
        mask = _mask_rows(sel, first, BLOCK)[:, None]
        q_idx = first + jnp.arange(BLOCK)
        mask = mask & (jnp.arange(T)[None, :] <= q_idx[:, None])
        s = jnp.einsum("bhqd,bhkd->bhqk", qb.astype(jnp.float32), kk,
                       precision=lax.Precision.HIGHEST) * D ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vv,
                          precision=lax.Precision.HIGHEST)

    n = T // BLOCK
    blocks = jnp.moveaxis(q.reshape(1, H, n, BLOCK, D), 2, 0)
    out = lax.map(block, (jnp.arange(n) * BLOCK, blocks))
    return jnp.moveaxis(out, 0, 2).reshape(1, H, T, D)


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2)))


def test_flash_over_a_selection_and_three_gradients_at_the_cells_shape():
    keys = jax.random.split(jax.random.PRNGKey(7), 7)
    qi = jax.random.normal(keys[0], (1, HI, T, DI), jnp.bfloat16)
    ki = jax.random.normal(keys[1], (1, T, DI), jnp.bfloat16)
    w = jax.random.normal(keys[2], (1, T, HI)) / 32
    sel = jax.jit(lambda a, b, c: ss.select(a, b, c, topk=TOPK))(qi, ki, w)
    q = jax.random.normal(keys[3], (1, H, T, D), jnp.bfloat16)
    k = jax.random.normal(keys[4], (1, HKV, T, D), jnp.bfloat16)
    v = jax.random.normal(keys[5], (1, HKV, T, D), jnp.bfloat16)
    ct = jax.random.normal(keys[6], (1, H, T, D), jnp.float32)

    def program(q, k, v, sel, ct):
        o, _ = flash_attention(q, k, v, layout="bhtd", selection=sel)
        return jnp.sum(o.astype(jnp.float32) * ct), o

    def reference(q, k, v, sel, ct):
        o = _chunked_masked(q, k, v, sel)
        return jnp.sum(o * ct), o

    # every array an argument, none a constant folded at compile time
    (_, o), grads = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1, 2), has_aux=True))(q, k, v, sel, ct)
    (_, o_ref), want = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1, 2), has_aux=True))(
        *(a.astype(jnp.float32) for a in (q, k, v)), sel, ct)
    gaps = [_rel(o, o_ref)] + [_rel(a, b) for a, b in zip(grads, want)]
    print("rel rms o, dq, dk, dv", gaps)
    assert max(gaps) < 2e-2, gaps


def test_the_selected_kernels_timed_beside_the_unmasked_ones():
    """Each of the three kernels over a selection alone (a jitted call that
    keeps one of the backward's outputs compiles one kernel), on a random
    indexer's selection, which opens every causal tile as the cell's does,
    beside the unmasked causal kernels at the same shape: ms a call, the
    median of five timings of ten calls."""
    fa = importlib.import_module("mpi_operator_tpu.kernels.flash_attention")
    keys = jax.random.split(jax.random.PRNGKey(11), 7)
    qi = jax.random.normal(keys[0], (1, HI, T, DI), jnp.bfloat16)
    ki = jax.random.normal(keys[1], (1, T, DI), jnp.bfloat16)
    w = jax.random.normal(keys[2], (1, T, HI)) / 32
    sel = jax.jit(lambda a, b, c: ss.select(a, b, c, topk=TOPK))(qi, ki, w)
    q, k, v, do = (jax.random.normal(key, shape, jnp.bfloat16) for key, shape
                   in zip(keys[3:], ((1, H, T, D), (1, HKV, T, D),
                                     (1, HKV, T, D), (1, H, T, D))))
    scale = D ** -0.5
    how = dict(scale=scale, interpret=False)
    plain = dict(causal=True, scale=scale, block_q=1024, block_k=1024,
                 interpret=False)
    o_s, lse_s = jax.jit(lambda q, k, v, wd, f: fa._flash_sel_fwd(
        q, k, v, wd, f, **how))(q, k, v, sel.words, sel.flags)
    o_u, lse_u = jax.jit(lambda q, k, v: fa._flash_fwd(q, k, v, **plain))(
        q, k, v)
    calls = {
        "sparse_flash_fwd": (lambda q, k, v, wd, f: fa._flash_sel_fwd(
            q, k, v, wd, f, **how)[0], (q, k, v, sel.words, sel.flags)),
        "flash_fwd": (lambda q, k, v: fa._flash_fwd(q, k, v, **plain)[0],
                      (q, k, v)),
    }
    for i, kind in ((0, "dq"), (1, "dkv")):
        calls[f"sparse_flash_{kind}"] = (
            lambda q, k, v, wd, f, o, lse, do, i=i: fa._flash_sel_bwd(
                q, k, v, wd, f, o, lse, do, **how)[i],
            (q, k, v, sel.words, sel.flags, o_s, lse_s, do))
        calls[f"flash_{kind}"] = (
            lambda q, k, v, o, lse, do, i=i: fa._flash_bwd(
                q, k, v, o, lse, do, **plain)[i],
            (q, k, v, o_u, lse_u, do))

    def timed(fn, args):
        f = jax.jit(fn)
        jax.block_until_ready(f(*args))
        runs = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(10):
                out = f(*args)
            jax.block_until_ready(out)
            runs.append((time.perf_counter() - t0) * 100)
        return float(np.median(runs))

    ms = {name: timed(*call) for name, call in calls.items()}
    for kind in ("fwd", "dq", "dkv"):
        print(f"{kind}: sparse_flash_{kind} {ms[f'sparse_flash_{kind}']:.3f}"
              f" ms, flash_{kind} {ms[f'flash_{kind}']:.3f} ms")
    print("three kernels: selected {:.3f} ms, unmasked {:.3f} ms".format(
        sum(v for n, v in ms.items() if n.startswith("sparse")),
        sum(v for n, v in ms.items() if not n.startswith("sparse"))))
    assert all(v > 0 for v in ms.values())


def test_the_index_loss_kernel_against_its_xla_twin():
    """At the cell's heads and widths and a quarter of its length (the
    twin holds a tile's scores of every head at once: 2 GB a tile at
    16,384)."""
    t = 4096
    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    qi = jax.random.normal(keys[0], (1, HI, t, DI))
    ki = jax.random.normal(keys[1], (1, t, DI))
    w = jax.random.normal(keys[2], (1, t, HI)) / 32
    q = jax.random.normal(keys[3], (1, H, t, D), jnp.bfloat16)
    k = jax.random.normal(keys[4], (1, HKV, t, D), jnp.bfloat16)
    v = jax.random.normal(keys[5], (1, HKV, t, D), jnp.bfloat16)

    # every array an argument of the jitted call, none a constant of it: a
    # closed-over array is folded into the program at compile time
    sel = jax.jit(lambda a, b, c: ss.select(a, b, c, topk=TOPK))(qi, ki, w)
    _, lse = jax.jit(lambda q, k, v, sel: flash_attention(
        q, k, v, layout="bhtd", selection=sel))(q, k, v, sel)
    kl = lambda fn: jax.jit(jax.value_and_grad(
        lambda a, b, c, q, k, lse, sel: fn(a, b, c, q, k, lse, sel,
                                           scale=D ** -0.5),
        argnums=(0, 1, 2)))(qi, ki, w, q, k, lse, sel)
    loss, grads = kl(ss.index_kl)
    with jax.default_matmul_precision("highest"):
        want, want_grads = kl(ss._kl_xla)
    gaps = [_rel(a, b) for a, b in zip(grads, want_grads)]
    print("kl", float(loss), float(want), "rel rms dqI, dkI, dw", gaps)
    assert abs(float(loss) - float(want)) < 1e-3 * abs(float(want))
    # the kernel's float32 products run at the MXU's default precision, the
    # twin's at highest: 1.6e-2, 1.1e-2, 1.4e-3 on a TPU v5 lite; a wrong
    # tile or sign reads order one
    assert max(gaps) < 4e-2


def test_how_many_selected_keys_bf16_moves():
    """The program's indexer (bf16 products, as the model computes it)
    against the same indexer in float32 at highest, one seed, published
    widths: the share of the float32 selection's keys that the bf16 one
    leaves out (printed for PERF.md)."""
    d = 2048
    keys = jax.random.split(jax.random.PRNGKey(2026), 4)
    y = jax.random.normal(keys[0], (1, T, d))
    wq = jax.random.normal(keys[1], (d, HI * DI)) * d ** -0.5
    wk = jax.random.normal(keys[2], (d, DI)) * d ** -0.5
    ww = jax.random.normal(keys[3], (d, HI)) * d ** -0.5

    def indexer(dtype, y, wq, wk, ww):
        y_, wq_, wk_, ww_ = (a.astype(dtype) for a in (y, wq, wk, ww))
        qi = jnp.einsum("btd,dhx->bhtx", y_, wq_.reshape(d, HI, DI))
        kk = (y_ @ wk_).astype(jnp.float32)
        kk = (kk - kk.mean(-1, keepdims=True)) * lax.rsqrt(
            kk.var(-1, keepdims=True) + 1e-6)
        qi = llama._rope_bhtd(qi, 1e7)
        kk = llama._rope(kk.astype(dtype)[:, :, None], 1e7)[:, :, 0]
        wts = (y_ @ ww_).astype(jnp.float32) * (HI ** -0.5 * DI ** -0.5)
        return qi, kk, wts

    # the arrays are arguments, not constants folded at compile time
    weights = (y, wq, wk, ww)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda *a: indexer(jnp.float32, *a))(*weights)
    prog = jax.jit(lambda *a: ss.select(*indexer(jnp.bfloat16, *a),
                                        topk=TOPK))(*weights)
    moved = jax.jit(lambda ref, prog, first: jnp.sum(
        _reference_mask(*ref, first, 256) & ~_mask_rows(prog, first, 256)))
    # eight blocks of 256 queries across the sequence, each past the top
    # (2048 keys a query, no ties among float32 scores of normal draws) and
    # on a block's boundary, where its bits begin
    firsts = [TOPK + 256 * (i * (T - TOPK - 256) // 256 // 7)
              for i in range(8)]
    with jax.default_matmul_precision("highest"):
        left_out = sum(int(moved(ref, prog, f)) for f in firsts)
    total = 8 * 256 * TOPK
    share = left_out / total
    print(f"bf16 indexer: {left_out} of {total} selected keys of the "
          f"float32 one left out, {100 * share:.2f} %")
    assert share < 0.25
