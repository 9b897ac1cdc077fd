"""The learned selection of keys (kernels/sparse_select.py) and the flash
kernels over it, on the CPU: the selection against ``lax.top_k``'s set,
exactly, with short rows and planted ties; its bits, and that they are
causal and inside the sequence; the three flash kernels over a selection
against a dense masked reference, forward and three gradients, at one,
two and eight query heads a KV head; the indexer loss's value and
gradients against autodiff of the plain KL; and flash calls without a
selection lowering to the jaxprs they lowered to before the selection
existed. The kernels' bodies
run in interpret mode (``interpret=True``), their ``jax.numpy`` twins as
the model runs them off the chip.

    JAX_PLATFORMS=cpu python -m pytest tests/test_sparse_attention.py -q
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mpi_operator_tpu.kernels import sparse_select as ss
from mpi_operator_tpu.kernels.flash_attention import flash_attention

HI, DI = 4, 16


def _eighths(key, shape, reach=4):
    """Numbers k / 8 with |k| <= reach: every product and sum below is
    exact in float32, so a score is the same whatever order adds it."""
    return jax.random.randint(key, shape, -reach, reach + 1).astype(
        jnp.float32) / 8


def _indexer(seed, t, b=1):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (_eighths(k1, (b, HI, t, DI)), _eighths(k2, (b, t, DI)),
            _eighths(k3, (b, t, HI)))


def _top_k_set(qi, ki, w, topk):
    """The plain selection: scores over causal keys, the topk-th largest by
    ``lax.top_k``, every key at or above it (every causal key where there
    are no more)."""
    t = ki.shape[1]
    score = jnp.einsum("bqh,bhqk->bqk", w, jax.nn.relu(
        jnp.einsum("bhqd,bkd->bhqk", qi, ki, precision="highest")),
        precision="highest")
    causal = jnp.tril(jnp.ones((t, t), bool))
    score = jnp.where(causal, score, -jnp.inf)
    k = min(topk, t)
    tau = lax.top_k(score, k)[0][..., k - 1:]
    tau = jnp.where((jnp.arange(t) < topk)[:, None], -jnp.inf, tau)
    return causal & (score >= tau), score


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("t,topk", [(80, 16), (300, 40), (300, 8)])
def test_the_selection_is_top_k_s_set_exactly(t, topk, interpret):
    """Scores of eighths tie often: every key at the threshold is kept, on
    both paths, and the rows with t + 1 <= topk keep every causal key."""
    qi, ki, w = _indexer(t + topk, t, b=2)
    sel = ss.select(qi, ki, w, topk=topk, interpret=interpret)
    want, score = _top_k_set(qi, ki, w, topk)
    got = ss.words_to_mask(sel.words, t, ss.select_tile(t))
    assert bool(jnp.all(got == want))
    counts = jnp.sum(want, axis=-1)
    assert bool(jnp.all(sel.count == counts))
    assert bool(jnp.all(counts[:, :topk] == jnp.arange(1, topk + 1)))
    assert int(jnp.sum(counts[:, topk:] > topk)) > 0  # ties were planted
    lse = jax.nn.logsumexp(jnp.where(want, score, -jnp.inf), axis=-1)
    np.testing.assert_allclose(sel.lse, lse, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
def test_a_planted_tie_at_the_threshold_is_kept_whole(interpret):
    """One query's scores hand-set: ten keys share the fourth largest
    value; topk 4 keeps the three above it and all ten."""
    t, topk = 64, 4
    qi = jnp.zeros((1, HI, t, DI)).at[:, 0, :, 0].set(1.0)
    k_col = jnp.zeros(t).at[jnp.arange(10, 20)].set(2.0)
    k_col = k_col.at[jnp.array([3, 30, 50])].set(jnp.array([5.0, 6.0, 7.0]))
    ki = jnp.zeros((1, t, DI)).at[0, :, 0].set(k_col)
    w = jnp.zeros((1, t, HI)).at[:, :, 0].set(1.0)
    sel = ss.select(qi, ki, w, topk=topk, interpret=interpret)
    mask = ss.words_to_mask(sel.words, t, ss.select_tile(t))[0]
    assert set(np.flatnonzero(mask[60])) == {3, 30, 50, *range(10, 20)}
    assert float(sel.count[0, 60]) == 13
    # a query before key 30 sees one key above the tie: it keeps the tie
    assert set(np.flatnonzero(mask[25])) == {3, *range(10, 20)}
    want, _ = _top_k_set(qi, ki, w, topk)
    assert bool(jnp.all(mask == want[0]))


@pytest.mark.parametrize("tile", [256, 1024])
def test_bits_unpack_a_tile_and_its_planes(tile):
    """``pack`` then ``words_to_mask`` is the identity, and ``unpack`` of a
    tile's word rows gives its rows, whole or a band of planes."""
    t = tile + 40
    mask = jax.random.bernoulli(jax.random.PRNGKey(tile), 0.3, (1, t, t))
    words = ss.pack(mask, tile)
    assert words.shape == (1, 2 * tile // 32, 2 * tile)
    assert bool(jnp.all(ss.words_to_mask(words, t, tile) == mask))
    full = jnp.pad(mask, [(0, 0), (0, 2 * tile - t), (0, 2 * tile - t)])
    g = tile // 32
    block = words[0, g:2 * g, :tile]  # q tile 1, k tile 0
    assert bool(jnp.all(ss.unpack(block, tile, 0, tile)
                        == full[0, tile:2 * tile, :tile]))
    rows = 128
    assert bool(jnp.all(ss.unpack(block, rows, rows // g, tile)
                        == full[0, tile + rows:tile + 2 * rows, :tile]))
    flags = ss.tile_flags(words, tile)
    want = jnp.any(full.reshape(1, 2, tile, 2, tile), axis=(2, 4))
    assert bool(jnp.all(flags == want))


def _qkv(key, t, h=4, hkv=2, d=16):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, (1, h, t, d)),
            jax.random.normal(k2, (1, hkv, t, d)),
            jax.random.normal(k3, (1, hkv, t, d)))


def _masked_attention(q, k, v, mask, scale):
    """Dense softmax over each query's mask, GQA, heads-major."""
    g = q.shape[1] // k.shape[1]
    kk, vv = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk, precision="highest") * scale
    s = jnp.where(mask[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return (jnp.einsum("bhqk,bhkd->bhqd", p, vv, precision="highest"),
            jax.nn.logsumexp(s, axis=-1))


def _against_a_dense_mask(q, k, v, sel, mask, interpret):
    """Forward, log-sum-exp and the three gradients over ``sel`` against
    dense attention masked to ``mask``."""
    scale = q.shape[-1] ** -0.5
    o, lse = flash_attention(q, k, v, layout="bhtd", selection=sel,
                             interpret=interpret)
    o_ref, lse_ref = _masked_attention(q, k, v, mask, scale)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(lse, lse_ref, atol=2e-5)
    ct = jax.random.normal(jax.random.PRNGKey(1), o.shape)
    got = jax.grad(lambda *a: jnp.sum(ct * flash_attention(
        *a, layout="bhtd", selection=sel, interpret=interpret)[0]),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(ct * _masked_attention(
        *a, mask, scale)[0]), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


# ragged lengths: 1100 is two tiles of 1024, the second mostly padding
_LENGTHS = [(80, 16), (300, 40), (1100, 150)]


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("t,topk", _LENGTHS)
def test_flash_over_a_selection_matches_a_dense_mask(t, topk, interpret):
    """Forward, log-sum-exp and the three gradients of the kernels over a
    selection (and of the chunked XLA reference the model runs off the
    chip) against dense attention masked to the same selection, two query
    heads a KV head."""
    qi, ki, w = _indexer(t, t)
    sel = ss.select(qi, ki, w, topk=topk)
    mask = ss.words_to_mask(sel.words, t, ss.select_tile(t))
    _against_a_dense_mask(*_qkv(jax.random.PRNGKey(t), t), sel, mask,
                          interpret)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("t,topk", _LENGTHS)
@pytest.mark.parametrize("h,hkv", [(2, 2), (8, 1)], ids=["g1", "g8"])
def test_a_kv_heads_query_heads_share_one_grid_step(h, hkv, t, topk,
                                                    interpret):
    """The kernels' grids run over KV heads, a step holding the KV head's
    g query heads and one mask for all of them: at one and at eight query
    heads a KV head (two: the test above), forward, log-sum-exp and the
    three gradients are dense attention masked to the selection."""
    qi, ki, w = _indexer(t + h, t)
    sel = ss.select(qi, ki, w, topk=topk)
    mask = ss.words_to_mask(sel.words, t, ss.select_tile(t))
    _against_a_dense_mask(*_qkv(jax.random.PRNGKey(t + hkv), t, h=h,
                                hkv=hkv), sel, mask, interpret)


def test_a_tile_with_no_selected_key_is_passed_over():
    """Flags at nought for a tile that holds selected keys: the kernels
    pass over it, and the result and its gradients are the masked
    attention without it, with two and with eight query heads a KV head.
    Ten rows whose keys all lie in that tile are left with none: their
    output is nought and they add nothing to any gradient."""
    t = 1100  # two tiles of 1024: (1, 0) is off the diagonal
    empty = jnp.arange(1030, 1040)
    mask = jnp.tril(jnp.ones((1, t, t), bool)).at[0, empty, 1024:].set(False)
    words = ss.pack(mask, 1024)
    flags = ss.tile_flags(words, 1024).at[0, 1, 0].set(0)
    sel = ss.Selection(words, flags, jnp.zeros((1, t)), jnp.zeros((1, t)))
    # the reference's empty rows see key 0 and take no cotangent
    skipped = mask.at[0, 1024:, :1024].set(False).at[0, empty, 0].set(True)
    for h, hkv in ((2, 1), (8, 2)):
        q, k, v = _qkv(jax.random.PRNGKey(3), t, h=h, hkv=hkv, d=8)
        scale = 8 ** -0.5
        o, lse = flash_attention(q, k, v, layout="bhtd", selection=sel,
                                 interpret=True)
        o_ref, lse_ref = _masked_attention(q, k, v, skipped, scale)
        assert not bool(jnp.any(o[:, :, empty]))
        full = jnp.ones(t, bool).at[empty].set(False)
        np.testing.assert_allclose(o[:, :, full], o_ref[:, :, full],
                                   atol=2e-5)
        np.testing.assert_allclose(lse[:, :, full], lse_ref[:, :, full],
                                   atol=2e-5)
        ct = jax.random.normal(jax.random.PRNGKey(1), o.shape)
        got = jax.grad(lambda *a: jnp.sum(ct * flash_attention(
            *a, layout="bhtd", selection=sel, interpret=True)[0]),
            argnums=(0, 1, 2))(q, k, v)
        ct = ct.at[:, :, empty].set(0.0)
        want = jax.grad(lambda *a: jnp.sum(ct * _masked_attention(
            *a, skipped, scale)[0]), argnums=(0, 1, 2))(q, k, v)
        assert not bool(jnp.any(got[0][:, :, empty]))
        np.testing.assert_allclose(got[0][:, :, full], want[0][:, :, full],
                                   atol=5e-5)
        for a, b in zip(got[1:], want[1:]):
            np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("t,topk", [(80, 100), (300, 40), (1100, 150),
                                    (1100, 2000)])
def test_the_bits_are_causal_and_inside_the_sequence(t, topk, interpret):
    """The flash kernels over a selection compare no positions: they trust
    that every selected pair is causal and that its key lies inside the
    sequence, in every row of the tiles, the padded ones included, and
    also where a row has fewer causal keys than the top (t + 1 <= topk)."""
    qi, ki, w = _indexer(t + topk, t, b=2)
    sel = ss.select(qi, ki, w, topk=topk, interpret=interpret)
    tile = ss.select_tile(t)
    t_pad = -(-t // tile) * tile
    bits = ss.words_to_mask(sel.words, t_pad, tile)
    q_idx, k_idx = jnp.arange(t_pad)[:, None], jnp.arange(t_pad)[None, :]
    allowed = (k_idx <= q_idx) & (k_idx < t)
    assert not bool(jnp.any(bits & ~allowed))
    assert bool(jnp.all(jnp.any(bits[:, :t], axis=-1)))  # and not empty


def _plain_kl(qi, ki, w, q, k, lse, mask, scale):
    """sum_t KL(p_t || softmax over S_t of I_t), p the heads' mean of
    softmax over S_t, stopped; dense, autodiff's to differentiate."""
    g = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, g, axis=1),
                   precision="highest") * scale
    p = lax.stop_gradient(jnp.mean(jnp.where(
        mask[:, None], jnp.exp(s - lse[..., None]), 0.0), axis=1))
    score = jnp.einsum("bqh,bhqk->bqk", w, jax.nn.relu(
        jnp.einsum("bhqd,bkd->bhqk", qi, ki, precision="highest")),
        precision="highest")
    log_q = jax.nn.log_softmax(jnp.where(mask, score, -jnp.inf), axis=-1)
    return jnp.sum(jnp.where(mask & (p > 0), p * (
        jnp.log(jnp.where(p > 0, p, 1.0)) - jnp.where(mask, log_q, 0.0)),
        0.0))


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("t,topk", [(80, 16), (300, 40)])
def test_the_indexer_loss_and_its_gradients_against_autodiff(t, topk,
                                                             interpret):
    """Value and the gradients of qI, kI and w (the kernel's, computed in
    the forward pass; the XLA path's, by autodiff of blocks) against
    autodiff of the plain dense KL; q, k and lse take no gradient."""
    keys = jax.random.split(jax.random.PRNGKey(t), 3)
    qi = jax.random.normal(keys[0], (1, HI, t, DI))
    ki = jax.random.normal(keys[1], (1, t, DI))
    w = jax.random.normal(keys[2], (1, t, HI)) * 0.1
    sel = ss.select(qi, ki, w, topk=topk)
    mask = ss.words_to_mask(sel.words, t, ss.select_tile(t))
    q, k, v = _qkv(jax.random.PRNGKey(t + 1), t)
    scale = 16 ** -0.5
    _, lse = flash_attention(q, k, v, layout="bhtd", selection=sel)
    loss, grads = jax.value_and_grad(
        lambda *a: ss.index_kl(*a, q, k, lse, sel, scale=scale,
                               interpret=interpret),
        argnums=(0, 1, 2))(qi, ki, w)
    want, want_grads = jax.value_and_grad(
        lambda *a: _plain_kl(*a, q, k, lse, mask, scale),
        argnums=(0, 1, 2))(qi, ki, w)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, atol=2e-5)
    dq = jax.grad(lambda q: ss.index_kl(qi, ki, w, q, k, lse, sel,
                                        scale=scale, interpret=interpret))(q)
    assert not bool(jnp.any(dq))


# sha256 (first 16 hex) of the jaxpr of flash_attention's forward and three
# gradients, in interpret mode, as the parent of the selection lowered it:
# (window, T, heads, KV heads, key width, value width)
_PARENT_JAXPRS = {
    (None, 2048, 4, 2, 128, 128): "477e929ff2f7ac50",
    (None, 1300, 4, 2, 128, 128): "3b647cc19e14a6b5",
    (512, 2048, 4, 2, 128, 128): "8e17a6632d3cb82c",
    (None, 2048, 4, 4, 192, 128): "f8a10771e57a0f68",
}


@pytest.mark.parametrize("shape", sorted(_PARENT_JAXPRS, key=str))
def test_calls_without_a_selection_lower_as_before(shape):
    """The kernels, grids and tiles of every call without a selection
    (causal, windowed, latent widths, a ragged length) are the jaxprs they
    were before the selection operand existed."""
    window, t, h, hkv, d, dv = shape
    q = jax.ShapeDtypeStruct((1, h, t, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, hkv, t, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, hkv, t, dv), jnp.bfloat16)
    f = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=window, interpret=True,
        layout="bhtd").astype(jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == \
        _PARENT_JAXPRS[shape]


# the same digest of every test-scale configuration's step gradient (remat
# on, 2 x 64 tokens), as the parent of the selection lowered it; the remat
# policy's address is struck from the text
_PARENT_MODELS = {
    "tiny": "38c48105defbeffe", "tiny_routed": "5ffd71e26030f9fa",
    "tiny_hybrid": "deceafa773c25c8a", "tiny_latent": "d3a4ddf832fa1774",
    "tiny_gated": "cf67baab74dfa1ff",
}


@pytest.mark.parametrize("name", sorted(_PARENT_MODELS))
def test_models_without_a_selection_lower_as_before(name):
    """A configuration with no indexer and no q/k norm builds the step it
    built before either existed: every cell's program is unchanged."""
    import dataclasses
    import re

    from mpi_operator_tpu.models import llama

    c = dataclasses.replace(getattr(llama, name)(), remat_layers=True)
    params = jax.eval_shape(lambda: llama.init(c, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)

    def loss(p, t):
        out = llama.loss_fn(c, p, {"tokens": t})
        return out[0] if isinstance(out, tuple) else out

    text = re.sub(r" at 0x[0-9a-f]+", "",
                  str(jax.make_jaxpr(jax.grad(loss))(params, tokens)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        _PARENT_MODELS[name]


def test_a_selection_builds_its_own_kernels_in_its_tile():
    """Over a selection the three kernels are the sparse ones, on the
    causal grid over KV heads (a step holds a KV head's query heads) in the
    selection's square tile, beside the selection's and the loss's
    kernels."""
    from tests.test_banded_attention import _pallas_calls

    t = 2048
    qi, ki, w = (jax.ShapeDtypeStruct(a.shape, jnp.float32)
                 for a in _indexer(0, t))
    q, k, v = (jax.ShapeDtypeStruct(a.shape, jnp.float32)
               for a in _qkv(jax.random.PRNGKey(0), t))

    def step(qi, ki, w, q, k, v):
        sel = ss.select(qi, ki, w, topk=64, interpret=True)
        o, lse = flash_attention(q, k, v, layout="bhtd", selection=sel,
                                 interpret=True)
        return jnp.sum(o) + ss.index_kl(qi, ki, w, q, k, lse, sel,
                                        scale=0.25, interpret=True)

    jaxpr = jax.make_jaxpr(jax.grad(step, argnums=(0, 1, 2, 3, 4, 5)))(
        qi, ki, w, q, k, v)
    calls = {name: (grid, tiles) for name, grid, tiles in
             _pallas_calls(jaxpr.jaxpr)}
    assert sorted(calls) == ["sparse_flash_dkv", "sparse_flash_dq",
                             "sparse_flash_fwd", "sparse_index_kl",
                             "sparse_select"]
    for name in ("sparse_flash_fwd", "sparse_flash_dq", "sparse_flash_dkv"):
        assert calls[name][0] == (1, 2, 2, 2)  # (b, h_kv, tiles, tiles)
    assert calls["sparse_select"][0] == (1, 8)  # 256 queries a step
    assert calls["sparse_index_kl"][0] == (1, 8, 4)  # 256 x 512 tiles


@pytest.mark.parametrize("t,tile", [(32, 256), (256, 256), (300, 512),
                                    (1000, 1024), (1024, 1024),
                                    (16384, 1024)])
def test_the_tile_a_selection_takes(t, tile):
    assert ss.select_tile(t) == tile


def test_gradients_keep_their_dtypes_in_bf16():
    """The kernels' custom backward passes hand back cotangents in their
    operands' dtypes: the model's bf16 projections take them as they are."""
    t = 300
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    qi = jax.random.normal(keys[0], (1, HI, t, DI), jnp.bfloat16)
    ki = jax.random.normal(keys[1], (1, t, DI), jnp.bfloat16)
    w = jax.random.normal(keys[2], (1, t, HI))
    q, k, v = (a.astype(jnp.bfloat16)
               for a in _qkv(jax.random.PRNGKey(1), t))

    def step(qi, ki, w, q, k, v):
        sel = ss.select(qi, ki, w, topk=40, interpret=True)
        o, lse = flash_attention(q, k, v, layout="bhtd", selection=sel,
                                 interpret=True)
        return jnp.sum(o.astype(jnp.float32)) + ss.index_kl(
            qi * 1, ki * 1, w, q, k, lse, sel, scale=0.25, interpret=True)

    grads = jax.grad(step, argnums=range(6))(qi, ki, w, q, k, v)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 2 + [jnp.float32] + [
        jnp.bfloat16] * 3


@pytest.mark.parametrize("change", [
    {"layer_kinds": ("window", "full"), "window": 8, "n_layers": 2},
    {"n_dense_layers": 1, "n_layers": 3},
    {"index_head_dim": 15},
    {"index_topk": 0},
])
def test_the_config_refuses_a_selection_it_does_not_compute(change):
    """An indexer is every full layer's, behind no leading dense pairs,
    with heads of an even width (they rotate) and a top of at least one."""
    import dataclasses

    from mpi_operator_tpu.models import llama

    base = llama.Config(
        vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, d_ff=64, qk_norm=True, index_heads=2, index_head_dim=8,
        index_topk=4, n_experts=4, experts_per_token=2, d_expert=16)
    assert base.indexed
    with pytest.raises(ValueError):
        dataclasses.replace(base, **change)
