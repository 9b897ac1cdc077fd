"""Compiled Pallas flash-attention numerics ON the TPU chip.

tests/test_flash_attention.py validates the kernel bodies under the Pallas
interpreter; this file is the hardware half of VERDICT's acceptance bar —
the kernel must have executed as a *compiled* kernel with outputs verified
against an independent XLA lowering (the chunked reference). Nothing else
makes this check on the chip: the benchmark compares whole steps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.kernels.flash_attention import (
    chunked_reference,
    flash_attention,
)


def _qkv(key, b=2, t=1024, h=8, hkv=4, d=128, dtype=jnp.bfloat16):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, t, h, d), dtype),
        jax.random.normal(kk, (b, t, hkv, d), dtype),
        jax.random.normal(kv, (b, t, hkv, d), dtype),
    )


def _ref(q, k, v, causal=True):
    return chunked_reference(q, k, v, causal=causal)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_compiled_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    got = flash_attention(q, k, v, causal=causal)  # auto → compiled kernel
    want = _ref(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2, rtol=3e-2,
    )


def test_gradients_compiled_match_reference():
    q, k, v = _qkv(jax.random.PRNGKey(1))

    def f_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=True).astype(jnp.float32) ** 2)

    def f_ref(q_, k_, v_):
        return jnp.sum(_ref(q_, k_, v_).astype(jnp.float32) ** 2)

    g1 = jax.jit(jax.grad(f_flash, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        scale = max(1.0, float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale,
            np.asarray(b, np.float32) / scale,
            atol=5e-2, rtol=5e-2,
        )


def test_llama_bench_shape_fwd_dq_dkv_match_reference():
    """The shape the full-width job runs per chip (llama.bench_single_chip
    at batch 8 x 2048): GQA 16/4, head dim 128, causal — the forward, dq
    and dk/dv kernels at their 1024 x 1024 tiles against the reference."""
    q, k, v = _qkv(jax.random.PRNGKey(4), b=8, t=2048, h=16, hkv=4, d=128)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(_ref(q, k, v), np.float32),
        atol=3e-2, rtol=3e-2,
    )

    def sq(fn):
        return lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_).astype(jnp.float32) ** 2)

    g1 = jax.jit(jax.grad(
        sq(lambda *a: flash_attention(*a, causal=True)), argnums=(0, 1, 2)
    ))(q, k, v)
    g2 = jax.jit(jax.grad(sq(_ref), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        scale = max(1.0, float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale,
            np.asarray(b, np.float32) / scale,
            atol=5e-2, rtol=5e-2,
        )


def test_uneven_tail_compiled():
    # t not a block multiple exercises the padded-tail masking on hardware
    q, k, v = _qkv(jax.random.PRNGKey(2), t=640 + 96)
    got = flash_attention(q, k, v, causal=True)
    want = _ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2, rtol=3e-2,
    )


def test_long_context_16k_trains():
    """The streamed kernels' raison d'être: fwd+bwd compile and run at a
    sequence length (16k) that the VMEM-resident kernel generation could
    not reach on this chip."""
    t = 16384
    q, k, v = _qkv(jax.random.PRNGKey(3), b=1, t=t, h=8, hkv=4, d=128)

    def f(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=True).astype(jnp.float32))

    grads = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


def test_banded_kernels_at_the_mellum_shape_match_reference():
    """The shape ``mellum2.steady-8k`` runs on a chip: b 2, 32 / 4 heads of
    128, T 8192, window 1024 — the forward and the three gradients of the
    banded kernels at their 1024 x 1024 tiles against the chunked
    reference with the band as a mask on positions."""
    q, k, v = _qkv(jax.random.PRNGKey(7), b=2, t=8192, h=32, hkv=4, d=128)
    window = 1024
    got = jax.jit(lambda *a: flash_attention(*a, causal=True, window=window))(
        q, k, v)
    want = jax.jit(lambda *a: chunked_reference(*a, window=window))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2, rtol=3e-2,
    )
    # a band is not the causal mask: the two differ past the window
    full = jax.jit(lambda *a: flash_attention(*a, causal=True))(q, k, v)
    assert float(jnp.max(jnp.abs(
        (full - got).astype(jnp.float32)[:, window:]))) > 0.1

    def sq(fn):
        return lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_).astype(jnp.float32) ** 2)

    g1 = jax.jit(jax.grad(sq(lambda *a: flash_attention(
        *a, causal=True, window=window)), argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(sq(lambda *a: chunked_reference(
        *a, window=window)), argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g1, g2):
        scale = max(1.0, float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale,
            np.asarray(b, np.float32) / scale,
            atol=5e-2, rtol=5e-2, err_msg=name,
        )


def test_latent_kernels_at_the_kanana_shape_match_reference():
    """The shape ``kanana2.steady-8k`` runs on a chip: b 2, 32 heads each
    with keys of its own, T 8192, keys and queries 192 wide (one and a half
    lane tiles: a whole-width block), values, the output and its cotangent
    128 — the forward and the three gradients of the compiled kernels at
    their 1024 x 1024 tiles against the chunked form in float32 at
    ``highest`` on the same bf16 numbers."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(kq, (2, 8192, 32, 192), jnp.bfloat16)
    k = jax.random.normal(kk, (2, 8192, 32, 192), jnp.bfloat16)
    v = jax.random.normal(kv, (2, 8192, 32, 128), jnp.bfloat16)
    f32 = lambda *a: [x.astype(jnp.float32) for x in a]

    def ref(q_, k_, v_):
        with jax.default_matmul_precision("highest"):
            return chunked_reference(*f32(q_, k_, v_), causal=True)

    got = jax.jit(lambda *a: flash_attention(*a, causal=True))(q, k, v)
    assert got.shape == (2, 8192, 32, 128) and got.dtype == jnp.bfloat16
    want = jax.jit(ref)(q, k, v)
    # the output is rounded to bf16 (2 ** -9 of values up to ~4) and the
    # probabilities are rounded to bf16 before they meet the values: 3e-2,
    # the equal-size tests' tolerance, is ten times that
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=3e-2, rtol=3e-2)

    def sq(fn):
        return lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_).astype(jnp.float32) ** 2)

    g1 = jax.jit(jax.grad(sq(lambda *a: flash_attention(*a, causal=True)),
                          argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(sq(ref), argnums=(0, 1, 2)))(q, k, v)
    for name, a, b, width in zip(("dq", "dk", "dv"), g1, g2,
                                 (192, 192, 128)):
        assert a.shape[-1] == width and a.dtype == jnp.bfloat16, name
        # against the leaf's largest element, as the other shapes' tests:
        # ds and p are rounded to bf16 before dq, dk and dv's products sum
        # them over up to 8192 positions, and the result is rounded again
        scale = max(1.0, float(jnp.max(jnp.abs(b))))
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale, np.asarray(b) / scale,
            atol=5e-2, rtol=5e-2, err_msg=name)
        # and not by luck of a loose tolerance: the two agree in the mean
        # to a hundredth of the leaf's root mean square
        gap = float(jnp.sqrt(jnp.mean((a.astype(jnp.float32) - b) ** 2)))
        assert gap < 2e-2 * float(jnp.sqrt(jnp.mean(b ** 2))), name
