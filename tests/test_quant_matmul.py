"""Quantized matmul numerics (kernels.quant_matmul, ISSUE 16 tentpole d).

The fast-tier tests run the quantizer eagerly on tiny shapes (no model
compile); the llama FFN integration ride the slow tier with the other
model compiles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.kernels.quant_matmul import (
    quant_error,
    quant_matmul,
)


def _xw(key, m=32, k=64, n=48, dtype=jnp.float32):
    kx, kw = jax.random.split(key)
    return (
        jax.random.normal(kx, (m, k), dtype),
        jax.random.normal(kw, (k, n), dtype),
    )


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_forward_tracks_exact_product(precision):
    x, w = _xw(jax.random.PRNGKey(0))
    # per-row/per-column absmax on gaussian data: relative Frobenius error
    # sits well under 2% for int8 (7 effective bits) and ~4% for e4m3
    err = quant_error(x, w, precision=precision)
    assert err < (0.02 if precision == "int8" else 0.06), err


def test_bf16_precision_is_identity():
    x, w = _xw(jax.random.PRNGKey(1))
    np.testing.assert_array_equal(
        np.asarray(quant_matmul(x, w, precision="bf16")), np.asarray(x @ w)
    )


def test_rejects_unknown_precision():
    x, w = _xw(jax.random.PRNGKey(2), m=2, k=4, n=2)
    with pytest.raises(ValueError, match="precision"):
        quant_matmul(x, w, precision="int4")


def test_leading_dims_flattened_and_restored():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 5, 16))
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 8))
    out = quant_matmul(x, w, precision="int8")
    assert out.shape == (2, 5, 8)
    # batched result must equal the 2D kernel applied row-block-wise
    flat = quant_matmul(x.reshape(10, 16), w, precision="int8")
    np.testing.assert_array_equal(np.asarray(out).reshape(10, 8), np.asarray(flat))


def test_scale_invariance_per_row():
    """Per-row activation scales: scaling ONE row of x must not disturb the
    quantization error of the others (a per-tensor scheme would)."""
    x, w = _xw(jax.random.PRNGKey(4))
    exact = np.asarray(x @ w)
    base = np.asarray(quant_matmul(x, w, precision="int8"))
    x_hot = x.at[0].mul(1000.0)
    hot = np.asarray(quant_matmul(x_hot, w, precision="int8"))
    np.testing.assert_allclose(hot[1:], base[1:], atol=1e-6)
    want = exact[0] * 1000.0
    rel = np.linalg.norm(hot[0] - want) / np.linalg.norm(want)
    assert rel < 0.02, rel


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_backward_is_full_precision_straight_through(precision):
    """The custom_vjp backward must be the EXACT full-precision matmul
    gradients — not the derivative of the quantized forward. A linear
    readout keeps the cotangent identical on both paths, so the gradients
    must agree to float rounding."""
    x, w = _xw(jax.random.PRNGKey(5), m=8, k=16, n=8)
    c = jax.random.normal(jax.random.PRNGKey(6), (8, 8))

    def f_quant(x, w):
        return jnp.sum(quant_matmul(x, w, precision=precision) * c)

    def f_exact(x, w):
        return jnp.sum((x @ w) * c)

    gx_q, gw_q = jax.grad(f_quant, argnums=(0, 1))(x, w)
    gx_e, gw_e = jax.grad(f_exact, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_q), np.asarray(gx_e), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw_q), np.asarray(gw_e), atol=1e-5)


def test_zero_input_quantizes_to_zero():
    x = jnp.zeros((4, 8))
    w = jnp.ones((8, 3))
    out = quant_matmul(x, w, precision="int8")
    assert not np.isnan(np.asarray(out)).any()
    np.testing.assert_array_equal(np.asarray(out), np.zeros((4, 3)))


def test_jit_compatible():
    x, w = _xw(jax.random.PRNGKey(6), m=4, k=8, n=4)
    eager = quant_matmul(x, w, precision="int8")
    jitted = jax.jit(
        lambda x, w: quant_matmul(x, w, precision="int8")
    )(x, w)
    np.testing.assert_allclose(np.asarray(eager), np.asarray(jitted), atol=1e-6)


# ---------- llama integration (slow tier: model compiles) ----------


@pytest.mark.slow
@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_llama_ffn_quant_loss_tracks_bf16(precision):
    import dataclasses

    from mpi_operator_tpu.models import llama

    cfg = llama.tiny()
    qcfg = dataclasses.replace(cfg, matmul_precision=precision)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": tokens}
    base = float(llama.loss_fn(cfg, params, batch))
    quant = float(llama.loss_fn(qcfg, params, batch))
    assert abs(quant - base) / base < 0.05, (base, quant)


@pytest.mark.slow
def test_llama_ffn_quant_trains():
    import dataclasses

    from mpi_operator_tpu.models import llama

    cfg = dataclasses.replace(llama.tiny(), matmul_precision="int8")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    loss = lambda p: llama.loss_fn(cfg, p, {"tokens": tokens})  # noqa: E731
    grads = jax.grad(loss)(params)
    # gradients reach the quantized FFN weights via the straight-through vjp
    g = grads["layers"]["w_gate"]["w"]
    assert float(jnp.max(jnp.abs(g))) > 0.0
    lr = 0.5
    stepped = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    assert float(loss(stepped)) < float(loss(params))


def test_llama_config_rejects_bad_precision():
    import dataclasses

    from mpi_operator_tpu.models import llama

    with pytest.raises(ValueError, match="matmul_precision"):
        dataclasses.replace(llama.tiny(), matmul_precision="int4")


@pytest.mark.parametrize("grouped", [False, True])
def test_fp8_is_rounded_by_arithmetic_the_compiler_keeps(grouped):
    """A chip with no fp8 product widens the operands again, and XLA drops
    a narrowing convert that a widening one follows: on a v5e the cast alone
    read as bf16 on every number (PERF.md section 7, row 18f). The rounding
    is a ``reduce_precision`` to e4m3's three mantissa bits before the cast,
    which the cast then leaves as it is."""
    from mpi_operator_tpu.kernels.quant_matmul import quant_ragged_dot

    x, w = _xw(jax.random.PRNGKey(4))
    if grouped:
        sizes = jnp.array([20, 12], jnp.int32)
        fn = lambda x, w: quant_ragged_dot(  # noqa: E731
            x, jnp.stack([w, -w]), sizes, precision="fp8")
    else:
        fn = lambda x, w: quant_matmul(x, w, precision="fp8")  # noqa: E731
    text = jax.jit(fn).lower(x, w).as_text()
    assert text.count("reduce_precision") == 2, text  # both operands
    assert text.count("format = e5m3") == 2, text
    scaled = x / (jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0)
    rounded = jax.lax.reduce_precision(scaled, 5, 3)
    np.testing.assert_array_equal(
        np.asarray(rounded),
        np.asarray(scaled.astype(jnp.float8_e4m3fn).astype(jnp.float32)))
