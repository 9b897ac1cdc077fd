"""Pallas flash-attention kernel numerics.

Every kernel test pins ``interpret=True`` so CPU runs exercise the actual
kernel body (auto mode on non-TPU backends falls back to the XLA chunked
reference, which would compare the reference against itself). The cases by
head size (values of another size than keys and queries: latent
attention's 192 / 128, and a pair of sizes that is no lane tile's multiple)
ride the fast tier; the rest is the slow one's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.kernels import flash_attention
from mpi_operator_tpu.kernels.flash_attention import chunked_reference
from mpi_operator_tpu.parallel.ring_attention import dense_attention

# slow tier: XLA compiles / subprocess gangs (see pytest.ini)
slow = pytest.mark.slow

# (keys' and queries' head size, values'): equal, unlike and no multiple of
# a lane tile, latent attention's published pair (at a short length)
HEAD_SIZES = [(16, 16), (24, 16), (192, 128)]


def _qkv(key, b=2, t=128, h=4, hkv=None, d=16, dtype=jnp.float32, dv=None):
    hkv = h if hkv is None else hkv
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, t, h, d), dtype),
        jax.random.normal(kk, (b, t, hkv, d), dtype),
        jax.random.normal(kv, (b, t, hkv, d if dv is None else dv), dtype),
    )


@pytest.mark.parametrize("d,dv", HEAD_SIZES)
@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense(causal, d, dv):
    q, k, v = _qkv(jax.random.PRNGKey(0), t=96, d=d, dv=dv)
    want = dense_attention(q, k, v, causal=causal, scale=d ** -0.5)
    assert want.shape == q.shape[:3] + (dv,)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    if causal:  # the chunked XLA form, what runs off the TPU
        np.testing.assert_allclose(
            chunked_reference(q, k, v, block_q=32), want, atol=2e-5, rtol=2e-5)


@slow
def test_gqa():
    q, k, v = _qkv(jax.random.PRNGKey(1), h=8, hkv=2)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    want = dense_attention(q, k, v, causal=True, scale=q.shape[-1] ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@slow
def test_uneven_blocks():
    # t not divisible by block sizes exercises the tail tiles
    q, k, v = _qkv(jax.random.PRNGKey(2), t=96)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    want = dense_attention(q, k, v, causal=True, scale=q.shape[-1] ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@slow
def test_bfloat16():
    q, k, v = _qkv(jax.random.PRNGKey(3), dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = dense_attention(q, k, v, causal=True, scale=q.shape[-1] ** -0.5)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2, rtol=3e-2
    )


@pytest.mark.parametrize("d,dv", HEAD_SIZES)
@pytest.mark.parametrize("form", ["kernels", "chunked"])
def test_gradients_match_dense(form, d, dv):
    """dq, dk (the keys' size) and dv (the values') of the three kernels in
    interpret mode, and of the chunked XLA form, against the dense oracle;
    grouped heads, a ragged last tile."""
    q, k, v = _qkv(jax.random.PRNGKey(4), t=80, h=4, hkv=2, d=d, dv=dv)
    cot = jax.random.normal(jax.random.PRNGKey(5), q.shape[:3] + (dv,))
    fn = {"kernels": lambda *a: flash_attention(
              *a, causal=True, block_q=32, block_k=32, interpret=True),
          "chunked": lambda *a: chunked_reference(*a, block_q=32)}[form]

    def through(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) * cot), argnums=(0, 1, 2))(
            q, k, v)

    g1 = through(fn)
    g2 = through(lambda *a: dense_attention(*a, causal=True, scale=d ** -0.5))
    for name, a, b in zip("qkv", g1, g2):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4, err_msg=f"d{name}")


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_gradients_gqa_uneven(causal):
    # GQA group-summed dk/dv + partial tail tiles through the backward kernels
    q, k, v = _qkv(jax.random.PRNGKey(7), t=96, h=8, hkv=2)

    def f_flash(q_, k_, v_):
        return jnp.sum(
            flash_attention(
                q_, k_, v_, causal=causal, block_q=64, block_k=64, interpret=True
            )
            ** 2
        )

    def f_dense(q_, k_, v_):
        return jnp.sum(
            dense_attention(q_, k_, v_, causal=causal, scale=q.shape[-1] ** -0.5)
            ** 2
        )

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4)


@slow
def test_jit_compiles():
    q, k, v = _qkv(jax.random.PRNGKey(5), t=64)
    f = jax.jit(lambda *a: flash_attention(*a, causal=True, block_q=32, block_k=32, interpret=True))
    out = f(q, k, v)
    assert out.shape == q.shape


@slow
def test_auto_mode_falls_back_off_tpu():
    # interpret=None on a non-TPU backend must use the XLA chunked reference
    # (exact vs dense), never the interpreted kernel.
    if jax.default_backend() == "tpu":
        pytest.skip("auto mode uses the real kernel on TPU")
    q, k, v = _qkv(jax.random.PRNGKey(6))
    got = flash_attention(q, k, v, causal=True)
    want = dense_attention(q, k, v, causal=True, scale=q.shape[-1] ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@slow
def test_bhtd_layout_matches_bthd():
    # heads-major inputs skip the wrapper transposes but must be numerically
    # identical to the model-layout path
    q, k, v = _qkv(jax.random.PRNGKey(8), h=8, hkv=2)
    want = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    got = flash_attention(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=True, block_q=32, block_k=32, interpret=True, layout="bhtd",
    )
    np.testing.assert_allclose(
        np.asarray(got.transpose(0, 2, 1, 3)), np.asarray(want), atol=2e-5, rtol=2e-5
    )


@slow
@pytest.mark.parametrize("d,dv", [(16, 16), (24, 16)])
def test_bhtd_layout_sharded_mesh_with_tensor_axis(d, dv):
    # the heads-major PartitionSpec puts the head axis in position 1 — a
    # wrong spec would shard the sequence dim and break GQA numerics; the
    # values' own head size goes through the same specs
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("data", "tensor"))
    q, k, v = _qkv(jax.random.PRNGKey(9), b=2, h=8, hkv=4, d=d, dv=dv)
    want = dense_attention(q, k, v, causal=True, scale=q.shape[-1] ** -0.5)
    got = flash_attention(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=True, block_q=32, block_k=32, interpret=True,
        mesh=mesh, layout="bhtd",
    )
    np.testing.assert_allclose(
        np.asarray(got.transpose(0, 2, 1, 3)), np.asarray(want), atol=2e-5, rtol=2e-5
    )
