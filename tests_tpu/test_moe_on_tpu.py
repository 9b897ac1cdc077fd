"""Compiled MoE (switch-routed expert FFN) numerics ON the TPU chip.

tests/test_pipeline_moe.py exercises routing/dispatch/EP on the virtual CPU
mesh; this is the hardware half: the scatter-into-capacity-buffers dispatch,
the vmapped expert FFNs, and their backward must compile and run on the real
chip, with the jitted program checked against the op-by-op execution of the
same math (jax.disable_jit — an independent lowering of every op)."""

import jax
import jax.numpy as jnp
import numpy as np

from mpi_operator_tpu.parallel import moe


def _setup(key, b=4, t=256, d=128, d_ff=512, e=8):
    cfg = moe.MoEConfig(d_model=d, d_ff=d_ff, n_experts=e)
    params = moe.init(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, t, d), jnp.float32)
    return cfg, params, x


def test_compiled_forward_matches_op_by_op():
    cfg, params, x = _setup(jax.random.PRNGKey(0))
    y_jit, aux_jit = jax.jit(
        lambda p, x: moe.apply(cfg, p, x)
    )(params, x)
    with jax.disable_jit():
        y_ref, aux_ref = moe.apply(cfg, params, x)
    np.testing.assert_allclose(
        np.asarray(y_jit), np.asarray(y_ref), atol=5e-2, rtol=5e-2
    )
    np.testing.assert_allclose(float(aux_jit), float(aux_ref), rtol=1e-3)
    # routing actually spread load: aux loss near its minimum of 1.0 means
    # the (random) router used many experts, not one
    assert 0.9 < float(aux_jit) < 3.0


def test_compiled_backward_runs_and_is_finite():
    cfg, params, x = _setup(jax.random.PRNGKey(2))

    @jax.jit
    def loss(p, x):
        y, aux = moe.apply(cfg, p, x)
        return jnp.mean(y * y) + 0.01 * aux

    g = jax.grad(loss)(params, x)
    leaves = jax.tree_util.tree_leaves(g)
    assert leaves and all(bool(jnp.all(jnp.isfinite(l))) for l in leaves)
    # router receives gradient through the gate scaling
    assert float(jnp.max(jnp.abs(g["router"]["w"]))) > 0.0
