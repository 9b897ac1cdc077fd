"""Pipeline (PP) tests.

The strategy is absent from the reference (SURVEY.md §2.5); these tests pin
its correctness: the SPMD pipeline must equal sequential layer application.
(The expert layer's tests are tests/test_moe.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.parallel.pipeline import run_pipeline
from mpi_operator_tpu.runtime import MeshPlan, build_mesh
from mpi_operator_tpu.runtime.topology import AXIS_DATA, AXIS_PIPE

# slow tier: XLA compiles / subprocess gangs (see pytest.ini)
pytestmark = pytest.mark.slow


# ---------- pipeline ----------


def _stage_fn(p, x):
    # one "layer": affine + nonlinearity
    return jnp.tanh(x @ p["w"] + p["b"])


def _stacked_params(key, n_layers, d):
    ks = jax.random.split(key, n_layers)
    return {
        "w": jnp.stack([jax.random.normal(k, (d, d)) * 0.5 for k in ks]),
        "b": jnp.zeros((n_layers, d)),
    }


def _sequential(params, x, n_layers):
    for i in range(n_layers):
        x = _stage_fn(jax.tree.map(lambda a: a[i], params), x)
    return x


@pytest.mark.parametrize("n_micro", [4, 8])
def test_pipeline_matches_sequential(n_micro):
    mesh = build_mesh(MeshPlan(axes={AXIS_DATA: 2, AXIS_PIPE: 4}))
    n_layers, d, b = 8, 16, 16
    params = _stacked_params(jax.random.PRNGKey(0), n_layers, d)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, d))
    want = _sequential(params, x, n_layers)
    got = jax.jit(
        lambda p, xx: run_pipeline(
            _stage_fn, p, xx, mesh, n_microbatches=n_micro
        )
    )(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_pipeline_no_pipe_axis_falls_back():
    mesh = build_mesh(MeshPlan(axes={AXIS_DATA: 8}))
    n_layers, d = 4, 8
    params = _stacked_params(jax.random.PRNGKey(0), n_layers, d)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, d))
    want = _sequential(params, x, n_layers)
    got = run_pipeline(_stage_fn, params, x, mesh, n_microbatches=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
