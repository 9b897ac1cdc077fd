"""Collective layer numerical tests on the 8-device CPU mesh.

The reference never tests its collective fabric (it's external MPI; SURVEY.md
§4 notes workload-level correctness is untested in-repo). This suite is the
upgrade: every verb is checked numerically against its MPI semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from mpi_operator_tpu.parallel import collectives as c

# slow tier: XLA compiles / subprocess gangs (see pytest.ini)
pytestmark = pytest.mark.slow

AXIS = "data"


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()).reshape(8), (AXIS,))


def smap(fn, mesh, in_specs=P(AXIS), out_specs=P(AXIS)):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs))


def test_psum_matches_allreduce(mesh):
    x = jnp.arange(8.0)
    out = smap(lambda v: c.psum(v, AXIS), mesh)(x)
    np.testing.assert_allclose(out, np.full(8, 28.0))


def test_pmean(mesh):
    x = jnp.arange(8.0)
    out = smap(lambda v: c.pmean(v, AXIS), mesh)(x)
    np.testing.assert_allclose(out, np.full(8, 3.5))


def test_reduce_to_root_only_root_holds_sum(mesh):
    x = jnp.arange(8.0)
    out = smap(lambda v: c.reduce_to_root(v, AXIS), mesh)(x)
    np.testing.assert_allclose(out, [28.0, 0, 0, 0, 0, 0, 0, 0])


def test_broadcast_root(mesh):
    x = jnp.arange(8.0) + 3.0
    out = smap(lambda v: c.broadcast_root(v, AXIS), mesh)(x)
    np.testing.assert_allclose(out, np.full(8, 3.0))


def test_all_gather_concatenates_shards(mesh):
    x = jnp.arange(8.0)
    out = smap(
        lambda v: c.all_gather(v, AXIS, tiled=True), mesh, out_specs=P(AXIS)
    )(x)
    # every shard now holds the full vector; global result tiles it 8x
    assert out.shape == (64,)
    np.testing.assert_allclose(out[:8], np.arange(8.0))


def test_reduce_scatter_is_allreduce_shard(mesh):
    # each device contributes the same 8-vector; reduce_scatter leaves
    # device i with sum over devices of shard i = 8 * x[i]
    x = jnp.tile(jnp.arange(8.0), (8,))
    out = smap(lambda v: c.reduce_scatter(v, AXIS), mesh)(x)
    np.testing.assert_allclose(out, np.arange(8.0) * 8)


def test_ring_shift_rotates_shards(mesh):
    x = jnp.arange(8.0)
    out = smap(lambda v: c.ring_shift(v, AXIS, shift=1), mesh)(x)
    np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))
    back = smap(lambda v: c.ring_shift(v, AXIS, shift=-1), mesh)(x)
    np.testing.assert_allclose(back, np.roll(np.arange(8.0), -1))


def test_all_to_all_transposes_ownership(mesh):
    # device i holds row i of an 8x8 matrix; all_to_all gives device i col i
    m = jnp.arange(64.0).reshape(8, 8)
    out = smap(
        lambda v: c.all_to_all(v, AXIS, split_axis=1, concat_axis=1),
        mesh,
        in_specs=P(AXIS, None),
        out_specs=P(AXIS, None),
    )(m)
    np.testing.assert_allclose(out, m.T)


def test_axis_index_and_size(mesh):
    out = smap(
        lambda v: v * 0 + c.axis_index(AXIS) + 10 * c.axis_size(AXIS), mesh
    )(jnp.zeros(8))
    np.testing.assert_allclose(out, 80 + np.arange(8.0))


def test_axis_size_static_is_python_int(mesh):
    sizes = []

    def f(v):
        sizes.append(c.axis_size_static(AXIS))
        return v

    smap(f, mesh)(jnp.zeros(8))
    assert sizes == [8]
    assert isinstance(sizes[0], int)
