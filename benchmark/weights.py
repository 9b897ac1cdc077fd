"""Seeded weights for any model whose reference states ``param_shapes``:
name -> (shape, std). One normal draw a leaf, float32, keyed by the seed and
the leaf's place in the sorted names, so the program's worker and the plain
reference draw the same numbers without either taking the other's."""

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def draw_leaf(shapes, name, key):
    shape, std = shapes[name]
    if std is None:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, sorted(shapes).index(name))
    return jax.random.normal(k, shape, jnp.float32) * std


def draw(shapes, key):
    return {name: draw_leaf(shapes, name, key) for name in shapes}
