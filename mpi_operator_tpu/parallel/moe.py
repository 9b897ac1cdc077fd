"""Routed (mixture-of-experts) feed-forward: a share of the experts, dropless.

The layer is told which experts it holds: ``n_experts`` published ones that
the router scores, of which the ``w_gate.shape[0]`` from ``first_expert`` on
live here. It routes every token over all published experts (softmax in
float32, the ``experts_per_token`` largest, their weights divided by their
sum), and computes its own experts' part of the result:

    y[t] = sum over the chosen experts e held here of
           w[t, e] * W_down[e] . (silu(W_gate[e] . x[t]) * (W_up[e] . x[t]))

What the absent experts would add is left out; the partial results of all
shares add up to the whole layer (tests/test_moe.py). No assignment to a
held expert is ever dropped: shapes are static with room for every
assignment (tokens x experts_per_token rows), whatever the imbalance.

How: the assignments are sorted by held expert (those to absent experts
last), the tokens' rows gathered in that order, the three products made as
grouped products over the held groups (``kernels.grouped_matmul``: on a TPU
the repo's own Pallas kernels ``moe_gmm`` / ``moe_gmm_dx`` / ``moe_gmm_dw``,
elsewhere ``lax.ragged_dot``; rows past the last group are never
touched), and the rows gathered back to their tokens with
their weights. Both gathers have hand-written transposes that are gathers
too: the sort is a permutation, so no scatter-add is ever needed.

With a mesh that has an ``expert`` axis each member holds its share of
``w_gate`` / ``w_up`` / ``w_down`` and the partial results are summed over
that axis; tokens stay where their batch axes put them. On one chip the
layer runs without that exchange, and nothing stands in for absent chips.

Scopes in the device trace: ``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``. Counters, as scalars of the step (no
sync): ``moe.assignments_held``, ``moe.load_max_over_mean``,
``moe.assignments_dropped`` (0, computed and not assumed).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mpi_operator_tpu.kernels.grouped_matmul import grouped_matmul
from mpi_operator_tpu.kernels.quant_matmul import quant_ragged_dot
from mpi_operator_tpu.runtime.topology import AXIS_DATA, AXIS_EXPERT, AXIS_FSDP

Params = Dict[str, Any]

ASSIGNMENTS_HELD = "moe.assignments_held"
LOAD_MAX_OVER_MEAN = "moe.load_max_over_mean"
ASSIGNMENTS_DROPPED = "moe.assignments_dropped"


def init(key, *, d_model: int, d_expert: int, n_experts: int,
         n_held: int) -> Params:
    """One layer's weights: the router over all published experts, the
    three matrices of the ``n_held`` experts held here. Projections std
    fan_in**-0.5, as the dense feed-forward's."""
    kr, kg, ku, kd = jax.random.split(key, 4)
    normal = lambda k, shape, fan_in: (
        jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5)
    return {
        "router": {"w": normal(kr, (d_model, n_experts), d_model)},
        "w_gate": {"w": normal(kg, (n_held, d_model, d_expert), d_model)},
        "w_up": {"w": normal(ku, (n_held, d_model, d_expert), d_model)},
        "w_down": {"w": normal(kd, (n_held, d_expert, d_model), d_expert)},
    }


def logical_axes() -> Params:
    return {
        "router": {"w": ("embed", None)},
        "w_gate": {"w": ("expert", "embed", "mlp")},
        "w_up": {"w": ("expert", "embed", "mlp")},
        "w_down": {"w": ("expert", "mlp", "embed")},
    }


def route(x32, router_w, experts_per_token: int):
    """(weights [N, k] float32 summing to 1, experts [N, k] int32): softmax
    over every published expert in float32, the k largest, renormalised.
    The product is made at ``highest`` precision: a default float32 product
    on a TPU rounds its inputs to bf16, and the k-th and (k+1)-th scores of
    some token always lie within that rounding."""
    logits = jnp.matmul(x32.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    top_w, top_e = lax.top_k(jax.nn.softmax(logits, axis=-1),
                             experts_per_token)
    return top_w / jnp.sum(top_w, axis=-1, keepdims=True), top_e


# -- the two gathers, each with a gather for its transpose --------------------
#
# ``pos[t, j]`` is the row of token t's j-th assignment in the sorted order,
# ``row_token[r]`` the token of row r: one permutation and its inverse. Rows
# of assignments to absent experts sort last; the grouped products never
# touch them, so what they hold is undefined and every read of them is
# masked with ``held`` (a select, not a product: it may be NaN).

@jax.custom_vjp
def _dispatch(x, row_token, pos, held):
    """xs[r] = x[row_token[r]]."""
    return jnp.take(x, row_token, axis=0)


def _dispatch_fwd(x, row_token, pos, held):
    return _dispatch(x, row_token, pos, held), (pos, held)


def _dispatch_bwd(res, d_xs):
    pos, held = res
    rows = jnp.take(d_xs, pos, axis=0)  # [N, k, D]
    d_x = jnp.sum(jnp.where(held[..., None], rows, 0).astype(jnp.float32),
                  axis=1)
    return d_x.astype(d_xs.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, weights, row_token, row_slot, pos, held):
    """y[t] = sum_j weights[t, j] * ys[pos[t, j]] over the held j."""
    rows = jnp.take(ys, pos, axis=0)  # [N, k, D]
    rows = jnp.where(held[..., None], rows, 0).astype(jnp.float32)
    return jnp.sum(rows * weights[..., None], axis=1).astype(ys.dtype)


def _combine_fwd(ys, weights, row_token, row_slot, pos, held):
    return (_combine(ys, weights, row_token, row_slot, pos, held),
            (ys, weights, row_token, row_slot, pos, held))


def _combine_bwd(res, d_y):
    ys, weights, row_token, row_slot, pos, held = res
    # in row order: one gather of d_y serves both cotangents
    d_rows = jnp.take(d_y, row_token, axis=0).astype(jnp.float32)  # [R, D]
    w_rows = jnp.where(held, weights, 0.0).reshape(-1)[row_slot]  # [R]
    d_ys = (d_rows * w_rows[:, None]).astype(ys.dtype)
    d_w_rows = jnp.sum(d_rows * ys.astype(jnp.float32), axis=-1)  # [R]
    d_w = jnp.where(held, jnp.take(d_w_rows, pos, axis=0), 0.0)
    return d_ys, d_w.astype(weights.dtype), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _grouped(xs, w, group_sizes, precision: str):
    """xs[rows of group g] @ w[g] for every held group at once."""
    if precision == "bf16":
        return grouped_matmul(xs, w, group_sizes)
    return quant_ragged_dot(xs, w, group_sizes, precision=precision)


def _share(x, router_in, router_w, w_gate, w_up, w_down, first_expert, *,
           experts_per_token: int, compute_dtype, matmul_precision: str):
    """One member's part: x [N, D] -> (y [N, D], this share's counts [G],
    assignments it could not give a row). ``router_in`` is what the router
    reads (float32), ``first_expert`` may be traced (a mesh member's)."""
    n, d = x.shape
    held_here = w_gate.shape[0]
    k = experts_per_token
    dt = compute_dtype

    with jax.named_scope("moe_router"):
        weights, experts = route(router_in, router_w, k)
        local = experts - first_expert
        held = jnp.logical_and(local >= 0, local < held_here)  # [N, k]
        # absent experts' assignments share one key past the held groups
        key = jnp.where(held, local, held_here).reshape(-1)
        counts = jnp.sum(
            key[:, None] == jnp.arange(held_here, dtype=key.dtype)[None, :],
            axis=0, dtype=jnp.int32)  # [G]

    with jax.named_scope("moe_dispatch"):
        row_slot = jnp.argsort(key, stable=True).astype(jnp.int32)  # [R]
        pos = jnp.argsort(row_slot).astype(jnp.int32).reshape(n, k)
        row_token = row_slot // k
        xs = _dispatch(x.astype(dt), row_token, pos, held)  # [R, D]
        # held assignments past the buffer's last row. The buffer has a row
        # for every assignment (R = N * k), so this reads 0; it is counted
        # from the buffer as built, for the day one is built smaller
        starts = jnp.cumsum(counts) - counts
        dropped = jnp.sum(counts) - jnp.sum(
            jnp.minimum(counts, jnp.maximum(xs.shape[0] - starts, 0)))

    with jax.named_scope("moe_experts"):
        gate = _grouped(xs, w_gate.astype(dt), counts, matmul_precision)
        up = _grouped(xs, w_up.astype(dt), counts, matmul_precision)
        ys = _grouped(jax.nn.silu(gate) * up, w_down.astype(dt), counts,
                      matmul_precision)

    with jax.named_scope("moe_combine"):
        y = _combine(ys, weights, row_token, row_slot, pos, held)
    return y, counts, dropped


def _counters(counts, dropped) -> Dict[str, jnp.ndarray]:
    """From every held expert's count (all shares together)."""
    total = jnp.sum(counts).astype(jnp.float32)
    mean = jnp.maximum(total / counts.shape[0], 1e-9)
    return {
        ASSIGNMENTS_HELD: total,
        LOAD_MAX_OVER_MEAN: jnp.max(counts).astype(jnp.float32) / mean,
        ASSIGNMENTS_DROPPED: jnp.asarray(dropped, jnp.float32),
    }


def _mesh_axes(mesh) -> Tuple[Tuple[str, ...], bool]:
    """(the batch axes tokens are spread over, whether experts are)."""
    if mesh is None:
        return (), False
    size = lambda a: mesh.shape[a] if a in mesh.axis_names else 1
    batch = tuple(a for a in (AXIS_DATA, AXIS_FSDP) if size(a) > 1)
    return batch, size(AXIS_EXPERT) > 1


def apply(params: Params, x, *, experts_per_token: int, first_expert: int = 0,
          router_in=None, compute_dtype=jnp.bfloat16,
          matmul_precision: str = "bf16", mesh: Mesh = None):
    """x [B, T, D] -> (y [B, T, D], counters). ``params`` as :func:`init`
    gives them: the router over all published experts and the matrices of
    the experts held, ``first_expert`` on. ``router_in`` [B, T, D] is what
    the router scores where that differs from ``x`` (a float32 copy of a
    bf16 activation); ``matmul_precision`` other than ``bf16`` sends the
    three expert products through ``kernels.quant_matmul``."""
    b, t, d = x.shape
    router_in = x if router_in is None else router_in
    w = (params["router"]["w"], params["w_gate"]["w"], params["w_up"]["w"],
         params["w_down"]["w"])
    share = functools.partial(
        _share, experts_per_token=experts_per_token,
        compute_dtype=compute_dtype, matmul_precision=matmul_precision)
    batch_axes, experts_spread = _mesh_axes(mesh)

    if not batch_axes and not experts_spread:
        y, counts, dropped = share(
            x.reshape(b * t, d), router_in.reshape(b * t, d), *w,
            first_expert)
        return y.reshape(b, t, d), _counters(counts, dropped)

    def member(x_, r_, router_w, w_gate, w_up, w_down):
        held_here = w_gate.shape[0]
        first = first_expert
        if experts_spread:
            first = first + lax.axis_index(AXIS_EXPERT) * held_here
        y, counts, dropped = share(
            x_.reshape(-1, d), r_.reshape(-1, d), router_w, w_gate, w_up,
            w_down, first)
        if batch_axes:  # every token's assignments, wherever its rows are
            counts = lax.psum(counts, batch_axes)
            dropped = lax.psum(dropped, batch_axes)
        if experts_spread:  # the shares' partial results add up
            y = lax.psum(y, AXIS_EXPERT)
            counts = lax.all_gather(counts, AXIS_EXPERT, tiled=True)
            dropped = lax.psum(dropped, AXIS_EXPERT)
        return y.reshape(x_.shape), counts, dropped

    tokens = P(batch_axes or None, None, None)
    held = P(AXIS_EXPERT if experts_spread else None, None, None)
    y, counts, dropped = jax.shard_map(
        member, mesh=mesh,
        in_specs=(tokens, tokens, P(), held, held, held),
        out_specs=(tokens, P(), P()), check_vma=False,
    )(x, router_in, *w)
    return y, _counters(counts, dropped)
