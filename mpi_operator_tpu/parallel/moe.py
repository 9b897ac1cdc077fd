"""Routed (mixture-of-experts) feed-forward: a share of the experts, dropless.

The layer is told which experts it holds: ``n_experts`` published ones that
the router scores, of which the ``w_up.shape[0]`` from ``first_expert`` on
live here. It routes every token over all published experts
(:func:`route`, in float32, by one of two score functions: softmax, the
``experts_per_token`` largest, their weights divided by their sum; or
sigmoid, the largest of score (plus a correction bias, where the layer has
one), weighed by the unbiased scores over their sum times a scale), and
computes its own experts' part of the result:

    y[t] = sum over the chosen experts e held here of  w[t, e] * W_down[e] . h
    h = silu(W_gate[e] . x[t]) * (W_up[e] . x[t])      gated: three products
    h = relu(W_up[e] . x[t]) ** 2          no ``w_gate`` in the tree: two

A *shared expert* (``shared_up`` / ``shared_down`` in the tree) is a dense
feed-forward of every token in the routed experts' form, added to the
routed result once: ``relu ** 2`` beside ungated experts, SwiGLU (with
``shared_gate`` in the tree) beside gated ones. It is what every chip
computes alike, so over an ``expert`` mesh axis it is added after the
members' sum and not once a member.

What the absent experts would add is left out; the partial results of all
shares (the shared expert counted once) add up to the whole layer
(tests/test_moe.py). No assignment to a
held expert is ever dropped: shapes are static with room for every
assignment (tokens x experts_per_token rows), whatever the imbalance.

How: the assignments are sorted by held expert (those to absent experts
last), the tokens' rows gathered in that order, the three products made as
grouped products over the held groups (``kernels.grouped_matmul``: on a TPU
the repo's own Pallas kernels ``moe_gmm`` / ``moe_gmm_dx`` / ``moe_gmm_dw``,
elsewhere ``lax.ragged_dot``; rows past the last group are never
touched), and the rows gathered back to their tokens with
their weights. Both gathers have hand-written transposes that are gathers
too: the sort is a permutation, so no scatter-add is ever needed. The
gathers say that their indices are in bounds (a permutation's are), so no
pass exists only to fill.

The buffer has a row for every assignment, R = tokens x experts_per_token,
and the held ones are its first H = sum(counts) rows. What runs over it *in
row order* outside the grouped products stops at the tile that holds row
H - 1: ``silu(gate) * up`` (``moe_silu_up``), its transpose
(``moe_silu_up_t``), the sum of the two products' cotangents on the gathered
rows (``moe_add``, in place of autodiff's ``add_any``; ungated experts read
their rows once and have ``moe_relu2`` and ``moe_relu2_t``) and the combine's
transpose, ``d_ys = d_rows * w`` with ``d_w = sum(d_rows * ys)`` beside it
(``moe_combine_t``, which gathers ``d_rows``, the tokens' cotangents in row
order, itself and so for the visited rows only): each a ``kernels.row_map``
whose grid's extent is ``cdiv(H, tile)``, read on the device. Rows past
that tile are left as the buffer held them, and every reader masks them
with ``held``. That path is taken where the grouped kernels are (a TPU,
shapes that tile) and the share leaves some published expert out; anywhere
else the same expressions run over all R rows in ``jax.numpy``.

The two gathers in token order, the combine's weighted sum and the
dispatch's transpose, read the held rows only on that same path, where the
rows are bf16 a whole number of lane tiles wide (``kernels.token_sum``:
``moe_token_sum``). The sort is stable, so a tile of consecutive tokens
owns one contiguous range of each held group's rows; the kernel copies
those ranges' 16-row lines by DMA and sums each token's rows in VMEM, the
weights in float32. Elsewhere each gathers every assignment's row and masks
the absent ones. One gather still moves all R rows: the dispatch's, in row
order.

With a mesh that has an ``expert`` axis each member holds its share of
``w_gate`` / ``w_up`` / ``w_down`` and the partial results are summed over
that axis; tokens stay where their batch axes put them. On one chip the
layer runs without that exchange, and nothing stands in for absent chips.

Scopes in the device trace: ``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``, ``moe_shared``. Counters, as scalars of
the step (no sync): ``moe.assignments_held``, ``moe.load_max_over_mean``,
``moe.assignments_dropped`` (0, computed and not assumed),
``moe.token_rows_read`` (the rows a gather in token order reads: the copied
lines on the kernel, R elsewhere).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mpi_operator_tpu.kernels import row_map, token_sum
from mpi_operator_tpu.kernels.grouped_matmul import grouped_matmul
from mpi_operator_tpu.kernels.quant_matmul import quant_ragged_dot
from mpi_operator_tpu.runtime.topology import AXIS_DATA, AXIS_EXPERT, AXIS_FSDP

Params = Dict[str, Any]

ASSIGNMENTS_HELD = "moe.assignments_held"
LOAD_MAX_OVER_MEAN = "moe.load_max_over_mean"
ASSIGNMENTS_DROPPED = "moe.assignments_dropped"
TOKEN_ROWS_READ = "moe.token_rows_read"


def init(key, *, d_model: int, d_expert: int, n_experts: int,
         n_held: int, gated: bool = True, d_shared: int = 0,
         score_bias: bool = False) -> Params:
    """One layer's weights: the router over all published experts (with
    ``score_bias`` its correction bias, nought), the matrices of the
    ``n_held`` experts held here (three, or without ``gated`` two), and with
    ``d_shared`` the shared expert's, as many. Projections std
    fan_in**-0.5, as the dense feed-forward's."""
    kr, kg, ku, kd = jax.random.split(key, 4)
    ksu, ksd = jax.random.split(jax.random.fold_in(key, 4))
    ksg = jax.random.fold_in(key, 5)
    normal = lambda k, shape, fan_in: (
        jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5)
    p = {
        "router": {"w": normal(kr, (d_model, n_experts), d_model)},
        "w_up": {"w": normal(ku, (n_held, d_model, d_expert), d_model)},
        "w_down": {"w": normal(kd, (n_held, d_expert, d_model), d_expert)},
    }
    if gated:
        p["w_gate"] = {"w": normal(kg, (n_held, d_model, d_expert), d_model)}
    if score_bias:
        p["router"]["bias"] = jnp.zeros((n_experts,), jnp.float32)
    if d_shared:
        p["shared_up"] = {"w": normal(ksu, (d_model, d_shared), d_model)}
        p["shared_down"] = {"w": normal(ksd, (d_shared, d_model), d_shared)}
        if gated:
            p["shared_gate"] = {
                "w": normal(ksg, (d_model, d_shared), d_model)}
    return p


def logical_axes(gated: bool = True, shared: bool = False,
                 score_bias: bool = False) -> Params:
    axes = {
        "router": {"w": ("embed", None)},
        "w_up": {"w": ("expert", "embed", "mlp")},
        "w_down": {"w": ("expert", "mlp", "embed")},
    }
    if gated:
        axes["w_gate"] = {"w": ("expert", "embed", "mlp")}
    if score_bias:
        axes["router"]["bias"] = (None,)
    if shared:
        axes["shared_up"] = {"w": ("embed", "mlp")}
        axes["shared_down"] = {"w": ("mlp", "embed")}
        if gated:
            axes["shared_gate"] = {"w": ("embed", "mlp")}
    return axes


def route(x32, router_w, experts_per_token: int, *, score: str = "softmax",
          bias=None, scale: float = 1.0):
    """(weights [N, k] float32, experts [N, k] int32) over every published
    expert, in float32, by one of two score functions, ``score``.

    Softmax: the k largest of the softmax, divided by their sum (they sum
    to 1). Sigmoid: the k largest of ``sigmoid(logits)``, plus ``bias`` [E]
    where there is one (a correction that is no parameter: it enters the
    choice alone, so nothing flows back to it), weighed by their unbiased
    scores over those scores' sum, times ``scale`` (they sum to ``scale``).

    The product is made at ``highest`` precision: a default float32 product
    on a TPU rounds its inputs to bf16, and the k-th and (k+1)-th scores of
    some token always lie within that rounding."""
    logits = jnp.matmul(x32.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if score not in ("softmax", "sigmoid") or (
            bias is not None and score == "softmax"):
        raise ValueError(f"score={score!r} with a bias: {bias is not None}; "
                         "softmax, or sigmoid with or without a bias")
    if score == "softmax":
        top_w, top_e = lax.top_k(jax.nn.softmax(logits, axis=-1),
                                 experts_per_token)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        return (top_w if scale == 1.0 else top_w * scale), top_e
    scores = jax.nn.sigmoid(logits)
    _, top_e = lax.top_k(
        scores if bias is None
        else scores + lax.stop_gradient(bias.astype(jnp.float32)),
        experts_per_token)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    return (top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20) * scale,
            top_e)


# -- the two gathers, each with a gather for its transpose --------------------
#
# ``pos[t, j]`` is the row of token t's j-th assignment in the sorted order,
# ``row_token[r]`` the token of row r: one permutation and its inverse. Rows
# of assignments to absent experts sort last; the grouped products never
# touch them and the row-order passes stop before them, so what they hold
# is undefined and every read of them is masked with ``held`` (a select, not
# a product: it may be NaN).
#
# ``rows`` is H, the number of held rows (a scalar on the device);
# ``interpret`` says how a pass in row order runs (``_row_passes``) and is
# what ``kernels.row_map`` takes under that name.

def _take(a, idx):
    """a[idx] along the rows, for indices that are a permutation's: in
    bounds, and said so (``take``'s default would follow the gather with a
    pass that puts NaN where an index was not)."""
    return jnp.take(a, idx, axis=0, mode="clip")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _dispatch(x, row_token, pos, held, plan, interpret):
    """xs[r] = x[row_token[r]]."""
    return _take(x, row_token)


def _dispatch_fwd(x, row_token, pos, held, plan, interpret):
    return _dispatch(x, row_token, pos, held, plan, interpret), (pos, held,
                                                                 plan)


def _dispatch_bwd(interpret, res, d_xs):
    pos, held, plan = res
    if plan is not None:  # each token's held rows, and no others
        return (token_sum.token_sum(d_xs, pos, plan, interpret=interpret),
                None, None, None, None)
    rows = _take(d_xs, pos)  # [N, k, D]
    d_x = jnp.sum(jnp.where(held[..., None], rows, 0).astype(jnp.float32),
                  axis=1)
    return d_x.astype(d_xs.dtype), None, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _combine_t(d_rows, ys, w_rows):
    """A row's part of the combine's transpose: (d_ys, d_w)."""
    return d_rows * w_rows, jnp.sum(d_rows * ys, axis=-1, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _combine(ys, weights, row_token, row_slot, pos, held, rows, plan,
             interpret):
    """y[t] = sum_j weights[t, j] * ys[pos[t, j]] over the held j."""
    if plan is not None:
        return token_sum.token_sum(ys, pos, plan, weights,
                                   interpret=interpret)
    gathered = _take(ys, pos)  # [N, k, D]
    gathered = jnp.where(held[..., None], gathered, 0).astype(jnp.float32)
    return jnp.sum(gathered * weights[..., None], axis=1).astype(ys.dtype)


def _combine_fwd(ys, weights, row_token, row_slot, pos, held, rows, plan,
                 interpret):
    return (_combine(ys, weights, row_token, row_slot, pos, held, rows, plan,
                     interpret),
            (ys, weights, row_token, row_slot, pos, held, rows))


def _moved(numbers, to):
    """out[to[i]] = numbers[i], ``to`` a permutation: a sort by it. (As a
    gather of 131,072 numbers by the inverse it takes the chip 1 to 2.5 ms,
    a number at a time; the sort takes a tenth of one.)"""
    return lax.sort((to, numbers), num_keys=1)[1]


def _combine_bwd(interpret, res, d_y):
    ys, weights, row_token, row_slot, pos, held, rows = res
    # in row order: one gather of d_y [R, D] serves both cotangents
    w_rows = _moved(jnp.where(held, weights, 0.0).reshape(-1),
                    pos.reshape(-1))  # [R]
    d_ys, d_w_rows = row_map.row_map(
        _combine_t, ((d_y, row_token), ys, w_rows),
        ((ys.shape[1], ys.dtype), (None, jnp.float32)), rows,
        name="moe_combine_t", interpret=interpret)
    d_w = jnp.where(held, _moved(d_w_rows, row_slot).reshape(pos.shape), 0.0)
    return (d_ys, d_w.astype(weights.dtype), None, None, None, None, None,
            None)


_combine.defvjp(_combine_fwd, _combine_bwd)


# -- between the products: the activation, and the rows read twice ------------

def _silu_up(gate, up):
    return (jax.nn.silu(gate) * up,)


def _silu_up_t(d_h, gate, up):
    """(d_gate, d_up) of ``silu(gate) * up``: silu'(g) = s (1 + g (1 - s)),
    s the logistic of g."""
    s = jax.nn.sigmoid(gate)
    return d_h * up * s * (1.0 + gate * (1.0 - s)), d_h * gate * s


def _add(a, b):
    return (a + b,)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _activate(gate, up, rows, interpret):
    """h = silu(gate) * up on the held rows."""
    h, = row_map.row_map(_silu_up, (gate, up), ((gate.shape[1], gate.dtype),),
                         rows, name="moe_silu_up", interpret=interpret)
    return h


def _activate_fwd(gate, up, rows, interpret):
    return _activate(gate, up, rows, interpret), (gate, up, rows)


def _activate_bwd(interpret, res, d_h):
    gate, up, rows = res
    d_gate, d_up = row_map.row_map(
        _silu_up_t, (d_h, gate, up),
        ((gate.shape[1], gate.dtype), (up.shape[1], up.dtype)), rows,
        name="moe_silu_up_t", interpret=interpret)
    return d_gate, d_up, None


_activate.defvjp(_activate_fwd, _activate_bwd)


def _relu2(up):
    return (jnp.square(jax.nn.relu(up)),)


def _relu2_t(d_h, up):
    return (d_h * 2.0 * jax.nn.relu(up),)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _activate_ungated(up, rows, interpret):
    """h = relu(up) ** 2 on the held rows."""
    h, = row_map.row_map(_relu2, (up,), ((up.shape[1], up.dtype),), rows,
                         name="moe_relu2", interpret=interpret)
    return h


def _activate_ungated_fwd(up, rows, interpret):
    return _activate_ungated(up, rows, interpret), (up, rows)


def _activate_ungated_bwd(interpret, res, d_h):
    up, rows = res
    d_up, = row_map.row_map(
        _relu2_t, (d_h, up), ((up.shape[1], up.dtype),), rows,
        name="moe_relu2_t", interpret=interpret)
    return d_up, None


_activate_ungated.defvjp(_activate_ungated_fwd, _activate_ungated_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _twice(xs, rows, interpret):
    """``xs`` for each of its two readers (the gate's product and the up's),
    so that the sum of their two cotangents is this layer's to bound and not
    autodiff's ``add_any`` over every row."""
    return xs, xs


def _twice_fwd(xs, rows, interpret):
    return (xs, xs), rows


def _twice_bwd(interpret, rows, d_both):
    d_gate_xs, d_up_xs = d_both
    d_xs, = row_map.row_map(
        _add, (d_gate_xs, d_up_xs), ((d_gate_xs.shape[1], d_gate_xs.dtype),),
        rows, name="moe_add", interpret=interpret)
    return d_xs, None


_twice.defvjp(_twice_fwd, _twice_bwd)


def _grouped(xs, w, group_sizes, precision: str):
    """xs[rows of group g] @ w[g] for every held group at once."""
    if precision == "bf16":
        return grouped_matmul(xs, w, group_sizes)
    return quant_ragged_dot(xs, w, group_sizes, precision=precision)


def _row_passes(r: int, d: int, f: int, held_here: int,
                n_experts: int) -> Optional[bool]:
    """How the passes in row order run, from what can be seen: as kernels
    bounded by the held rows (``False``: compiled) where the grouped kernels
    run (a TPU, widths that tile) and the rows come in lines of 128; over
    all ``r`` rows in ``jax.numpy`` (``None``) anywhere else, and where the
    share holds every published expert: no row can be absent then, so there
    is nothing to pass over."""
    if held_here == n_experts or jax.default_backend() != "tpu":
        return None
    return False if row_map.mappable(r, d, f) else None


def _share(x, router_in, w, first_expert, *, experts_per_token: int,
           compute_dtype, matmul_precision: str, router_scale: float,
           router_score: str):
    """One member's part: x [N, D] -> (y [N, D], this share's counts [G],
    assignments it could not give a row, rows the gathers in token order
    read).
    ``w`` holds the router's and the held experts' arrays by name (``bias``
    and ``w_gate`` where the layer has them), ``router_in`` is what the
    router reads (float32), ``first_expert`` may be traced (a mesh
    member's)."""
    n, d = x.shape
    w_gate, w_up, w_down = w.get("w_gate"), w["w_up"], w["w_down"]
    held_here = w_up.shape[0]
    k = experts_per_token
    dt = compute_dtype
    interpret = _row_passes(n * k, d, w_up.shape[2], held_here,
                            w["router"].shape[1])

    with jax.named_scope("moe_router"):
        weights, experts = route(router_in, w["router"], k,
                                 score=router_score, bias=w.get("bias"),
                                 scale=router_scale)
        local = experts - first_expert
        held = jnp.logical_and(local >= 0, local < held_here)  # [N, k]
        # absent experts' assignments share one key past the held groups
        key = jnp.where(held, local, held_here).reshape(-1)
        counts = jnp.sum(
            key[:, None] == jnp.arange(held_here, dtype=key.dtype)[None, :],
            axis=0, dtype=jnp.int32)  # [G]
        rows = jnp.sum(counts)  # H: the held rows are the buffer's first

    with jax.named_scope("moe_dispatch"):
        row_slot = jnp.argsort(key, stable=True).astype(jnp.int32)  # [R]
        pos = jnp.argsort(row_slot).astype(jnp.int32).reshape(n, k)
        row_token = row_slot // k
        # the gathers in token order read the held rows only where the
        # passes in row order are bounded and the kernel takes the shapes
        plan = None
        if interpret is not None and token_sum.takes(n, d, dt):
            plan = token_sum.plan(key.reshape(n, k), held_here)
        xs = _dispatch(x.astype(dt), row_token, pos, held, plan,
                       interpret)  # [R, D]
        # held assignments past the buffer's last row. The buffer has a row
        # for every assignment (R = N * k), so this reads 0; it is counted
        # from the buffer as built, for the day one is built smaller
        starts = jnp.cumsum(counts) - counts
        dropped = rows - jnp.sum(
            jnp.minimum(counts, jnp.maximum(xs.shape[0] - starts, 0)))

    with jax.named_scope("moe_experts"):
        if w_gate is None:
            up = _grouped(xs, w_up.astype(dt), counts, matmul_precision)
            h = _activate_ungated(up, rows, interpret)
        else:
            xs_gate, xs_up = _twice(xs, rows, interpret)
            gate = _grouped(xs_gate, w_gate.astype(dt), counts,
                            matmul_precision)
            up = _grouped(xs_up, w_up.astype(dt), counts, matmul_precision)
            h = _activate(gate, up, rows, interpret)
        ys = _grouped(h, w_down.astype(dt), counts, matmul_precision)

    with jax.named_scope("moe_combine"):
        y = _combine(ys, weights, row_token, row_slot, pos, held, rows,
                     plan, interpret)
    read = n * k if plan is None else plan.rows_read
    return y, counts, dropped, read


def _counters(counts, dropped, read) -> Dict[str, jnp.ndarray]:
    """From every held expert's count (all shares together), and the rows
    the gathers in token order read (a member's, all tokens together)."""
    total = jnp.sum(counts).astype(jnp.float32)
    mean = jnp.maximum(total / counts.shape[0], 1e-9)
    return {
        ASSIGNMENTS_HELD: total,
        LOAD_MAX_OVER_MEAN: jnp.max(counts).astype(jnp.float32) / mean,
        ASSIGNMENTS_DROPPED: jnp.asarray(dropped, jnp.float32),
        TOKEN_ROWS_READ: jnp.asarray(read, jnp.float32),
    }


def _mesh_axes(mesh) -> Tuple[Tuple[str, ...], bool]:
    """(the batch axes tokens are spread over, whether experts are)."""
    if mesh is None:
        return (), False
    size = lambda a: mesh.shape[a] if a in mesh.axis_names else 1
    batch = tuple(a for a in (AXIS_DATA, AXIS_FSDP) if size(a) > 1)
    return batch, size(AXIS_EXPERT) > 1


def _shared(params: Params, x, dt):
    """The shared expert of every token: ``relu(x . up) ** 2 . down``, or
    with ``shared_gate`` in the tree ``(silu(x . gate) * (x . up)) . down``."""
    with jax.named_scope("moe_shared"):
        up = (x.astype(dt) @ params["shared_up"]["w"].astype(dt)).astype(
            jnp.float32)
        if "shared_gate" in params:
            gate = x.astype(dt) @ params["shared_gate"]["w"].astype(dt)
            h = jax.nn.silu(gate.astype(jnp.float32)) * up
        else:
            h = jnp.square(jax.nn.relu(up))
        return h.astype(dt) @ params["shared_down"]["w"].astype(dt)


def apply(params: Params, x, *, experts_per_token: int, first_expert: int = 0,
          router_in=None, router_scale: float = 1.0,
          router_score: str = "softmax",
          compute_dtype=jnp.bfloat16, matmul_precision: str = "bf16",
          mesh: Mesh = None):
    """x [B, T, D] -> (y [B, T, D], counters). ``params`` as :func:`init`
    gives them: the router over all published experts and the matrices of
    the experts held, ``first_expert`` on. What the tree holds says what the
    layer is: a router ``bias`` a correction in the sigmoid score's choice
    (``router_score``: :func:`route`), no ``w_gate`` ungated ``relu ** 2``
    experts, ``shared_up`` a shared expert (gated where ``shared_gate`` is
    there too).
    ``router_in`` [B, T, D] is what the router scores where that differs
    from ``x`` (a float32 copy of a bf16 activation); ``router_scale``
    multiplies the weights; ``matmul_precision`` other than ``bf16`` sends
    the routed experts' products through ``kernels.quant_matmul``."""
    b, t, d = x.shape
    router_in = x if router_in is None else router_in
    w = {"router": params["router"]["w"], **{
        name: params[name]["w"] for name in ("w_gate", "w_up", "w_down")
        if name in params}}
    if "bias" in params["router"]:
        w["bias"] = params["router"]["bias"]
    share = functools.partial(
        _share, experts_per_token=experts_per_token,
        compute_dtype=compute_dtype, matmul_precision=matmul_precision,
        router_scale=router_scale, router_score=router_score)
    batch_axes, experts_spread = _mesh_axes(mesh)

    if not batch_axes and not experts_spread:
        y, *counted = share(
            x.reshape(b * t, d), router_in.reshape(b * t, d), w, first_expert)
        y = y.reshape(b, t, d)
    else:
        def member(x_, r_, w_):
            held_here = w_["w_up"].shape[0]
            first = first_expert
            if experts_spread:
                first = first + lax.axis_index(AXIS_EXPERT) * held_here
            y, counts, dropped, read = share(
                x_.reshape(-1, d), r_.reshape(-1, d), w_, first)
            if batch_axes:  # every token's assignments, wherever its rows are
                counts = lax.psum(counts, batch_axes)
                dropped, read = lax.psum((dropped, read), batch_axes)
            if experts_spread:  # the shares' partial results add up
                y = lax.psum(y, AXIS_EXPERT)
                counts = lax.all_gather(counts, AXIS_EXPERT, tiled=True)
                dropped = lax.psum(dropped, AXIS_EXPERT)
                read = lax.pmean(read, AXIS_EXPERT)  # each reads its own
            return y.reshape(x_.shape), counts, dropped, read

        tokens = P(batch_axes or None, None, None)
        held = P(AXIS_EXPERT if experts_spread else None, None, None)
        y, *counted = jax.shard_map(
            member, mesh=mesh,
            in_specs=(tokens, tokens,
                      {name: held if name.startswith("w_") else P()
                       for name in w}),
            out_specs=(tokens, P(), P(), P()), check_vma=False,
        )(x, router_in, w)
    if "shared_up" in params:  # every chip's alike: once, after the sum
        y = y + _shared(params, x, compute_dtype)
    return y, _counters(*counted)
