"""The routed feed-forward (parallel/moe.py): a share of the experts,
dropless. On the CPU ``lax.ragged_dot`` is a masked dense product, so what
is checked here is the layer's own arithmetic: the routing, the sort, both
gathers and their hand-written transposes, the counters, the shares over a
mesh. The plain loop it is held against visits each expert over every
token (no sort, no grouped product, no capacity)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.kernels import grouped_matmul, row_map, token_sum
from mpi_operator_tpu.kernels.quant_matmul import quant_ragged_dot
from mpi_operator_tpu.parallel import moe
from mpi_operator_tpu.runtime import MeshPlan, build_mesh
from mpi_operator_tpu.runtime.topology import AXIS_DATA, AXIS_EXPERT
from tests import poisoned_rows

D, F, E, K = 32, 48, 8, 2
F32 = dict(compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def whole():
    p = moe.init(jax.random.PRNGKey(0), d_model=D, d_expert=F, n_experts=E,
                 n_held=E)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D), jnp.float32)
    return p, x


def _share(p, first, count):
    return {"router": p["router"], **{
        n: {"w": p[n]["w"][first:first + count]}
        for n in ("w_gate", "w_up", "w_down")}}


def _loop(p, x, first=0):
    xf = x.reshape(-1, D)
    w, e = moe.route(xf, p["router"]["w"], K)
    out = jnp.zeros_like(xf)
    for i in range(p["w_gate"]["w"].shape[0]):
        gate = jax.nn.silu(xf @ p["w_gate"]["w"][i])
        y = (gate * (xf @ p["w_up"]["w"][i])) @ p["w_down"]["w"][i]
        out = out + y * jnp.sum(
            jnp.where(e == first + i, w, 0.0), -1, keepdims=True)
    return out.reshape(x.shape)


def test_the_whole_layer_is_the_plain_loop(whole):
    p, x = whole
    y, counters = moe.apply(p, x, experts_per_token=K, **F32)
    np.testing.assert_allclose(y, _loop(p, x), atol=1e-5, rtol=1e-5)
    assert float(counters[moe.ASSIGNMENTS_HELD]) == 2 * 24 * K
    assert float(counters[moe.ASSIGNMENTS_DROPPED]) == 0


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(whole, shares):
    """The partial results of all shares, each computed by the layer as a
    one-chip share, sum to the whole layer; so do their counts."""
    p, x = whole
    each = E // shares
    total, held = 0.0, 0.0
    for first in range(0, E, each):
        y, counters = moe.apply(_share(p, first, each), x, first_expert=first,
                                experts_per_token=K, **F32)
        np.testing.assert_allclose(
            y, _loop(_share(p, first, each), x, first), atol=1e-5, rtol=1e-5)
        total, held = total + y, held + float(counters[moe.ASSIGNMENTS_HELD])
    np.testing.assert_allclose(total, _loop(p, x), atol=2e-5, rtol=2e-5)
    assert held == 2 * 24 * K


@pytest.mark.parametrize("expert", [0, 2])
def test_dropless_under_total_imbalance(whole, expert):
    """A router that sends every token to one held expert first: that
    expert gets a row for every token, nothing is dropped, and the result
    is the plain loop's."""
    p, x = whole
    x = jnp.abs(x)  # the loaded column's score then beats every other
    router = jnp.zeros((D, E)).at[:, 4 + expert].set(50.0)
    share = dict(_share(p, 4, 3), router={"w": router})
    y, counters = moe.apply(share, x, first_expert=4, experts_per_token=K,
                            **F32)
    np.testing.assert_allclose(y, _loop(share, x, 4), atol=1e-5, rtol=1e-5)
    assert float(counters[moe.ASSIGNMENTS_DROPPED]) == 0
    # the loaded expert has a row of every token: the fullest holds at
    # least 3 / (1 + the others' share) of the mean
    assert float(counters[moe.ASSIGNMENTS_HELD]) >= 2 * 24
    assert float(counters[moe.LOAD_MAX_OVER_MEAN]) > 1.5


@pytest.mark.parametrize("leaf", ["x", "router", "w_gate", "w_up", "w_down"])
def test_gradients_are_the_plain_loops(whole, leaf):
    """Each gather's hand-written transpose (a gather too) against
    autodiff of the plain loop, for a share in the middle of the experts."""
    p, x = whole
    share = _share(p, 2, 4)
    cot = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def through(fn):
        g = jax.grad(lambda p, x: jnp.sum(fn(p, x) * cot), argnums=(0, 1))(
            share, x)
        return g[1] if leaf == "x" else g[0][leaf]["w"]

    got = through(lambda p, x: moe.apply(
        p, x, first_expert=2, experts_per_token=K, **F32)[0])
    want = through(lambda p, x: _loop(p, x, 2))
    assert float(jnp.max(jnp.abs(want))) > 0
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("axes", [
    {AXIS_EXPERT: 4}, {AXIS_EXPERT: 2, AXIS_DATA: 2}, {AXIS_DATA: 2}])
def test_over_a_mesh_the_shares_are_summed(whole, axes):
    """An ``expert`` axis: each member holds its share and the partial
    results are summed; batch axes: each member routes its own tokens."""
    p, x = whole
    size = int(np.prod(list(axes.values())))
    mesh = build_mesh(MeshPlan(axes=axes), jax.devices()[:size])
    y, counters = jax.jit(lambda p, x: moe.apply(
        p, x, experts_per_token=K, mesh=mesh, **F32))(p, x)
    want, want_counters = moe.apply(p, x, experts_per_token=K, **F32)
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=2e-5)
    for name in want_counters:
        assert float(counters[name]) == pytest.approx(
            float(want_counters[name]))


@pytest.fixture(scope="module")
def both_paths():
    """The layer and its five gradients at a size the kernels tile (128
    rows of 128, experts 256 wide, 4 of 8 held), by ``lax.ragged_dot`` (the
    CPU's path) and with the Pallas kernels, interpreted, put in
    ``_grouped``'s place."""
    p = _share(moe.init(jax.random.PRNGKey(7), d_model=128, d_expert=256,
                        n_experts=8, n_held=8), 2, 4)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 128), jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def layer_and_gradients():
        apply = lambda p, x: moe.apply(
            p, x, first_expert=2, experts_per_token=K, **F32)[0]
        y, vjp = jax.vjp(apply, p, x)
        d_p, d_x = vjp(cot)
        return {"y": y, "x": d_x, **{n: d_p[n]["w"] for n in d_p}}

    want = layer_and_gradients()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "_grouped", lambda xs, w, sizes, precision:
                      grouped_matmul(xs, w, sizes, interpret=True))
        return layer_and_gradients(), want


@pytest.mark.parametrize(
    "leaf", ["y", "x", "router", "w_gate", "w_up", "w_down"])
def test_with_the_kernels_the_layer_and_its_gradients_are_the_same(
        both_paths, leaf):
    got, want = both_paths
    assert float(jnp.max(jnp.abs(want[leaf]))) > 0
    np.testing.assert_allclose(got[leaf], want[leaf], atol=2e-5, rtol=2e-4)


# -- the passes in row order, bounded by the held rows -----------------------

ROWS, TILE = 1280, 256  # 2 x 320 tokens, two assignments each: five tiles


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def bounded(request):
    """The same layer over 1280 rows (tiles of 256) of which about half are
    held, by the CPU's path and on the bounded one: every kernel under the
    Pallas interpreter, every row past the held ones NaN before and after
    every pass (tests/poisoned_rows.py). In bfloat16 the combine's
    transpose gathers its rows inside the kernel."""
    dtype = jnp.dtype(request.param)
    p = _share(moe.init(jax.random.PRNGKey(7), d_model=256, d_expert=128,
                        n_experts=8, n_held=8), 2, 4)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 320, 256), jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def layer_and_gradients():
        counters = {}

        def apply(p, x):
            y, seen = moe.apply(p, x.astype(dtype), first_expert=2,
                                experts_per_token=K, compute_dtype=dtype,
                                router_in=x)
            counters.update(jax.tree.map(jax.lax.stop_gradient, seen))
            return y.astype(jnp.float32)

        y, vjp = jax.vjp(apply, p, x)
        d_p, d_x = vjp(cot)
        return ({"y": y, "x": d_x, **{n: d_p[n]["w"] for n in d_p}},
                {n: float(v) for n, v in counters.items()})

    want = layer_and_gradients()
    with poisoned_rows.patched(interpret=True):
        return layer_and_gradients(), want, dtype


@pytest.mark.parametrize(
    "leaf", ["y", "x", "router", "w_gate", "w_up", "w_down"])
def test_bounded_and_poisoned_the_layer_and_its_gradients_are_the_same(
        bounded, leaf):
    (got, _), (want, _), dtype = bounded
    scale = float(jnp.max(jnp.abs(want[leaf])))
    assert scale > 0
    assert bool(jnp.all(jnp.isfinite(got[leaf])))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got[leaf], want[leaf], atol=2e-5,
                                   rtol=2e-4)
    else:  # both round float32 results to bf16, in another order of sums
        np.testing.assert_allclose(got[leaf] / scale, want[leaf] / scale,
                                   atol=2 ** -6)


def test_rows_worked_is_the_visited_tiles_or_the_whole_buffer(bounded):
    """The bounded passes visit the held rows' tiles, the layer's own bound
    (its held count); with every row held, the whole buffer. The layer's
    counters are the same on both paths, but the rows read in token order:
    all of them on the CPU's path, and in bf16, where the kernel takes
    them, the aligned ranges, as counted by hand from the routing."""
    (_, got), (_, want), dtype = bounded
    held = want[moe.ASSIGNMENTS_HELD]
    assert 0 < held < ROWS - TILE and held % TILE  # a tile is cut
    assert row_map.row_tile(ROWS) == TILE
    assert int(row_map.rows_worked(int(held), ROWS)) == -(-held // TILE) * TILE
    assert int(row_map.rows_worked(ROWS, ROWS)) == ROWS
    assert set(got) == set(want) == {
        moe.ASSIGNMENTS_HELD, moe.LOAD_MAX_OVER_MEAN,
        moe.ASSIGNMENTS_DROPPED, moe.TOKEN_ROWS_READ}
    for name in set(want) - {moe.TOKEN_ROWS_READ}:
        assert got[name] == want[name]
    assert want[moe.TOKEN_ROWS_READ] == ROWS
    if dtype == jnp.float32:  # the kernel takes bf16 rows only
        assert got[moe.TOKEN_ROWS_READ] == ROWS
        return
    p = _share(moe.init(jax.random.PRNGKey(7), d_model=256, d_expert=128,
                        n_experts=8, n_held=8), 2, 4)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 320, 256), jnp.float32)
    _, experts = moe.route(x.reshape(-1, 256), p["router"]["w"], K)
    local = np.asarray(experts) - 2
    group = np.where((local >= 0) & (local < 4), local, 4)
    read = _rows_read_by_hand(group, 4, token_sum.TILE)
    assert held < read < ROWS
    assert got[moe.TOKEN_ROWS_READ] == read


# -- the two gathers in token order, reading the held rows only -------------

def routed_buffer(n, k, held, experts, width, seed, *, never=()):
    """An assignment buffer as the layer builds it: ``n`` tokens each routed
    to ``k`` distinct experts of ``experts`` drawn evenly (never one of
    ``never``), the first ``held`` of them held here; rows sorted stably by
    held group, random bf16 where held and NaN from the last held row on.
    (rows [n k, width], pos [n, k], group [n, k] (``held`` where absent),
    weights [n, k] float32, the held rows' count)."""
    rng = np.random.default_rng(seed)
    draw = rng.random((n, experts))
    draw[:, list(never)] = 2.0
    chosen = np.argsort(draw, axis=1)[:, :k]
    group = np.where(chosen < held, chosen, held).astype(np.int32)
    row_slot = np.argsort(group.reshape(-1), kind="stable")
    pos = np.argsort(row_slot).reshape(n, k).astype(np.int32)
    rows = int(np.sum(chosen < held))
    src = jax.random.normal(jax.random.PRNGKey(seed), (n * k, width),
                            jnp.bfloat16)
    src = jnp.where((jnp.arange(n * k) < rows)[:, None], src, jnp.nan)
    weights = rng.random((n, k)).astype(np.float32)
    return (src, jnp.asarray(pos), jnp.asarray(group), jnp.asarray(weights),
            rows)


def _rows_read_by_hand(group, held, tile):
    """The rows a token tile's copies move, tile by tile and group by group:
    a group's rows of the tile, from the 16-row line at or before the first
    to the end of the line that holds the last."""
    group = np.asarray(group)
    read, start = 0, 0
    for g in range(held):
        tokens = np.nonzero(group == g)[0]  # in token order, as sorted
        for i in np.unique(tokens // tile):
            rows = start + np.nonzero(tokens // tile == i)[0]
            read += -(-(rows[-1] + 1) // 16) * 16 - rows[0] // 16 * 16
        start += len(tokens)
    return read


def _both_sites(src, pos, group, weights, held, plan, interpret):
    """The combine's forward (weighted) and the dispatch's transpose (no
    weight) of ``src`` as the layer calls them: on the kernel with
    ``plan``, in ``jax.numpy`` without."""
    n, k = pos.shape
    is_held = group < held
    row_slot = jnp.argsort(group.reshape(-1), stable=True).astype(jnp.int32)
    combine = moe._combine(src, weights, row_slot // k, row_slot, pos,
                           is_held, jnp.sum(is_held), plan, interpret)
    _, vjp = jax.vjp(lambda x: moe._dispatch(
        x, row_slot // k, pos, is_held, plan, interpret),
        jnp.zeros((n, src.shape[1]), src.dtype))
    return {"combine": combine, "dispatch_t": vjp(src)[0]}


TOKEN_SUM_CASES = {  # n, k, held, published, width, never drawn
    "mellum2": (256, 8, 16, 64, 256, ()),
    "nemotron3nano": (256, 6, 8, 128, 128, ()),
    "kanana2": (256, 6, 16, 128, 128, ()),
    "every assignment held": (256, 2, 4, 4, 128, ()),
    "none held": (256, 2, 4, 64, 128, range(4)),
    "an empty group": (256, 6, 16, 64, 128, (5,)),
}


@pytest.fixture(scope="module", params=sorted(TOKEN_SUM_CASES))
def token_sums(request):
    n, k, held, experts, width, never = TOKEN_SUM_CASES[request.param]
    src, pos, group, weights, rows = routed_buffer(
        n, k, held, experts, width, seed=len(request.param), never=never)
    plan = token_sum.plan(group, held)
    got = _both_sites(src, pos, group, weights, held, plan, True)
    want = _both_sites(src, pos, group, weights, held, None, None)
    return request.param, (group, held, rows, plan), got, want


@pytest.mark.parametrize("site", ["combine", "dispatch_t"])
def test_the_token_sum_is_the_masked_gathers_sum(token_sums, site):
    """Both call sites at each routed cell's k and held share and at the
    edges, two token tiles of 128, the rows past the held ones NaN: the
    kernel (interpreted) gives what the gather of every assignment, masked
    and summed in float32, gives, to the rounding of the sum's order."""
    case, (_, held, rows, _), got, want = token_sums
    a = np.asarray(got[site], np.float32)
    b = np.asarray(want[site], np.float32)
    assert np.all(np.isfinite(a)) and np.all(np.isfinite(b))
    if case == "none held":
        assert rows == 0 and not np.any(a) and not np.any(b)
        return
    assert rows > 0 and np.any(b)
    np.testing.assert_allclose(a, b, rtol=2 ** -7,
                               atol=2 ** -16 * np.abs(b).max())


def test_the_plans_ranges_are_the_held_rows_lines(token_sums):
    """The rows the copies move are the lines of the held rows, counted by
    hand: no fewer than the held rows, and at most two lines more a range
    (a line two ranges share is copied for each). Every assignment held:
    some tile's range starts inside a line that the group before it ends
    in, and the last range ends at the buffer's end."""
    case, (group, held, rows, plan), _, _ = token_sums
    n, k = group.shape
    read = int(plan.rows_read)
    assert read == _rows_read_by_hand(group, held, token_sum.TILE)
    assert rows <= read <= rows + 32 * int(jnp.sum(plan.ranges % 256 > 0))
    assert int(jnp.sum(plan.ranges % 256)) == rows
    if case == "every assignment held":
        lo, count = plan.ranges // 256, plan.ranges % 256
        sizes = jnp.sum(count.reshape(-1, held), axis=0)
        starts = jnp.repeat((jnp.cumsum(sizes) - sizes)[None], n // 128, 0)
        assert bool(jnp.any((lo // 16 * 16 < starts.reshape(-1))
                            & (count > 0)))
        assert int(jnp.max(-(-(lo + count) // 16) * 16)) == rows == n * k


@pytest.mark.parametrize("site", ["combine", "dispatch_t"])
def test_a_range_started_a_row_late_loses_that_row_and_nothing_else(site):
    """Every range of the plan one row late, the rows past the held ones
    finite: the kernel (interpreted) sums each token's rows but the first
    of each range, which no token then finds, and is far from the sound
    sum."""
    n, k, held, experts, width, _ = TOKEN_SUM_CASES["mellum2"]
    src, pos, group, weights, rows = routed_buffer(n, k, held, experts,
                                                   width, seed=40)
    src = jnp.where(jnp.isnan(src), jnp.ones_like(src), src)
    plan = token_sum.plan(group, held)
    late = plan._replace(ranges=jnp.where(plan.ranges % 256 > 0,
                                          plan.ranges + 256, plan.ranges))
    c = weights if site == "combine" else None
    got = np.asarray(token_sum.token_sum(src, pos, late, c, interpret=True),
                     np.float32)
    tile = token_sum.TILE
    lo = np.asarray(plan.ranges // 256).reshape(n // tile, held)
    g = np.asarray(group)
    first = (g < held) & (np.asarray(pos) == lo[
        np.arange(n)[:, None] // tile, np.minimum(g, held - 1)])
    assert int(first.sum()) == int(jnp.sum(plan.ranges % 256 > 0)) > 0

    def summed(keep):
        rows_ = np.take(np.asarray(src, np.float32), np.asarray(pos), axis=0)
        scale = np.ones((n, k), np.float32) if c is None else np.asarray(c)
        return np.sum(np.where(keep[..., None], rows_ * scale[..., None], 0),
                      axis=1)

    want, sound = summed((g < held) & ~first), summed(g < held)
    np.testing.assert_allclose(got, want, rtol=2 ** -7,
                               atol=2 ** -16 * np.abs(want).max())
    assert np.sqrt(np.mean((got - sound) ** 2) / np.mean(sound ** 2)) > 0.1


def test_the_kernel_is_taken_for_bf16_rows_of_whole_lane_tiles():
    assert token_sum.takes(16384, 2304, jnp.bfloat16)
    assert not token_sum.takes(16384, 2304, jnp.float32)
    assert not token_sum.takes(16384, 2300, jnp.bfloat16)
    assert not token_sum.takes(576, 256, jnp.bfloat16)  # tiles of 64 tokens


@pytest.mark.parametrize("shapes,path", [
    ((131072, 2304, 896, 16, 64), False),  # the cell's, on a TPU
    ((131072, 2304, 896, 64, 64), None),   # every published expert held
    ((96, 32, 48, 4, 8), None),            # widths the kernels do not tile
    ((144, 128, 128, 4, 8), None),         # rows they do not
])
def test_the_path_is_chosen_from_what_can_be_seen(monkeypatch, shapes, path):
    """Bounded where the grouped kernels run and a published expert is
    left out; off the TPU never."""
    assert moe._row_passes(*shapes) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe._row_passes(*shapes) is path


def test_no_gather_is_followed_by_a_fill(whole):
    """Four gathers of rows by a permutation's indices, each told that they
    are in bounds: the lowered step holds no select under ``jit(_take)``
    (what ``take``'s default puts NaN with, a pass over the whole buffer)."""
    p, x = whole
    text = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(moe.apply(
        _share(p, 2, 4), x, first_expert=2, experts_per_token=K)[0]
        .astype(jnp.float32)), argnums=(0, 1))).lower(p, x).as_text()
    takes = text.split("func.func private @_take")[1:]
    assert len(takes) == text.count("call @_take") == 4
    for body in takes:
        body = body.split("func.func")[0]
        assert "stablehlo.gather" in body
        assert "stablehlo.select" not in body and "nan" not in body.lower()


def test_off_the_tpu_the_grouped_products_are_ragged_dots(whole):
    """The choice is an observation of the backend: on the CPU the step
    holds ``lax.ragged_dot`` and its two transposes, and no kernel."""
    p, x = whole
    text = str(jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(moe.apply(
        p, x, experts_per_token=K)[0].astype(jnp.float32)),
        argnums=(0, 1)))(p, x))
    assert text.count("ragged_dot_general") == 9  # three products, thrice
    assert "pallas_call" not in text


def test_int8_expert_products_are_near_and_not_the_same(whole):
    p, x = whole
    y, _ = moe.apply(p, x, experts_per_token=K, **F32)
    y8, _ = moe.apply(p, x, experts_per_token=K, matmul_precision="int8",
                      **F32)
    gap = float(jnp.linalg.norm(y8 - y) / jnp.linalg.norm(y))
    assert 1e-3 < gap < 5e-2
    # backward is straight-through: the plain grouped product's transposes
    g = jax.grad(lambda p: jnp.sum(moe.apply(
        p, x, experts_per_token=K, matmul_precision="int8", **F32)[0] ** 2))(p)
    assert all(bool(jnp.all(jnp.isfinite(a))) and float(jnp.abs(a).max()) > 0
               for a in jax.tree.leaves(g))


def test_quant_ragged_dot_scales_each_experts_columns():
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (20, 16))
    # one expert a hundred times the other: a scale shared between them
    # would round the small one away
    w = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 8))
    w = w * jnp.array([1.0, 100.0])[:, None, None]
    sizes = jnp.array([12, 5], jnp.int32)  # three rows belong to no group
    got = quant_ragged_dot(x, w, sizes, precision="int8")
    want = jax.lax.ragged_dot(x, w, sizes)
    for rows in (slice(0, 12), slice(12, 17)):
        gap = float(jnp.linalg.norm(got[rows] - want[rows])
                    / jnp.linalg.norm(want[rows]))
        assert 1e-4 < gap < 3e-2
    with pytest.raises(ValueError, match="precision"):
        quant_ragged_dot(x, w, sizes, precision="int4")


def test_the_router_is_float32_whatever_the_activations(whole):
    p, x = whole
    w, e = moe.route(x.reshape(-1, D).astype(jnp.bfloat16),
                     p["router"]["w"], K)
    assert w.dtype == jnp.float32 and e.shape == (48, K)
    np.testing.assert_allclose(jnp.sum(w, -1), 1.0, atol=1e-6)


# -- the other form: sigmoid scores, ungated experts, a shared expert --------

E2, K2, SCALE = 128, 6, 2.5
SIGMOID = {"router_score": "sigmoid", "router_scale": SCALE}


@pytest.fixture(scope="module")
def whole_sigmoid():
    """All 128 published experts held, ungated, with a shared expert; the
    correction bias drawn at the spacing of the top scores."""
    p = moe.init(jax.random.PRNGKey(4), d_model=D, d_expert=F, n_experts=E2,
                 n_held=E2, gated=False, d_shared=40, score_bias=True)
    p["router"]["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(5), (E2,))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, D), jnp.float32)
    return p, x


def _share2(p, first, count, shared=True):
    out = {"router": p["router"], **{
        n: {"w": p[n]["w"][first:first + count]} for n in ("w_up", "w_down")}}
    if shared:
        out.update(shared_up=p["shared_up"], shared_down=p["shared_down"])
    return out


def _plain_route(xf, p):
    """The published rule, written out: sigmoid scores, the choice by score
    plus bias, the weights from the unbiased scores."""
    s = jax.nn.sigmoid(jnp.matmul(xf, p["router"]["w"], precision="highest"))
    chosen = jnp.argsort(-(s + p["router"]["bias"]), axis=-1)[:, :K2]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * SCALE, chosen


def _loop2(p, x, first=0, shared=True):
    xf = x.reshape(-1, D)
    w, e = _plain_route(xf, p)
    relu2 = lambda a: jnp.square(jax.nn.relu(a))
    out = jnp.zeros_like(xf)
    for i in range(p["w_up"]["w"].shape[0]):
        y = relu2(xf @ p["w_up"]["w"][i]) @ p["w_down"]["w"][i]
        out = out + y * jnp.sum(
            jnp.where(e == first + i, w, 0.0), -1, keepdims=True)
    if shared:
        out = out + relu2(xf @ p["shared_up"]["w"]) @ p["shared_down"]["w"]
    return out.reshape(x.shape)


def test_the_sigmoid_route_is_the_published_rule(whole_sigmoid):
    p, x = whole_sigmoid
    xf = x.reshape(-1, D)
    w, e = moe.route(xf, p["router"]["w"], K2, score="sigmoid",
                     bias=p["router"]["bias"], scale=SCALE)
    want_w, want_e = _plain_route(xf, p)
    np.testing.assert_array_equal(e, want_e)
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, -1), SCALE, rtol=1e-6)
    # the bias changes the choice and not the weight: without it other
    # experts are chosen for many tokens, and an expert chosen either way
    # has the weight its unbiased score gives among its companions
    _, unbiased = moe.route(xf, p["router"]["w"], K2, score="sigmoid",
                            bias=jnp.zeros((E2,)), scale=SCALE)
    changed = jnp.any(jnp.sort(e, -1) != jnp.sort(unbiased, -1), axis=-1)
    assert 0.2 < float(jnp.mean(changed)) < 1.0
    s = jax.nn.sigmoid(jnp.matmul(xf, p["router"]["w"], precision="highest"))
    picked = jnp.take_along_axis(s, e, axis=-1)
    np.testing.assert_allclose(
        w, picked / jnp.sum(picked, -1, keepdims=True) * SCALE, rtol=1e-6)
    # a buffer: nothing flows back to it
    grad = jax.grad(lambda b: jnp.sum(moe.route(
        xf, p["router"]["w"], K2, score="sigmoid", bias=b,
        scale=SCALE)[0] ** 2))(
            p["router"]["bias"])
    assert not np.any(np.asarray(grad))


def test_a_sigmoid_route_without_a_bias_chooses_by_score_alone(
        whole_sigmoid):
    """``score="sigmoid"`` with no correction bias: the k largest sigmoid
    scores, weighed by themselves over their sum times the scale; and the
    layer takes that rule from ``router_score`` where its tree has no bias.
    The score is always said: a bias with the softmax is refused."""
    p, x = whole_sigmoid
    xf = x.reshape(-1, D)
    w, e = moe.route(xf, p["router"]["w"], K2, scale=SCALE, score="sigmoid")
    s = jax.nn.sigmoid(jnp.matmul(xf, p["router"]["w"], precision="highest"))
    np.testing.assert_array_equal(
        jnp.sort(e, -1), jnp.sort(jnp.argsort(-s, axis=-1)[:, :K2], -1))
    picked = jnp.take_along_axis(s, e, axis=-1)
    np.testing.assert_allclose(
        w, picked / jnp.sum(picked, -1, keepdims=True) * SCALE, rtol=1e-6)
    # the same as a bias of nought, and not the softmax's rule
    zero = moe.route(xf, p["router"]["w"], K2, score="sigmoid",
                     bias=jnp.zeros((E2,)), scale=SCALE)
    np.testing.assert_array_equal(e, zero[1])
    np.testing.assert_allclose(w, zero[0], rtol=1e-6)
    soft_w, _ = moe.route(xf, p["router"]["w"], K2, scale=SCALE)
    assert float(jnp.max(jnp.abs(soft_w - w))) > 1e-2
    unbiased = dict(p, router={"w": p["router"]["w"]})
    got, _ = moe.apply(unbiased, x, experts_per_token=K2, router_scale=SCALE,
                       router_score="sigmoid", **F32)
    want, _ = moe.apply(dict(p, router={"w": p["router"]["w"],
                                        "bias": jnp.zeros((E2,))}),
                        x, experts_per_token=K2, **SIGMOID, **F32)
    np.testing.assert_allclose(got, want, atol=1e-6)
    with pytest.raises(ValueError, match="softmax, or sigmoid"):
        moe.route(xf, p["router"]["w"], K2, bias=jnp.zeros((E2,)))


def test_the_ungated_layer_with_its_shared_expert_is_the_plain_loop(
        whole_sigmoid):
    p, x = whole_sigmoid
    apply = lambda p, x: moe.apply(p, x, experts_per_token=K2,
                                   **SIGMOID, **F32)[0]
    np.testing.assert_allclose(apply(p, x), _loop2(p, x), atol=2e-5,
                               rtol=2e-5)
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(apply(p, x) * cot), (0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(_loop2(p, x) * cot), (0, 1))(p, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=2e-4)
    assert "w_gate" not in p and not np.any(np.asarray(got[0]["router"]["bias"]))


def test_sixteen_shares_and_the_shared_expert_once_add_up(whole_sigmoid):
    """The guide's test of the cut: sixteen shares of 8 experts, each
    computed by the layer as a one-chip share, with what every chip
    computes alike, the shared expert, counted once, sum to the uncut
    layer; so do their counts."""
    p, x = whole_sigmoid
    each = E2 // 16
    total, held = 0.0, 0.0
    for first in range(0, E2, each):
        y, counters = moe.apply(
            _share2(p, first, each, shared=False), x, first_expert=first,
            experts_per_token=K2, **SIGMOID, **F32)
        np.testing.assert_allclose(
            y, _loop2(_share2(p, first, each), x, first, shared=False),
            atol=1e-5, rtol=1e-5)
        total, held = total + y, held + float(counters[moe.ASSIGNMENTS_HELD])
    xf = x.reshape(-1, D)
    relu2 = lambda a: jnp.square(jax.nn.relu(a))
    total = total + (relu2(xf @ p["shared_up"]["w"])
                     @ p["shared_down"]["w"]).reshape(x.shape)
    whole, _ = moe.apply(p, x, experts_per_token=K2, **SIGMOID, **F32)
    np.testing.assert_allclose(total, whole, atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(total, _loop2(p, x), atol=3e-5, rtol=3e-5)
    assert held == 2 * 24 * K2
    # one share with the shared expert is that share plus the expert
    one, _ = moe.apply(_share2(p, 8, each), x, first_expert=8,
                       experts_per_token=K2, **SIGMOID, **F32)
    np.testing.assert_allclose(one, _loop2(_share2(p, 8, each), x, 8),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("axes", [{AXIS_EXPERT: 4}, {AXIS_DATA: 2,
                                                     AXIS_EXPERT: 2}])
def test_over_a_mesh_the_shared_expert_is_added_once(whole_sigmoid, axes):
    p, x = whole_sigmoid
    n = int(np.prod(list(axes.values())))
    mesh = build_mesh(MeshPlan(axes=axes), jax.devices()[:n])
    y, _ = jax.jit(lambda p, x: moe.apply(
        p, x, experts_per_token=K2, **SIGMOID, mesh=mesh, **F32))(p, x)
    np.testing.assert_allclose(y, _loop2(p, x), atol=3e-5, rtol=3e-5)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def bounded_ungated(request):
    """The ungated layer over 1280 rows of which about half are held, its
    experts 144 wide (no multiple of 128: a whole-width block), by the
    CPU's path and on the bounded one, every kernel interpreted and every
    row past the held ones NaN around every pass. In bfloat16 the two
    gathers in token order are the token sum's kernel."""
    dtype = jnp.dtype(request.param)
    p = moe.init(jax.random.PRNGKey(7), d_model=256, d_expert=144,
                 n_experts=8, n_held=8, gated=False, d_shared=128,
                 score_bias=True)
    p = {"router": p["router"], "shared_up": p["shared_up"],
         "shared_down": p["shared_down"],
         **{n: {"w": p[n]["w"][2:6]} for n in ("w_up", "w_down")}}
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 320, 256), jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def layer_and_gradients():
        apply = lambda p, x: moe.apply(
            p, x.astype(dtype), first_expert=2, experts_per_token=K,
            **SIGMOID, compute_dtype=dtype,
            router_in=x)[0].astype(jnp.float32)
        y, vjp = jax.vjp(apply, p, x)
        d_p, d_x = vjp(cot)
        return {"y": y, "x": d_x, "router": d_p["router"]["w"],
                **{n: d_p[n]["w"] for n in ("w_up", "w_down", "shared_up")}}

    want = layer_and_gradients()
    with poisoned_rows.patched(interpret=True):
        return layer_and_gradients(), want, dtype


@pytest.mark.parametrize(
    "leaf", ["y", "x", "router", "w_up", "w_down", "shared_up"])
def test_bounded_and_poisoned_the_ungated_layer_is_the_same(
        bounded_ungated, leaf):
    got, want, dtype = bounded_ungated
    scale = float(jnp.max(jnp.abs(want[leaf])))
    assert scale > 0
    assert bool(jnp.all(jnp.isfinite(got[leaf])))
    if dtype == jnp.float32:  # float32's rounding, in another order of sums
        np.testing.assert_allclose(got[leaf], want[leaf],
                                   atol=max(5e-5, 2e-6 * scale), rtol=2e-4)
    else:  # both round float32 results to bf16, in another order of sums
        np.testing.assert_allclose(got[leaf] / scale, want[leaf] / scale,
                                   atol=2 ** -6)


# -- gated experts beside a gated shared expert: the latent-attention model's --

E3, K3, SCALE3, F3 = 128, 6, 2.448, 24
SIGMOID3 = {"router_score": "sigmoid", "router_scale": SCALE3}


@pytest.fixture(scope="module")
def whole_gated_sigmoid():
    """All 128 published experts held, gated, sigmoid scores with a
    correction bias, beside a gated shared expert twice an expert's width."""
    p = moe.init(jax.random.PRNGKey(14), d_model=D, d_expert=F3,
                 n_experts=E3, n_held=E3, gated=True, d_shared=2 * F3,
                 score_bias=True)
    p["router"]["bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(15), (E3,))
    x = jax.random.normal(jax.random.PRNGKey(16), (2, 24, D), jnp.float32)
    return p, x


def _reference_layer(p, x, first=0, count=E3, shared=True):
    """The uncut layer by the benchmark's plain reference
    (benchmark/reference/deepseek_v3.py: float32, a loop over the experts):
    experts ``first`` .. ``first + count``, with the shared expert or
    without."""
    import importlib
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    reference = importlib.import_module("reference.deepseek_v3")
    conf = {"n_group": 1, "topk_group": 1, "num_experts_per_tok": K3,
            "norm_topk_prob": True, "routed_scaling_factor": SCALE3,
            "stands_for": {"experts_held": {"first": 0}}}
    w = {"router": p["router"]["w"], **{
        n: p[n]["w"][first:first + count]
        for n in ("w_gate", "w_up", "w_down")}}
    cast = lambda a: a
    out = reference.routed_experts(conf, x, w, p["router"]["bias"], cast,
                                   first=first)
    if shared:
        out = out + reference.swiglu(
            x, p["shared_gate"]["w"], p["shared_up"]["w"],
            p["shared_down"]["w"], cast)
    return out


def _share3(p, first, count, shared=True):
    out = {"router": p["router"], **{
        n: {"w": p[n]["w"][first:first + count]}
        for n in ("w_gate", "w_up", "w_down")}}
    if shared:
        out.update({n: p[n] for n in
                    ("shared_gate", "shared_up", "shared_down")})
    return out


def test_a_shared_expert_takes_the_routed_experts_form():
    gated = moe.init(jax.random.PRNGKey(0), d_model=D, d_expert=F3,
                     n_experts=8, n_held=4, gated=True, d_shared=40)
    ungated = moe.init(jax.random.PRNGKey(0), d_model=D, d_expert=F3,
                       n_experts=8, n_held=4, gated=False, d_shared=40)
    assert gated["shared_gate"]["w"].shape == (D, 40)
    assert "shared_gate" not in ungated and "w_gate" not in ungated
    # the leaves both forms have are the same draw
    for name in ("shared_up", "shared_down", "router"):
        np.testing.assert_array_equal(gated[name]["w"], ungated[name]["w"])
    assert set(moe.logical_axes(gated=True, shared=True)) - set(
        moe.logical_axes(gated=False, shared=True)) == {
            "w_gate", "shared_gate"}
    assert "shared_gate" not in moe.logical_axes(gated=True, shared=False)


def test_the_gated_layer_with_its_gated_shared_expert_is_the_plain_reference(
        whole_gated_sigmoid):
    p, x = whole_gated_sigmoid
    apply = lambda p, x: moe.apply(p, x, experts_per_token=K3,
                                   **SIGMOID3, **F32)[0]
    np.testing.assert_allclose(apply(p, x), _reference_layer(p, x),
                               atol=2e-5, rtol=2e-5)
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(apply(p, x) * cot), (0, 1))(p, x)
    want = jax.grad(
        lambda p, x: jnp.sum(_reference_layer(p, x) * cot), (0, 1))(p, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=2e-4)
    assert float(jnp.max(jnp.abs(got[0]["shared_gate"]["w"]))) > 1e-3
    assert not np.any(np.asarray(got[0]["router"]["bias"]))


def test_eight_shares_and_the_gated_shared_expert_once_add_up(
        whole_gated_sigmoid):
    """The guide's test of the cut, for the eight-way deployment: eight
    shares of 16 experts, each computed by the layer as a one-chip share,
    with what every chip computes alike, the gated shared expert, counted
    once, sum to what the uncut plain reference gives for the whole layer;
    so do their counts."""
    p, x = whole_gated_sigmoid
    each = E3 // 8
    total, held = 0.0, 0.0
    for first in range(0, E3, each):
        y, counters = moe.apply(
            _share3(p, first, each, shared=False), x, first_expert=first,
            experts_per_token=K3, **SIGMOID3, **F32)
        np.testing.assert_allclose(
            y, _reference_layer(p, x, first, each, shared=False),
            atol=1e-5, rtol=1e-5)
        total, held = total + y, held + float(counters[moe.ASSIGNMENTS_HELD])
    shared = _reference_layer(p, x, 0, 0) - _reference_layer(
        p, x, 0, 0, shared=False)
    total = total + shared
    np.testing.assert_allclose(total, _reference_layer(p, x), atol=3e-5,
                               rtol=3e-5)
    whole, _ = moe.apply(p, x, experts_per_token=K3, **SIGMOID3,
                         **F32)
    np.testing.assert_allclose(total, whole, atol=3e-5, rtol=3e-5)
    assert held == 2 * 24 * K3
    # one share with the shared expert is that share plus the expert, once
    one, _ = moe.apply(_share3(p, 16, each), x, first_expert=16,
                       experts_per_token=K3, **SIGMOID3, **F32)
    np.testing.assert_allclose(
        one, _reference_layer(p, x, 16, each), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("axes", [{AXIS_EXPERT: 4}, {AXIS_DATA: 2,
                                                     AXIS_EXPERT: 2}])
def test_over_a_mesh_the_gated_shared_expert_is_added_once(
        whole_gated_sigmoid, axes):
    p, x = whole_gated_sigmoid
    n = int(np.prod(list(axes.values())))
    mesh = build_mesh(MeshPlan(axes=axes), jax.devices()[:n])
    y, _ = jax.jit(lambda p, x: moe.apply(
        p, x, experts_per_token=K3, **SIGMOID3, mesh=mesh,
        **F32))(p, x)
    np.testing.assert_allclose(y, _reference_layer(p, x), atol=3e-5,
                               rtol=3e-5)
