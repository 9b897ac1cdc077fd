"""The routed feed-forward (parallel/moe.py) compiled ON the TPU chip.

tests/test_moe.py checks the layer against a plain loop over the experts
on the CPU, where ``lax.ragged_dot`` is a masked dense product; this is the
hardware half: the grouped products as the Pallas kernels of
kernels/grouped_matmul.py, which leave the rows past the last group
untouched, the sort, both gathers and their hand-written transposes, at a
width the MXU tiles (d 256, experts 128 wide, 16 published of which 4 held,
2 a token), against that plain loop in float32 at ``highest`` precision,
once more with every row past the held ones NaN around every pass
(tests/poisoned_rows.py); one product at the benchmark cell's shapes against
the compiler's own ``lax.ragged_dot``, both timed; the passes in row
order (kernels/row_map.py) at those shapes, bounded by a quarter of the rows
and by all of them, against the compiler's own fusions over all rows; and
the two gathers in token order (kernels/token_sum.py) at the three routed
cells' shapes against the float32 ``jax.numpy`` form, with every range
started a row late, both timed, on one chip and on four under
``shard_map``."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mpi_operator_tpu.kernels import row_map, token_sum
from mpi_operator_tpu.kernels.grouped_matmul import grouped_matmul
from mpi_operator_tpu.parallel import moe
from tests import poisoned_rows
from tests.test_moe import _rows_read_by_hand, routed_buffer

D, F, E, HELD, FIRST, K = 256, 128, 16, 4, 4, 2


def _setup(key, tokens=(4, 512)):
    full = moe.init(key, d_model=D, d_expert=F, n_experts=E, n_held=E)
    share = {"router": full["router"], **{
        n: {"w": full[n]["w"][FIRST:FIRST + HELD]}
        for n in ("w_gate", "w_up", "w_down")}}
    x = jax.random.normal(jax.random.fold_in(key, 1), (*tokens, D),
                          jnp.float32)
    return share, x


def _loop(p, x):
    """Each held expert over every token, weighted by what the router gave
    it (nought for most): no sort, no grouped product."""
    with jax.default_matmul_precision("highest"):
        xf = x.reshape(-1, D)
        w, e = moe.route(xf, p["router"]["w"], K)
        out = jnp.zeros_like(xf)
        for i in range(HELD):
            gate = jax.nn.silu(xf @ p["w_gate"]["w"][i])
            y = (gate * (xf @ p["w_up"]["w"][i])) @ p["w_down"]["w"][i]
            out = out + y * jnp.sum(
                jnp.where(e == FIRST + i, w, 0.0), -1, keepdims=True)
        return out.reshape(x.shape)


def _apply(p, x, **kw):
    return moe.apply(p, x, experts_per_token=K, first_expert=FIRST, **kw)


def test_compiled_share_matches_the_plain_loop_and_drops_nothing():
    p, x = _setup(jax.random.PRNGKey(0))
    y, counters = jax.jit(lambda p, x: _apply(p, x))(p, x)
    want = jax.jit(_loop)(p, x)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(
        np.asarray(y, np.float32) / scale, np.asarray(want) / scale,
        atol=3e-2)
    assert float(counters[moe.ASSIGNMENTS_DROPPED]) == 0.0
    # a quarter of the experts held: about a quarter of the assignments
    rows = x.shape[0] * x.shape[1] * K
    held = float(counters[moe.ASSIGNMENTS_HELD])
    assert 0.15 < held / rows < 0.35
    # on the chip the passes in row order stop at the last held row's tile
    tile = row_map.row_tile(rows)
    assert tile == 512
    assert int(row_map.rows_worked(int(held), rows)) == (
        -(-held // tile) * tile) < rows


def test_with_poisoned_tails_the_compiled_share_and_its_gradients_hold():
    """NaN in every row past the held ones of every buffer, before and
    after every grouped product and every pass in row order, forward and
    backward: the layer and its five gradients are finite and the plain
    loop's. What a row past the held ones holds reaches nothing."""
    p, x = _setup(jax.random.PRNGKey(4))
    through = lambda fn: jax.jit(jax.value_and_grad(
        lambda p, x: jnp.mean(fn(p, x).astype(jnp.float32) ** 2),
        argnums=(0, 1)))(p, x)
    with poisoned_rows.patched(interpret=False):
        got = through(lambda p, x: _apply(p, x)[0])
    want = through(_loop)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale, np.asarray(b) / scale,
            atol=5e-2)


def test_compiled_gradients_match_the_plain_loop():
    p, x = _setup(jax.random.PRNGKey(2))
    through = lambda fn: jax.jit(jax.grad(
        lambda p, x: jnp.mean(fn(p, x).astype(jnp.float32) ** 2),
        argnums=(0, 1)))(p, x)
    got = through(lambda p, x: _apply(p, x)[0])
    want = through(_loop)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale, np.asarray(b) / scale,
            atol=5e-2)


@pytest.mark.parametrize("precision,low,high", [
    ("int8", 1e-3, 0.05),
    # e4m3 keeps three mantissa bits: a v5e has no fp8 product, and a cast
    # that the compiler takes out again would read as bf16 here (under 1e-2)
    ("fp8", 2e-2, 0.15),
])
def test_narrow_expert_products_run_and_differ(precision, low, high):
    p, x = _setup(jax.random.PRNGKey(3))
    y = jax.jit(lambda p, x: _apply(p, x)[0])(p, x)
    yq = jax.jit(lambda p, x: _apply(
        p, x, matmul_precision=precision)[0])(p, x)
    gap = float(jnp.linalg.norm((yq - y).astype(jnp.float32))
                / jnp.linalg.norm(y.astype(jnp.float32)))
    assert low < gap < high, gap


def _ms(fn, *args, calls=20):
    """Milliseconds a call, the best of three rounds, after two warm calls."""
    for _ in range(2):
        out = jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - start) / calls * 1e3)
    return best, out


@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304)])
def test_the_cells_products_are_ragged_dots_and_faster(k, n):
    """``mellum2.steady-8k``'s two shapes: 131,072 rows of which a quarter
    are real, in 16 uneven groups. Forward and both transposes against
    ``lax.ragged_dot``'s on the real rows (the others hold NaN going in),
    each alone in its own program, both timed and printed (``-s``)."""
    rows, groups = 131072, 16
    rng = np.random.default_rng(k)
    sizes = rng.multinomial(rows, [1 / 64] * 64)[:groups].astype(np.int32)
    real = int(sizes.sum())
    sizes = jnp.asarray(sizes)
    key = jax.random.PRNGKey(n)
    past = (jnp.arange(rows) >= real)[:, None]
    xs = jnp.where(past, jnp.nan, jax.random.normal(
        key, (rows, k), jnp.bfloat16))
    w = (jax.random.normal(jax.random.fold_in(key, 1), (groups, k, n))
         * k ** -0.5).astype(jnp.bfloat16)
    ct = jnp.where(past, jnp.nan, jax.random.normal(
        jax.random.fold_in(key, 2), (rows, n), jnp.bfloat16))

    def three(product):
        forward = jax.jit(lambda xs, w: product(xs, w, sizes))
        d_xs = jax.jit(lambda ct, w: jax.vjp(
            lambda a: product(a, w, sizes), jnp.zeros_like(xs))[1](ct)[0])
        d_w = jax.jit(lambda xs, ct: jax.vjp(
            lambda b: product(xs, b, sizes), jnp.zeros_like(w))[1](ct)[0])
        return {"forward": _ms(forward, xs, w), "d_xs": _ms(d_xs, ct, w),
                "d_w": _ms(d_w, xs, ct)}

    got, want = three(grouped_matmul), three(lax.ragged_dot)
    for name in got:
        (ms, a), (ragged_ms, b) = got[name], want[name]
        print(f"{k}x{n} {name}: kernel {ms:.3f} ms, "
              f"lax.ragged_dot {ragged_ms:.3f} ms, {real} real rows")
        if name != "d_w":
            a, b = a[:real], b[:real]
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.all(np.isfinite(a)), name
        # both round a float32 sum to bf16 once
        np.testing.assert_allclose(a, b, atol=2 ** -7 * np.abs(b).max(),
                                   err_msg=name)
        assert ms < ragged_ms, name


@pytest.mark.parametrize("name,width", [
    ("moe_silu_up", 896), ("moe_silu_up_t", 896), ("moe_add", 2304),
    ("moe_combine_t", 2304)])
def test_the_cells_row_passes_are_their_expressions_and_faster(name, width):
    """``mellum2.steady-8k``'s buffers, 131,072 rows: each map bounded by a
    quarter of the rows (what the cell holds) and by all of them (the worst
    case) against the same body as the compiler fuses it over all rows, all
    timed and printed (``-s``). Bounded by a quarter it must win; by all of
    them it may cost 5 % more, or ``moe._row_passes`` is wrong to take it
    whenever an expert is absent."""
    rows, bf16 = 131072, jnp.bfloat16
    key = jax.random.PRNGKey(width)
    wide = [jax.random.normal(jax.random.fold_in(key, i), (rows, width), bf16)
            for i in range(3)]
    a_row = jax.random.normal(key, (rows,), jnp.float32)
    # the combine's transpose gathers its first operand from the tokens'
    # rows, eight assignments a token
    tokens = jax.random.normal(jax.random.fold_in(key, 3),
                               (rows // 8, width), bf16)
    row_of = jax.random.permutation(key, rows).astype(jnp.int32) // 8
    body, operands, outs = poisoned_rows.the_layers_maps(
        wide, a_row, tokens, row_of)[name]
    fused = jax.jit(lambda *a: row_map.row_map(body, a, outs, rows, name=name))
    kernel = jax.jit(lambda held, *a: row_map.row_map(
        body, a, outs, held, name=name, interpret=False))
    fused_ms, want = _ms(fused, *operands)
    quarter_ms, got = _ms(kernel, jnp.int32(rows // 4 + 5), *operands)
    all_ms, _ = _ms(kernel, jnp.int32(rows), *operands)
    jax.block_until_ready(kernel(jnp.int32(0), *operands))  # no tile at all
    print(f"{name} {rows}x{width}: fused over all rows {fused_ms:.3f} ms, "
          f"bounded by a quarter {quarter_ms:.3f} ms, by all {all_ms:.3f} ms")
    worked = int(row_map.rows_worked(rows // 4 + 5, rows))
    for a, b in zip(got, want):
        a = np.asarray(a[:worked], np.float32)
        b = np.asarray(b[:worked], np.float32)
        np.testing.assert_allclose(a, b, rtol=2 ** -6, atol=2 ** -6,
                                   err_msg=name)
    assert quarter_ms < 0.5 * fused_ms, name
    assert all_ms < 1.05 * fused_ms, name


def test_the_gated_shared_expert_at_the_kanana_shape():
    """``kanana2.steady-8k``'s shared expert, 2048 -> 1536 -> 2048 over
    16,384 tokens, gated (``shared_gate`` in the tree), compiled in bf16
    against the same expression in float32 at ``highest``; and added once to
    a share's routed result."""
    d, f = 2048, 1536
    p = moe.init(jax.random.PRNGKey(21), d_model=d, d_expert=768,
                 n_experts=128, n_held=16, gated=True, d_shared=f,
                 score_bias=True)
    x = jax.random.normal(jax.random.PRNGKey(22), (2, 8192, d), jnp.float32)
    got = jax.jit(lambda p, x: moe._shared(p, x, jnp.bfloat16))(p, x)
    assert got.shape == x.shape and got.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = (jax.nn.silu(x @ p["shared_gate"]["w"])
                * (x @ p["shared_up"]["w"])) @ p["shared_down"]["w"]
    # three products on bf16 operands (2 ** -9 each) and two roundings of
    # the result: the gap is a few thousandths of the result's size
    gap = float(jnp.sqrt(jnp.mean((got.astype(jnp.float32) - want) ** 2)))
    size = float(jnp.sqrt(jnp.mean(want ** 2)))
    assert gap < 1e-2 * size, (gap, size)
    both = lambda p: jax.jit(lambda p, x: moe.apply(
        p, x, experts_per_token=6, router_scale=2.448,
        router_score="sigmoid")[0])(p, x)
    routed = {n: v for n, v in p.items() if not n.startswith("shared_")}
    added = both(p).astype(jnp.float32) - both(routed).astype(jnp.float32)
    gap = float(jnp.sqrt(jnp.mean((added - want) ** 2)))
    assert gap < 2e-2 * size, (gap, size)


# -- the two gathers in token order, at the routed cells' shapes --------------

CELLS = {  # tokens a chip, k, held, published, width
    "mellum2.steady-8k": (16384, 8, 16, 64, 2304),
    "nemotron3nano.steady-8k": (16384, 6, 8, 128, 2688),
    "kanana2.steady-8k": (16384, 6, 16, 128, 2048),
}


def _rms(v):
    return float(jnp.sqrt(jnp.mean(jnp.square(v.astype(jnp.float32)))))


def _in_float32(src, pos, group, c, held):
    """The token sums as the layer's ``jax.numpy`` form makes them, but in
    float32 from the gather on and not rounded at the end."""
    rows = jnp.take(src, pos, axis=0, mode="clip").astype(jnp.float32)
    rows = jnp.where((group < held)[..., None], rows, 0.0)
    return jnp.sum(rows * (1.0 if c is None else c[..., None]), axis=1)


def _in_bf16(src, pos, group, c, held):
    """What the layer ran before the kernel: the gather of every
    assignment, masked, weighed and summed in float32, rounded once."""
    return _in_float32(src, pos, group, c, held).astype(src.dtype)


def _late(plan):
    """The plan with every range starting one row late: each range's first
    row is then summed by no token."""
    return plan._replace(ranges=jnp.where(plan.ranges % 256 > 0,
                                          plan.ranges + 256, plan.ranges))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_token_sums_are_the_float32_forms_and_faster(cell):
    """At the cell's shapes (uniform routing at its k and held share, rows
    past the held ones NaN) both call sites on the kernel against the
    float32 form, rms error over rms; every range started a row late, with
    the rows past the held ones finite, far off; the kernel and the
    ``jax.numpy`` form it replaces timed (``-s``)."""
    n, k, held, experts, width = CELLS[cell]
    src, pos, group, weights, rows = routed_buffer(
        n, k, held, experts, width, seed=n + k + held)
    finite = jnp.where(jnp.isnan(src), jnp.ones_like(src), src)
    plan = jax.jit(token_sum.plan, static_argnums=1)(group, held)
    kernel = jax.jit(token_sum.token_sum)
    for site, c in (("combine", weights), ("dispatch_t", None)):
        want = jax.jit(_in_float32, static_argnums=4)(src, pos, group, c,
                                                      held)
        kernel_ms, got = _ms(kernel, src, pos, plan, c)
        gather_ms, _ = _ms(jax.jit(_in_bf16, static_argnums=4), src, pos,
                           group, c, held)
        err = _rms(got.astype(jnp.float32) - want) / _rms(want)
        late = kernel(finite, pos, _late(plan), c).astype(jnp.float32)
        bad = _rms(late - want) / _rms(want)
        print(f"{cell} {site}: kernel {kernel_ms:.3f} ms, gather of every "
              f"assignment {gather_ms:.3f} ms; rms error {err:.2e}, ranges "
              f"a row late {bad:.2e}; rows read {int(plan.rows_read)} of "
              f"{n * k}, {rows} held")
        assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
        assert err < 3e-3, (site, err)  # bf16's rounding of the result
        # a range's first row lost: a sixteenth to a sixth of the held rows
        assert bool(jnp.all(jnp.isfinite(late))) and bad > 0.1, (site, bad)
        assert kernel_ms < 0.5 * gather_ms, site
    assert rows < int(plan.rows_read) < 0.7 * n * k


def test_on_four_chips_the_token_sums_run_under_shard_map():
    """A Pallas call has no partitioning rule: with the tokens over a mesh
    of four, each chip plans and sums its own tokens' rows of its own
    buffer (``mellum2.steady-8k``'s shapes a chip), as the layer's members
    do, against the float32 form on the same chip."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if jax.device_count() < 4:
        pytest.skip("a mesh of four needs four chips")
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    n, k, held, experts, width = CELLS["mellum2.steady-8k"]
    parts = [routed_buffer(n, k, held, experts, width, seed=40 + i)
             for i in range(4)]
    put = lambda i: jax.device_put(jnp.concatenate([p[i] for p in parts]),
                                   NamedSharding(mesh, P("data")))
    src, pos, group, weights = (put(i) for i in range(4))

    def member(src, pos, group, c):
        plan = token_sum.plan(group, held)
        return (token_sum.token_sum(src, pos, plan, c),
                _in_float32(src, pos, group, c, held))

    both = jax.jit(jax.shard_map(
        member, mesh=mesh, in_specs=(P("data"),) * 4,
        out_specs=(P("data"), P("data")), check_vma=False))
    text = both.lower(src, pos, group, weights).compile().as_text()
    assert "moe_token_sum" in text and "all-gather" not in text
    assert f"bf16[{n * k},{width}]" in text  # a chip's own buffer
    got, want = both(src, pos, group, weights)
    for i in range(4):
        rows = slice(i * n, (i + 1) * n)
        err = _rms(got[rows].astype(jnp.float32) - want[rows]) / _rms(
            want[rows])
        print(f"chip {i}: rms error {err:.2e}")
        assert err < 3e-3


def test_on_four_chips_of_experts_the_layer_and_its_counter_hold():
    """The layer on a mesh of four ``expert`` members, each holding four of
    the sixteen experts and every token: each member's token sums run on
    its own buffer, the partial results add up to the plain loop over all
    sixteen, and ``moe.token_rows_read`` is the members' mean of the lines
    each copies, as counted by hand from the routing."""
    from mpi_operator_tpu.runtime import MeshPlan, build_mesh
    from mpi_operator_tpu.runtime.topology import AXIS_EXPERT
    if jax.device_count() < 4:
        pytest.skip("a mesh of four needs four chips")
    mesh = build_mesh(MeshPlan(axes={AXIS_EXPERT: 4}), jax.devices()[:4])
    key = jax.random.PRNGKey(5)
    p = moe.init(key, d_model=D, d_expert=F, n_experts=E, n_held=E)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 512, D),
                          jnp.float32)
    y, counters = jax.jit(lambda p, x: moe.apply(
        p, x, experts_per_token=K, mesh=mesh))(p, x)
    with jax.default_matmul_precision("highest"):
        xf = x.reshape(-1, D)
        w, e = jax.jit(moe.route, static_argnums=2)(xf, p["router"]["w"], K)
        gate = jax.nn.silu(jnp.einsum("td,edf->etf", xf, p["w_gate"]["w"]))
        up = jnp.einsum("td,edf->etf", xf, p["w_up"]["w"])
        ys = jnp.einsum("etf,efd->etd", gate * up, p["w_down"]["w"])
        mine = (e[None] == jnp.arange(E)[:, None, None])  # [E, T, K]
        want = jnp.einsum("etd,et->td", ys, jnp.sum(
            jnp.where(mine, w[None], 0.0), axis=-1)).reshape(x.shape)
    scale = float(jnp.max(jnp.abs(want)))
    gap = float(jnp.max(jnp.abs(y.astype(jnp.float32) - want))) / scale
    e = np.asarray(e)
    read = np.mean([_rows_read_by_hand(
        np.where((e >= m * 4) & (e < m * 4 + 4), e - m * 4, 4), 4,
        token_sum.TILE) for m in range(4)])
    got = float(counters[moe.TOKEN_ROWS_READ])
    print(f"four expert members: largest gap {gap:.2e} of the largest "
          f"output; rows read {got} a member, {read} by hand, of {e.size}")
    assert gap < 3e-2
    assert float(counters[moe.ASSIGNMENTS_HELD]) == e.size
    assert abs(got - read) <= 0.01 * read and got < 0.7 * e.size
