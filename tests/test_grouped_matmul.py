"""kernels/grouped_matmul.py: the three kernels' bodies under the Pallas
interpreter, at sizes the MXU tiles, against ``lax.ragged_dot`` and its
``jax.vjp``. The chip's half is tests_tpu/test_moe_on_tpu.py. The compiles
for a described chip at the end take in kernels/row_map.py's maps,
kernels/token_sum.py's sums, kernels/ssd.py's scan and
kernels/ssm_conv_gate.py's two passes too: one file holds every test that
loads the TPU's compiler."""

import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import SingleDeviceSharding

from mpi_operator_tpu.kernels import row_map, token_sum
from mpi_operator_tpu.parallel import moe
from tests import poisoned_rows

# the package exports the function under the module's name
gm = importlib.import_module("mpi_operator_tpu.kernels.grouped_matmul")

R, G = 1024, 4  # two row tiles of 512

LAYOUTS = {
    "even_groups": [256, 256, 256, 256],
    "an_empty_group": [384, 0, 384, 256],
    "every_row_in_one_group": [0, 1024, 0, 0],
    "a_boundary_inside_a_tile": [100, 413, 1, 510],
    "rows_past_the_last_group": [300, 0, 250, 63],
}


def _operands(sizes, k, n, dtype=jnp.bfloat16):
    """Rows past the last group hold NaN, in ``xs`` and in the cotangent:
    no result on a real row, and no ``d_w``, may have read them."""
    key = jax.random.PRNGKey(sum(sizes) + k)
    real = sum(sizes)
    past = (jnp.arange(R) >= real)[:, None]
    xs = jnp.where(past, jnp.nan, jax.random.normal(key, (R, k))).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (G, k, n))
         * k ** -0.5).astype(dtype)
    ct = jnp.where(past, jnp.nan, jax.random.normal(
        jax.random.fold_in(key, 2), (R, n))).astype(dtype)
    return xs, w, ct, jnp.asarray(sizes, jnp.int32), real


def _near(got, want, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.all(np.isfinite(got)), what
    # both round a float32 sum to bf16 once: an ulp of the largest entry
    np.testing.assert_allclose(got, want, atol=2 ** -7 * np.abs(want).max(),
                               err_msg=what)


@pytest.mark.parametrize("k,n", [(384, 896), (896, 384)])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("product", ["forward", "d_xs", "d_w"])
def test_each_product_is_ragged_dots(product, layout, k, n):
    xs, w, ct, sizes, real = _operands(LAYOUTS[layout], k, n)
    zero_past = lambda a: jnp.where((jnp.arange(R) < real)[:, None], a, 0)
    got, got_vjp = jax.vjp(
        lambda a, b: gm.grouped_matmul(a, b, sizes, interpret=True), xs, w)
    want, want_vjp = jax.vjp(
        lambda a, b: lax.ragged_dot(a, b, sizes), zero_past(xs), w)
    assert got.dtype == want.dtype == jnp.bfloat16
    if product == "forward":
        _near(got[:real], want[:real], "forward")
        return
    d_xs, d_w = got_vjp(ct)
    want_d_xs, want_d_w = want_vjp(zero_past(ct))
    assert d_xs.dtype == want_d_xs.dtype and d_w.dtype == want_d_w.dtype
    if product == "d_xs":
        _near(d_xs[:real], want_d_xs[:real], "d_xs")
    else:  # every group's, an empty group's too: zeros
        _near(d_w, want_d_w, "d_w")


@pytest.mark.parametrize("k,n", [(384, 208), (208, 384)])
@pytest.mark.parametrize("product", ["forward", "d_xs", "d_w"])
def test_a_width_that_is_no_multiple_of_128_is_one_whole_block(product, k, n):
    """Experts 1856 = 14.5 x 128 wide, in small: 208 = 1.625 x 128 as what
    is contracted and as what comes out, a boundary inside a tile and rows
    past the last group, against ``lax.ragged_dot``."""
    assert gm._lane_tiles(208) == [208] and gm.tileable(R, k, n)
    for footprint in (gm._gmm_bytes, gm._dw_bytes):
        assert gm._tiles(R, k, n, 2, footprint) == (512, k, n)
    for layout in ("a_boundary_inside_a_tile", "rows_past_the_last_group"):
        test_each_product_is_ragged_dots(product, layout, k, n)


@pytest.mark.parametrize("product", ["forward", "d_xs", "d_w"])
def test_widths_the_budget_splits_are_accumulated_in_float32(
        monkeypatch, product):
    """A VMEM budget that 896 x 384 does not fit whole: 128 x 128 tiles,
    seven or three steps of contraction kept in the float32 scratch."""
    monkeypatch.setattr(gm, "_VMEM_BUDGET", 3 << 19)
    assert gm._tiles(R, 896, 384, 2, gm._gmm_bytes) == (512, 128, 128)
    assert gm._tiles(R, 896, 384, 2, gm._dw_bytes) == (512, 128, 128)
    test_each_product_is_ragged_dots(
        product, "a_boundary_inside_a_tile", 896, 384)


def test_tiles_come_from_the_shapes():
    """The cell's two products, whole-width where the budget allows; a row
    count that is no multiple of 512 takes the power of two it has."""
    assert gm._row_tile(131072) == 512 and gm._row_tile(96) == 32
    for k, n in ((2304, 896), (896, 2304)):
        for footprint in (gm._gmm_bytes, gm._dw_bytes):
            tm, tk, tn = gm._tiles(131072, k, n, 2, footprint)
            assert (131072 % tm, k % tk, n % tn) == (0, 0, 0)
            assert tk % 128 == 0 and tn % 128 == 0
            assert footprint(tm, tk, tn, 2) <= gm._VMEM_BUDGET
    assert gm.tileable(4096, 256, 128)
    assert not gm.tileable(96, 32, 48) and not gm.tileable(100, 128, 128)
    # experts 1856 wide: the whole width or none of it, so the transpose
    # that would hold 2688 x 1856 twice over splits the other width
    assert gm.tileable(98304, 2688, 1856) and gm.tileable(98304, 1856, 2688)
    assert not gm.tileable(98304, 2688, 1860)  # half a packed sublane over
    assert gm._tiles(98304, 2688, 1856, 2, gm._gmm_bytes) == (512, 2688, 1856)
    assert gm._tiles(98304, 2688, 1856, 2, gm._dw_bytes) == (512, 896, 1856)
    assert gm._tiles(98304, 1856, 2688, 2, gm._dw_bytes) == (512, 1856, 896)


def _dots(jaxpr):
    """Every ``dot_general`` of a jaxpr, through calls and kernel bodies."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dots(sub)


def test_operands_are_bf16_and_the_accumulator_float32():
    xs, w, ct, sizes, _ = _operands(LAYOUTS["even_groups"], 384, 896)

    def all_three(xs, w, ct):
        y, vjp = jax.vjp(
            lambda a, b: gm.grouped_matmul(a, b, sizes, interpret=True), xs, w)
        return y, vjp(ct)

    closed = jax.make_jaxpr(all_three)(xs, w, ct)
    dots = list(_dots(closed.jaxpr))
    assert len(dots) >= 3  # moe_gmm, moe_gmm_dx, moe_gmm_dw
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2
        assert eqn.params["preferred_element_type"] == jnp.float32
        assert eqn.outvars[0].aval.dtype == jnp.float32
    y, (d_xs, d_w) = jax.eval_shape(all_three, xs, w, ct)
    assert {y.dtype, d_xs.dtype, d_w.dtype} == {jnp.dtype(jnp.bfloat16)}


def test_the_bf16_layer_holds_no_narrower_type(monkeypatch):
    """``moe.apply`` at ``matmul_precision="bf16"``, as the CPU lowers it,
    with the kernels in the grouped product's place, and with the passes in
    row order as kernels too: no int8 and no fp8 anywhere in the text."""
    p = moe.init(jax.random.PRNGKey(0), d_model=256, d_expert=128,
                 n_experts=8, n_held=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 256), jnp.bfloat16)

    def lowered():  # a new function each time: jit keeps no trace of the last
        return jax.jit(jax.grad(lambda p, x: jnp.sum(moe.apply(
            p, x, experts_per_token=2, matmul_precision="bf16")[0]
            .astype(jnp.float32)))).lower(p, x).as_text()

    texts = [lowered()]
    monkeypatch.setattr(
        moe, "_grouped", lambda xs, w, sizes, precision:
        gm.grouped_matmul(xs, w, sizes, interpret=True))
    texts.append(lowered())
    monkeypatch.setattr(moe, "_row_passes", lambda *shapes: True)
    texts.append(lowered())
    assert len(set(texts)) == 3
    for text in texts:
        assert "bf16" in text
        for narrow in ("i8", "f8E", "f8e"):
            assert narrow not in text


# -- the cell's shapes, compiled for the chip's compiler (no chip needed) -----
# Every kernel's compile test is in THIS file, the scan's (kernels/ssd.py)
# too: one process at a time may load the TPU's library, the tests run a
# file a worker, and a second file that described the topology would find
# it taken and skip in silence.

@pytest.fixture(scope="module")
def topo():
    """A described v5e host of four chips: Mosaic refuses here what it would
    refuse there (a tile the VMEM limit does not hold, a slice off the
    tiling), and the partitioner a kernel it cannot split."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304)])
def test_the_cells_products_compile_for_a_v5e_at_whole_width(one_chip, k, n):
    """``mellum2.steady-8k``'s 131,072 rows in 16 groups: the three kernels
    at the tiles the rule gives, under the names the trace is read by."""
    rows, groups = 131072, 16
    for footprint in (gm._gmm_bytes, gm._dw_bytes):
        assert gm._tiles(rows, k, n, 2, footprint) == (512, k, n)
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def all_three(xs, w, ct, sizes):
        y, vjp = jax.vjp(lambda a, b: gm._grouped(a, b, sizes, False), xs, w)
        return y, vjp(ct)

    text = jax.jit(all_three).lower(
        spec((rows, k)), spec((groups, k, n)), spec((rows, n)),
        spec((groups,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in (r"moe_gmm_*\.\d", r"moe_gmm_dx_*\.\d", r"moe_gmm_dw_*\.\d"):
        assert re.search(name, text), name


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)])
def test_products_1856_wide_compile_for_a_v5e(one_chip, k, n):
    """``nemotron3nano.steady-8k``'s 98,304 rows in 8 groups, experts 1856
    = 14.5 x 128 wide: Mosaic takes the whole-width block in all three
    kernels (it would refuse here what it refuses on the chip)."""
    rows, groups = 98304, 8
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def all_three(xs, w, ct, sizes):
        y, vjp = jax.vjp(lambda a, b: gm._grouped(a, b, sizes, False), xs, w)
        return y, vjp(ct)

    text = jax.jit(all_three).lower(
        spec((rows, k)), spec((groups, k, n)), spec((rows, n)),
        spec((groups,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)])
def test_products_768_wide_compile_for_a_v5e(one_chip, k, n):
    """``kanana2.steady-8k``'s 98,304 rows in 16 groups, experts 768 = six
    lane tiles wide, the three kernels under their names."""
    rows, groups = 98304, 16
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def all_three(xs, w, ct, sizes):
        y, vjp = jax.vjp(lambda a, b: gm._grouped(a, b, sizes, False), xs, w)
        return y, vjp(ct)

    text = jax.jit(all_three).lower(
        spec((rows, k)), spec((groups, k, n)), spec((rows, n)),
        spec((groups,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("heads,window", [(48, None), (64, 512)])
def test_the_flash_kernels_compile_for_a_v5e_at_the_gated_cells_heads(
        one_chip, heads, window):
    """``lagunaxs2.steady-8k``'s attention: 2 rows x 8192 positions, 48
    query heads over 8 KV heads (group 6) causal on full layers, 64 over 8
    (group 8) under a 512-key band on window layers, heads of 128; forward,
    ``dq`` and ``dk`` / ``dv`` at the tiles each call picks (1024 x 1024 on
    full layers, 512 x 512 walking the band on window layers), each one
    Mosaic call under its name."""
    fa = importlib.import_module("mpi_operator_tpu.kernels.flash_attention")
    spec = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=one_chip)
    tile = 1024 if window is None else fa._band_tile(window, 8192)

    def all_three(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: fa._flash(
            q, k, v, True, 128 ** -0.5, tile, tile, False, window), q, k, v)
        return o, vjp(do)

    q, kv = spec(2, heads, 8192, 128), spec(2, 8, 8192, 128)
    text = jax.jit(all_three).lower(q, kv, kv, q).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert re.search(name + r"_*\.\d", text), name


@pytest.mark.parametrize("d,dv", [(192, 128), (128, 128)])
def test_the_flash_kernels_compile_for_a_v5e_at_unlike_head_sizes(
        one_chip, d, dv):
    """Latent attention's shapes in ``kanana2.steady-8k``: 2 rows x 32 heads
    x 8192 positions, keys and queries 192 wide (one and a half lane tiles:
    a whole-width block), values, the output and its cotangent 128; forward,
    ``dq`` and ``dk`` / ``dv``, each one Mosaic call under its name. Beside
    it the equal sizes every other cell runs."""
    fa = importlib.import_module("mpi_operator_tpu.kernels.flash_attention")
    spec = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=one_chip)

    def all_three(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: fa._flash(
            q, k, v, True, d ** -0.5, 1024, 1024, False, None), q, k, v)
        return o, vjp(do)

    compiled = jax.jit(all_three).lower(
        spec(2, 32, 8192, d), spec(2, 32, 8192, d), spec(2, 32, 8192, dv),
        spec(2, 32, 8192, dv)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert re.search(name + r"_*\.\d", text), name
    o, (dq, dk, d_v) = jax.eval_shape(
        all_three, spec(2, 32, 8192, d), spec(2, 32, 8192, d),
        spec(2, 32, 8192, dv), spec(2, 32, 8192, dv))
    assert (o.shape[-1], dq.shape[-1], dk.shape[-1], d_v.shape[-1]) == (
        dv, d, d, dv)


@pytest.mark.parametrize("name", ["moe_relu2", "moe_relu2_t"])
def test_the_ungated_row_passes_compile_for_a_v5e_at_1856(one_chip, name):
    rows, width = 98304, 1856
    assert row_map.row_tile(rows) == 512 and row_map.mappable(rows, width)
    assert not row_map.mappable(rows, 100)
    wide = jax.ShapeDtypeStruct((rows, width), jnp.bfloat16,
                                sharding=one_chip)
    held = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    body, operands = {"moe_relu2": (moe._relu2, (wide,)),
                      "moe_relu2_t": (moe._relu2_t, (wide, wide))}[name]
    text = jax.jit(lambda rows, *operands: row_map.row_map(
        body, operands, ((width, jnp.bfloat16),), rows, name=name,
        interpret=False)).lower(held, *operands).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(name + r"_*\.\d", text), name


@pytest.mark.parametrize("name,width", [
    ("moe_silu_up", 896), ("moe_silu_up_t", 896), ("moe_add", 2304),
    ("moe_combine_t", 2304)])
def test_the_cells_row_passes_compile_for_a_v5e(one_chip, name, width):
    """The routed feed-forward's four maps over ``mellum2.steady-8k``'s
    131,072 rows by 896 and by 2304, blocks of 512 rows by the whole width:
    each one Mosaic call under the name the trace will show, inside the
    VMEM limit its launcher states (Mosaic refuses a kernel that is not)."""
    rows = 131072
    assert row_map.row_tile(rows) == 512 and row_map.mappable(rows, width)
    wide = jax.ShapeDtypeStruct((rows, width), jnp.bfloat16,
                                sharding=one_chip)
    a_row = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one_chip)
    held = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    # the combine's transpose gathers its first operand's rows itself, from
    # the 16,384 tokens' cotangent held whole in VMEM (75 MB)
    tokens = jax.ShapeDtypeStruct((rows // 8, width), jnp.bfloat16,
                                  sharding=one_chip)
    row_of = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    assert row_map._gathers_inside(tokens) == (width == 2304)
    body, operands, outs = poisoned_rows.the_layers_maps(
        (wide,) * 3, a_row, tokens, row_of)[name]
    text = jax.jit(lambda rows, *operands: row_map.row_map(
        body, operands, outs, rows, name=name, interpret=False)).lower(
        held, *operands).compile().as_text()
    assert "jit(_take)" not in text  # no gather of the compiler's
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(name + r"_*\.\d", text), name
    # a number a row goes in and comes out as it lies in HBM: no copy into
    # a layout that pads it to a tile's width
    assert not re.search(r"f32\[131072,1\]", text)


# the routed cells' layers a chip: tokens, k, held of published experts,
# model width, expert width, gated
ROUTED_CELLS = {
    "mellum2.steady-8k": (16384, 8, 16, 64, 2304, 896, True),
    "nemotron3nano.steady-8k": (16384, 6, 8, 128, 2688, 1856, False),
    "kanana2.steady-8k": (16384, 6, 16, 128, 2048, 768, True),
}


@pytest.mark.parametrize("cell", sorted(ROUTED_CELLS))
def test_the_cells_token_sums_compile_for_a_v5e(one_chip, cell):
    """Both call sites of the token sum (kernels/token_sum.py), weighted and
    not, at the cell's buffer: one Mosaic call each under the name the trace
    is read by, inside the VMEM limit its launcher states."""
    n, k, held, _, d, _, _ = ROUTED_CELLS[cell]
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    plan = token_sum.Plan(spec((n // 128 * held,), jnp.int32),
                          spec((), jnp.int32))
    assert token_sum.takes(n, d, jnp.bfloat16)
    text = jax.jit(lambda src, pos, plan, c: (
        token_sum.token_sum(src, pos, plan, c),
        token_sum.token_sum(src, pos, plan))
    ).lower(spec((n * k, d), jnp.bfloat16), spec((n, k), jnp.int32), plan,
            spec((n, k), jnp.float32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert re.search(r"moe_token_sum_*\.\d", text)


@pytest.mark.parametrize("cell", sorted(ROUTED_CELLS))
def test_on_the_kernel_path_the_layer_gathers_no_row_of_every_assignment(
        one_chip, monkeypatch, cell):
    """The cell's routed layer, value and gradients, compiled for a v5e on
    the path the chip takes (the grouped products, the passes in row order
    and the token sums as kernels): the two gathers in token order are the
    token sum, and nothing gathers an ``[N, k, D]`` array of every
    assignment's row; the dispatch's gather in row order is still there."""
    n, k, held, experts, d, f, gated = ROUTED_CELLS[cell]
    monkeypatch.setattr(moe, "_row_passes", lambda *shapes: False)
    monkeypatch.setattr(moe, "_grouped", lambda xs, w, sizes, precision:
                        gm.grouped_matmul(xs, w, sizes, interpret=False))
    p = jax.eval_shape(lambda: moe.init(
        jax.random.PRNGKey(0), d_model=d, d_expert=f, n_experts=experts,
        n_held=held, gated=gated, score_bias=not gated))
    put = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    x = jax.ShapeDtypeStruct((2, n // 2, d), jnp.bfloat16, sharding=one_chip)
    loss = lambda p, x: jnp.sum(moe.apply(
        p, x, experts_per_token=k,
        router_score="softmax" if gated else "sigmoid")[0]
        .astype(jnp.float32) ** 2)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        jax.tree.map(put, p), x).compile().as_text()
    assert re.search(r"moe_token_sum_*\.\d", text)
    assert not re.search(rf"bf16\[{n},{k},{d}\]\S* gather\(", text)
    assert not re.search(rf"\[{n},{k},{d}\]", text)
    assert re.search(rf"bf16\[{n * k},{d}\]\S* gather\(", text)


def test_the_token_sums_compile_for_four_v5e_chips_under_shard_map(topo):
    """``mellum2.steady-8k``'s buffer a chip on a mesh of four, the tokens
    over ``data``: each chip plans and sums its own tokens' rows of its own
    buffer, as the layer's members do; nothing is gathered around the
    kernel."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    n, k, held, _, d, _, _ = ROUTED_CELLS["mellum2.steady-8k"]
    mesh = Mesh(np.array(topo.devices), ("data",))
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P("data")))

    def member(src, pos, group, c):
        plan = token_sum.plan(group, held)
        return token_sum.token_sum(src, pos, plan, c), token_sum.token_sum(
            src, pos, plan)

    text = jax.jit(jax.shard_map(
        member, mesh=mesh, in_specs=(P("data"),) * 4,
        out_specs=(P("data"), P("data")), check_vma=False)).lower(
        spec((4 * n * k, d), jnp.bfloat16), spec((4 * n, k), jnp.int32),
        spec((4 * n, k), jnp.int32), spec((4 * n, k), jnp.float32)
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert re.search(rf"bf16\[{n * k},{d}\]", text)  # a chip's own buffer
    assert "all-gather" not in text and "all-to-all" not in text


@pytest.mark.parametrize("pass_states", [True, False])
def test_the_cells_scan_compiles_for_a_v5e(one_chip, pass_states):
    """``nemotron3nano.steady-8k``'s state-space scan, 2 rows of 8192 in
    chunks of 128, 64 heads of 64 in 8 groups, state 128 (kernels/ssd.py):
    forward, replay and backward are three Mosaic calls under the names the
    trace is read by, and what they need beside their operands is the saved
    states, not a [Q, Q] matrix a head."""
    from mpi_operator_tpu.kernels import ssd
    bsz, t, h, p, g, n, chunk = 2, 8192, 64, 64, 8, 128, 128
    assert ssd.tileable(chunk, n, h // g, p)
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def value_and_grads(*v):
        loss = lambda x, dt, a, b, c, d: jnp.sum(jax.checkpoint(
            lambda *v: ssd.scan(*v, skip=d, chunk=chunk,
                                pass_states=pass_states, interpret=False))(
            x, dt, a, b, c).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=range(6))(*v)

    compiled = jax.jit(value_and_grads).lower(
        spec((bsz, t, h, p)), spec((bsz, t, h), jnp.float32),
        spec((h,), jnp.float32), spec((bsz, t, g, n)),
        spec((bsz, t, g, n)), spec((h,), jnp.float32)).compile()
    text = compiled.as_text()
    # with the fault planted the replay saves no states: it IS the forward,
    # and the compiler keeps one of the two
    assert text.count('custom_call_target="tpu_custom_call"') == (
        3 if pass_states else 2)
    assert re.search(r"ssd_fwd_*\.\d", text)
    assert re.search(r"ssd_bwd_*\.\d", text)
    # one head's decays over every (row, chunk) would be 537 MB in float32;
    # the saved states are 268 MB and the operands' copies the rest
    decays = bsz * (t // chunk) * h * chunk * chunk * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * decays


def test_the_cells_scan_compiles_for_four_v5e_chips_under_shard_map(topo):
    """The same scan on a 2 x 2 mesh (rows over ``data``, groups over
    ``tensor``): the kernels are a device's own, one row of 8192 and four
    groups each, and nothing is gathered around them: no collective but
    the sums of A's and D's gradients over the rows' axis."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mpi_operator_tpu.kernels import ssd
    bsz, t, h, p, g, n, chunk = 2, 8192, 64, 64, 8, 128, 128
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "tensor"))
    spec = lambda shape, parts, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P(*parts)))
    wide = ("data", None, "tensor", None)

    def value_and_grads(*v):
        loss = lambda x, dt, a, b, c, d: jnp.sum(jax.checkpoint(
            lambda *v: ssd.scan(*v, skip=d, chunk=chunk, interpret=False,
                                mesh=mesh))(x, dt, a, b, c).astype(
            jnp.float32))
        return jax.value_and_grad(loss, argnums=range(6))(*v)

    compiled = jax.jit(value_and_grads).lower(
        spec((bsz, t, h, p), wide),
        spec((bsz, t, h), wide[:3], jnp.float32),
        spec((h,), ("tensor",), jnp.float32), spec((bsz, t, g, n), wide),
        spec((bsz, t, g, n), wide),
        spec((h,), ("tensor",), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    # a device's share: x [1, 8192, 2048] in, and its saved states
    assert re.search(r"bf16\[1,8192,2048\]", text)
    assert "all-gather" not in text and "all-to-all" not in text
    decays = bsz * (t // chunk) * h * chunk * chunk * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * decays // 4



def _the_cells_two_passes(mesh, bsz=2):
    """Value and gradients of ``nemotron3nano.steady-8k``'s convolution
    (rows of 8192; ``x`` 4096, ``B`` and ``C`` 1024 channels each, K = 4)
    and gated norm (4096 channels in 8 groups) through their kernels
    (kernels/ssm_conv_gate.py), each operand read where it lies in the
    input projection's result, 10,304 wide (models/mamba2.py), under a
    layer's checkpoint; and the shapes they take."""
    from mpi_operator_tpu.kernels import ssm_conv_gate as scg
    t, inner, state, k, groups = 8192, 4096, 1024, 4, 8
    conv, proj = inner + 2 * state, 2 * inner + 2 * state + 64
    parts = ((0, inner), (inner, state), (inner + state, state))
    assert all(scg.conv_tileable(t, width, k, inner + at)
               for at, width in parts)
    assert scg.gate_tileable(bsz * t, inner, groups)

    def value_and_grads(zxbcdt, w, bias, y, scale):
        def layer(zxbcdt, w, bias, y, scale):
            x, b, c = (scg.conv_silu(
                zxbcdt, w[at:at + width], bias[at:at + width],
                first=inner + at, interpret=False, mesh=mesh)
                for at, width in parts)
            gated = scg.gate_norm(y, zxbcdt, scale, groups=groups, eps=1e-5,
                                  interpret=False, mesh=mesh)
            return sum(jnp.sum(v.astype(jnp.float32) ** 2)
                       for v in (x, b, c, gated))
        return jax.value_and_grad(jax.checkpoint(layer), argnums=range(5))(
            zxbcdt, w, bias, y, scale)

    f32 = jnp.float32
    return value_and_grads, (
        ((bsz, t, proj), jnp.bfloat16), ((conv, k), f32), ((conv,), f32),
        ((bsz, t, inner), jnp.bfloat16), ((inner,), f32))


def test_the_cells_convolution_and_gated_norm_compile_for_a_v5e(one_chip):
    """Twelve Mosaic calls under the names the trace is read by (forward,
    replay and backward; the convolution's once each for ``x``, ``B`` and
    ``C``), no copy of a
    slice of the projection's result in front of them, and beside the
    operands and results nothing but the four results kept for the loss's
    cotangents and those, in bf16: nothing padded, widened to float32 or
    reshaped to groups in HBM (one float32 copy of the convolved channels
    alone is 403 MB)."""
    value_and_grads, shapes = _the_cells_two_passes(None)
    compiled = jax.jit(value_and_grads).lower(*(
        jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        for s, d in shapes)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 12
    for name in ("ssm_conv_fwd", "ssm_conv_bwd", "ssm_gate_fwd",
                 "ssm_gate_bwd"):
        assert re.search(name + r"_*\.\d", text), name
    entry = text[text.index("ENTRY"):]
    assert not re.search(
        r"bf16\[2,8192,(4096|6144|1024)\]\S* (slice|copy)\(", entry)
    wide = lambda shape: 2 * int(np.prod(shape))  # bytes in bf16
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < 1.05 * 2 * (wide((2, 8192, 6144)) + wide(shapes[3][0]))


def test_the_cells_two_passes_compile_for_four_v5e_chips_under_shard_map(
        topo):
    """On a mesh of four (rows over ``fsdp`` and ``data``) the kernels are
    a device's own, one row of 8192 with every channel, and nothing is
    gathered around them: no collective but the sums of the weights',
    bias's and scale's gradients over the rows' axes."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "fsdp"))
    value_and_grads, shapes = _the_cells_two_passes(mesh, bsz=4)
    rows = P(("data", "fsdp"))
    compiled = jax.jit(value_and_grads).lower(*(
        jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(
            mesh, rows if len(s) == 3 else P()))
        for s, d in shapes)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 12
    assert re.search(r"bf16\[1,8192,10304\]", text)  # a device's share
    assert not re.search(r"bf16\[4,8192,(10304|4096)\]", text)
    assert "all-gather" not in text and "all-to-all" not in text
