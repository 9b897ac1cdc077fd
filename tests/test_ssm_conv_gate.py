"""The Mamba-2 layer's two memory-bound passes (kernels/ssm_conv_gate.py):
the Pallas kernels through the interpreter (``interpret=True``) against the
``jax.numpy`` forms they stand for (``ssd.causal_conv`` with its silu,
``_gate_norm_passes``), in float32: the value and every cotangent, over
several tiles of positions (the halo across a tile's edge in both
directions), at a row's first positions and across the rows of a batch;
with the fault planted (``halo=False``: a tile starts from nought and hands
nothing back) failing the same comparisons. Nothing in the benchmark's
``correct`` bounds the convolution's weights and bias from above (PERF.md
section 7, row 19a): these cases are what holds them."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.kernels import ssd
from mpi_operator_tpu.kernels import ssm_conv_gate as scg
from tests.test_ssd import _bare_kernels, _equations

# three tiles of 16 positions, three of 128 channels (a group each); K as
# published
B, T, C, K = 2, 48, 384, 4
GROUPS, EPS = 3, 1e-5
# the gated norm's tiles: (channels, groups) -> blocks across the channels
GATE_LAYOUTS = {"a_group_a_block": (C, GROUPS, 3),
                "four_groups_a_block": (512, 4, 1)}
TOLERANCE = 2e-6  # of a result's norm: float32 sums in another order
CONV_PARTS = ("y", "dx", "dw", "dbias")
GATE_PARTS = ("out", "dy", "dz", "dscale")


def _conv_passes(x, w, bias):
    return jax.nn.silu(ssd.causal_conv(x, w, bias)).astype(x.dtype)


def _conv_kernels(x, w, bias, **how):
    return scg.conv_silu(x, w, bias, **{"interpret": True, **how})


def _gate_passes(y, z, scale, groups=GROUPS):
    return scg._gate_norm_passes(y, z, scale, groups, EPS)


def _gate_kernels(y, z, scale, groups=GROUPS, **how):
    return scg.gate_norm(y, z, scale, groups=groups, eps=EPS,
                         **{"interpret": True, **how})


def _conv_inputs(b=B, t=T, c=C, k=K, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    return (jax.random.normal(ks[0], (b, t, c)).astype(dtype),
            jax.random.normal(ks[1], (c, k)) * k ** -0.5,
            0.3 * jax.random.normal(ks[2], (c,)),
            jax.random.normal(ks[3], (b, t, c)))


def _gate_inputs(b=B, t=T, c=C, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    return (jax.random.normal(ks[0], (b, t, c)).astype(dtype),
            jax.random.normal(ks[1], (b, t, c)).astype(dtype),
            1.0 + 0.2 * jax.random.normal(ks[2], (c,)),
            jax.random.normal(ks[3], (b, t, c)))


def _value_and_cotangents(f, operands, weights, rounded=jnp.float32):
    """(the value, then the cotangent of each operand) of ``sum f w``, the
    weights rounded to ``rounded`` whatever the value's dtype."""
    value, pull = jax.vjp(f, *operands)
    return (value, *pull(weights.astype(rounded).astype(value.dtype)))


def _gap(got, want):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def conv_results():
    *operands, weights = _conv_inputs()
    return {name: _value_and_cotangents(f, operands, weights)
            for name, f in (
                ("passes", _conv_passes), ("kernels", _conv_kernels),
                ("fault", lambda *v: _conv_kernels(*v, halo=False)))}


@pytest.fixture(scope="module", params=sorted(GATE_LAYOUTS))
def gate_results(request):
    c, groups, _ = GATE_LAYOUTS[request.param]
    *operands, weights = _gate_inputs(c=c)
    return {name: _value_and_cotangents(
        lambda *v: f(*v, groups=groups), operands, weights)
        for name, f in (("passes", _gate_passes), ("kernels", _gate_kernels))}


def test_the_shapes_here_span_tiles_in_both_directions():
    grid, s = scg._conv_specs(T, C)
    assert grid == (3, 3) and (s["tile"], s["lanes"]) == (16, 128)
    for c, groups, blocks in GATE_LAYOUTS.values():
        assert scg._gate_specs(B * T, c, groups, 0, 4)[0] == (
            B * T // 32, blocks)


@pytest.mark.parametrize("part", range(4), ids=CONV_PARTS)
def test_the_convolutions_kernels_are_the_passes_and_the_fault_is_not(
        conv_results, part):
    """Forward and each cotangent; with the halo dropped the same numbers
    are off by far more than the comparison allows."""
    want = conv_results["passes"][part]
    assert _gap(conv_results["kernels"][part], want) < TOLERANCE
    assert _gap(conv_results["fault"][part], want) > 1e-2


@pytest.mark.parametrize("part", range(4), ids=GATE_PARTS)
def test_the_gated_norms_kernels_are_the_passes(gate_results, part):
    want = gate_results["passes"][part]
    assert _gap(gate_results["kernels"][part], want) < TOLERANCE


@pytest.mark.parametrize("part", (0, 1), ids=("y", "dx"))
def test_a_tiles_edge_is_where_the_dropped_halo_shows(conv_results, part):
    """Forward: a tile's first K - 1 positions miss the tile before.
    Backward: its last K - 1 miss what the tile after hands back, and its
    first K - 1 take a ``d_pre`` rebuilt without the tile before. Every
    other position is the passes' with the fault too."""
    want = np.asarray(conv_results["passes"][part])
    got = np.asarray(conv_results["kernels"][part])
    bad = np.asarray(conv_results["fault"][part])
    tile = scg._conv_specs(T, C)[1]["tile"]
    at = np.arange(T) % tile
    # ... but not at a row's own ends, where there is nought to miss
    edge = (at < K - 1) & (np.arange(T) >= tile)
    if part == 1:
        edge |= (at >= tile - (K - 1)) & (np.arange(T) < T - tile)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=1e-5 * scale, rtol=1e-5)
    np.testing.assert_allclose(bad[:, ~edge], want[:, ~edge],
                               atol=1e-5 * scale, rtol=1e-5)
    off = np.abs(bad - want)[:, edge].max(axis=(0, 2))
    assert np.all(off > 1e-2 * scale), off


def test_a_rows_first_positions_see_nought_and_nothing_of_the_row_before():
    """Position t < K - 1 of either row is the plain sum of the taps that
    reach it, whatever the other row holds; the kernels' first tile reads
    some block as its halo (the tile's own) and must not use it."""
    x, w, bias, _ = _conv_inputs()
    got = np.asarray(_conv_kernels(x, w, bias))
    xn, wn, bn = (np.asarray(v) for v in (x, w, bias))
    for t in range(K - 1):
        pre = bn + sum(wn[:, tap] * xn[:, t - (K - 1) + tap]
                       for tap in range(K) if t - (K - 1) + tap >= 0)
        np.testing.assert_allclose(got[:, t], pre / (1 + np.exp(-pre)),
                                   rtol=1e-5, atol=1e-6)
    # row 0's last positions moved: row 1 is the same to the last bit,
    # forward, and its cotangent takes nothing from row 0's
    moved = x.at[0, T - (K - 1):].add(3.0)
    np.testing.assert_array_equal(
        np.asarray(_conv_kernels(moved, w, bias))[1], got[1])
    dx = lambda weights: jax.grad(
        lambda v: jnp.sum(_conv_kernels(v, w, bias) * weights))(x)
    ones = jnp.ones_like(x)
    np.testing.assert_array_equal(
        np.asarray(dx(ones.at[1, :K].set(5.0)))[0], np.asarray(dx(ones))[0])


# a projection's result as the layer has it: z (two groups of 128), then
# the convolved channels, then 64 columns that are neither (10,304 is no
# whole number of lane tiles either)
Z, TAIL = 256, 64
WHERE_THEY_LIE = {
    "every_convolved_channel": (Z, C), "the_last_of_them": (Z + 256, 128),
    "the_gate": (0, Z), "the_gates_second_group": (128, 128)}


@pytest.mark.parametrize("what", sorted(WHERE_THEY_LIE))
def test_a_kernel_reads_its_columns_where_they_lie_in_a_wider_array(what):
    """``first``: the operand is the whole projection's result and the
    kernels cut their blocks from column ``first`` on; the value and the
    cotangents are the passes' on the slice, the wide operand's cotangent
    nought in every other column."""
    first, width = WHERE_THEY_LIE[what]
    wide = jax.random.normal(jax.random.PRNGKey(13), (B, T, Z + C + TAIL))
    ks = jax.random.split(jax.random.PRNGKey(14), 4)
    weights = jax.random.normal(ks[0], (B, T, width))
    if "gate" in what:
        y = jax.random.normal(ks[1], (B, T, width))
        scale = 1.0 + 0.2 * jax.random.normal(ks[2], (width,))
        groups = width // 128
        f = lambda wide, how: how(y, wide, scale, groups=groups)
        passes = lambda y, z, s, groups: _gate_passes(
            y, z[..., first:first + width], s, groups=groups)
        kernels = lambda *v, groups: _gate_kernels(
            *v, groups=groups, first=first)
    else:
        w = jax.random.normal(ks[1], (width, K)) * K ** -0.5
        bias = 0.3 * jax.random.normal(ks[2], (width,))
        f = lambda wide, how: how(wide, w, bias)
        passes = lambda x, w, b: _conv_passes(x[..., first:first + width], w, b)
        kernels = lambda *v: _conv_kernels(*v, first=first)
    want = _value_and_cotangents(lambda v: f(v, passes), (wide,), weights)
    got = _value_and_cotangents(lambda v: f(v, kernels), (wide,), weights)
    assert got[0].shape == (B, T, width)
    for g, w_ in zip(got, want):
        assert _gap(g, w_) < TOLERANCE
    outside = np.ones(wide.shape[-1], bool)
    outside[first:first + width] = False
    assert not np.any(np.asarray(got[1])[..., outside])
    assert not scg.conv_tileable(T, width, K, first=first + 64)
    assert not scg.gate_tileable(B * T, 256, 2, first=64)


@pytest.mark.parametrize("k", (2, 3, 9))
def test_the_taps_are_counted_from_the_weights_shape(k):
    """A later hybrid's shorter or longer convolution: K from ``w``, up to
    the halo a float32 tile holds."""
    *operands, weights = _conv_inputs(b=1, t=32, c=128, k=k)
    for got, want in zip(
            _value_and_cotangents(_conv_kernels, operands, weights),
            _value_and_cotangents(_conv_passes, operands, weights)):
        assert _gap(got, want) < TOLERANCE


@pytest.mark.parametrize("pass_", ("conv", "gate"))
def test_bf16_operands_are_widened_inside_and_rounded_once(pass_):
    """bf16 in, bf16 out and bf16 cotangents for the wide operands, float32
    ones for the weights, each within a rounding of the float32 passes on
    the same rounded operands; the passes' own bf16 results no nearer."""
    half = jnp.bfloat16
    if pass_ == "conv":
        *operands, weights = _conv_inputs(dtype=half)
        kernels, passes, wide = _conv_kernels, _conv_passes, 1
    else:
        *operands, weights = _gate_inputs(dtype=half)
        kernels, passes, wide = _gate_kernels, _gate_passes, 2
    exact = [v.astype(jnp.float32) for v in operands]
    want = _value_and_cotangents(passes, exact, weights, half)
    got = _value_and_cotangents(kernels, operands, weights, half)
    theirs = _value_and_cotangents(passes, operands, weights, half)
    for i, (g, w, t) in enumerate(zip(got, want, theirs)):
        assert g.dtype == t.dtype == (half if i <= wide else jnp.float32)
        assert _gap(g, w) < (4e-3 if i <= wide else 1e-5), i
        assert _gap(g, w) < 1.05 * _gap(t, w) + 1e-6, i


def test_widths_that_do_not_tile_take_the_passes_on_any_backend(monkeypatch):
    """``interpret=None`` chooses by the backend and the shapes alone: off a
    TPU the passes; on one the kernels where the widths are whole lane
    tiles and the rows whole packed tiles."""
    assert scg.conv_tileable(8192, 6144, 4)  # the configuration's
    assert scg.gate_tileable(2 * 8192, 4096, 8)
    assert not scg.conv_tileable(8192, 6144 + 64, 4)
    assert not scg.conv_tileable(8192 + 8, 6144, 4)
    assert not scg.conv_tileable(8192, 6144, 1)  # no convolution
    assert not scg.conv_tileable(8192, 6144, 10)  # a halo of 9
    assert not scg.gate_tileable(2 * 8192, 4096, 64)  # groups of 64
    assert not scg.gate_tileable(2 * 8192, 4096, 3)
    assert not scg.gate_tileable(8, 4096, 8)
    *conv, _ = _conv_inputs()
    *gate, _ = _gate_inputs()
    # a new function a call: ``make_jaxpr`` keeps a function's traces
    here = lambda c, g: jax.make_jaxpr(lambda c, g: (
        scg.conv_silu(*c), scg.gate_norm(*g, groups=GROUPS, eps=EPS)))(c, g)
    assert "pallas_call" not in str(here(conv, gate))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _bare_kernels(here(conv, gate).jaxpr)[0] == [
        "ssm_conv_fwd", "ssm_gate_fwd"]
    # 192 channels (groups of 64), 40 positions: the passes there too, the
    # same numbers
    *narrow, _ = _conv_inputs(t=40, c=192)
    odd = [v[:, :40, :192] if v.ndim == 3 else v[:192] for v in gate]
    assert "pallas_call" not in str(here(narrow, odd))
    np.testing.assert_array_equal(scg.conv_silu(*narrow),
                                  _conv_passes(*narrow))
    np.testing.assert_array_equal(
        scg.gate_norm(*odd, groups=GROUPS, eps=EPS), _gate_passes(*odd))


def test_the_kernels_work_in_float32_and_read_each_operand_once():
    """Off the jaxpr of the value and gradients with bf16 operands: four
    kernels by their names; inside them every multiply, ``logistic`` and
    ``rsqrt`` is float32; around them no float32 array of an operand's
    size (the passes pad and widen ``x``, and reshape the gate to groups)
    and no pad."""
    half = jnp.bfloat16
    *conv, cw = _conv_inputs(dtype=half)
    *gate, gw = _gate_inputs(dtype=half)

    def both(kernels):
        conv_f, gate_f = ((_conv_kernels, _gate_kernels) if kernels
                          else (_conv_passes, _gate_passes))
        f = lambda c, g: (jnp.sum(conv_f(*c) * cw) + jnp.sum(gate_f(*g) * gw))
        return jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(conv, gate)

    jaxpr = both(True)
    outside = list(_equations(jaxpr.jaxpr, into_kernels=False))
    calls = [e for e in outside if e.primitive.name == "pallas_call"]
    assert sorted(e.params["name"] for e in calls) == [
        "ssm_conv_bwd", "ssm_conv_fwd", "ssm_gate_bwd", "ssm_gate_fwd"]
    for call in calls:
        inside = list(_equations(call.params["jaxpr"], into_kernels=True))
        work = [e for e in inside
                if e.primitive.name in ("mul", "logistic", "rsqrt")
                and jnp.issubdtype(e.outvars[0].aval.dtype, jnp.floating)]
        assert len(work) > K
        assert all(e.outvars[0].aval.dtype == jnp.float32 for e in work)
        wide = [v.aval for v in call.invars if v.aval.ndim >= 2
                and v.aval.size >= B * T * C]
        assert wide and all(a.dtype == half for a in wide)

    def widened(jaxpr):
        """float32 arrays of an operand's size made outside a kernel, other
        than the loss's own product with its float32 weights."""
        return [e.primitive.name
                for e in _equations(jaxpr.jaxpr, into_kernels=False)
                for v in e.outvars
                if e.primitive.name in ("pad", "convert_element_type",
                                        "reshape")
                and getattr(v.aval, "size", 0) >= B * T * C
                and v.aval.dtype == jnp.float32]

    assert "pad" in widened(both(False)) and "reshape" in widened(both(False))
    assert not {"pad", "reshape"} & set(widened(jaxpr))


@pytest.mark.parametrize("axes", [
    {"data": 2, "fsdp": 2, "expert": 2}, {"fsdp": 4}])
def test_on_a_mesh_the_kernels_run_under_shard_map_over_the_rows(axes):
    """A Pallas call has no partitioning rule: on several devices each runs
    the kernels on its rows with every channel; ``w``, ``bias`` and
    ``scale``, whole on every device, get their cotangents summed."""
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:int(np.prod(list(axes.values())))]).reshape(
            tuple(axes.values())), tuple(axes))
    *conv, cw = _conv_inputs(b=4)
    *gate, gw = _gate_inputs(b=4)

    def value_and_grads(mesh):
        f = lambda c, g: (
            jnp.sum(_conv_kernels(*c, mesh=mesh) * cw)
            + jnp.sum(_gate_kernels(*g, mesh=mesh) * gw))
        return jax.value_and_grad(f, argnums=(0, 1))

    jaxpr = jax.make_jaxpr(value_and_grads(mesh))(conv, gate)
    bare, under = _bare_kernels(jaxpr.jaxpr)
    assert not bare and sorted(under) == [
        "ssm_conv_bwd", "ssm_conv_fwd", "ssm_gate_bwd", "ssm_gate_fwd"]
    local = [e for e in _equations(jaxpr.jaxpr, into_kernels=False)
             if e.primitive.name == "pallas_call"][0].invars[0].aval
    assert local.shape == (1, T, C)  # a device's row, every channel
    want, want_g = jax.jit(value_and_grads(None))(conv, gate)
    got, got_g = jax.jit(value_and_grads(mesh))(conv, gate)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for w, g in zip(jax.tree.leaves(want_g), jax.tree.leaves(got_g)):
        assert _gap(g, w) < TOLERANCE
    # one device: nothing to partition, and no shard_map
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    assert not _bare_kernels(jax.make_jaxpr(value_and_grads(one))(
        conv, gate).jaxpr)[1]


def test_where_a_tensor_axis_would_split_the_channels_the_passes_run(
        monkeypatch):
    """With the backend read as a TPU and widths that tile: on a mesh with
    a ``tensor`` axis of more than one device the two passes are the
    compiler's to partition (no kernel of theirs, bare or under a
    ``shard_map``); with that axis of one device, or none, the kernels."""
    devices = np.array(jax.devices()[:4])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    *conv, _ = _conv_inputs(b=4)
    *gate, _ = _gate_inputs(b=4)

    def names(shape, axes):
        mesh = jax.sharding.Mesh(devices.reshape(shape), axes)
        bare, under = _bare_kernels(jax.make_jaxpr(lambda c, g: (
            scg.conv_silu(*c, mesh=mesh),
            scg.gate_norm(*g, groups=GROUPS, eps=EPS, mesh=mesh)))(
            conv, gate).jaxpr)
        assert not bare
        return under

    assert names((2, 2), ("fsdp", "tensor")) == []
    assert names((4, 1), ("fsdp", "tensor")) == [
        "ssm_conv_fwd", "ssm_gate_fwd"]


def test_a_mamba_layers_step_holds_the_four_kernels_under_shard_map(
        monkeypatch):
    """The decoder hands its mesh down to the two passes as to the scan:
    with the backend read as a TPU and shapes that tile, the traced value
    and gradient of a Mamba layer's loss on a mesh of rows holds every
    kernel under ``shard_map`` (traced, not lowered: no TPU is here): the
    convolution's once each for ``x``, ``B`` and ``C`` where they lie in
    the projection's result, the forward ones twice (the layer's checkpoint
    keeps nothing of theirs), and no slice of the projection the width of
    ``z`` or of the convolved channels in front of them."""
    from mpi_operator_tpu.models import llama, mamba2
    from mpi_operator_tpu.runtime import MeshPlan, build_mesh
    cfg = dataclasses.replace(
        llama.tiny_hybrid(), n_layers=1, layer_kinds=("mamba",),
        ssm_head_dim=64, ssm_state=128, ssm_chunk=128, remat_layers=True)
    mesh = build_mesh(MeshPlan(axes={"data": 2, "fsdp": 2}),
                      jax.devices()[:4])
    params = jax.eval_shape(lambda: llama.init(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p, t: llama.loss_fn(cfg, p, {"tokens": t}, mesh=mesh)[0]))(
        params, tokens)
    bare, under = _bare_kernels(jaxpr.jaxpr)
    assert not bare
    assert sorted(under) == sorted(
        ["ssd_bwd", "ssd_fwd", "ssm_gate_bwd"] + 2 * ["ssm_gate_fwd"]
        + 3 * ["ssm_conv_bwd"] + 6 * ["ssm_conv_fwd"])
    inner, conv, proj = mamba2.widths(cfg)
    cut = [e.outvars[0].aval.shape[-1]
           for e in _equations(jaxpr.jaxpr, into_kernels=False)
           if e.primitive.name in ("slice", "split")
           and e.invars[0].aval.shape == (*tokens.shape, proj)]
    assert cut and set(cut) == {proj - inner - conv}  # dt's alone
