"""The chunked state-space scan (kernels/ssd.py) compiled ON the TPU chip.

tests/test_ssd.py checks both of its forms against the recurrence on the
CPU in float32 (the kernels through the Pallas interpreter); this is the
hardware half, at the benchmark configuration's sizes a head (H = 64 heads
of P = 64, 8 groups, state N = 128, chunks of 128) over T = 1024, eight
chunks: bf16 operands as the model gives them against the literal
recurrence in float32 (a multiply and a sum, no matrix unit), forward and
gradients, the Pallas kernels (what ``scan`` runs here) beside the
``jax.numpy`` products on the same inputs, with an error no larger (what
that is held to: ``TIE`` below); heads that forget within a chunk beside
heads that carry state over all eight; with the fault planted (states not
passed) failing; both forms timed at the cell's 2 x 8192 with the layer's
skip, forward and forward with backward; and, where the host has four
chips, the kernels under ``shard_map`` on a 2 x 2 mesh against one chip's,
alone and inside a Mamba layer's loss.

And the layer's two memory-bound passes (kernels/ssm_conv_gate.py) at the
cell's own shapes, 2 x 8192 by 6144 channels with K = 4 and by 4096
channels in 8 groups: the compiled kernels against the float32
``jax.numpy`` forms, values and all six cotangents, in bf16 (beside the
bf16 passes' own error) and in float32, each operand read where it lies in
the input projection's 10,304-wide result (their milliseconds are the
step's trace's to read: a call of under a millisecond is not the host
clock's); and on four chips under ``shard_map`` with the rows over
``fsdp``."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.kernels import ssd
from mpi_operator_tpu.kernels import ssm_conv_gate as scg
from tests.test_ssd import recurrence

B, T, H, P, G, N, CHUNK = 2, 1024, 64, 64, 8, 128, 128
NAMES = ("x", "dt", "A", "B", "C", "D")
# "No larger" than the products' error, as it is asserted: (1) the rms of
# the kernels' error over the draws is no larger than the products'; (2) on
# each draw it is no larger than the products' largest on any of the draws,
# their own range being the yardstick of what a draw does to it (A's
# gradient is 64 numbers a draw, and the products' own error on it runs
# from 1.7e-3 to 4.6e-3 over the three). Draw by draw it does not hold:
# on draw 5 A's reads 1.98e-3 against the products' 1.68e-3. TIE is the
# room of both for two sums of the same numbers made in another order
# (the forward and D's gradient read the same to four digits).
TIE = 1.01
NO_SKIP = jnp.zeros((H,), jnp.float32)

kernels = lambda *v, **kw: ssd.scan(*v, chunk=CHUNK, **{"skip": NO_SKIP, **kw})
products = lambda *v, **kw: ssd._scan_products(
    *v, chunk=CHUNK, **{"pass_states": True, **kw})


def _with_skip(*v, skip, **kw):
    """The products and the skip after them, as ``scan`` runs them where
    it runs no kernel."""
    y = products(*v, **kw).astype(jnp.float32)
    return (y + skip[:, None] * v[0].astype(jnp.float32)).astype(v[0].dtype)


def _inputs(t=T, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, t, H, P))
    b = jax.random.normal(ks[1], (B, t, G, N)) * N ** -0.5
    c = jax.random.normal(ks[2], (B, t, G, N))
    # the configuration's draw: A_log std 8, dt_bias std 1
    a = -jnp.exp(8.0 * jax.random.normal(ks[3], (H,)))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (B, t, H))
                         + jax.random.normal(ks[5], (H,)))
    return x, dt, a, b, c


def _halved(x, dt, a, b, c):
    half = lambda v: v.astype(jnp.bfloat16)
    return half(x), dt, a, half(b), half(c)


def _rms(v):
    return float(jnp.sqrt(jnp.mean(jnp.square(v.astype(jnp.float32)))))


def test_the_kernels_run_here_and_the_products_where_shapes_do_not_tile():
    assert ssd.tileable(CHUNK, N, H // G, P)
    x, dt, a, b, c = _halved(*_inputs(t=2 * CHUNK))
    text = jax.jit(kernels).lower(x, dt, a, b, c).as_text()
    assert "ssd_fwd" in text
    # a state of 64 is no whole lane tile: the products, the same answer
    small = (x, dt, a, b[..., :64], c[..., :64])
    assert "ssd_fwd" not in jax.jit(kernels).lower(*small).as_text()
    np.testing.assert_array_equal(
        jax.jit(kernels)(*small), jax.jit(products)(*small))


def test_chunked_scan_in_bf16_is_the_float32recurrence_on_the_chip():
    args = _inputs()
    x, dt, a, b, c = args
    share = float(ssd.carry_share(dt, a, chunk=CHUNK))
    assert 0.15 < share < 0.6  # both regimes among the 64 heads
    rounded = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)
    exact = (rounded(x), dt, a, rounded(b), rounded(c))
    want = jax.jit(recurrence)(*exact)
    scale = _rms(want)
    err = {}
    for name, scan in (("kernels", kernels), ("products", products)):
        got = jax.jit(scan)(*_halved(*args))
        assert got.dtype == jnp.bfloat16
        err[name] = _rms(got.astype(jnp.float32) - want) / scale
        bad = jax.jit(lambda *v: scan(*v, pass_states=False))(*_halved(*args))
        bad_err = _rms(bad.astype(jnp.float32) - want) / scale
        assert np.isfinite(err[name]) and err[name] < 2e-2, err  # bf16's
        assert bad_err > 10 * err[name], (name, err, bad_err)
        # float32 operands: the same products at the matrix unit's default
        # precision, the decays and the states float32 throughout
        err32 = _rms(jax.jit(scan)(*exact) - want) / scale
        assert err32 < 2e-2, (name, err32)
        print(f"{name}: forward rms error {err[name]:.3e} in bf16, "
              f"{err32:.3e} in float32, states not passed {bad_err:.3e}")
    assert err["kernels"] <= TIE * err["products"], err
    print(f"carry share {share:.2f}")


def test_gradients_in_bf16_are_the_float32recurrences_on_the_chip():
    """Over three draws, each of one row (the recurrence's backward keeps
    two states a position, 4 GB), the kernels' error held to the products'
    as ``TIE``'s comment says."""
    rounded = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)
    forms = (("kernels", kernels), ("products", _with_skip))
    errors = {name: [] for name, _ in forms}
    seeds = (5, 6, 7)
    for seed in seeds:
        args = tuple(v[:1] if v.ndim > 1 else v for v in _inputs(seed=seed))
        x, dt, a, b, c = args
        d = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(8), (H,))
        weights = jax.random.normal(jax.random.PRNGKey(9), x.shape)

        def grads(scan, operands):
            # the layer's skip with it, as the layer calls the scan
            loss = lambda *v: jnp.sum(
                scan(*v[:5], skip=v[5]).astype(jnp.float32) * weights)
            return jax.jit(jax.grad(loss, argnums=range(6)))(*operands)

        want = grads(lambda *v, skip: recurrence(*v) + skip[:, None] * v[0],
                     (rounded(x), dt, a, rounded(b), rounded(c), d))
        for name, scan in forms:
            got = grads(scan, _halved(*args) + (d,))
            bad = grads(lambda *v, **kw: scan(*v, pass_states=False, **kw),
                        _halved(*args) + (d,))
            err = [_rms(g.astype(jnp.float32) - w) / _rms(w)
                   for g, w in zip(got, want)]
            bad_err = [_rms(g.astype(jnp.float32) - w) / _rms(w)
                       for g, w in zip(bad, want)]
            print(f"{name}, draw {seed}: gradient rms error " + ", ".join(
                f"{n} {e:.3e} (fault {f:.2e})"
                for n, e, f in zip(NAMES, err, bad_err)))
            for n, e, f in zip(NAMES, err, bad_err):
                assert np.isfinite(e) and e < 3e-2, (name, n, e)
                # D's gradient is sum dy x: no state is in it
                assert f > 5 * e or n == "D", (name, n, e, f)
            errors[name].append(err)
    errors = {name: np.array(v) for name, v in errors.items()}  # [draw, name]
    over = {name: np.sqrt(np.mean(np.square(v), axis=0))
            for name, v in errors.items()}
    for name, v in over.items():
        print(f"{name}, rms over the draws: " + ", ".join(
            f"{n} {e:.3e}" for n, e in zip(NAMES, v)))
    print("draws on which the kernels' error is the larger: " + (", ".join(
        f"{n} on {seed} ({k:.3e} against {p:.3e})"
        for seed, ks, ps in zip(seeds, errors["kernels"], errors["products"])
        for n, k, p in zip(NAMES, ks, ps) if k > TIE * p) or "none"))
    worst = np.max(errors["products"], axis=0)
    for i, n in enumerate(NAMES):
        assert over["kernels"][i] <= TIE * over["products"][i], (n, over)
        assert np.all(errors["kernels"][:, i] <= TIE * worst[i]), (n, errors)


def test_both_forms_timed_at_the_cells_two_rows_of_8192():
    args = _halved(*_inputs(t=8192, seed=6))
    x = args[0]

    def timed(f):
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(5):
            out = f(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) * 200

    d = jnp.ones((H,))
    for name, scan in (("kernels", kernels), ("products", _with_skip)):
        with_skip = lambda *v: scan(*v, skip=d)
        loss = lambda *v: jnp.sum(with_skip(*v).astype(jnp.float32) ** 2)
        fwd = timed(jax.jit(with_skip))
        both = timed(jax.jit(jax.grad(loss, argnums=range(5))))
        print(f"{name}, {B} x {x.shape[1]}, one layer's scan: forward "
              f"{fwd:.2f} ms, forward and backward {both:.2f} ms")


def _four_chips():
    """The first four chips, found out when the test runs (conftest.py: no
    backend at import); a host of one skips."""
    if jax.device_count() < 4:
        pytest.skip("a mesh of 2 x 2 needs four chips")
    return jax.devices()[:4]


def test_on_four_chips_the_kernels_run_under_shard_map_to_one_chips_answer():
    """A Pallas call has no partitioning rule: on a 2 x 2 mesh (rows over
    ``data``, groups over ``tensor``) each chip runs the kernels on its row
    and its four groups. Value and gradients against the same call without
    a mesh, on one chip; both timed."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(_four_chips()).reshape(2, 2), ("data", "tensor"))
    x, dt, a, b, c = _halved(*_inputs(t=8192, seed=6))
    d = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(8), (H,))
    weights = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def value_and_grads(mesh):
        loss = lambda *v: jnp.sum(ssd.scan(
            *v[:5], skip=v[5], chunk=CHUNK, mesh=mesh).astype(jnp.float32)
            * weights)
        return jax.jit(jax.value_and_grad(loss, argnums=range(6)))

    wide = P("data", None, "tensor", None)
    put = lambda v, spec: jax.device_put(v, NamedSharding(mesh, spec))
    spread = (put(x, wide), put(dt, P("data", None, "tensor")),
              put(a, P("tensor")), put(b, wide), put(c, wide),
              put(d, P("tensor")))
    sharded = value_and_grads(mesh)
    text = sharded.lower(*spread).compile().as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert "bf16[1,8192,2048]" in text  # a chip's share of x
    assert "all-gather" not in text
    want, want_g = value_and_grads(None)(x, dt, a, b, c, d)
    got, got_g = sharded(*spread)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for name, w, g in zip(NAMES, want_g, got_g):
        gap = _rms(g.astype(jnp.float32) - w.astype(jnp.float32)) / _rms(w)
        print(f"four chips against one, {name}: {gap:.3e}")
        # x, dt, B, C: the same kernel on the same numbers; A, D: their sum
        # over the rows made across chips. Far under bf16's rounding (3e-3)
        assert gap < 1e-3, (name, gap)

    def timed(f, *v):
        jax.block_until_ready(f(*v))
        t0 = time.perf_counter()
        for _ in range(5):
            out = f(*v)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) * 200

    print(f"kernels, {B} x 8192, forward and backward: one chip "
          f"{timed(value_and_grads(None), x, dt, a, b, c, d):.2f} ms, "
          f"2 x 2 chips {timed(sharded, *spread):.2f} ms")


@pytest.mark.parametrize("axes,kernels_of", [
    ({"fsdp": 2, "tensor": 2}, ("ssd",)),
    ({"data": 2, "fsdp": 2}, ("ssd", "ssm_conv", "ssm_gate"))])
def test_on_four_chips_a_mamba_layers_loss_is_one_chips(axes, kernels_of):
    """The decoder on a mesh: a Mamba layer's projections partitioned by
    the compiler, its scan's kernels under ``shard_map`` and, where no
    ``tensor`` axis splits the channels, the convolution's and the gated
    norm's too; loss and gradient norm against the same model without a
    mesh on one chip."""
    from mpi_operator_tpu.models import llama
    from mpi_operator_tpu.parallel.sharding import named_sharding
    from mpi_operator_tpu.runtime import MeshPlan, build_mesh
    cfg = dataclasses.replace(
        llama.tiny_hybrid(), d_model=256, n_layers=2,
        layer_kinds=("mamba", "mamba"), ssm_heads=16, ssm_head_dim=64,
        ssm_groups=4, ssm_state=128, ssm_chunk=128)
    mesh = build_mesh(MeshPlan(axes=axes), _four_chips())
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 1024), 0, cfg.vocab)

    def value_and_norm(mesh):
        def f(p, t):
            (loss, _), g = jax.value_and_grad(
                lambda p: llama.loss_fn(cfg, p, {"tokens": t}, mesh=mesh),
                has_aux=True)(p)
            return loss, jnp.sqrt(sum(jnp.sum(jnp.square(v.astype(
                jnp.float32))) for v in jax.tree.leaves(g)))
        return jax.jit(f)

    want = value_and_norm(None)(params, tokens)
    spread = jax.tree.map(
        lambda v, axes: jax.device_put(v, named_sharding(mesh, axes)),
        params, llama.logical_axes(cfg),
        is_leaf=lambda v: isinstance(v, tuple))
    sharded = value_and_norm(mesh)
    text = sharded.lower(spread, tokens).compile().as_text()
    for name in ("ssd", "ssm_conv", "ssm_gate"):
        assert (name + "_fwd" in text) == (name in kernels_of), name
        assert (name + "_bwd" in text) == (name in kernels_of), name
    got = sharded(spread, tokens)
    print(f"a Mamba layer's loss and gradient norm, one chip {want}, "
          f"{axes} {got}")
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-2)



# ---- the convolution with its silu, the gate with its grouped norm ----

CONV_SHAPE, K = (2, 8192, 6144), 4
GATE_SHAPE, GROUPS, EPS = (2, 8192, 4096), 8, 1e-5
# the input projection's result as the layer has it: z, the convolved
# channels, dt. The kernels read their columns where they lie in it
PROJ_SHAPE, CONV_FIRST = (2, 8192, 4096 + 6144 + 64), 4096
CONV_NAMES = ("y", "dx", "dw", "dbias")
GATE_NAMES = ("out", "dy", "dz", "dscale")


def _conv_kernels(proj, w, bias, **how):
    return scg.conv_silu(proj, w, bias, first=CONV_FIRST, **how)


def _conv_passes(proj, w, bias):
    x = proj[..., CONV_FIRST:CONV_FIRST + CONV_SHAPE[2]]
    return jax.nn.silu(ssd.causal_conv(x, w, bias)).astype(x.dtype)


def _gate_kernels(y, proj, scale, **how):
    return scg.gate_norm(y, proj, scale, groups=GROUPS, eps=EPS, **how)


def _gate_passes(y, proj, scale):
    return scg._gate_norm_passes(y, proj[..., :GATE_SHAPE[2]], scale, GROUPS,
                                 EPS)


def _two_passes(rows=2, seed=21):
    """{pass: (kernels, passes, wide operands bf16-exact in float32, the
    weights, the cotangent, the results' names)} at the cell's widths."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    exact = lambda key, shape: jax.random.normal(
        key, (rows, *shape[1:])).astype(jnp.bfloat16).astype(jnp.float32)
    c = CONV_SHAPE[2]
    return {
        "conv": (_conv_kernels, _conv_passes, (exact(ks[0], PROJ_SHAPE),),
                 (jax.random.normal(ks[1], (c, K)) * K ** -0.5,
                  0.3 * jax.random.normal(ks[2], (c,))),
                 exact(ks[3], CONV_SHAPE), CONV_NAMES),
        "gate": (_gate_kernels, _gate_passes,
                 (exact(ks[4], GATE_SHAPE), exact(ks[5], PROJ_SHAPE)),
                 (1.0 + 0.2 * jax.random.normal(ks[6], (GATE_SHAPE[2],)),),
                 exact(ks[7], GATE_SHAPE), GATE_NAMES)}


def _value_and_cotangents(f, dtype=None):
    """jitted: (wide operands, weights, cotangent) -> (value, cotangents),
    the wide ones cast to ``dtype`` first."""
    def run(wide, weights, ct):
        if dtype is not None:
            wide = tuple(v.astype(dtype) for v in wide)
        value, pull = jax.vjp(f, *wide, *weights)
        return (value, *pull(ct.astype(value.dtype)))
    return jax.jit(run)


def _gaps(got, want):
    return [_rms(g.astype(jnp.float32) - w) / _rms(w)
            for g, w in zip(got, want)]


@pytest.mark.parametrize("pass_", ("conv", "gate"))
def test_the_two_passes_kernels_are_the_float32_passes_at_the_cells_shapes(
        pass_):
    """Compiled, at 2 x 8192: in bf16 as the model gives the operands, each
    result within a rounding of the float32 ``jax.numpy`` passes on the same
    numbers and no further than the bf16 passes' own (``TIE``); in float32
    to float32's own level. All eight results, all six cotangents."""
    kernels, passes, wide, weights, ct, names = _two_passes()[pass_]
    text = _value_and_cotangents(kernels, jnp.bfloat16).lower(
        wide, weights, ct).as_text()
    assert f"ssm_{pass_}_fwd" in text and f"ssm_{pass_}_bwd" in text
    want = _value_and_cotangents(passes)(wide, weights, ct)
    half = {name: _gaps(_value_and_cotangents(f, jnp.bfloat16)(
        wide, weights, ct), want)
        for name, f in (("kernels", kernels), ("passes", passes))}
    full = _gaps(_value_and_cotangents(kernels)(wide, weights, ct), want)
    for i, name in enumerate(names):
        print(f"{pass_} {name}: bf16 kernels {half['kernels'][i]:.3e}, "
              f"bf16 passes {half['passes'][i]:.3e}, float32 kernels "
              f"{full[i]:.3e}")
        assert np.isfinite(half["kernels"][i]), name
        assert half["kernels"][i] <= TIE * half["passes"][i] + 1e-6, name
        assert half["kernels"][i] < 4e-3, name  # bf16's rounding
        assert full[i] < 2e-5, name
    # the fault: every tile of positions from nought
    if pass_ == "conv":
        bad = _gaps(_value_and_cotangents(
            lambda *v: kernels(*v, halo=False), jnp.bfloat16)(
            wide, weights, ct), want)
        print("conv, the halo dropped: " + ", ".join(
            f"{n} {e:.3e}" for n, e in zip(names, bad)))
        assert all(b > 5 * e for b, e in zip(bad, half["kernels"])), bad


def test_on_four_chips_the_two_passes_run_under_shard_map_over_fsdp():
    """Four rows of 8192 over ``fsdp`` = 4, a row a chip with every
    channel: values and cotangents against the same calls without a mesh on
    one chip. The wide ones are the same kernel on the same numbers; the
    weights', bias's and scale's are sums made across chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(_four_chips()), ("fsdp",))
    put = lambda v, spec: jax.device_put(v, NamedSharding(mesh, spec))
    for pass_, (kernels, _p, wide, weights, ct, names) in (
            _two_passes(rows=4).items()):
        wide = tuple(v.astype(jnp.bfloat16) for v in wide)
        sharded = _value_and_cotangents(
            lambda *v: kernels(*v, mesh=mesh))
        spread = (tuple(put(v, P("fsdp")) for v in wide),
                  tuple(put(v, P()) for v in weights), put(ct, P("fsdp")))
        text = sharded.lower(*spread).compile().as_text()
        assert f"ssm_{pass_}_fwd" in text and f"ssm_{pass_}_bwd" in text
        assert f"bf16[1,8192,{wide[0].shape[2]}]" in text  # a chip's share
        assert "all-gather" not in text
        want = _value_and_cotangents(kernels)(wide, weights, ct)
        got = sharded(*spread)
        for name, gap in zip(names, _gaps(got, [
                w.astype(jnp.float32) for w in want])):
            print(f"four chips against one, {pass_} {name}: {gap:.3e}")
            assert gap < 1e-5, (name, gap)
