"""The routed feed-forward (parallel/moe.py) compiled ON the TPU chip.

tests/test_moe.py checks the layer against a plain loop over the experts
on the CPU, where ``lax.ragged_dot`` is a masked dense product; this is the
hardware half: the grouped products as the Pallas kernels of
kernels/grouped_matmul.py, which leave the rows past the last group
untouched, the sort, both gathers and their hand-written transposes, at a
width the MXU tiles (d 256, experts 128 wide, 16 published of which 4 held,
2 a token), against that plain loop in float32 at ``highest`` precision;
and one product at the benchmark cell's shapes against the compiler's own
``lax.ragged_dot``, both timed."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mpi_operator_tpu.kernels.grouped_matmul import grouped_matmul
from mpi_operator_tpu.parallel import moe

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
    held = float(counters[moe.ASSIGNMENTS_HELD]) / (x.shape[0] * x.shape[1] * K)
    assert 0.15 < held < 0.35


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
