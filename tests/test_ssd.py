"""The chunked state-space scan and the short convolution (kernels/ssd.py)
against the recurrence they stand for, written here as a plain loop over
positions: at the published starting values (A = 1 .. H, dt in [0.001,
0.1]) and at the benchmark configuration's draw (A_log std 8, dt_bias std
1: heads that forget within a chunk beside heads that carry state over
many), over more than two chunks, forward and gradient; with the fault
planted (states not passed between chunks) failing the same comparison.
Both implementations of the scan: the ``jax.numpy`` products (what
``scan`` runs off a TPU) and the Pallas kernels through the interpreter
(``interpret=True``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.kernels import ssd

B, T, H, P, G, N, CHUNK = 2, 40, 4, 8, 2, 16, 8
# how ``scan`` is reached: as the CPU reaches it, or the kernels' bodies
IMPLEMENTATIONS = {"products": {}, "kernels": {"interpret": True}}
NO_SKIP = np.zeros((H,), np.float32)


def _scan(*v, **how):
    """``ssd.scan``, without the layer's ``D x`` unless the test gives D."""
    return ssd.scan(*v, **{"skip": NO_SKIP, **how})


def recurrence(x, dt, a, b, c):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t . C_t, a
    position a step in float32; S . C a multiply and a sum (on a TPU no
    matrix unit rounds it). Shapes as ``ssd.scan`` takes them."""
    bsz, _t, h, p = x.shape
    g, n = b.shape[2:]
    per_head = lambda v: jnp.repeat(v, h // g, axis=2)
    b, c = per_head(b), per_head(c)

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.sum(s * c_t[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((bsz, h, p, n), jnp.float32), tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)




def _inputs(kind):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    b = jax.random.normal(ks[1], (B, T, G, N))
    c = jax.random.normal(ks[2], (B, T, G, N))
    raw = jax.random.normal(ks[3], (B, T, H))
    if kind == "published":
        a_log = jnp.log(jnp.arange(1.0, H + 1))
        dt0 = jnp.exp(jax.random.uniform(ks[4], (H,))
                      * (np.log(0.1) - np.log(0.001)) + np.log(0.001))
        dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
        raw = 0.1 * raw
    else:  # the configuration's draw, heads of both regimes by hand
        a_log = jnp.array([-9.0, -3.0, 0.5, 28.0])  # |A| to 1.4e12
        dt_bias = jax.random.normal(ks[5], (H,))
    return x, raw, dt_bias, a_log, b, c


def _through(scan, args):
    x, raw, dt_bias, a_log, b, c = args
    dt = jax.nn.softplus(raw + dt_bias)
    return scan(x, dt, -jnp.exp(a_log), b, c)


@pytest.mark.parametrize("implementation", list(IMPLEMENTATIONS))
@pytest.mark.parametrize("kind", ["published", "draw"])
def test_chunked_scan_is_therecurrence_forward_and_gradient(
        kind, implementation):
    args = _inputs(kind)
    how = IMPLEMENTATIONS[implementation]
    chunked = lambda *a: _scan(*a, chunk=CHUNK, **how)
    faulty = lambda *a: _scan(*a, chunk=CHUNK, pass_states=False, **how)
    want = _through(recurrence, args)
    got = _through(chunked, args)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=1e-4)
    # the fault: every chunk starts from nought. The first chunk agrees,
    # the later ones do not, by far more than the comparison allows
    bad = _through(faulty, args)
    np.testing.assert_allclose(bad[:, :CHUNK], want[:, :CHUNK],
                               atol=2e-5 * scale, rtol=1e-4)
    assert float(jnp.max(jnp.abs(bad - want))) > 1e-2 * scale

    weights = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    loss = lambda scan: lambda *a: jnp.sum(_through(scan, a) * weights)
    grads = lambda scan: jax.grad(loss(scan), argnums=range(6))(*args)
    want_g, got_g, bad_g = grads(recurrence), grads(chunked), grads(faulty)
    for name, w, g, f in zip(("x", "dt", "dt_bias", "A_log", "B", "C"),
                             want_g, got_g, bad_g):
        norm = float(jnp.linalg.norm(w))
        assert float(jnp.linalg.norm(g - w)) < 1e-4 * norm, name
        assert float(jnp.linalg.norm(f - w)) > 1e-2 * norm, name


@pytest.mark.parametrize("implementation", list(IMPLEMENTATIONS))
def test_the_skip_adds_d_x_forward_and_gradient(implementation):
    """``skip=D``: the layer's ``D x`` added to the scan's result (inside
    the kernels, where they run), with its gradients for x and for D."""
    x, raw, dt_bias, a_log, b, c = _inputs("draw")
    dt, a = jax.nn.softplus(raw + dt_bias), -jnp.exp(a_log)
    d = jnp.array([1.0, -0.5, 2.0, 0.25])
    how = IMPLEMENTATIONS[implementation]
    weights = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    want = lambda x, d: jnp.sum(
        (recurrence(x, dt, a, b, c) + d[:, None] * x) * weights)
    got = lambda x, d: jnp.sum(
        _scan(x, dt, a, b, c, chunk=CHUNK, skip=d, **how) * weights)
    for w, g in zip(jax.value_and_grad(want, argnums=(0, 1))(x, d),
                    jax.value_and_grad(got, argnums=(0, 1))(x, d)):
        for wl, gl in zip(jax.tree.leaves(w), jax.tree.leaves(g)):
            np.testing.assert_allclose(
                gl, wl, rtol=1e-4, atol=2e-5 * float(jnp.max(jnp.abs(wl))))
    # and with the fault planted the skip is all a later chunk's x gives
    # its own position beside the chunk's own past
    plain = _scan(x, dt, a, b, c, chunk=CHUNK, pass_states=False, **how)
    with_skip = _scan(x, dt, a, b, c, chunk=CHUNK, skip=d,
                         pass_states=False, **how)
    np.testing.assert_allclose(with_skip - plain, d[:, None] * x,
                               rtol=1e-4, atol=1e-5)


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused():
    x, raw, dt_bias, a_log, b, c = _inputs("published")
    with pytest.raises(ValueError, match="whole number of chunks"):
        _scan(x, jax.nn.softplus(raw), -jnp.exp(a_log), b, c, chunk=16)


@pytest.mark.parametrize("implementation", list(IMPLEMENTATIONS))
def test_bf16_operands_accumulate_in_float32(implementation):
    args = _inputs("draw")
    want = _through(recurrence, args)
    x, raw, dt_bias, a_log, b, c = args
    half = lambda v: v.astype(jnp.bfloat16)
    halved = (half(x), raw, dt_bias, a_log, half(b), half(c))
    scan = lambda *a: _scan(*a, chunk=CHUNK,
                               **IMPLEMENTATIONS[implementation])
    got = _through(scan, halved)
    assert got.dtype == jnp.bfloat16
    err = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert 1e-4 < err < 2e-2  # bf16's rounding, not float32's and no more
    # and the gradients: cotangents in bf16 where the operands are, float32
    # for dt and A, each within bf16's rounding of the recurrence's
    weights = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = lambda f, a: jax.grad(
        lambda *v: jnp.sum(_through(f, v).astype(jnp.float32) * weights),
        argnums=range(6))(*a)
    for name, w, g, operand in zip(("x", "dt", "dt_bias", "A_log", "B", "C"),
                                   grads(recurrence, args),
                                   grads(scan, halved), halved):
        assert g.dtype == operand.dtype, name
        gap = float(jnp.linalg.norm(g.astype(jnp.float32) - w)
                    / jnp.linalg.norm(w))
        assert 1e-4 < gap < 3e-2, (name, gap)


def test_in_bf16_the_kernels_gradients_are_as_near_as_the_products():
    """Chunks long enough (64) and heads slow enough that a position's
    d cum is the small difference of two long sums: over a span they cancel
    only if both are the same bilinear form of the same rounded operands
    (an operand left unrounded on one side reads seven times the products'
    error in d dt here). Against the recurrence on the rounded operands,
    float32."""
    t, h, p, g, n, chunk = 256, 4, 16, 2, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (1, t, h, p))
    b = jax.random.normal(ks[1], (1, t, g, n)) * n ** -0.5
    c = jax.random.normal(ks[2], (1, t, g, n))
    a = -jnp.array([0.001, 0.05, 1.0, 30.0])
    dt = jax.nn.softplus(jax.random.normal(ks[4], (1, t, h)))
    weights = jax.random.normal(ks[5], x.shape)
    half = lambda v: v.astype(jnp.bfloat16)
    exact = lambda v: half(v).astype(jnp.float32)

    def grads(scan, *operands):
        return jax.grad(lambda *v: jnp.sum(
            scan(*v).astype(jnp.float32) * weights), argnums=range(5))(
            *operands)

    want = grads(recurrence, exact(x), dt, a, exact(b), exact(c))
    gaps = {}
    for name, how in IMPLEMENTATIONS.items():
        got = grads(lambda *v: _scan(*v, chunk=chunk, **how),
                    half(x), dt, a, half(b), half(c))
        gaps[name] = [float(jnp.linalg.norm(g_.astype(jnp.float32) - w)
                            / jnp.linalg.norm(w)) for g_, w in zip(got, want)]
    for name, ours, theirs in zip(("x", "dt", "A", "B", "C"),
                                  gaps["kernels"], gaps["products"]):
        assert ours < 2e-2, (name, ours)
        # A's is four numbers: noise; the others are whole arrays
        assert ours < 1.3 * theirs or name == "A", (name, ours, theirs)


def test_the_kernels_keep_decays_sums_and_states_in_float32():
    """Every ``exp`` inside the kernels takes and gives float32 and the
    states' scratch is float32, with bf16 operands: read off the jaxpr."""
    x, raw, dt_bias, a_log, b, c = _inputs("draw")
    half = lambda v: v.astype(jnp.bfloat16)
    state = N // 2  # not a group's R P channels: the scratch's shape shows
    f = lambda *a: jnp.sum(_scan(
        *a, chunk=CHUNK, interpret=True).astype(jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2, 3, 4)))(
        half(x), jax.nn.softplus(raw + dt_bias), -jnp.exp(a_log),
        half(b[..., :state]), half(c[..., :state]))
    calls = [e for e in _equations(jaxpr.jaxpr, into_kernels=False)
             if e.primitive.name == "pallas_call"]
    assert [c.params["name"] for c in calls] == [
        "ssd_fwd", "ssd_bwd"]
    for call in calls:
        inside = list(_equations(call.params["jaxpr"], into_kernels=True))
        exps = [e for e in inside if e.primitive.name == "exp"]
        assert len(exps) >= 2 * H // G
        assert all(e.invars[0].aval.dtype == jnp.float32 for e in exps)
        products = [e for e in inside if e.primitive.name == "dot_general"]
        assert products and all(
            e.invars[0].aval.dtype == e.invars[1].aval.dtype == jnp.bfloat16
            and e.outvars[0].aval.dtype == jnp.float32 for e in products)
        # scratch comes last among the kernel's references: the states
        # (their cotangent) [R P, N] float32
        scratch = call.params["jaxpr"].invars[-1].aval
        assert scratch.shape == (H // G * P, state)
        assert scratch.dtype == jnp.float32


def _equations(jaxpr, *, into_kernels):
    """Every equation of a jaxpr and of the jaxprs inside it; those inside
    a ``pallas_call`` only on request."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_kernels:
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, into_kernels=into_kernels)


def _bare_kernels(jaxpr, sharded=False):
    """The names of the ``pallas_call``s of a jaxpr that lie under no
    ``shard_map``, and of those that lie under one."""
    bare, under = [], []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            (under if sharded else bare).append(eqn.params["name"])
            continue
        inside = sharded or eqn.primitive.name == "shard_map"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            b, u = _bare_kernels(sub, inside)
            bare, under = bare + b, under + u
    return bare, under


@pytest.mark.parametrize("axes,heads_split", [
    ({"data": 2, "fsdp": 2, "tensor": 2}, True),
    ({"data": 2, "tensor": 4}, False),  # four ways do not divide two groups
    ({"fsdp": 4, "expert": 2}, False)])
def test_on_a_mesh_the_kernels_run_under_shard_map_to_the_same_answer(
        axes, heads_split):
    """A Pallas call has no partitioning rule: on a mesh of several devices
    the kernels run on each device's rows (and groups, where the ``tensor``
    axis divides them), and the value and every gradient are the one
    device's; ``a`` and ``D``, whole on every device, get theirs summed."""
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:8]).reshape(tuple(axes.values())),
        tuple(axes))
    x, raw, dt_bias, a_log, b, c = _inputs("draw")
    rows = lambda v: jnp.concatenate([v, v[::-1] * 0.5])  # four rows
    x, raw, b, c = rows(x), rows(raw), rows(b), rows(c)
    operands = (x, jax.nn.softplus(raw + dt_bias), -jnp.exp(a_log), b, c,
                jnp.array([1.0, -0.5, 2.0, 0.25]))
    weights = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def value_and_grads(mesh):
        loss = lambda *v: jnp.sum(ssd.scan(
            *v[:5], skip=v[5], chunk=CHUNK, interpret=True, mesh=mesh)
            * weights)
        return jax.value_and_grad(loss, argnums=range(6))

    jaxpr = jax.make_jaxpr(value_and_grads(mesh))(*operands)
    assert _bare_kernels(jaxpr.jaxpr) == ([], ["ssd_fwd", "ssd_bwd"])
    local = [e for e in _equations(jaxpr.jaxpr, into_kernels=False)
             if e.primitive.name == "pallas_call"][0].invars[0].aval
    # a device's x: its share of the four rows, and of the groups' channels
    ways = axes.get("data", 1) * axes.get("fsdp", 1)
    assert local.shape == (4 // ways, T, H * P // (2 if heads_split else 1))
    want, want_g = jax.jit(value_and_grads(None))(*operands)
    got, got_g = jax.jit(value_and_grads(mesh))(*operands)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, w, g_ in zip(("x", "dt", "A", "B", "C", "D"), want_g, got_g):
        np.testing.assert_allclose(
            g_, w, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(w))),
            err_msg=name)
    # one device: nothing to partition, and no shard_map
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    assert _bare_kernels(jax.make_jaxpr(value_and_grads(one))(
        *operands).jaxpr) == (["ssd_fwd", "ssd_bwd"], [])


def test_a_mamba_layers_step_on_a_sharded_mesh_holds_no_bare_kernel(
        monkeypatch):
    """The decoder hands its mesh down to the scan: with the backend read
    as a TPU and shapes that tile, the traced value and gradient of a
    Mamba layer's loss on a mesh of eight holds ``ssd_fwd`` and ``ssd_bwd``
    under ``shard_map`` only (traced, not lowered: no TPU is here)."""
    from mpi_operator_tpu.models import llama
    from mpi_operator_tpu.runtime import MeshPlan, build_mesh
    cfg = dataclasses.replace(
        llama.tiny_hybrid(), n_layers=1, layer_kinds=("mamba",),
        ssm_head_dim=64, ssm_state=128, ssm_chunk=128)
    assert ssd.tileable(cfg.ssm_chunk, cfg.ssm_state,
                        cfg.ssm_heads // cfg.ssm_groups, cfg.ssm_head_dim)
    mesh = build_mesh(MeshPlan(axes={"data": 2, "fsdp": 2, "tensor": 2}),
                      jax.devices()[:8])
    params = jax.eval_shape(lambda: llama.init(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p, t: llama.loss_fn(cfg, p, {"tokens": t}, mesh=mesh)[0]))(
        params, tokens)
    bare, under = _bare_kernels(jaxpr.jaxpr)
    assert not bare
    assert sorted(set(under)) == ["ssd_bwd", "ssd_fwd"]


def test_nothing_the_size_of_every_heads_decays_leaves_the_kernels():
    """Outside the ``pallas_call``s of the kernel path's value and
    gradient no array has B x chunks x H x Q x Q elements or more: the
    [Q, Q] decays a (row, chunk, head), which the products form writes to
    HBM, stay in VMEM."""
    # the configuration's proportions: P half of Q, N = Q (64, 128, 128)
    t, chunk = 32, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (B, t, H, chunk // 2))
    b = jax.random.normal(ks[1], (B, t, G, chunk))
    c = jax.random.normal(ks[2], (B, t, G, chunk))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, t, H)))
    a = -jnp.arange(1.0, H + 1)
    limit = B * (t // chunk) * H * chunk * chunk

    def largest(**how):
        f = lambda *v: jnp.sum(_scan(*v, chunk=chunk, **how))
        jaxpr = jax.make_jaxpr(jax.value_and_grad(f, argnums=range(5)))(
            x, dt, a, b, c)
        return max(v.aval.size
                   for e in _equations(jaxpr.jaxpr, into_kernels=False)
                   for v in e.outvars if hasattr(v.aval, "size"))

    # the largest thing outside is x or the saved states [B, G, chunks, N,
    # R P], each half the limit; the products form is over it, by its decays
    assert largest(interpret=True) < limit <= largest()


@pytest.mark.parametrize("kept,calls", [
    ((), ["ssd_fwd", "ssd_fwd", "ssd_bwd"]),
    (("ssd_y", "ssd_states"), ["ssd_fwd", "ssd_bwd"])])
def test_a_checkpoint_that_keeps_the_kernels_results_replays_no_forward(
        kept, calls):
    """The differentiated forward names its ``y`` and entering states; a
    layer's ``jax.checkpoint`` that saves those names (models/llama.py's
    does) holds one forward kernel, one that saves nothing holds two."""
    x, raw, dt_bias, a_log, b, c = _inputs("draw")
    dt, a = jax.nn.softplus(raw + dt_bias), -jnp.exp(a_log)
    layer = jax.checkpoint(
        lambda *v: 2.0 * _scan(*v, chunk=CHUNK, interpret=True),
        policy=jax.checkpoint_policies.save_only_these_names(*kept))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *v: jnp.sum(layer(*v)), argnums=range(5)))(x, dt, a, b, c)
    assert [e.params["name"]
            for e in _equations(jaxpr.jaxpr, into_kernels=False)
            if e.primitive.name == "pallas_call"] == calls


def test_a_shape_that_does_not_tile_takes_the_products_on_any_backend():
    """``interpret=None`` chooses by the backend and the shapes alone: off a
    TPU the products; on one the kernels where ``chunk``, ``N`` and a group's
    ``R P`` channels are whole lane tiles."""
    assert ssd.tileable(128, 128, 8, 64)  # the configuration's
    assert not ssd.tileable(CHUNK, N, H // G, P)  # this file's
    assert not ssd.tileable(128, 128, 8, 24)  # R P = 192
    assert not ssd.tileable(128, 128, 16, 24)  # P in no whole sublanes
    assert not ssd.tileable(128, 64, 8, 64)
    assert not ssd.tileable(64, 128, 8, 64)
    args = _inputs("draw")
    auto = jax.make_jaxpr(lambda *a: _through(
        lambda *v: _scan(*v, chunk=CHUNK), a))(*args)
    assert "pallas_call" not in str(auto)
    np.testing.assert_array_equal(
        _through(lambda *v: _scan(*v, chunk=CHUNK), args),
        _through(lambda *v: ssd._scan_products(
            *v, chunk=CHUNK, pass_states=True), args))


def test_carry_share_counts_the_chunks_that_hand_state_on():
    dt = jnp.full((1, 4 * CHUNK, 3), 0.1)
    # whole-chunk decays exp(8 x 0.1 x A): 0.92, 0.10 - a hair, 3e-4
    a = -jnp.array([0.1, np.log(10.0) / 0.8 + 1e-3, 10.0])
    assert float(ssd.carry_share(dt, a, chunk=CHUNK)) == pytest.approx(1 / 3)


def test_the_convolution_is_the_plain_loop_at_the_sequences_start():
    k, channels = 4, 6
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, channels))
    w = jax.random.normal(jax.random.PRNGKey(1), (channels, k))
    bias = jax.random.normal(jax.random.PRNGKey(2), (channels,))
    got = np.asarray(ssd.causal_conv(x, w, bias))
    xn, wn = np.asarray(x), np.asarray(w)
    for t in range(10):
        want = np.asarray(bias).copy()
        for tap in range(k):
            src = t - (k - 1) + tap
            if src >= 0:  # nought before the start
                want = want + wn[:, tap] * xn[:, src]
        np.testing.assert_allclose(got[:, t], want, rtol=1e-5, atol=1e-6)
    # each channel alone, and no position sees a later one
    moved = x.at[:, 5:, 0].add(1.0)
    diff = np.asarray(ssd.causal_conv(moved, w, bias)) - got
    assert np.all(diff[:, :5] == 0) and np.all(diff[:, :, 1:] == 0)
    assert np.any(diff[:, 5:, 0] != 0)
