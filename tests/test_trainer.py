"""Trainer tests: sharded train steps on the 8-device CPU mesh.

This is the pjit replacement for hvd.DistributedOptimizer — the tests check
the things Horovod promises (grads averaged across the gang ≡ large-batch
step; params stay in sync) fall out of the global-view compilation."""

import jax
import numpy as np
import pytest

from mpi_operator_tpu.models import llama, mnist, resnet
from mpi_operator_tpu.ops import Trainer, TrainerConfig
from mpi_operator_tpu.ops.data import make_global_batch, prefetch, synthetic_tokens
from mpi_operator_tpu.runtime import MeshPlan, build_mesh
from mpi_operator_tpu.runtime.topology import (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_TENSOR,
)

# slow tier: XLA compiles / subprocess gangs (see pytest.ini)
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def dp_mesh():
    return build_mesh(MeshPlan(axes={AXIS_DATA: 8}))


def _mnist_setup(mesh, cfg_kw=None):
    cfg = mnist.Config(hidden=32)
    params = mnist.init(cfg, jax.random.PRNGKey(0))
    tr = Trainer(
        lambda p, b: mnist.loss_fn(cfg, p, b),
        mnist.logical_axes(cfg),
        mesh,
        TrainerConfig(**(cfg_kw or {"learning_rate": 1e-3})),
    )
    state = tr.init_state(params)
    key = jax.random.PRNGKey(1)
    host_batch = {
        "image": np.asarray(jax.random.normal(key, (16, 28, 28, 1))),
        "label": np.asarray(jax.random.randint(key, (16,), 0, 10)),
    }
    batch = make_global_batch(mesh, host_batch)
    return tr, state, batch


def test_train_step_decreases_loss(dp_mesh):
    tr, state, batch = _mnist_setup(dp_mesh)
    losses = []
    for _ in range(5):
        state, metrics = tr.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert int(state.step) == 5
    assert np.isfinite(losses).all()


def test_the_step_is_traced_once(dp_mesh):
    """A fresh state is of the type the step gives back (every leaf on the
    mesh, ``step`` too): the second step finds the first's program and does
    not trace, lower and load it again (seconds of a start at full size)."""
    tr, state, batch = _mnist_setup(dp_mesh)
    for _ in range(3):
        state, _ = tr.train_step(state, batch)
    assert tr._step_fn._cache_size() == 1


def test_batch_is_sharded_over_data_axis(dp_mesh):
    tr, state, batch = _mnist_setup(dp_mesh)
    shard_shapes = {s.data.shape for s in batch["image"].addressable_shards}
    assert shard_shapes == {(2, 28, 28, 1)}  # 16 / 8 devices


def test_dp_step_equals_single_device_step(dp_mesh):
    """The defining Horovod property: a DP step over the sharded global
    batch must equal a single-device step over the full batch."""
    tr, state, batch = _mnist_setup(dp_mesh, {"learning_rate": 0.01, "optimizer": "sgd", "grad_clip_norm": 0.0})
    cfg = mnist.Config(hidden=32)
    params0 = jax.tree.map(np.asarray, state.params)
    state1, _ = tr.train_step(state, batch)

    # single-device reference
    full = {k: np.asarray(v) for k, v in batch.items()}
    g = jax.grad(lambda p: mnist.loss_fn(cfg, p, full))(params0)
    want = jax.tree.map(lambda p, gr: p - 0.01 * gr, params0, g)
    got = jax.tree.map(np.asarray, state1.params)
    # bf16 compute + per-device reduction order ⇒ small numeric skew
    for w, gt in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(w, gt, atol=1e-4, rtol=0)


def test_stateful_model_resnet(dp_mesh):
    cfg = resnet.Config(depth="resnet50", num_classes=10, image_size=32, width=8)
    params, mstate = resnet.init(cfg, jax.random.PRNGKey(0))
    paxes, saxes = resnet.logical_axes(cfg)
    tr = Trainer(
        lambda p, s, b: resnet.loss_fn(cfg, p, s, b),
        paxes,
        dp_mesh,
        TrainerConfig(learning_rate=1e-3, optimizer="momentum"),
        has_model_state=True,
        model_state_axes=saxes,
    )
    state = tr.init_state(params, mstate)
    key = jax.random.PRNGKey(1)
    batch = make_global_batch(
        dp_mesh,
        {
            "image": np.asarray(jax.random.normal(key, (16, 32, 32, 3))),
            "label": np.asarray(jax.random.randint(key, (16,), 0, 10)),
        },
    )
    state, metrics = tr.train_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # BN running stats moved
    assert not np.allclose(
        np.asarray(state.model_state["stem_bn"]["mean"]), 0.0
    )


def test_llama_fsdp_tensor_sequence_mesh():
    """Full 3-axis mesh: fsdp×tensor×sequence — params sharded, ring
    attention active, loss finite and step runs."""
    mesh = build_mesh(
        MeshPlan(axes={AXIS_FSDP: 2, AXIS_TENSOR: 2, AXIS_SEQ: 2})
    )
    cfg = llama.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tr = Trainer(
        lambda p, b: llama.loss_fn(cfg, p, b, mesh=mesh),
        llama.logical_axes(cfg),
        mesh,
        TrainerConfig(learning_rate=1e-3),
    )
    state = tr.init_state(params)
    # wq [layers, d, q_dim] should be sharded over fsdp (embed) and tensor (heads)
    wq = state.params["layers"]["wq"]["w"]
    assert wq.addressable_shards[0].data.shape[1] == cfg.d_model // 2
    assert wq.addressable_shards[0].data.shape[2] == cfg.q_dim // 2
    it = synthetic_tokens(global_batch=4, seq_len=32, vocab=cfg.vocab)
    batch = make_global_batch(mesh, next(it))
    losses = []
    for _ in range(3):
        state, metrics = tr.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_opt_moments_follow_param_shardings():
    """Regression: same-shape params with different layouts (llama wq vs wo
    when q_dim == d_model) must each get their OWN moment sharding — path
    matching, not shape matching."""
    mesh = build_mesh(MeshPlan(axes={AXIS_FSDP: 4, AXIS_TENSOR: 2}))
    cfg = llama.Config(
        vocab=128, d_model=64, n_layers=1, n_heads=4, n_kv_heads=4,
        head_dim=16, d_ff=128,  # q_dim == d_model == 64
    )
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tr = Trainer(
        lambda p, b: llama.loss_fn(cfg, p, b, mesh=mesh),
        llama.logical_axes(cfg),
        mesh,
        TrainerConfig(learning_rate=1e-3),
    )
    state = tr.init_state(params)
    mu = state.opt_state[1][0].mu  # chain(clip, adamw) -> adamw ScaleByAdam
    for name in ("wq", "wo"):
        p_sh = state.params["layers"][name]["w"].sharding
        m_sh = mu["layers"][name]["w"].sharding
        assert p_sh == m_sh, (name, p_sh, m_sh)


def test_prefetch_propagates_producer_errors(dp_mesh):
    def bad_iter():
        yield {"tokens": np.zeros((8, 4), np.int32)}
        raise RuntimeError("pipeline broke")

    gen = prefetch(bad_iter(), dp_mesh)
    next(gen)
    with pytest.raises(RuntimeError, match="pipeline broke"):
        next(gen)


def test_prefetch_close_releases_producer_thread(dp_mesh):
    """Regression (ISSUE 16): a consumer that abandons the generator early
    must not leave the producer thread parked on a full queue forever —
    that thread holds `depth` device-resident global batches alive. close()
    (or GC of the generator) must propagate a stop to the producer."""
    import threading
    import time

    def endless():
        while True:
            yield {"tokens": np.zeros((8, 4), np.int32)}

    gen = prefetch(endless(), dp_mesh, depth=2)
    next(gen)  # producer is now running and will fill + block on the queue
    gen.close()  # early abandonment: GeneratorExit hits the consumer loop

    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if not any(
            t.name == "tpujob-prefetch" and t.is_alive()
            for t in threading.enumerate()
        ):
            break
        time.sleep(0.05)
    else:
        raise AssertionError(
            "prefetch producer thread still alive after generator close()"
        )


def test_prefetch_device_transform_applies_on_global_batch(dp_mesh):
    it = synthetic_tokens(global_batch=8, seq_len=4, vocab=100)

    def shift(batch):
        return {k: v + 1 for k, v in batch.items()}

    plain = next(prefetch(synthetic_tokens(global_batch=8, seq_len=4,
                                           vocab=100), dp_mesh))
    shifted = next(prefetch(it, dp_mesh, device_transform=jax.jit(shift)))
    np.testing.assert_array_equal(
        np.asarray(shifted["tokens"]), np.asarray(plain["tokens"]) + 1
    )
    assert shifted["tokens"].sharding.spec == plain["tokens"].sharding.spec


def test_prefetch_yields_sharded_batches(dp_mesh):
    it = synthetic_tokens(global_batch=8, seq_len=4, vocab=100)

    def take(n, gen):
        out = []
        for _ in range(n):
            out.append(next(gen))
        return out

    batches = take(3, prefetch(it, dp_mesh))
    assert all(b["tokens"].shape == (8, 4) for b in batches)
    assert batches[0]["tokens"].sharding.spec == batches[1]["tokens"].sharding.spec
