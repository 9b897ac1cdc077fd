"""Explicit-allreduce MNIST DP worker — the fourth BASELINE acceptance
config (≙ /root/reference/examples/mxnet/mxnet_mnist.py, Horovod-MXNet DP).

The MXNet example's idiom is what this re-creates, TPU-natively: where
examples/mnist_worker.py uses the sharded-jit Trainer (reductions derived
from shardings), this worker drives the *raw collective verbs*
(parallel/collectives.py) exactly the way Horovod hooks MXNet:

  - weights start deliberately divergent per host, then host 0's are
    broadcast to everyone (≙ hvd.broadcast_parameters);
  - each step computes local gradients on the host's batch shard and
    mean-allreduces them explicitly under shard_map
    (≙ hvd.DistributedOptimizer wrapping the MXNet Trainer);
  - the update is hand-rolled SGD on the replicated weights — no optax,
    no Trainer.

Env: MNIST_AR_STEPS (default 30), MNIST_AR_BATCH per host (default 32),
MNIST_AR_LR (default 0.5).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from mpi_operator_tpu.ops.data import make_global_batch
from mpi_operator_tpu.parallel import collectives
from mpi_operator_tpu.runtime import bootstrap, mesh_from_context
from mpi_operator_tpu.runtime.topology import AXIS_DATA


def init_params(key):
    """Two-layer MLP, 784→128→10, from-scratch weight dicts."""
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (784, 128), jnp.float32) * 784**-0.5,
        "b1": jnp.zeros((128,), jnp.float32),
        "w2": jax.random.normal(k2, (128, 10), jnp.float32) * 128**-0.5,
        "b2": jnp.zeros((10,), jnp.float32),
    }


def local_loss(params, batch):
    """Cross-entropy on this host's shard — no collectives in here; the
    gradient averaging below is the ONLY cross-host communication, exactly
    the Horovod contract."""
    x = batch["image"].reshape(batch["image"].shape[0], -1)
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    lp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(lp, batch["label"][:, None], axis=1))


def main():
    ctx = bootstrap.initialize()
    mesh = mesh_from_context(ctx)

    steps = int(os.environ.get("MNIST_AR_STEPS", "30"))
    per_host = int(os.environ.get("MNIST_AR_BATCH", "32"))
    lr = float(os.environ.get("MNIST_AR_LR", "0.5"))

    # ≙ hvd.broadcast_parameters: init diverges per host on purpose; host
    # 0's weights win. (With one host the broadcast is the identity.)
    params = init_params(jax.random.PRNGKey(ctx.host_id))
    if ctx.is_distributed:
        from jax.experimental import multihost_utils

        params = jax.tree.map(
            lambda x: jnp.asarray(multihost_utils.broadcast_one_to_all(np.asarray(x))),
            params,
        )

    def step(params, batch):
        loss, grads = jax.value_and_grad(local_loss)(params, batch)
        # ≙ hvd.DistributedOptimizer: explicit mean-allreduce of gradients
        grads = jax.tree.map(lambda g: collectives.pmean(g, AXIS_DATA), grads)
        new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new_params, collectives.pmean(loss, AXIS_DATA)

    rep = P()
    sharded = P(AXIS_DATA)
    step = jax.jit(
        jax.shard_map(
            step,
            mesh=mesh,
            in_specs=({k: rep for k in params}, {"image": sharded, "label": sharded}),
            out_specs=({k: rep for k in params}, rep),
        )
    )

    rng = np.random.default_rng(ctx.host_id)
    batch = make_global_batch(
        mesh,
        {
            "image": rng.standard_normal((per_host, 28, 28, 1)).astype(np.float32),
            "label": rng.integers(0, 10, (per_host,)).astype(np.int32),
        },
    )

    first = last = None
    for _ in range(steps):
        params, loss = step(params, batch)
        loss = float(loss)
        first = loss if first is None else first
        last = loss

    if ctx.is_coordinator:
        print(
            json.dumps(
                {
                    "workload": "mnist_allreduce",
                    "first_loss": round(first, 4),
                    "last_loss": round(last, 4),
                    "steps": steps,
                    "hosts": ctx.num_hosts,
                }
            ),
            flush=True,
        )
        assert last < first, "training did not reduce the loss"


if __name__ == "__main__":
    main()
