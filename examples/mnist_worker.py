"""Data-parallel MNIST training worker (≙ the reference's Horovod TF MNIST
example, examples/horovod/tensorflow_mnist.py — hvd DP allreduce; SURVEY.md
§2.6). SPMD: every host runs this; the trainer's global-view jit supplies
the gradient reduction Horovod did explicitly."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

from mpi_operator_tpu.models import mnist
from mpi_operator_tpu.ops import Trainer, TrainerConfig
from mpi_operator_tpu.ops.data import make_global_batch
from mpi_operator_tpu.runtime import bootstrap, mesh_from_context


def main():
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 30
    ctx = bootstrap.initialize()
    mesh = mesh_from_context(ctx)

    cfg = mnist.Config()
    params = mnist.init(cfg, jax.random.PRNGKey(0))
    trainer = Trainer(
        lambda p, b: mnist.loss_fn(cfg, p, b),
        mnist.logical_axes(cfg),
        mesh,
        TrainerConfig(learning_rate=1e-3),
    )
    state = trainer.init_state(params)

    per_host = 32
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
        state, metrics = trainer.train_step(state, batch)
        loss = float(metrics["loss"])
        first = loss if first is None else first
        last = loss
    if ctx.is_coordinator:
        print(f"mnist: loss {first:.4f} -> {last:.4f} over {steps} steps "
              f"({ctx.num_hosts} hosts)")
        assert last < first, "training did not reduce the loss"


if __name__ == "__main__":
    main()
