"""ResNet training worker — the headline benchmark THROUGH the operator path.

≙ the reference's tf_cnn_benchmarks job
(/root/reference/examples/v1/tensorflow-benchmarks.yaml: resnet101, batch
64/device, synthetic imagenet, Horovod DP). SPMD: every host runs this; the
controller-injected TPUJOB_* env provides rendezvous, and the sharded-jit
trainer supplies the gradient reduction mpirun+Horovod provided there.

Config via env (so the same manifest scales from the CPU e2e test to a real
v5e slice): RESNET_DEPTH, RESNET_BATCH (per chip), RESNET_STEPS,
RESNET_IMAGE (edge pixels), RESNET_CLASSES.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import json
import time

import jax

from mpi_operator_tpu.models import resnet
from mpi_operator_tpu.ops import Trainer, TrainerConfig
from mpi_operator_tpu.ops.data import make_global_batch, synthetic_imagenet
from mpi_operator_tpu.runtime import bootstrap, mesh_from_context


def main():
    ctx = bootstrap.initialize()
    mesh = mesh_from_context(ctx)

    depth = os.environ.get("RESNET_DEPTH", "resnet101")
    per_chip = int(os.environ.get("RESNET_BATCH", "128"))
    steps = int(os.environ.get("RESNET_STEPS", "30"))
    image = int(os.environ.get("RESNET_IMAGE", "224"))
    classes = int(os.environ.get("RESNET_CLASSES", "1000"))

    cfg = resnet.Config(depth=depth, image_size=image, num_classes=classes)
    params, mstate = resnet.init(cfg, jax.random.PRNGKey(0))
    paxes, saxes = resnet.logical_axes(cfg)
    trainer = Trainer(
        lambda p, s, b: resnet.loss_fn(cfg, p, s, b),
        paxes,
        mesh,
        TrainerConfig(learning_rate=0.1, optimizer="momentum", grad_clip_norm=0.0),
        has_model_state=True,
        model_state_axes=saxes,
    )
    state = trainer.init_state(params, mstate)

    global_batch = per_chip * jax.device_count()
    stream = synthetic_imagenet(
        global_batch=global_batch, image_size=image, num_classes=classes
    )
    batch = make_global_batch(mesh, next(stream))

    # warmup/compile
    state, metrics = trainer.train_step(state, batch)
    jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = trainer.train_step(state, batch)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0

    if ctx.is_coordinator:
        img_s = global_batch * steps / dt
        print(json.dumps({
            "model": depth,
            "images_per_sec": round(img_s, 2),
            "images_per_sec_per_chip": round(img_s / jax.device_count(), 2),
            "hosts": ctx.num_hosts,
            "chips": jax.device_count(),
            "global_batch": global_batch,
            "loss": round(float(metrics["loss"]), 4),
        }))


if __name__ == "__main__":
    main()
