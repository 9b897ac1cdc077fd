"""Workload model library (≙ the reference's examples/ images).

The reference ships training workloads as opaque container images — TF
benchmarks ResNet-101, Horovod TF MNIST, MXNet MNIST
(/root/reference/examples/, SURVEY.md §2.6). Here the workloads are a
first-class library, TPU-native:

- plain functional JAX (init/apply pairs over param pytrees) so pjit sees
  every array;
- every model exposes a ``logical_axes`` pytree (same structure as params)
  consumed by parallel/sharding.py — the same model runs pure-DP, FSDP, TP,
  or sequence-parallel by swapping the rule table, never by editing the model;
- bf16 compute / f32 params+optimizer by default (MXU-native).

Families: mnist (≙ examples/horovod/tensorflow_mnist.py and the MXNet MNIST)
and resnet (≙ tf_cnn_benchmarks --model=resnet101), the upstream-parity
examples; llama, the decoder the benchmark's cells train (its operation
counts live with the benchmark, benchmark/counts/).
"""

from mpi_operator_tpu.models import llama, mnist, resnet

# name → (module, config factory); the factory bakes in the depth/preset so
# registry users can't get a module whose default Config contradicts the name
MODELS = {
    "mnist": (mnist, mnist.Config),
    "resnet50": (resnet, lambda: resnet.Config(depth="resnet50")),
    "resnet101": (resnet, lambda: resnet.Config(depth="resnet101")),
    "llama3-8b": (llama, llama.llama3_8b),
    "llama-tiny": (llama, llama.tiny),
}

__all__ = ["mnist", "resnet", "llama", "MODELS"]
