"""Pallas TPU kernels for the framework's hot ops.

The reference has no kernels of its own — its hot path is Horovod/NCCL plus
whatever cuDNN the workload images carry. Here the XLA-compiled model is
already fast; these kernels target the ops where hand scheduling beats the
compiler: attention (VMEM-resident online softmax, no [T,T] materialization)
the routed feed-forward's grouped products (whole-width tiles over the
row tiles that hold real rows) and its elementwise passes over the same
buffers (``row_map``: a map that stops at the last held row's tile, where
the compiler's fusion runs over every row the static shape has).
``ssd`` is the state-space layers' chunked scan and short convolution: the
scan as two kernels with a custom backward (a chunk's decays, ``C B^T`` and
the states never leave VMEM) on a TPU where the shapes tile, ``jax.numpy``
products the compiler lowers elsewhere; the convolution shifted
multiply-adds. ``ssm_conv_gate`` is those layers' two memory-bound passes
(the convolution with its silu, the gate with its grouped RMS norm) as
row-tiled kernels with custom backward passes, one read of each operand in
its own dtype, on a TPU where the widths tile; the ``jax.numpy`` passes
elsewhere.
Written per /opt/skills/guides/pallas_guide.md; every kernel has an
interpret-mode path so the CPU test suite checks numerics.
"""

from mpi_operator_tpu.kernels import ssd, ssm_conv_gate
from mpi_operator_tpu.kernels.flash_attention import flash_attention
from mpi_operator_tpu.kernels.grouped_matmul import grouped_matmul
from mpi_operator_tpu.kernels.quant_matmul import quant_matmul, quant_ragged_dot

__all__ = ["flash_attention", "grouped_matmul", "quant_matmul",
           "quant_ragged_dot", "ssd", "ssm_conv_gate"]
