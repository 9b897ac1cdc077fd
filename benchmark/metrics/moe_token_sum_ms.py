"""Device milliseconds a step in the routed feed-forward's token sums, the
Pallas kernel ``moe_token_sum`` (mpi_operator_tpu/kernels/token_sum.py):
the combine's weighted sum and the dispatch's transpose, every call, all
phases (forward, replay, backward). A program without the kernel reads
None."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "moe_token_sum")
