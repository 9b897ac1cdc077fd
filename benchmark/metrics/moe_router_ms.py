"""Device milliseconds a step under scope ``moe_router``: the float32
scores over every published expert, the softmax, the top-k, the counts."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "moe_router")
