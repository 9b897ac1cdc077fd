"""Device milliseconds a step of operations under scope ``moe_shared`` (the
shared expert: a dense relu ** 2 feed-forward of every token beside the
routed experts), forward, backward and replay together."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "moe_shared")
