"""Device milliseconds a step of operations under scope ``mamba`` (the
Mamba-2 layers: input projection, convolution, scan, gated norm, output
projection), forward, backward and replay together."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "mamba")
