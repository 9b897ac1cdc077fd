"""Device milliseconds a step of operations under scope ``ssm_scan`` (the
state-space scan of every Mamba-2 layer: ``kernels/ssd.py``'s chunked form
with ``dt``'s softplus and the skip ``D x``), forward, backward and replay
together."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "ssm_scan")
