"""Device milliseconds a step of operations under scopes ``ssm_conv`` (the
short causal convolution and its silu) and ``ssm_gate_norm`` (the gate and
the grouped RMS norm) together: the elementwise, bandwidth-bound part of
the Mamba-2 layers, forward, backward and replay."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "ssm_conv", "ssm_gate_norm")
