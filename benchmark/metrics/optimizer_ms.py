"""Device milliseconds a step of operations under scope ``optimizer``: the
clip's norm, the AdamW update and its application."""

import named_trace


def read(r):
    return named_trace.phase_ms(r, "optimizer")
