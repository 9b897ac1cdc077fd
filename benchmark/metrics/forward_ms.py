"""Device milliseconds a step of operations under scope ``model`` that are
neither the backward pass nor a replay: the forward pass."""

import named_trace


def read(r):
    return named_trace.phase_ms(r, "forward")
