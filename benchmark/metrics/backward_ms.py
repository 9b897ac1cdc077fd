"""Device milliseconds a step of operations whose ``op_name`` holds
``transpose(`` and that are no replay: the backward pass."""

import named_trace


def read(r):
    return named_trace.phase_ms(r, "backward")
