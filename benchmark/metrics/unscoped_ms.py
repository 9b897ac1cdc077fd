"""The step's device milliseconds that none of forward, backward, recompute
and optimizer claims: operations with no scope in their ``op_name`` and the
device's idle inside the step. The named reduction's own honesty: the five
add up to the step."""

import named_trace


def read(r):
    return named_trace.phase_ms(r, "unscoped")
