"""Device milliseconds a step in the Pallas kernel named ``flash_dkv``
(the backward kernel that gives dk and dv), from the named trace."""

import named_trace


def read(r):
    return named_trace.kernel_ms(r, "flash_dkv")
