"""Device milliseconds a step in the Pallas kernel named ``flash_fwd``
(the forward kernel), from the named trace."""

import named_trace


def read(r):
    return named_trace.kernel_ms(r, "flash_fwd")
