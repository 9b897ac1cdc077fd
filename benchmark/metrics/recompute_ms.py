"""Device milliseconds a step of operations under ``rematted_computation``:
the forward replayed inside the backward pass by ``jax.checkpoint`` (the
layers under ``remat_layers``, the chunks of the long-context loss)."""

import named_trace


def read(r):
    return named_trace.phase_ms(r, "recompute")
