"""Device milliseconds a step of operations under scope ``moe_shared`` in
the cell whose shared expert is the gated one (SwiGLU beside gated routed
experts), forward, backward and replay together: ``moe_shared_ms``'s
reduction under a name of its own, since that entry's list of cells is an
accepted one and names the cell of the ungated relu ** 2 shared expert."""

from metrics.moe_shared_ms import read  # noqa: F401
