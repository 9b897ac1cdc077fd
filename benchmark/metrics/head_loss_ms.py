"""Device milliseconds a step of operations under scope ``head_loss`` (the
final norm, the head's product, the cross-entropy), forward, backward and
replay together: a second cut of the step, not a fifth part."""

import named_trace


def read(r):
    return named_trace.scope_ms(r, "head_loss")
