"""Device milliseconds a step of operations under scope ``lead`` (the
leading dense pairs in front of a routed model's periods: their attention
and their dense feed-forward), forward, backward and replay together."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "lead")
