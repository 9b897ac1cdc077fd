"""Device milliseconds a step of operations under scope ``attention_gate``
(the per-head output gate of attention), every layer's, forward, backward
and replay together: the gate's projection from the layer's normed input
and its sigmoid in every phase, and the backward of its product with each
head's output. Not the forward's or the replay's product itself: the
compiler fuses that multiply into the output projection's product (``wo``),
whose fusion carries the projection's name, so its time is the
projection's."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "attention_gate")
