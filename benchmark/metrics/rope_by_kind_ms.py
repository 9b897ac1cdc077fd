"""Device milliseconds a step of operations under scope ``attention_rope``
(the rotations of queries and keys, by each layer kind's theta and rotated
share, with the split and concatenation of a head's rotated and unrotated
dims where only part of it turns), forward, backward and replay together."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "attention_rope")
