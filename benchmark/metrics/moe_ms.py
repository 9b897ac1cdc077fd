"""Device milliseconds a step of operations under scope ``moe`` (the routed
feed-forward: router, dispatch, expert products, combine), forward,
backward and replay together. The grouped products themselves are the TPU
compiler's own Mosaic calls, which it names ``ragged-dot-none`` and gives
no scope: they are added by that name (nothing else makes one)."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "moe", op_names.GROUPED_PRODUCT)
