"""Device milliseconds a step under scopes ``moe_dispatch`` (the sort of the
assignments, the rows' gather) and ``moe_combine`` (the gather back and the
weighted sum), with their transposes: what the routed feed-forward pays to
move rows, beside its products."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "moe_dispatch", "moe_combine")
