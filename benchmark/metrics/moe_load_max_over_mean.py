"""The fullest held expert's assignments over the mean of the held ones, in
the worst layer of the newest finished step (the program's counter
``moe.load_max_over_mean``): 1 is even; the grouped product's time follows
the sum, a deployment's exchange the maximum."""

from metrics import op_names


def read(r):
    return (op_names.counters(r) or {}).get("moe.load_max_over_mean")
