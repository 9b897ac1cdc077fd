"""The share of the scan's (row, chunk, head) whose decay over the whole
chunk exceeds 0.1, mean a Mamba-2 layer (the program's counter
``ssm.carry_share``, from the step's own ``dt``): how much of the scan
hands state from one chunk to the next. Near nought the pass of states
carries nothing and a fault in it would read ``correct``."""

from metrics import op_names


def read(r):
    share = (op_names.counters(r) or {}).get("ssm.carry_share")
    return None if share is None else 100.0 * share
