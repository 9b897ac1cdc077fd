"""The share of the step's assignments (tokens x experts a token) that fell
to experts held on this chip, mean a layer (the program's counter
``moe.assignments_held``): the rows the expert products really see, against
held / published experts of the configuration if the router is even."""

from metrics import op_names


def read(r):
    held = (op_names.counters(r) or {}).get("moe.assignments_held")
    if held is None:
        return None
    tr = r["traffic"]
    return 100.0 * held / (tr["rows_per_chip"] * tr["seq_len"]
                           * r["conf"]["num_experts_per_tok"])
