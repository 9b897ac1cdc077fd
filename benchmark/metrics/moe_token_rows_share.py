"""The rows the routed feed-forward's two gathers in token order read, mean
a layer (the program's counter ``moe.token_rows_read``), as a share of the
step's assignments (tokens x experts a token), as
``moe_assignments_held_share`` divides: 100 where every assignment's row is
gathered, near the held share where only the held rows' lines are copied.
A program without the counter reads None."""

from metrics import op_names


def read(r):
    rows = (op_names.counters(r) or {}).get("moe.token_rows_read")
    if rows is None:
        return None
    tr = r["traffic"]
    return 100.0 * rows / (tr["rows_per_chip"] * tr["seq_len"]
                           * r["conf"]["num_experts_per_tok"])
