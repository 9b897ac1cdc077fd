"""The whole step's share of the chip's bf16 peak, from the trace: the
FLOPs one step's tokens need on one chip (forward and backward, causal
attention as causal, recompute not counted) over the device time of one
whole run of the step's program and the peak."""


def read(r):
    t = r["trace"]
    if not t or not r["peaks"] or not t["step_s"]:
        return None
    tr = r["traffic"]
    flops = (tr["rows_per_chip"] * tr["seq_len"]
             * r["counts"].train_flops_per_token(r["conf"], tr["seq_len"]))
    return 100.0 * flops / (t["step_s"] * r["peaks"]["bf16_flops_per_s"])
