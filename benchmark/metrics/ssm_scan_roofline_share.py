"""The least time the chip needs for the step's state-space scans (their
FLOPs at the bf16 peak or their bytes at the HBM peak, whichever is longer:
``counts.ssd_step_work``, every Mamba-2 layer, forward and backward, a
chunk's causal mask counted as causal) over the device time under scope
``ssm_scan``, replay included: the replay is time and no work."""

from metrics import op_names


def read(r):
    spent_ms = op_names.ms(r, "ssm_scan")
    work = getattr(r["counts"], "ssd_step_work", None)
    if not spent_ms or work is None or not r["peaks"]:
        return None
    tr = r["traffic"]
    flops, nbytes = work(r["conf"], tr["rows_per_chip"], tr["seq_len"])
    least = max(flops / r["peaks"]["bf16_flops_per_s"],
                nbytes / r["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (spent_ms * 1e-3)
