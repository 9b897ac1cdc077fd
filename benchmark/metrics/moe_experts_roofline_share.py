"""The least time the chip needs for the step's expert products (their
FLOPs at the bf16 peak or their bytes at the HBM peak, whichever is longer;
``counts.expert_step_work`` at the step's own ``moe.assignments_held``, the
mean a layer, times the layers) over the device time under scope
``moe_experts`` and in the grouped products' own Mosaic calls (the TPU
compiler names them ``ragged-dot-none`` and gives them no scope), replay
included: the replay is time and no work."""

from metrics import op_names


def read(r):
    spent_ms = op_names.ms(r, "moe_experts", op_names.GROUPED_PRODUCT)
    held = (op_names.counters(r) or {}).get("moe.assignments_held")
    if not spent_ms or not held or not r["peaks"]:
        return None
    flops, nbytes = r["counts"].expert_step_work(r["conf"], held)
    least = max(flops / r["peaks"]["bf16_flops_per_s"],
                nbytes / r["peaks"]["hbm_bytes_per_s"])
    return 100.0 * r["conf"]["num_hidden_layers"] * least / (spent_ms * 1e-3)
