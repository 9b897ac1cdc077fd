"""The least time the chip needs for one step's attention (its products at
the bf16 peak or its bytes at the HBM peak, whichever is longer) over the
traced time of one step's Pallas custom calls. The three flash kernels
carry no name of their own, so they are read as one."""


def read(r):
    t = r["trace"]
    if not t or not r["peaks"] or not t["flash_step_s"]:
        return None
    flops, nbytes = r["counts"].attention_step_work(
        r["conf"], r["traffic"]["rows_per_chip"], r["traffic"]["seq_len"])
    least = max(flops / r["peaks"]["bf16_flops_per_s"],
                nbytes / r["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / t["flash_step_s"]
