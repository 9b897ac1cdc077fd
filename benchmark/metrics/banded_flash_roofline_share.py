"""The least time the chip needs for one step's attention with each layer's
mask counted as what it is (a window as a window, a causal mask as causal:
``counts.attention_step_work``), over the traced time of the three flash
kernels by their names (``flash_fwd``, ``flash_dq``, ``flash_dkv``)."""

import named_trace


def read(r):
    kernels = [named_trace.kernel_ms(r, k)
               for k in ("flash_fwd", "flash_dq", "flash_dkv")]
    if not r["peaks"] or not all(kernels):
        return None
    flops, nbytes = r["counts"].attention_step_work(
        r["conf"], r["traffic"]["rows_per_chip"], r["traffic"]["seq_len"])
    least = max(flops / r["peaks"]["bf16_flops_per_s"],
                nbytes / r["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (sum(kernels) * 1e-3)
