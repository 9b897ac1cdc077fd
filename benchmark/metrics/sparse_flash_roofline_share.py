"""The least time the chip needs for one step's attention over the selected
keys (each query's ``topk``, every causal key where it has no more:
``counts.attention_step_work`` at the selected pairs) over the traced time
of the three kernels that read the selection, by their names
(``sparse_flash_fwd``, ``sparse_flash_dq``, ``sparse_flash_dkv``). They
compute every pair of a tile that holds a selected key, so a selection
scattered over the prefix reads low: the count is of the mathematics."""

from metrics import pallas_ms


def read(r):
    return pallas_ms.roofline_share(
        r, getattr(r["counts"], "attention_step_work", None),
        "sparse_flash_fwd", "sparse_flash_dq", "sparse_flash_dkv")
