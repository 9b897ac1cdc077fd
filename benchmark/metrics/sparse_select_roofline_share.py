"""The least time the chip needs for one step's selections (the indexer's
scores at every causal pair at the bf16 peak, or the bytes read and the
bits written at the HBM peak, whichever is longer: this configuration's
``counts.select_step_work``) over the traced time of the ``sparse_select``
kernel. The threshold's 32 counts a row are integer work on the vector
units that the count does not hold: they read as a lower share."""

from metrics import pallas_ms


def read(r):
    return pallas_ms.roofline_share(
        r, getattr(r["counts"], "select_step_work", None), "sparse_select")
