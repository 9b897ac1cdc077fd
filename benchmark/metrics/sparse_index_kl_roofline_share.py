"""The least time the chip needs for one step's indexer losses (the main
attention's probabilities at the selected pairs, every head, and the
indexer's scores and their two gradients' products there:
``counts.index_kl_step_work``) over the traced time of the
``sparse_index_kl`` kernel, which computes every pair of a causally open
tile."""

from metrics import pallas_ms


def read(r):
    return pallas_ms.roofline_share(
        r, getattr(r["counts"], "index_kl_step_work", None),
        "sparse_index_kl")
