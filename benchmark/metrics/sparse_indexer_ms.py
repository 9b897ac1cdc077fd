"""Device milliseconds a step under scope ``sparse_indexer`` (models/
llama.py): the learned selection's indexer, every layer's, all phases -
its queries', key's and weights' projections from the layer's normed
input, the key's norm and both rotations, and the indexer's KL loss (the
``sparse_index_kl`` kernel, which computes the loss and its gradients in
the forward pass), with the projections' backward. A program without the
scope reads None."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "sparse_indexer")
