"""Device milliseconds a step of operations under scope ``attention_latent``
(latent attention of every layer, the leading dense ones' too: norm,
projections, rotation, the three flash kernels and the output's product),
forward, backward and replay together."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "attention_latent")
