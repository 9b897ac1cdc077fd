"""Device milliseconds a step of what surrounds latent attention's kernels:
operations under the scopes ``latent_q``, ``latent_kv_down`` (with the
latent's norm), ``latent_kv_up``, ``latent_rope`` and ``latent_out``,
forward, backward and replay together."""

from metrics import op_names


def read(r):
    return op_names.ms(r, "latent_q", "latent_kv_down", "latent_kv_up",
                       "latent_rope", "latent_out")
