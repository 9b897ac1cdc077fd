"""Set-up spans ``cache_config + rendezvous + attach``: what
``bootstrap.initialize`` does, the TPU attach above all."""

import named_trace


def read(r):
    return named_trace.setup_s(r, "cache_config", "rendezvous", "attach")
