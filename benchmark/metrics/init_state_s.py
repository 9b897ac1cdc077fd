"""Set-up spans ``init_state + restore``: the state drawn on the device, or
read back from a checkpoint (host seconds: the draw is only enqueued)."""

import named_trace


def read(r):
    return named_trace.setup_s(r, "init_state", "restore")
