"""Set-up span ``ckpt_open``: ``CheckpointManager(...)`` and its
``latest_step()`` in ``run_elastic`` (the first ``import orbax`` of the
process is inside)."""

import named_trace


def read(r):
    return named_trace.setup_s(r, "ckpt_open")
