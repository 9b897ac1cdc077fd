"""``bootstrap_s`` (the worker's two marks) less every set-up span the
program reports: what of the worker's start no span sees yet. The first
span starts at the process's start, a little before the first mark, so a
few hundredths of a second below zero is the interpreter's own start."""

import named_trace
from metrics import bootstrap_s


def read(r):
    setup = named_trace.setup_spans(r)
    if not setup:
        return None
    return bootstrap_s.read(r) - sum(setup.values())
