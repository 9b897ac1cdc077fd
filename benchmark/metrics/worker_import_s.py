"""Set-up span ``pre_bootstrap``: from the worker process's start as the OS
has it to the entry of ``bootstrap.initialize``: the interpreter, ``import
jax``, the program's imports."""

import named_trace


def read(r):
    return named_trace.setup_s(r, "pre_bootstrap")
