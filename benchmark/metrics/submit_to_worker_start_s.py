"""Harness clock at run_job to the worker's first line of Python."""


def read(r):
    return r["report"]["marks"]["worker_start"] - r["t_submit"]
