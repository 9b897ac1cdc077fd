"""Worker start to the first batch drawn: imports, bootstrap.initialize,
the mesh, and the state drawn on the device."""


def read(r):
    m = r["report"]["marks"]
    return m["first_batch"] - m["worker_start"]
