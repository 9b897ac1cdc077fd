"""From the start of run.py to the opening of the window: submit,
reconcile, bind, launch, bootstrap, init on the device, the first step with
its compile or cache hit, the warm-up steps and their readings."""


def read(r):
    return r["report"]["marks"]["window_open"] - r["t0"]
