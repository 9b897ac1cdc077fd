"""1 - the union of device operations over the traced window."""


def read(r):
    t = r["trace"]
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
