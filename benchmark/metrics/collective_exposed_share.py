"""Time a collective runs on a chip while no other operation does, over
the traced window, averaged over the chips."""


def read(r):
    t = r["trace"]
    if not t or not t["collective_s"]:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
