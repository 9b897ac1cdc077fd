"""All the tokens of all the steps of the window, over all its seconds
between its two syncs, over the chips."""


def read(r):
    rep = r["report"]
    return rep["tokens"] / rep["window_s"] / r["chips"]
