"""The share of the selection kernels' causally open (query tile, key tile)
pairs that hold a selected key, mean a layer (the program's counter
``sparse.tiles_open_share``), in percent: what skipping a tile leaves to
skip. A program without the counter reads None."""

from metrics import op_names


def read(r):
    share = (op_names.counters(r) or {}).get("sparse.tiles_open_share")
    return None if share is None else 100.0 * share
