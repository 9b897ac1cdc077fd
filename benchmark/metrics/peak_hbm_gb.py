"""memory_stats()["peak_bytes_in_use"] of the fullest chip, read when the
window has closed and before the reference runs."""


def read(r):
    peak = r["report"]["device"]["memory_peak_bytes"]
    return None if peak is None else peak / 1e9
