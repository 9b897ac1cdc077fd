"""The stepstats ``compile`` bucket: the first train_step call, traced,
compiled or fetched from the cache, and dispatched."""


def read(r):
    stats = r["report"].get("stepstats") or {}
    return (stats.get("buckets") or {}).get("compile")
