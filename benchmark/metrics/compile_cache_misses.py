"""compile_cache.cache_stats() at the worker's report; 0 on a warm run."""


def read(r):
    return r["report"]["compile_cache"]["misses"]
