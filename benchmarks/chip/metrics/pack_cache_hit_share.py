"""Hits over lookups of the packed-posting row cache in the window, from
the cache's own counters in the service's metrics registry."""


def read(run):
    hits = run.counters.get("cache.pack.hits", 0)
    misses = run.counters.get("cache.pack.misses", 0)
    if hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
