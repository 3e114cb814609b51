"""``window_misses.<kind>``: plan-cache misses during the window, from the
change in ``CacheStats.misses``.  Warm-up builds every entry, so it should
read 0; anything more is a build on the request path."""


def read(*, reduction, counters, cell):
    return counters.get("window_misses")
