"""Share of the window's logical block reads the store's clock cache served
(StoreStats hits / reads)."""


def read(run):
    s = run["store"]
    if s is None or s.reads <= 0:
        return None
    return 100.0 * s.cache_hits / s.reads
