"""Structural rebuilds of the dense tick snapshot over the window per tick
(the program's own counter, `TickStateCache.full_rebuilds`, the series
`hq_tick_cache_full_rebuilds_total` of a server): a worker that starts or
ends a multi-node task leaves or rejoins the row set."""


def read(observed):
    before, after = observed.get("cache_before"), observed.get("cache_after")
    if not before or not after or not observed.get("ticks"):
        return None
    if "full_rebuilds" not in after:
        return None  # a program without this counter
    return (after["full_rebuilds"]
            - before.get("full_rebuilds", 0)) / observed["ticks"]
