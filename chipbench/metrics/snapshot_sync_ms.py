"""Median per tick of bringing the dense snapshot up to date (the program's
span `sync`, timed inside `TickStateCache.sync` where the work happens: the
dirty rows written, the flipped workers re-read, on a structural change
every worker walked), host clock, ms.  It lies inside the harness's own
`snapshot` reading, which also holds the call and the harness's span."""

import statistics

KEY = "sync"


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks or not any(KEY in p for p in ticks):
        return None  # a program without this span
    return statistics.median(p.get(KEY, 0.0) for p in ticks)
