"""Median per tick of the ready path between two ticks (the program's span
`cycle/ready`: every `reactor.on_new_tasks` call since the previous tick —
tasks inserted, dependencies counted, the ready ones queued; 0 for a tick
before which nothing was submitted), host clock, ms.  It lies outside the
tick's `total` and inside the cycle that `ticks_per_s` counts."""

import statistics

KEY = "cycle/ready"


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks or not any(KEY in p for p in ticks):
        return None  # a program without this span
    return statistics.median(p.get(KEY, 0.0) for p in ticks)
