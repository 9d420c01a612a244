"""Median per tick of the host phases the tick already times (snapshot,
batches, assemble, solve_host_prep, mapping, apply), host clock, ms."""

import statistics


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks:
        return None
    names = observed["host_phases"]
    return statistics.median(
        sum(p.get(n, 0.0) for n in names) for p in ticks
    )
