"""Median per tick of the reservation step of `--gang-drain busy` (the
program's span `gangs/reserve`, inside `gangs`: the tick's gang rows
reserve busy workers across ticks, read from the snapshot's idleness,
group and reservation columns), host clock, ms."""

import statistics

KEY = "gangs/reserve"


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks or not any(KEY in p for p in ticks):
        return None  # a program without this span
    return statistics.median(p.get(KEY, 0.0) for p in ticks)
