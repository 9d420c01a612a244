"""Median per tick of queueing multi-node tasks between two ticks (the
program's span `cycle/ready/mn_sort`, inside `cycle/ready`: `_make_ready`
appends the task to `core.mn_queue` and sorts the whole queue, once a
task; 0 for a tick before which no multi-node task became ready), host
clock, ms."""

import statistics

KEY = "cycle/ready/mn_sort"


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks or not any(KEY in p for p in ticks):
        return None  # a program without this span
    return statistics.median(p.get(KEY, 0.0) for p in ticks)
