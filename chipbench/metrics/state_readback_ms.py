"""Median per tick of the two state readbacks after the counts and the copy
into the residency's mirror (`device_sync/state`), the program's own span,
ms."""

import statistics

KEY = "device_sync/state"


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks or not any(KEY in p for p in ticks):
        return None  # a program without this span
    return statistics.median(p.get(KEY, 0.0) for p in ticks)
