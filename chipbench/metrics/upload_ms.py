"""Median per tick of the host time to bring the device-resident state up to
date before the solve (`solve_dispatch/upload`: the dirty-row scatter or a
full upload, `DeviceResidency.sync`), the program's own span, ms."""

import statistics

KEY = "solve_dispatch/upload"


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks or not any(KEY in p for p in ticks):
        return None  # a program without this span
    return statistics.median(p.get(KEY, 0.0) for p in ticks)
