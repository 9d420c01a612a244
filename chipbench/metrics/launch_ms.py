"""Median per tick of the host time to place the replicated inputs and enqueue
the kernel and the slicer (`solve_dispatch/launch`), the program's own span,
ms."""

import statistics

KEY = "solve_dispatch/launch"


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks or not any(KEY in p for p in ticks):
        return None  # a program without this span
    return statistics.median(p.get(KEY, 0.0) for p in ticks)
