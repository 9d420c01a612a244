"""Median per tick of the reactor's fused gang phase (the program's span
`gangs`: the gang rows built from the multi-node queue, the worker-side
inputs aligned to the snapshot, the gang sentinels applied), host clock,
ms."""

import statistics


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks or not any("gangs" in p for p in ticks):
        return None  # a program without this span
    return statistics.median(p.get("gangs", 0.0) for p in ticks)
