"""Median per tick of what the host spends dispatching the solve and waiting
for its counts (`solve_dispatch` + `device_sync`: execute, readback and
wait, not kernel time), host clock, ms."""

import statistics


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks:
        return None
    names = observed["device_phases"]
    return statistics.median(
        sum(p.get(n, 0.0) for n in names) for p in ticks
    )
