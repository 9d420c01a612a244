"""Median per tick of the wait for the kernel and the counts' readback
(`device_sync/counts`), the program's own span, ms; less `kernel_ms` it is
launch latency plus the readback of the counts."""

import statistics

KEY = "device_sync/counts"


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks or not any(KEY in p for p in ticks):
        return None  # a program without this span
    return statistics.median(p.get(KEY, 0.0) for p in ticks)
