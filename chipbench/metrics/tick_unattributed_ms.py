"""Median per tick of the part of a tick that no top-level phase covers:
`total` less the host and device phases the driver lists (`host_phases`,
`device_phases`: the top-level keys of a tick), host clock, ms.  Children
(a key with a `/`) lie inside their parents, `sync` lies inside the
harness's `snapshot`, and the keys under `cycle/` lie between ticks, outside
`total`: none of them is subtracted.  What is left is glue between the
spans: work that shows in `tick_ms_p50` and in no layer."""

import statistics


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks or not all("total" in p for p in ticks):
        return None
    names = tuple(observed["host_phases"]) + tuple(observed["device_phases"])
    return statistics.median(
        p["total"] - sum(p.get(n, 0.0) for n in names) for p in ticks
    )
