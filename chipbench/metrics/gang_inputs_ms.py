"""Median per tick of the host work the gang inputs cost on their way to
the solve (the program's spans `gangs/inputs`: idleness and group of every
row; `assemble/gang`: `gang_nodes`, `gang_ok` and the (W, G) one-hot built;
`solve_host_prep/gang`: the three padded into fresh arrays), host clock, ms.
Packing and putting them is part of `upload_ms`."""

import statistics

NEW_SPANS = ("assemble/gang", "solve_host_prep/gang")   # since PR 33
SPANS = ("gangs/inputs",) + NEW_SPANS


def read(observed):
    ticks = observed.get("tick_phases_ms")
    if not ticks or not any(all(k in p for k in NEW_SPANS) for p in ticks):
        return None  # a program without these spans
    return statistics.median(sum(p.get(k, 0.0) for k in SPANS) for p in ticks)
