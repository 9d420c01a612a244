"""Puts the residency made over the window per tick (the model's own
counter, `resident_stats()["puts_total"]`: every `device_put` on the way
in, the packed buffer and whatever the placement cache missed)."""


def read(observed):
    before, after = observed.get("uploads_before"), observed.get("uploads_after")
    if not before or not after or not observed.get("ticks"):
        return None
    if "puts_total" not in after:
        return None  # a host solve, or a program without this counter
    return (after["puts_total"]
            - before.get("puts_total", 0)) / observed["ticks"]
