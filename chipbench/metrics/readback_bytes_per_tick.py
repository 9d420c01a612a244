"""Bytes the resident model read back from the device over the window (its
own counter, `resident_stats()["readback_bytes_total"]`: the counts and the
two state arrays of every solve) per tick."""


def read(observed):
    before, after = observed.get("uploads_before"), observed.get("uploads_after")
    if not before or not after or not observed.get("ticks"):
        return None
    if "readback_bytes_total" not in after:
        return None  # a program without this counter
    return (after["readback_bytes_total"]
            - before.get("readback_bytes_total", 0)) / observed["ticks"]
