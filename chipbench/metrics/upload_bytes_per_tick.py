"""Bytes the resident model uploaded over the window (its own counter,
`resident_stats()["upload_bytes_total"]`) per tick."""


def read(observed):
    before, after = observed.get("uploads_before"), observed.get("uploads_after")
    if not before or not after or not observed.get("ticks"):
        return None
    if "upload_bytes_total" not in after:
        return None
    return (after["upload_bytes_total"]
            - before.get("upload_bytes_total", 0)) / observed["ticks"]
