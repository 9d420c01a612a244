"""Full uploads of the resident worker state over the window per tick (the
model's own counter, `resident_stats()["full_uploads"]`): more than half the
rows differ from the device's, or the worker bucket changed."""


def read(observed):
    before, after = observed.get("uploads_before"), observed.get("uploads_after")
    if not before or not after or not observed.get("ticks"):
        return None
    if "full_uploads" not in after:
        return None  # a host solve, or a program without this counter
    return (after["full_uploads"]
            - before.get("full_uploads", 0)) / observed["ticks"]
