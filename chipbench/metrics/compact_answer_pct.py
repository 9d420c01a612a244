"""Share of the window's device solves whose answer crossed to the host in
the packed buffer's compact form (the model's own counters,
`resident_stats()["answers_compact"]` over `["answers_total"]`: the rest
crossed dense, because the extents were small or because a compact buffer
overflowed into the dense fallback), in percent."""


def read(observed):
    before, after = observed.get("uploads_before"), observed.get("uploads_after")
    if not before or not after:
        return None
    if "answers_total" not in after:
        return None  # a program without this counter
    solves = after["answers_total"] - before.get("answers_total", 0)
    if not solves:
        return None
    return 100.0 * (after["answers_compact"]
                    - before.get("answers_compact", 0)) / solves
