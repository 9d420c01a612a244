"""Wall time inside scheduling ticks (`Core.tick_stats`, phase `total`) over
the window's wall time, %."""


def read(observed):
    if not observed.get("window_s") or "tick_total_ms" not in observed:
        return None
    return 100.0 * observed["tick_total_ms"] / 1e3 / observed["window_s"]
