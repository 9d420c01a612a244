"""Scan steps the program counted over the window per tick (its own counter,
`hq_solve_scan_steps_total`: live batches x variants of every dense solve,
on the sharded path one water-fill all-gather each)."""


def read(observed):
    if "scan_steps_in_window" not in observed or not observed.get("ticks"):
        return None  # a program without this counter
    return observed["scan_steps_in_window"] / observed["ticks"]
