"""Reserved workers that ran a single-node task at the solve, over the
window per tick (the program's own counter,
`hq_solve_gang_reserved_busy_total`, summed per tick by the reservation
step): the drain still to come behind the waiting gangs."""


def read(observed):
    if "reserved_busy_in_window" not in observed or not observed.get("ticks"):
        return None  # a program without this counter
    return observed["reserved_busy_in_window"] / observed["ticks"]
