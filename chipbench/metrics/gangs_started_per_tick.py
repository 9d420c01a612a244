"""Gangs the fused solve started over the window per tick (the program's
own counter, `hq_solve_gang_groups`: gangs whose sentinel assignments the
reactor validated and applied)."""


def read(observed):
    if "gangs_started_in_window" not in observed or not observed.get("ticks"):
        return None  # a program without this counter
    return observed["gangs_started_in_window"] / observed["ticks"]
