"""CPU time of the process over the window per task finished, us.  The
SimWorkers, the client and the invariant monitor share the process, so this
is the whole simulated deployment's host cost per task, not the server's."""


def read(observed):
    if not observed.get("finished_in_window") or "cpu_s" not in observed:
        return None
    return observed["cpu_s"] * 1e6 / observed["finished_in_window"]
