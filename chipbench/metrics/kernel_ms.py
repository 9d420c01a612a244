"""Device time of the solve program per solve: the sum of the durations of
its events on the trace's modules line over their number, ms."""


def read(observed):
    reduced = observed.get("trace")
    if not reduced or not reduced["kernel_calls"]:
        return None
    return reduced["kernel_s"] / reduced["kernel_calls"] * 1e3
