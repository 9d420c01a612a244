"""Device time of the sharded solve's collectives (the water-fill's per-step
gather, which a v5e runs as an all-reduce) per solve, mean over the devices:
the sum of their durations inside the sharded program's calls in the traced
span (the driver makes the sum from the whole trace) over the number of
those calls on all devices, ms."""


def read(observed):
    reduced = observed.get("trace")
    if (observed.get("collective_s") is None or not reduced
            or not reduced["kernel_calls"]):
        return None
    return observed["collective_s"] / reduced["kernel_calls"] * 1e3
