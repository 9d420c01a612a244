"""The least time the chip could take for one solve with its gang rows and
reservations (operations and bytes counted from the live extents B, V, W,
R, the groups and the gang rows, `chipbench/shared_cost.py`; the larger of
the two over the chip's peaks) over the measured device time per solve, %."""

from chipbench import shared_cost


def read(observed):
    reduced = observed.get("trace")
    if (not reduced or not reduced["kernel_calls"] or not reduced["kernel_s"]
            or not observed.get("reservations")):
        return None  # no trace, or a run without reservations
    cost = shared_cost.shared_scan_cost(
        **observed["extents"], G=observed["groups"],
        gang_rows=observed["gang_rows"])
    least, _bound = shared_cost.least_seconds(cost, observed["device_kind"])
    return 100.0 * least / (reduced["kernel_s"] / reduced["kernel_calls"])
