"""The least time the chip could take for one solve (operations and bytes
counted from the live extents B, V, W, R; the larger of ops over peak and
bytes over peak bandwidth) over the kernel's measured time, %."""

from chipbench import kernel_cost


def read(observed):
    reduced = observed.get("trace")
    if not reduced or not reduced["kernel_calls"] or not reduced["kernel_s"]:
        return None
    cost = kernel_cost.cut_scan_cost(**observed["extents"])
    least, _bound = kernel_cost.least_seconds(cost, observed["device_kind"])
    return 100.0 * least / (reduced["kernel_s"] / reduced["kernel_calls"])
