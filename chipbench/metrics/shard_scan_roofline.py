"""The least time one chip could take for its share of one sharded solve
(operations, bytes and gathered bytes counted from the live extents B, V,
W / D, R; the largest of the three over their peaks) over the sharded
program's measured device time per solve per device, %."""

from chipbench import shard_cost


def read(observed):
    reduced = observed.get("trace")
    if (not reduced or not reduced["kernel_calls"] or not reduced["kernel_s"]
            or "mesh_devices" not in observed):
        return None
    cost = shard_cost.shard_scan_cost(
        **observed["extents"], D=observed["mesh_devices"])
    least, _bound = shard_cost.least_seconds(cost, observed["device_kind"])
    return 100.0 * least / (reduced["kernel_s"] / reduced["kernel_calls"])
