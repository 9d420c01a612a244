"""The least time one chip could take for its share of one sharded solve
with gang rows (operations, bytes and gathered bytes counted from the live
extents B, V, W / D, R, the groups and the gang rows,
`chipbench/gang_shard_cost.py`; the largest of the three over their peaks)
over the sharded program's measured device time per solve per device, %."""

from chipbench import gang_shard_cost


def read(observed):
    reduced = observed.get("trace")
    if (not reduced or not reduced["kernel_calls"] or not reduced["kernel_s"]
            or "mesh_devices" not in observed or "gang_rows" not in observed):
        return None
    cost = gang_shard_cost.gang_shard_scan_cost(
        **observed["extents"], G=observed["groups"],
        D=observed["mesh_devices"], gang_rows=observed["gang_rows"])
    least, _bound = gang_shard_cost.least_seconds(
        cost, observed["device_kind"])
    return 100.0 * least / (reduced["kernel_s"] / reduced["kernel_calls"])
