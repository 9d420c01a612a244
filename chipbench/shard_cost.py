"""What one chip's share of the sharded cut scan has to compute and move,
counted from the problem's live extents and never from the implementation,
and the least time a chip of known peaks could take for it.

The worker axis is split over D chips; the batch table is replicated.  One
chip's share is `kernel_cost.cut_scan_cost` at W / D rows, with what the
whole-node requests add (the workers' totals as a second state array, the
marks beside the needs, one compare and one mask per worker and resource)
and what crossing the chips adds: in every scan step each chip sends its C
per-class capacity sums and receives those of the other chips.
"""

from __future__ import annotations

from chipbench import kernel_cost

VISIT_CLASSES = 16  # per-class sums a chip contributes to a gather


def shard_scan_cost(B: int, V: int, W: int, R: int, D: int) -> dict:
    """Operations and bytes of one solve on one of D chips (4-byte integers
    throughout): B batches x V variants, W workers in all, R resources."""
    rows = -(-W // D)
    cost = kernel_cost.cut_scan_cost(B=B, V=V, W=rows, R=R)
    steps = B * V
    return {
        # free == total and total > 0 per resource, then the pool zeroed
        "ops": cost["ops"] + steps * rows * 3 * R,
        # the totals read once, the whole-node marks beside the needs
        "bytes": cost["bytes"] + 4 * (rows * R + steps * R),
        # per step: C sums out, and C from each of the other chips in
        "ici_bytes": 4 * steps * VISIT_CLASSES * D,
    }


def least_seconds(cost: dict, device_kind: str) -> tuple[float, str]:
    """(seconds, which bound): the largest of operations over the peak rate,
    bytes over the memory's bandwidth, gathered bytes over the links'."""
    peak = kernel_cost.peaks(device_kind)
    bounds = {
        "ops": cost["ops"] / peak["ops_per_s"],
        "bytes": cost["bytes"] / peak["hbm_bytes_per_s"],
        "ici": cost["ici_bytes"] * 8 / peak["ici_bits_per_s"],
    }
    bound = max(bounds, key=bounds.get)
    return bounds[bound], bound
