"""What the cut scan has to compute and move, counted from the problem's
live extents and never from the implementation, and the least time a chip
of known peaks could take for it."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}"
        )
    return table[device_kind]


def cut_scan_cost(B: int, V: int, W: int, R: int) -> dict:
    """Operations and bytes of one solve over B batches x V variants,
    W workers, R resources (4-byte integers throughout).

    Per (batch, variant): for every worker and requested resource one
    quotient and one running minimum (2 R W); the slot bound, the bound by
    what is left of the batch and the clamp at zero (3 W); a prefix sum over
    the workers in visit order and the water-fill clip (3 W); the update of
    free resources (2 R W) and slots (W).
    Bytes: the worker state is read and written once (free W R, slots and
    lifetime W each), the batch table is read (needs B V R, sizes B,
    min_time B V, one visit class per worker and batch variant as a byte),
    and the counts (B V W) are written."""
    ops = B * V * W * (4 * R + 7)
    bytes_moved = 4 * (
        2 * W * R + 3 * W + B * V * R + B + B * V + B * V * W
    ) + B * V * W
    return {"ops": ops, "bytes": bytes_moved}


def least_seconds(cost: dict, device_kind: str) -> tuple[float, str]:
    """(seconds, which bound)."""
    peak = peaks(device_kind)
    by_ops = cost["ops"] / peak["ops_per_s"]
    by_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (by_ops, "ops") if by_ops >= by_bytes else (by_bytes, "bytes")
