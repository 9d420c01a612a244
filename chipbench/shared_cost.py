"""What the cut scan with gang rows and reservations has to compute and
move, counted from the problem's live extents and never from the
implementation, and the least time a chip of known peaks could take for it.

`gang_cost.gang_scan_cost`'s terms, and what the reservations add: the
reservation column is read once (W integers); every single-node row's
capacity on every worker is masked by it (one operation per worker a batch
and variant, B V W); and each gang row compares the column with its own
code and masks the workers reserved for another gang out of its eligible
ones (3 W), makes a second selection over its own reserved workers (adds
them to their group's count, ranks them, compares and masks: 4 W; picks the
group: 2 G) and chooses between the two selections (W).
"""

from __future__ import annotations

from chipbench import gang_cost


def shared_scan_cost(B: int, V: int, W: int, R: int, G: int,
                     gang_rows: int) -> dict:
    """Operations and bytes of one solve (4-byte integers throughout): B
    rows of which `gang_rows` are gangs, V variants, W workers in G groups,
    R resources, a reservation code per worker."""
    cost = gang_cost.gang_scan_cost(B=B, V=V, W=W, R=R, G=G,
                                    gang_rows=gang_rows)
    return {
        "ops": cost["ops"] + B * V * W + gang_rows * (8 * W + 2 * G),
        "bytes": cost["bytes"] + 4 * W,
    }


least_seconds = gang_cost.least_seconds
