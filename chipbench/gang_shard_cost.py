"""What one chip's share of the sharded cut scan with gang rows has to
compute and move, counted from the problem's live extents and never from the
implementation, and the least time a chip of known peaks could take for it.

The worker axis (the W workers that run no gang) is split over D chips, the
batch table is replicated.  One chip's share is `kernel_cost.cut_scan_cost`
at W / D rows over all B rows (a gang row is a row of the counts like any
other), with what crossing the chips adds to a step (`shard_cost`: each chip
sends its per-class capacity sums and receives the others') and what the
gangs add (`gang_cost`'s terms, at this chip's rows): per worker one mark of
idleness and one group number are read, and per gang row the node count (the
inputs as the problem defines them, not the (W, G) one-hot the program
sends); each gang row decides eligibility, counts its group, ranks and takes
or holds, clears what it took (7 + R + 2 per worker) and picks a group
(2 G); and because a group's members may lie on several chips, each gang row
gathers the G per-group counts of eligible workers: G out, G from each of the
other chips in.  This deployment has no whole-node class, so
`shard_cost.shard_scan_cost`'s whole-node terms (the totals, the marks, three
operations per worker and resource a step) are not counted: they are work it
does not have.
"""

from __future__ import annotations

from chipbench import kernel_cost, shard_cost


def gang_shard_scan_cost(B: int, V: int, W: int, R: int, G: int, D: int,
                         gang_rows: int) -> dict:
    """Operations and bytes of one solve on one of D chips (4-byte integers
    throughout): B rows of which `gang_rows` are gangs, V variants, W workers
    in all in G groups, R resources."""
    rows = -(-W // D)
    cost = kernel_cost.cut_scan_cost(B=B, V=V, W=rows, R=R)
    return {
        "ops": cost["ops"] + gang_rows * (rows * (7 + R + 2) + 2 * G),
        "bytes": cost["bytes"] + 4 * (2 * rows + B),
        "ici_bytes": 4 * (B * V * shard_cost.VISIT_CLASSES
                          + gang_rows * G) * D,
    }


least_seconds = shard_cost.least_seconds
