"""What the cut scan with gang rows has to compute and move, counted from
the problem's live extents and never from the implementation, and the least
time a chip of known peaks could take for it.

The scan is `kernel_cost.cut_scan_cost` over all B rows (a gang row is a row
of the counts like any other).  What the gangs add: per worker one mark of
idleness and one group number are read, and per gang row the node count;
each gang row decides, for every worker, whether it is eligible (idle, not
yet touched, has a task slot: 3), adds it to its group's count (1), and,
for the group chosen, ranks it among the eligible and takes or holds it
(a prefix, a compare, a mask: 3), then clears what it took from the state
(R + 2); picking the group is a compare and a running best over the G
groups (2 G).
"""

from __future__ import annotations

from chipbench import kernel_cost


def gang_scan_cost(B: int, V: int, W: int, R: int, G: int,
                   gang_rows: int) -> dict:
    """Operations and bytes of one solve (4-byte integers throughout): B
    rows of which `gang_rows` are gangs, V variants, W workers in G groups,
    R resources."""
    cost = kernel_cost.cut_scan_cost(B=B, V=V, W=W, R=R)
    return {
        "ops": cost["ops"] + gang_rows * (W * (7 + R + 2) + 2 * G),
        "bytes": cost["bytes"] + 4 * (2 * W + B),
    }


least_seconds = kernel_cost.least_seconds
