"""A control, not a reference of any configuration: `gang_plain` with the
gang selection made shard by shard, as a sharded solve would make it if each
chip ranked a group's eligible workers among its own rows alone (the sharded
kernel's `same_group_before` read as 0).

The rows of a solve are the workers that run no gang, in worker order, padded
to a power of two and split contiguously over `SHARDS` chips.  The groups'
eligible counts are still cluster-wide, so the same group is chosen; but each
shard takes (or holds) the first n eligible workers of that group among its
own rows, so a group whose eligible workers lie on two shards gives the gang
more than n members.  `chipbench/control_gang_shard.py` puts it in the
program's place: the comparison has to catch a selection that ignores the
other chips.
"""

from __future__ import annotations

import numpy as np

from chipbench.reference import gang_plain

SHARDS = 4


class Reference(gang_plain.Reference):
    def _gang_row(self, n: int, avail: np.ndarray, order: list):
        members, held = super()._gang_row(n, avail, order)
        picked = members if members is not None else held
        if not picked:
            return members, held
        chosen = self.group[picked[0]]
        dense = np.cumsum(~self.in_gang) - 1     # row of a worker in the solve
        rows = int(dense[-1]) + 1
        per_shard = -(-(1 << max(rows - 1, 1).bit_length()) // SHARDS)
        eligible = np.flatnonzero(avail & (self.group == chosen))
        shard = dense[eligible] // per_shard
        local = [w for s in np.unique(shard)
                 for w in eligible[shard == s][:n].tolist()]
        return (local, []) if members is not None else (None, local)
