"""Plain reference of one scheduling tick with multi-node tasks that
reserve busy workers and drain them (`--gang-drain busy`): `gang_plain`'s
tick plus reservations.

What the reservations add, as the configuration states them under
`guarantees.order`, `reserved_exclusive` and `reservation_honoured` (the
program documents them in docs/scheduler.md, "The tick"):

- a reservation step comes first in a tick, over the tick's gang rows in
  their order.  A row's gang lifts its reservation while a ready single-node
  task of a strictly higher user priority exists (all of this world's
  classes fit some worker); it changes nothing while some group holds n idle
  workers free for it; it keeps its reservation while it holds n reserved
  workers; else it reserves anew in the group with the most workers free
  for it (the first in group order on ties; none if that is under n), the n
  first by (idle first, fewest running tasks, worker number).  Free for a
  gang: running no gang, reserved for no gang or for it, as the
  reservations stand when its turn comes (an earlier row's count);
- a reserved worker offers nothing to any single-node row of the tick, and
  no gang row but its own's sees it;
- a gang row whose own reserved workers that it sees number n takes them
  (the first group in group order with n, the lowest-numbered there), ahead
  of every other group; else it selects as `gang_plain` does among the
  workers reserved for no other gang;
- a gang's reservation ends when it starts, leaves the queue, is no
  longer among the tick's gang rows (lifted before the reservation step)
  or is outranked; it stands across ticks otherwise.

Nothing here is imported from the program, and neither `gang_plain` nor
`tick_plain` is edited: their tick's loop is written out again below with
the two masks in it.
"""

from __future__ import annotations

from collections import deque

import numpy as np

from chipbench.reference import gang_plain

NONE = -1


class Reference(gang_plain.Reference):
    def __init__(self, world, groups: str = "one_group",
                 reserve: bool = True, feed_reserved: bool = False,
                 lift_each_tick: bool = False):
        """All but `world` exist for the controls only.  `reserve=False`
        makes no reservation (`--gang-drain idle`); `feed_reserved` lets
        reserved workers take single-node tasks; `lift_each_tick` lifts
        every reservation at the end of the tick that made it;
        `groups="any_group"` is `gang_plain`'s."""
        super().__init__(world, groups=groups)
        self.reserve = reserve
        self.feed_reserved = feed_reserved
        self.lift_each_tick = lift_each_tick
        self.resv = np.full(len(self.group), NONE, dtype=np.int64)
        # what the last tick's reservation step left: gang -> its rows
        self.last_reservations: dict[int, list[int]] = {}

    # -- the reservation step ----------------------------------------------
    def _top_priority(self):
        """The highest user priority of a ready single-node task."""
        for p in range(self.n_p - 1, -1, -1):
            if any(self.levels[c * self.n_p + p]
                   for c in range(self.needs.shape[0])):
                return p
        return None

    def _reserve(self, rows, order: list) -> None:
        idle = ~self.in_gang & (self.slots == self.all_slots)
        running = self.all_slots - self.slots
        top = self._top_priority()
        n_groups = int(self.group.max()) + 1
        self.resv[~np.isin(self.resv, [g for g, _n in rows])] = NONE
        for g, n in rows:
            mine = self.resv == g
            if top is not None and top > self.gang_prio:
                self.resv[mine] = NONE
                continue
            free = ~self.in_gang & ((self.resv == NONE) | mine)
            if (np.bincount(self.group[free & idle],
                            minlength=n_groups) >= n).any():
                continue
            if int(mine.sum()) == n:
                continue
            self.resv[mine] = NONE
            counts = np.bincount(self.group[free], minlength=n_groups)
            best = max(order, key=lambda grp: counts[grp])  # first on ties
            if counts[best] < n:
                continue
            members = np.flatnonzero(free & (self.group == best)).tolist()
            members.sort(key=lambda w: (not idle[w], running[w], w))
            self.resv[members[:n]] = g

    def _own_pick(self, n: int, mine: np.ndarray, order: list):
        """The first group with n of `mine`, its n lowest-numbered."""
        counts = np.bincount(self.group[mine],
                             minlength=int(self.group.max()) + 1)
        chosen = next((grp for grp in order if counts[grp] >= n), None)
        if chosen is None:
            return None
        return np.flatnonzero(mine & (self.group == chosen))[:n].tolist()

    # -- one tick ------------------------------------------------------------
    def tick(self):
        """As `gang_plain`'s tick, with the reservation step first and the
        two masks; `last_reservations` then holds what the step left."""
        batches = tick_batches = super(gang_plain.Reference, self)._batches()
        ranks = (super(gang_plain.Reference, self)._visit_ranks()
                 if batches else None)
        rows = [self.gang_queue[i] for i in range(
            min(gang_plain.GANG_ROWS_PER_TICK, len(self.gang_queue)))]
        order = self._group_order()
        if self.reserve:
            self._reserve(rows, order)
        resv = self.resv
        self.last_reservations = {}
        for w in np.flatnonzero(resv != NONE).tolist():
            self.last_reservations.setdefault(int(resv[w]), []).append(w)
        reserved = (resv != NONE) if not self.feed_reserved else \
            np.zeros(len(resv), dtype=bool)
        hidden = np.zeros(len(self.group), dtype=bool)  # taken or held so far
        started: list = []
        cells, taken = [np.zeros((0, 4), dtype=np.int64)], {}
        levels = sorted({p for _c, p, _n in tick_batches} | (
            {self.gang_prio} if rows else set()), reverse=True)
        for p in levels:
            if p == self.gang_prio:
                for g, n in rows:
                    avail = (~self.in_gang & ~hidden
                             & (self.slots == self.all_slots)
                             & ((resv == NONE) | (resv == g)))
                    members = self._own_pick(n, avail & (resv == g), order)
                    held = []
                    if members is None:
                        members, held = self._gang_row(n, avail, order)
                    if members is not None:
                        started.append((g, members))
                        hidden[members] = True
                    hidden[held] = True
            segment = [b for b in batches if b[1] == p]
            if segment:
                got_cells, got_taken = self._scan(segment, ranks,
                                                  hidden | reserved)
                cells.append(got_cells)
                taken.update(got_taken)
        for g, members in started:
            self.in_gang[members] = True
            self.free[members] = 0
            self.slots[members] = 0
            self.running_gangs[g] = members
            resv[resv == g] = NONE
        begun = {g for g, _m in started}
        self.gang_queue = deque(r for r in self.gang_queue
                                if r[0] not in begun)
        self.last_gangs = started
        if self.lift_each_tick:
            resv.fill(NONE)
        return np.concatenate(cells), taken
