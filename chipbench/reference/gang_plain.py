"""Plain reference of one scheduling tick with multi-node tasks: the
semantics of `tick_plain` (single-node tasks, resource variants, user
priorities) plus gangs, tasks that run on n whole workers of one group.

One cluster of W rows with a group a row, numpy and Python integers.  What a
gang adds, as the configuration states it under `guarantees.order` and the
`gang_*` guarantees (the program documents it in docs/scheduler.md, "The
tick"):

- rows: a tick takes the first 16 ready gangs in queue order as rows, one
  each.  Rows are scanned highest user priority first; within a priority the
  gang rows come before the single-node rows, among themselves in queue
  order; single-node rows keep `tick_plain`'s order, which is reckoned from
  the state at the tick's start, as are the visit classes;
- what a gang row sees: the workers that run nothing, hold no gang and were
  touched by no earlier row of this tick (neither given a task nor taken or
  held by a gang row);
- what it does: it takes the first group with at least n such workers, and
  there the n lowest-numbered; if no group has n, it starts nothing and
  holds the up-to-n lowest-numbered such workers of the group that has most
  (the first on ties) against every later row of the tick;
- "first" group: groups are ordered by their lowest-numbered worker among
  those that run no gang at the tick's start, so the order moves as gangs
  start and end;
- a worker that runs a gang takes no other task until the gang ends, and
  then all its n workers are idle at once.  It counts for nothing in a tick:
  not towards scarcity, not towards the achievable share, in no visit class.

Nothing here is imported from the program.  `tick_plain` gives what is
unchanged: batch order, quotient, visit classes, water-fill, queues.  The
single-node rows between two gang rows are scanned by `tick_plain`'s own
tick, with the workers that gang rows took or hold hidden from it.
"""

from __future__ import annotations

from collections import deque

import numpy as np

from chipbench.reference import tick_plain

GANG_ROWS_PER_TICK = 16
GROUP_MODES = ("one_group", "any_group")


class Reference(tick_plain.Reference):
    def __init__(self, world, capacity: str = "exact",
                 stale_rows: bool = False, groups: str = "one_group",
                 hold: bool = True, late_gang_ends: bool = False,
                 idle_only: bool = True, skip_head: bool = False):
        """All but `world` exist for the controls only.
        `groups="any_group"` takes a gang's workers from the whole cluster;
        `hold=False` lets a gang that cannot start hold nothing;
        `late_gang_ends` frees a finished gang's workers a tick late;
        `idle_only=False` lets a gang take workers that run tasks;
        `skip_head` never offers the queue's first gang to a tick."""
        if groups not in GROUP_MODES:
            raise ValueError(groups)
        super().__init__(world, capacity=capacity, stale_rows=stale_rows)
        self.hold = hold
        self.late_gang_ends = late_gang_ends
        self.idle_only = idle_only
        self.skip_head = skip_head
        self._late_gangs: list = []
        self.all_slots = world.worker_slots.copy()
        self.group = (np.zeros_like(world.worker_group)
                      if groups == "any_group" else world.worker_group)
        self.in_gang = np.zeros(len(self.group), dtype=bool)
        self.gang_prio = int(world.gang_prio)
        # ready gangs, oldest first: (gang number, nodes)
        self.gang_queue = deque(enumerate(world.gang_nodes.tolist()))
        self.n_gangs = len(self.gang_queue)
        self.running_gangs: dict[int, list[int]] = {}
        self.last_gangs: list = []   # what the last tick started
        self._segment = self._ranks = None

    # -- what happens between ticks ---------------------------------------
    def arrive(self, task_ids, levels, gang_nodes=()) -> None:
        """New ready tasks, and new ready gangs at the queue's tail,
        numbered on from the last."""
        super().arrive(task_ids, levels)
        for n in gang_nodes:
            self.gang_queue.append((self.n_gangs, int(n)))
            self.n_gangs += 1

    def finish(self, task_ids, gangs=()) -> int:
        """Release what the tasks hold and what the gangs hold; returns how
        many of either were not running."""
        unknown = super().finish(task_ids)
        ended, self._late_gangs = self._late_gangs, []
        for g in gangs:
            members = self.running_gangs.pop(int(g), None)
            if members is None:
                unknown += 1
            elif self.late_gang_ends:
                self._late_gangs.append(members)
            else:
                ended.append(members)
        for members in ended:
            self.in_gang[members] = False
            self.free[members] = self.total[members]
            self.slots[members] = self.all_slots[members]
        return unknown

    # -- one tick ------------------------------------------------------------
    def _batches(self):
        return super()._batches() if self._segment is None else self._segment

    def _visit_ranks(self):
        return super()._visit_ranks() if self._ranks is None else self._ranks

    def _group_order(self) -> list:
        """Groups by their lowest-numbered worker that runs no gang."""
        groups = self.group[~self.in_gang]
        _values, first = np.unique(groups, return_index=True)
        return groups[np.sort(first)].tolist()

    def _gang_row(self, n: int, avail: np.ndarray, order: list):
        """(members or None, workers held): the row's pick among `avail`."""
        counts = np.bincount(self.group[avail],
                             minlength=int(self.group.max()) + 1)
        chosen = next((g for g in order if counts[g] >= n), None)
        starts = chosen is not None
        if not starts:
            if not self.hold or not order:
                return None, []
            chosen = max(order, key=lambda g: counts[g])  # first on ties
        picked = np.flatnonzero(avail & (self.group == chosen))[:n].tolist()
        return (picked, []) if starts else (None, picked)

    def tick(self):
        """Place what fits.  Returns (cells, taken) as `tick_plain` does, of
        the single-node tasks; `last_gangs` then holds, for every gang this
        tick started, (gang number, its workers)."""
        # order and visit classes as the state at the tick's start gives them
        batches = super()._batches()
        ranks = super()._visit_ranks() if batches else None
        first = int(self.skip_head)
        rows = [self.gang_queue[i] for i in range(
            first, min(first + GANG_ROWS_PER_TICK, len(self.gang_queue)))]
        order = self._group_order()
        hidden = np.zeros(len(self.group), dtype=bool)  # taken or held so far
        started: list = []
        cells, taken = [np.zeros((0, 4), dtype=np.int64)], {}
        levels = sorted({p for _c, p, _n in batches} | (
            {self.gang_prio} if rows else set()), reverse=True)
        for p in levels:
            if p == self.gang_prio:
                for g, n in rows:
                    avail = ~self.in_gang & ~hidden
                    if self.idle_only:
                        avail &= self.slots == self.all_slots
                    members, held = self._gang_row(n, avail, order)
                    if members is not None:
                        started.append((g, members))
                        hidden[members] = True
                    hidden[held] = True
            segment = [b for b in batches if b[1] == p]
            if segment:
                got_cells, got_taken = self._scan(segment, ranks, hidden)
                cells.append(got_cells)
                taken.update(got_taken)
        for g, members in started:
            self.in_gang[members] = True
            self.free[members] = 0
            self.slots[members] = 0
            self.running_gangs[g] = members
        begun = {g for g, _m in started}
        self.gang_queue = deque(r for r in self.gang_queue
                                if r[0] not in begun)
        self.last_gangs = started
        return np.concatenate(cells), taken

    def _scan(self, segment, ranks, hidden):
        """`tick_plain`'s tick over `segment`, in the order and with the
        visit classes given, the `hidden` workers offering nothing."""
        rows = np.flatnonzero(hidden)
        free, slots = self.free[rows].copy(), self.slots[rows].copy()
        self.free[rows] = 0
        self.slots[rows] = 0
        self._segment, self._ranks = segment, ranks
        try:
            return super().tick()
        finally:
            self._segment = self._ranks = None
            self.free[rows] = free
            self.slots[rows] = slots
