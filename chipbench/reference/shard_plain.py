"""Plain reference of one scheduling tick with whole-node requests: the
semantics of `tick_plain` (single-node tasks, resource variants, user
priorities) plus requests for a worker's whole pool of a resource
(`cpus = all`, upstream's allocation policy `all`).

One cluster of W rows, numpy and Python integers, no sharding: how the
program splits the workers over chips must not show in any placement.

What a whole-node entry changes, as the configuration states it under
`guarantees.order` and `guarantees.whole_node` (the program documents it in
docs/scheduler.md, "The tick"):

- capacity: a variant with a whole-node entry fits a worker at most once,
  and only while nothing of that pool is held (free equals the worker's
  total, and the total is not zero); its other amounts bound it as usual;
- what it takes: the worker's whole pool of that resource, and its amounts
  of the others; what it gives back when it finishes is the same;
- batch order: the entry asks for the resource, so the resource's scarcity
  counts; towards the achievable share it adds 1 / W (one worker's pool of
  W, whatever the pools' sizes) and bounds the fit by W;
- waste class: the resource counts as asked for, so it is never waste.

Nothing here is imported from the program.  `tick_plain` gives what is
unchanged: the scarcity weights, the quotient, the visit classes and the
queues.
"""

from __future__ import annotations

import numpy as np

from chipbench.generate import UNIT
from chipbench.reference import tick_plain
from chipbench.reference.tick_plain import (
    MAX_BATCH,
    MAX_CUTS_PER_QUEUE,
    scarcity_weights,
)

WHOLE_NODE_MODES = ("all", "as_21_cpus")


class Reference(tick_plain.Reference):
    def __init__(self, world, capacity: str = "exact",
                 stale_rows: bool = False, whole_node: str = "all"):
        """`whole_node="as_21_cpus"` exists for a control only: a
        whole-node request is then read as one for 21 cpus, the class
        whose place the whole-node classes took."""
        if whole_node not in WHOLE_NODE_MODES:
            raise ValueError(whole_node)
        super().__init__(world, capacity=capacity, stale_rows=stale_rows)
        self.whole = world.class_all
        if whole_node == "as_21_cpus":
            self.needs = np.where(self.whole, 21 * UNIT, self.needs)
            self.whole = np.zeros_like(self.whole)
        # per class and variant, for the scan: the amounts as a list, the
        # resources asked for by amount and whole, and both as one mask
        self._steps = [
            [(self.needs[c, v].tolist(),
              np.flatnonzero(self.needs[c, v] > 0).tolist(),
              np.flatnonzero(self.whole[c, v]).tolist(),
              (self.needs[c, v] > 0) | self.whole[c, v])
             for v in range(int(self.n_variants[c]))]
            for c in range(self.needs.shape[0])
        ]

    def finish(self, task_ids) -> int:
        unknown = 0
        release, self._late = self._late, []
        for t in task_ids:
            placed = self.running.pop(int(t), None)
            if placed is None:
                unknown += 1
            elif self.stale_rows:
                self._late.append(placed)
            else:
                release.append(placed)
        if release:
            # what a task holds: its amounts, and the worker's whole pool
            # where its variant asked for one
            w, c, v = np.asarray(release, dtype=np.int64).T
            np.add.at(self.free, w,
                      self.needs[c, v] + self.whole[c, v] * self.total[w])
            np.add.at(self.slots, w, 1)
        return unknown

    # -- one tick ------------------------------------------------------------
    def _batches(self):
        """[(class, user priority, size)] in scan order."""
        col_totals = np.maximum(self.free, 0).sum(axis=0)
        weights = scarcity_weights(col_totals)
        totals = col_totals.tolist()
        n_r = len(totals)
        n_w = self.free.shape[0]
        keys = {}

        def class_key(c):
            scarcity = float("inf")
            per_variant = []
            for v in range(int(self.n_variants[c])):
                need = self.needs[c, v].tolist()
                whole = self.whole[c, v].tolist()
                v_score = 0.0
                for r in range(n_r):
                    if need[r] > 0 or whole[r]:
                        v_score = max(v_score, float(weights[r]))
                scarcity = min(scarcity, v_score)
                share, fit = 0.0, float("inf")
                for r in range(n_r):
                    if whole[r]:
                        share += 1.0 / max(n_w, 1)
                        fit = min(fit, float(n_w))
                        continue
                    if need[r] <= 0:
                        continue
                    if totals[r] <= 0:
                        fit = 0.0
                        break
                    share += need[r] / totals[r]
                    fit = min(fit, totals[r] // need[r])
                if fit == float("inf"):
                    fit = 0.0
                per_variant.append((1.0 * share, fit))
            return (0.0 if scarcity == float("inf") else scarcity,
                    per_variant)

        batches = []
        n_c = self.needs.shape[0]
        for c in range(n_c):
            sizes = [
                (p, len(self.levels[c * self.n_p + p]))
                for p in range(self.n_p - 1, -1, -1)
                if self.levels[c * self.n_p + p]
            ]
            if len(sizes) > MAX_CUTS_PER_QUEUE:
                head = sizes[: MAX_CUTS_PER_QUEUE - 1]
                tail = sizes[MAX_CUTS_PER_QUEUE - 1:]
                sizes = head + [(tail[0][0], sum(n for _, n in tail))]
            batches.extend((c, p, n) for p, n in sizes)
        batches.sort(key=lambda b: (b[1], -b[0]), reverse=True)

        def sort_key(b):
            c, p, size = b
            if c not in keys:
                keys[c] = class_key(c)
            scarcity, per_variant = keys[c]
            best = (0.0, 0.0)
            for value, fit in per_variant:
                cand = (value * (size if size < fit else fit), -value)
                if cand > best:
                    best = cand
            return ((p, 0, 0), scarcity, best)

        batches.sort(key=sort_key, reverse=True)
        return batches

    def tick(self):
        """Place what fits.  Returns (cells, taken) as `tick_plain` does."""
        batches = self._batches()
        if not batches:
            return np.zeros((0, 4), dtype=np.int64), {}
        # `tick_plain`'s visit classes take the row of what a variant asks
        # for and read its zeros as unused: here the row is the mask of what
        # is asked for by amount or whole
        ranks_for = self._visit_ranks()
        # Only workers that could hold the smallest variant of any class
        # matter, and within a tick resources only shrink (a whole-node
        # entry needs at least something of its pool free).  Exact
        # shortcuts, not approximations.
        big = np.iinfo(np.int64).max
        asked = np.where(self.needs > 0, self.needs,
                         np.where(self.whole, 1, big))
        least = asked.reshape(-1, asked.shape[-1]).min(axis=0)
        absent = ((self.needs > 0) | self.whole).sum(
            axis=2, keepdims=True) == 0
        common = ((self.needs > 0) | self.whole | absent).all(axis=(0, 1))
        live = np.nonzero(
            (self.slots > 0)
            & (self.free[:, common] >= least[common]).all(axis=1)
        )[0]
        free, slots = self.free[live], self.slots[live]
        total = self.total[live]
        n_w = len(live)
        cells = []
        taken = {}
        most = free.max(axis=0, initial=0).tolist()
        for c, p, size in batches:
            if not n_w:
                break
            remaining = min(int(size), MAX_BATCH)
            level = c * self.n_p + p
            for v, (need_list, cols, whole_cols, asked) in enumerate(
                    self._steps[c]):
                if remaining <= 0:
                    break
                if not cols and not whole_cols:
                    continue  # an absent variant
                if any(a > m for a, m in zip(need_list, most)):
                    continue
                # only rows that hold one task's amounts can hold any: the
                # step runs on those (an exact shortcut: the others have
                # capacity 0 and the water-fill passes over them)
                fits = slots > 0
                if self.capacity == "exact":
                    for r in cols:
                        fits &= free[:, r] >= need_list[r]
                for r in whole_cols:
                    fits &= (free[:, r] == total[:, r]) & (total[:, r] > 0)
                rows = np.flatnonzero(fits)
                if not len(rows):
                    continue
                need = self.needs[c, v]
                cap = (tick_plain._capacity(free[rows], need, need > 0,
                                            self.capacity)
                       if cols else np.full(len(rows), MAX_BATCH))
                if whole_cols:
                    cap = np.minimum(cap, 1)
                cap = np.minimum(cap, slots[rows])
                np.clip(cap, 0, remaining, out=cap)
                if not cap.any():
                    continue
                order = np.lexsort((rows, ranks_for(asked)[live[rows]]))
                cap_sorted = cap[order]
                cum = np.cumsum(cap_sorted)
                take_sorted = np.clip(
                    remaining - (cum - cap_sorted), 0, cap_sorted
                )
                assign = np.zeros(n_w, dtype=np.int64)
                assign[rows[order]] = take_sorted
                remaining -= int(take_sorted.sum())
                free[rows] -= assign[rows, None] * need[None, :]
                for r in whole_cols:
                    free[rows, r] *= 1 - assign[rows]
                slots[rows] -= assign[rows]
                most = free.max(axis=0, initial=0).tolist()
                ws = np.nonzero(assign)[0]
                queue = self.levels[level]
                ids = taken.setdefault(level, [])
                for w, n in zip(live[ws].tolist(), assign[ws].tolist()):
                    cells.append((level, v, w, n))
                    for _ in range(n):
                        t = queue.popleft()
                        ids.append(t)
                        self.running[t] = (w, c, v)
        self.free[live] = free
        self.slots[live] = slots
        return np.asarray(cells, dtype=np.int64).reshape(-1, 4), taken
