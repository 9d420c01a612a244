"""Plain reference of one scheduling tick: single-node tasks, resource
variants, user priorities.  No gangs, no ALL-policy requests, no policy
weights: a configuration that needs those brings a reference of its own.

It follows the semantics the configuration states, in numpy and Python
integers, on data the harness generated itself:

1. Ready tasks are grouped into batches, one per (class, priority level) that
   holds a task.  Batches are scanned highest user priority first; within a
   priority, most-constrained class first (scarcity of the scarcest resource
   its most flexible variant asks for), then by the achievable share value,
   ties by class number.
2. A batch tries its variants in order.  A variant's capacity on a worker is
   the exact integer quotient min_r(free // need), bounded by the worker's
   free task slots.  The batch is water-filled over the workers in the order
   (waste class, worker number), waste being the scarcity of the resources
   the worker still has and the variant does not ask for.
3. Within a batch the oldest tasks are placed first.

Nothing here is imported from the program.  The order of steps 1 and 2 is
the one the configuration states under `guarantees.order` (the program
documents it in docs/scheduler.md, "The tick"); its arithmetic was written
after `scheduler/tick.py assemble_solve_inputs`, `ops/assign.py
host_visit_classes`, `scarcity_weights` and `greedy_cut_scan_numpy` as they
stood at PR 21, cut to the features above and without the program's range
compression (amounts are exact integers here).  What does not depend on that
order (capacity, oldest first, priority within a class) the tick driver
audits from the program's own placements, without this file.
"""

from __future__ import annotations

from collections import deque

import numpy as np

WASTE_Q = 65536
N_VISIT_CLASSES = 16
MAX_CUTS_PER_QUEUE = 32
MAX_BATCH = 2**30

CAPACITY_MODES = ("exact", "float32_nofix")


def scarcity_weights(total_amounts) -> np.ndarray:
    """(R,) float32, rarer cluster-wide = larger, normalised to sum 1."""
    total = np.asarray(total_amounts, dtype=np.float64)
    present = total > 0
    inv = np.where(
        present, total.max(initial=0.0) / np.maximum(total, 1.0), 0.0
    )
    norm = inv.sum()
    if norm <= 0:
        return np.zeros_like(total, dtype=np.float32)
    return (inv / norm).astype(np.float32)


def _capacity(free, need, needed, mode):
    if mode == "exact":
        return np.min(free[:, needed] // need[needed], axis=1)
    # the quotient as the kernel first takes it, one float32 multiply by the
    # reciprocal, without the integer correction that makes it exact
    num = free[:, needed].astype(np.float32)
    inv = np.float32(1.0) / need[needed].astype(np.float32)
    return np.min(np.floor(num * inv[None, :]).astype(np.int64), axis=1)


class Reference:
    """The cluster as the reference accounts it, and the tick over it."""

    def __init__(self, world, capacity: str = "exact",
                 stale_rows: bool = False):
        """`capacity` and `stale_rows` exist for the controls only.  With
        `stale_rows` the tick does not see what finished since the last
        tick: the rows a delta upload would have carried are a tick late.
        With `capacity="float32_nofix"` a variant's capacity is the float32
        quotient without its correction."""
        if capacity not in CAPACITY_MODES:
            raise ValueError(capacity)
        self.capacity = capacity
        self.stale_rows = stale_rows
        self._late: list = []
        self.total = world.worker_total.copy()
        self.free = world.worker_total.copy()
        self.slots = world.worker_slots.copy()
        self.needs = world.class_needs
        self.n_variants = world.class_variants
        self.n_p = int(world.n_priorities)
        n_c = self.needs.shape[0]
        self.levels = [deque() for _ in range(n_c * self.n_p)]
        order = np.argsort(
            world.task_class.astype(np.int64) * self.n_p + world.task_prio,
            kind="stable",
        )
        keys = (world.task_class.astype(np.int64) * self.n_p
                + world.task_prio)[order]
        bounds = np.searchsorted(keys, np.arange(n_c * self.n_p + 1))
        for lv in range(n_c * self.n_p):
            self.levels[lv].extend(order[bounds[lv]:bounds[lv + 1]].tolist())
        # where each started task runs: task id -> (worker, class, variant)
        self.running: dict[int, tuple[int, int, int]] = {}

    # -- what happens between ticks ---------------------------------------
    def arrive(self, task_ids, levels) -> None:
        """New ready tasks; a level is class * priority levels + priority."""
        for t, level in zip(task_ids, levels):
            self.levels[int(level)].append(int(t))

    def finish(self, task_ids) -> int:
        """Release what the tasks hold; returns how many were not running
        (a task the reference never started cannot finish)."""
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
        for w, c, v in release:
            self.free[w] += self.needs[c, v]
            self.slots[w] += 1
        return unknown

    # -- one tick ------------------------------------------------------------
    def _batches(self):
        """[(class, user priority, size)] in scan order."""
        col_totals = np.maximum(self.free, 0).sum(axis=0)
        weights = scarcity_weights(col_totals)
        totals = col_totals.tolist()
        n_r = len(totals)
        keys = {}

        def class_key(c):
            scarcity = float("inf")
            per_variant = []
            for v in range(int(self.n_variants[c])):
                need = self.needs[c, v].tolist()
                v_score = 0.0
                for r in range(n_r):
                    if need[r] > 0:
                        v_score = max(v_score, float(weights[r]))
                scarcity = min(scarcity, v_score)
                share, fit = 0.0, float("inf")
                for r in range(n_r):
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

    def _visit_ranks(self):
        """A function from a variant's need row to the (W,) class in which
        each worker is visited: the dense rank of the scarcity-weighted sum
        of the resources the worker still has and the variant does not ask
        for."""
        has = self.free > 0
        scarcity = scarcity_weights(self.free.sum(axis=0))
        weighted = has * scarcity[None, :]
        memo = {}

        def ranks_for(need):
            unused = tuple((need == 0).tolist())
            ranks = memo.get(unused)
            if ranks is None:
                waste = np.einsum(
                    "mr,wr->mw",
                    np.asarray([unused], dtype=np.float32), weighted,
                )[0]
                key = np.round(waste * WASTE_Q).astype(np.int64)
                ranks = np.searchsorted(np.unique(key), key)
                np.clip(ranks, 0, N_VISIT_CLASSES - 1, out=ranks)
                memo[unused] = ranks
            return ranks

        return ranks_for

    def tick(self):
        """Place what fits.  Returns (cells, taken): cells is an (n, 4) int64
        array of (level, variant, worker, count) and taken maps a level to
        the task ids that left its queue, oldest first."""
        batches = self._batches()
        if not batches:
            return np.zeros((0, 4), dtype=np.int64), {}
        ranks_for = self._visit_ranks()
        # Only workers that could hold the smallest variant of any class
        # matter, and within a tick resources only shrink: scan those rows,
        # and pass over a variant that asks for more than any of them has.
        # Both are exact shortcuts, not approximations.
        asked = np.where(self.needs > 0, self.needs, np.iinfo(np.int64).max)
        least = asked.reshape(-1, asked.shape[-1]).min(axis=0)
        # resources that every variant of every class asks for
        common = (
            (self.needs > 0) | (self.needs.sum(axis=2, keepdims=True) == 0)
        ).all(axis=(0, 1))
        live = np.nonzero(
            (self.slots > 0)
            & (self.free[:, common] >= least[common]).all(axis=1)
        )[0]
        free, slots = self.free[live], self.slots[live]
        n_w = len(live)
        idx = np.arange(n_w)
        cells = []
        taken = {}
        most = free.max(axis=0, initial=0).tolist()
        for b, (c, p, size) in enumerate(batches):
            if not n_w:
                break
            remaining = min(int(size), MAX_BATCH)
            level = c * self.n_p + p
            for v in range(int(self.n_variants[c])):
                if remaining <= 0:
                    break
                need = self.needs[c, v]
                needed = need > 0
                if not needed.any():
                    continue
                if any(a > m for a, m in zip(need.tolist(), most)):
                    continue
                cap = np.minimum(
                    _capacity(free, need, needed, self.capacity), slots
                )
                np.clip(cap, 0, remaining, out=cap)
                if not cap.any():
                    continue
                order = np.lexsort((idx, ranks_for(need)[live]))
                cap_sorted = cap[order]
                cum = np.cumsum(cap_sorted)
                take_sorted = np.clip(
                    remaining - (cum - cap_sorted), 0, cap_sorted
                )
                assign = np.empty(n_w, dtype=np.int64)
                assign[order] = take_sorted
                remaining -= int(take_sorted.sum())
                free -= assign[:, None] * need[None, :]
                slots -= assign
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
