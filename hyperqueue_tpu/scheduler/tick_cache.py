"""Persistent tick-state cache: the dense snapshot survives across ticks.

Before this cache, every `reactor.schedule()` re-materialized the whole
dense solver state from Python dicts: `core.worker_rows()` rebuilt all
`WorkerRow`s, and `assemble_solve_inputs` re-allocated and re-filled the
`free`/`total`/`nt_free`/`lifetime` arrays from scratch.  At the 1M x 1k
north-star shape that host bookkeeping — not the solve — dominated the
tick (BASELINE.json; same lesson as Gavel's round-based policy engine:
the reallocation round must be far cheaper than the work it places).

`TickStateCache` keeps `free`/`total` `(N, R)` and `nt_free`/`lifetime`
`(N,)` alive over EVERY connected worker, in `core.workers` order, with an
eligibility mask (`mn_task == 0 and mn_reserved == 0 and not draining`),
and derives the solve's dense `(W, R)` rows from them.  It walks no worker
to learn what changed — it is told:

- content: `Worker.assign`/`unassign` (server/worker.py), the ONE funnel
  for free/nt_free mutation, add the worker's row to the cache's dirty
  set; `sync()` writes those rows in one C-level conversion, whatever their
  share of the rows;
- membership flips: a site that changes one worker's eligibility (a gang
  starts or ends, a reservation is set or lifted, a drain begins) names it,
  `Core.bump_membership(worker)`; `sync()` re-reads the named workers, and
  when any flipped cuts the dense arrays anew (`flatnonzero(mask)`, `take`).
  Counted in `membership_flips`; nothing is rebuilt;
- structural changes: the first sync, a worker connected or lost, a
  `bump_membership()` that names no worker (legal: "walk everything"), a
  named worker the rows do not hold, a worker count or membership epoch the
  cache was not told of.  Every worker is walked, every array built, and
  ONLY these increment `full_rebuilds` — steady-state ticks, gang churn
  included, must keep it still (pinned by tests/test_tick_cache.py);
- resource-map widening pads zero columns;
- gang inputs (`gang_inputs`): a fused solve with gang rows needs each dense
  row's host idleness (nothing assigned, nothing prefilled) and group.  The
  first tick that asks after a build walks every worker once and writes two
  columns over all rows: `_idle` and `_group` (the group names interned to
  codes).  From then on the column of idleness is told, by `Worker.tell_idle`
  where `assigned_tasks` (`assign`/`unassign`) or `prefilled_tasks`
  (`PrefilledTasks.add`/`discard`) goes empty or stops being so, and a
  tick reads both columns at the dense rows.  A build drops them; a cache no
  tick with gang rows ever asked keeps none, and the funnels then test one
  attribute;
- reservations: the gang task each worker is reserved for is a third
  column, `_resv`, written by a build and told by `Core.reserve_mn`
  (`tell_reserved`).  Under `--gang-drain busy` (`keep_reserved`) a
  reserved worker stays a dense row, so a reservation moves no row; under
  `--gang-drain idle` a reservation is also a membership flip as above,
  and the column at the dense rows is all none.

One cache a core: a worker is attached (`tick_row`, `tick_dirty`, and while
the columns exist `tick_idle`) to the cache that last built its rows over
it.

Correctness contract: an incremental assemble must be BIT-IDENTICAL to a
from-scratch assemble of the same state.  `paranoid_check` runs both
paths and asserts array equality; the server exposes it as
`hq server start --paranoid-tick N` and the randomized golden test
(tests/test_tick_cache.py) drives ~hundreds of mutation steps through it.

The cache deliberately disables itself (sync() returns None) while any
eligible worker carries a min-utilization floor: floored workers move in
and out of the dense row set per tick (run_tick's carve-out), so their
presence makes membership time-dependent — and they are rare, autoalloc
-spawned workers.  The legacy from-scratch path remains for that case.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from itertools import chain
from operator import attrgetter

import numpy as np

from hyperqueue_tpu.utils.trace import TRACER


@dataclass(slots=True)
class DenseSnapshot:
    """One tick's dense worker-side state, aligned row-for-row.

    Arrays are OWNED by the cache and reused next tick: consumers must
    treat them as read-only (assemble_solve_inputs copies before any
    range-compression shift).
    """

    worker_ids: list[int]        # row -> worker_id, solve order
    free: np.ndarray             # (W, R) int64, uncompressed fractions
    total: np.ndarray            # (W, R) int64 pool totals
    nt_free: np.ndarray          # (W,) int32, clamped >= 0
    lifetime: np.ndarray         # (W,) int32 seconds


@dataclass
class TickPhaseStats:
    """Per-phase tick latency breakdown, recorded by the reactor.

    One entry per key that `TRACER.phase` wrote into the tick's `phases`
    dict during one schedule(), in order: gangs, sync (child /build, only
    when the snapshot was built whole), batches, assemble, solve_host_prep
    (child /visit), solve_dispatch (children /upload, /launch), device_sync
    (children /counts, /state), pipeline_wait (the pipelined tick's wait,
    parent of the device_sync children there), mapping, prefill (children
    /fill, /displace, /rebalance), decide, total (the root) and
    unattributed (the root's self time: `total` less the top-level keys).
    A key with a `/` lies inside its parent in time, but for the keys under
    `cycle/` (cycle/ready, cycle/ready/mn_sort): the work since the
    previous tick, outside `total` (span catalog: docs/observability.md).
    Surfaced through `hq server stats` and the benchmark's `tick_phases_ms`
    so a latency regression names its phase instead of one opaque number.
    """

    ticks: int = 0
    totals_ms: dict = field(default_factory=dict)   # phase -> cumulative ms
    last_ms: dict = field(default_factory=dict)     # phase -> last tick ms
    max_ms: dict = field(default_factory=dict)      # phase -> max ms

    def record(self, phases: dict) -> None:
        self.ticks += 1
        for name, ms in phases.items():
            self.totals_ms[name] = self.totals_ms.get(name, 0.0) + ms
            self.last_ms[name] = ms
            if ms > self.max_ms.get(name, 0.0):
                self.max_ms[name] = ms

    def snapshot(self) -> dict:
        out = {
            "ticks": self.ticks,
            "phases": {
                name: {
                    "total_ms": round(total, 3),
                    "mean_ms": round(total / max(self.ticks, 1), 4),
                    "last_ms": round(self.last_ms.get(name, 0.0), 4),
                    "max_ms": round(self.max_ms.get(name, 0.0), 4),
                }
                for name, total in sorted(self.totals_ms.items())
            },
        }
        return out

    def shares(self) -> dict:
        """Phase -> fraction of `total`, the whole tick: the top-level
        phases and `unattributed` (what no span covers) sum to 1.0; a
        child's share is its part of the same whole.  The keys under
        `cycle/` lie between ticks and have no share of one.

        The per-phase half of the profiling plane's attribution (ISSUE
        19): `hq server stats` and the simulator's results carry these
        next to the profiler's per-plane CPU shares, so a latency
        regression names the phase whose share grew rather than one
        opaque wall-clock number."""
        total = self.totals_ms.get("total", 0.0)
        if total <= 0:
            return {}
        return {
            name: round(t / total, 4)
            for name, t in sorted(self.totals_ms.items())
            if name != "total" and not name.startswith("cycle/")
        }


_FREE = attrgetter("free")
_NT_FREE = attrgetter("nt_free")


def eligible(w, keep_reserved: bool = False) -> bool:
    """Is this worker a row of the dense solve?  Not while it runs a gang,
    is reserved for one (unless `keep_reserved`: `--gang-drain busy`, where
    the reservation is a column of the rows), or drains (`Core.worker_rows`
    asks the same)."""
    return (
        w.mn_task == 0
        and (keep_reserved or w.mn_reserved == 0)
        and not w.draining
    )


def _matrix(lists: list, n_r: int) -> np.ndarray:
    """`lists` (one per row, each at most `n_r` long) as an (n, n_r) int64
    array in ONE C-level conversion; a list shorter than the map (a worker
    that lacks the map's later resources, or one older than a widening) is
    zero-filled, as the scratch path fills it."""
    if set(map(len, lists)) - {n_r}:
        pad = [0] * n_r
        lists = [
            f if len(f) == n_r else (f + pad[len(f):])[:n_r] for f in lists
        ]
    return np.fromiter(
        chain.from_iterable(lists), dtype=np.int64, count=len(lists) * n_r
    ).reshape(len(lists), n_r)


class TickStateCache:
    """Dense snapshot of the schedulable workers, told what changed."""

    def __init__(self) -> None:
        # --- rows over ALL connected workers, in core.workers order ---
        self._workers: list = []
        self._ids: np.ndarray | None = None         # (N,) worker ids
        self._free: np.ndarray | None = None        # (N, R)
        self._total: np.ndarray | None = None       # (N, R)
        self._nt_free: np.ndarray | None = None     # (N,)
        self._lifetime: np.ndarray | None = None    # (N,)
        self._mask: np.ndarray | None = None        # (N,) bool: eligible
        self._mu: np.ndarray | None = None  # (N,) bool, None = no mu worker
        self._timed_rows: list[int] = []  # rows with a finite time limit
        # row -> position among the dense rows, -1 while not eligible
        self._pos: np.ndarray | None = None
        self._rows: np.ndarray | None = None        # dense position -> row
        # --- what the cache was told since the last sync ---
        # rows whose free/nt_free moved (Worker.assign/unassign add their
        # own row).  A whole build starts a NEW set, so a worker of an
        # earlier build writes into a set nobody reads, and `is` tells
        # whether a worker belongs to the rows as they stand
        self._dirty: set[int] = set()
        self._flipped: set[int] = set()  # rows a bump_membership named
        # a bump named no worker, or one the rows do not hold: walk everything
        self._unnamed = False
        self._heard = 0              # bumps heard since the last sync
        self._epoch = 0              # core.membership_epoch at the last sync
        self._mu_blocked = False
        # --- the gang inputs' columns over all rows: None until a tick with
        # gang rows asks, and again after every build (`gang_inputs`) ---
        self._idle: np.ndarray | None = None   # (N,) bool, told by workers
        self._group: np.ndarray | None = None  # (N,) int64 group code
        # --- the reservation column over all rows: the gang task each
        # worker is reserved for (0: none), written by a build and then
        # told by `Core.reserve_mn` alone (`tell_reserved`).  While
        # `keep_reserved` (`--gang-drain busy`, `Core.set_gang_drain`) a
        # reserved worker stays a dense row, and the solve reads it ---
        self._resv: np.ndarray | None = None   # (N,) int64
        self.keep_reserved = False
        # --- phases timed where no tick's dict was in reach (a `sync` its
        # caller gave none, the ready path between two ticks): key -> ms,
        # summed, until the next tick's record takes them (`take_parked`) ---
        self.parked: dict[str, float] = {}
        # --- the dense rows: the eligible workers, same order ---
        self.worker_ids: list[int] = []
        self.n_r = 0
        self.free: np.ndarray | None = None
        self.total: np.ndarray | None = None
        self.nt_free: np.ndarray | None = None
        self.lifetime: np.ndarray | None = None
        # telemetry (exposed via server stats / bench --phases)
        self.full_rebuilds = 0
        self.incremental_syncs = 0
        self.membership_flips = 0
        self.rows_rewritten_last = 0
        self.rows_moved_last = 0
        self.gang_input_walks = 0  # gang inputs read from the workers
        self.gang_input_reads = 0  # gang inputs read from the columns
        # `--gang-drain busy` (reactor.fused_gang_reserve): members newly
        # reserved, and reserved members that ran a single-node task at
        # the solve, summed per tick
        self.gang_reserved = 0
        self.gang_reserved_busy = 0
        # sort-key memo for assemble_solve_inputs: the (scarcity,
        # objective) keys are pure per rq class + per-tick free totals;
        # totals are often unchanged tick-over-tick (e.g. release then
        # re-assign), so the whole per-class key dict is reusable
        self.sort_key_sig: tuple | None = None
        self.sort_keys: dict = {}
        # batch-layout memo: needs/min_time/all_mask/weights are pure in
        # the sorted rq-id sequence (+ dims), which steady ticks repeat
        self.batch_layout_sig: tuple | None = None
        self.batch_layout: dict | None = None

    # ------------------------------------------------------------------
    def membership_changed(self, worker=None) -> None:
        """`Core.bump_membership`'s word to the cache: `worker`'s
        eligibility (mn_task, mn_reserved, draining) may have flipped, or
        is about to; with no worker named, anything may have changed and
        the next sync builds the rows whole."""
        self._heard += 1
        if worker is not None and worker.tick_dirty is self._dirty:
            self._flipped.add(worker.tick_row)
        else:
            self._unnamed = True

    def tell_reserved(self, worker) -> None:
        """`Core.reserve_mn`'s word: `worker`'s reservation changed.
        Written into the column; a worker the rows do not hold makes the
        next sync build them whole, as an unnamed bump does."""
        if worker.tick_dirty is self._dirty and self._resv is not None:
            self._resv[worker.tick_row] = worker.mn_reserved
        else:
            self._unnamed = True

    def reservations(self) -> np.ndarray:
        """The reservation column at the dense rows: (W,) int64, the gang
        task each row is reserved for, 0 for none."""
        return self._resv.take(self._rows)

    def reservations_told(self, workers: dict) -> bool:
        """Does the column hold every attached worker's reservation?
        (`Core.sanity_check`)"""
        if self._resv is None:
            return True
        return all(
            self._resv[w.tick_row] == w.mn_reserved
            for w in workers.values() if w.tick_dirty is self._dirty
        )

    def take_parked(self, phases: dict) -> None:
        """Move what was timed since the last tick's record into `phases`:
        `run_tick` and `reactor.schedule` call it, once a tick each."""
        parked = self.parked
        if parked:
            for key, ms in parked.items():
                phases[key] = phases.get(key, 0.0) + ms
            parked.clear()

    def sync(self, core, phases: dict | None = None) -> DenseSnapshot | None:
        """Bring the dense arrays up to date with `core`; returns the
        snapshot, or None when the cache cannot serve this tick (a
        min-utilization worker is present — see module docstring).  Timed
        here as `sync` (child `sync/build` when every worker is walked),
        into the tick's `phases`, or parked for the tick that runs next."""
        if phases is None:
            phases = self.parked
        with TRACER.phase(phases, "sync"):
            n_r = len(core.resource_map)
            epoch = core.membership_epoch
            if (
                self._free is None
                or self._unnamed
                # a bump the cache did not hear (another cache was the
                # core's then, or the epoch was moved by hand)
                or epoch - self._epoch != self._heard
                # a worker put into, or taken out of, core.workers
                # unannounced
                or len(core.workers) != len(self._workers)
            ):
                with TRACER.phase(phases, "sync/build"):
                    self._build(core, n_r)
            else:
                if n_r != self.n_r:
                    self._widen(n_r)
                self._apply()
            self._epoch = epoch
            self._heard = 0
        if self._mu_blocked or not self.worker_ids:
            return None
        return DenseSnapshot(
            worker_ids=self.worker_ids,
            free=self.free,
            total=self.total,
            nt_free=self.nt_free,
            lifetime=self.lifetime,
        )

    # ------------------------------------------------------------------
    def _build(self, core, n_r: int) -> None:
        """Structural change (first sync, connect, disconnect, an unnamed
        bump): walk every worker and build every array.  Counted — steady
        state, gang starts and ends included, must never get here."""
        self.full_rebuilds += 1
        self._unnamed = False
        self._flipped.clear()
        workers = self._workers = list(core.workers.values())
        n = len(workers)
        self.n_r = n_r
        self._ids = np.fromiter(
            (w.worker_id for w in workers), dtype=np.int64, count=n
        )
        self._free = np.zeros((n, n_r), dtype=np.int64)
        self._total = _matrix([w.resources.amounts for w in workers], n_r)
        self._nt_free = np.zeros(n, dtype=np.int32)
        self._lifetime = np.fromiter(
            (w.lifetime_secs() for w in workers), dtype=np.int32, count=n
        )
        self._timed_rows = [
            i for i, w in enumerate(workers)
            if w.configuration.time_limit_secs > 0
        ]
        keep = self.keep_reserved
        self._mask = np.fromiter(
            (eligible(w, keep) for w in workers), dtype=bool, count=n
        )
        self._resv = np.fromiter(
            (w.mn_reserved for w in workers), dtype=np.int64, count=n
        )
        mu = np.fromiter(
            (w.configuration.min_utilization > 0.001 for w in workers),
            dtype=bool, count=n,
        )
        self._mu = mu if mu.any() else None
        self._pos = np.empty(n, dtype=np.int64)
        self._idle = self._group = None
        dirty = self._dirty = set()
        for i, w in enumerate(workers):
            w.tick_row = i
            w.tick_dirty = dirty
            w.tick_idle = None
        self._write_content(np.arange(n), workers)
        self._cut()
        self.rows_rewritten_last = len(self.worker_ids)
        self.rows_moved_last = len(self.worker_ids)

    def _widen(self, n_r: int) -> None:
        """Resource map grew: pad new zero columns (a worker's dense row
        may lag the map right after a new name is interned — the scratch
        path zero-fills the same columns)."""
        pad = ((0, 0), (0, n_r - self.n_r))
        self._free = np.pad(self._free, pad)
        self._total = np.pad(self._total, pad)
        self.free = np.pad(self.free, pad)
        self.total = np.pad(self.total, pad)
        self.n_r = n_r

    def _write_content(self, rows: np.ndarray, workers: list) -> None:
        """free and nt_free of `workers` into their `rows`, one C-level
        conversion each (pool totals are static per worker: only a build
        writes them)."""
        self._free[rows] = _matrix(list(map(_FREE, workers)), self.n_r)
        nt_free = np.fromiter(
            map(_NT_FREE, workers), dtype=np.int32, count=len(workers)
        )
        self._nt_free[rows] = np.maximum(nt_free, 0, out=nt_free)

    def _cut(self) -> None:
        """The dense arrays anew: the eligible rows, in order."""
        rows = self._rows = np.flatnonzero(self._mask)
        self._pos.fill(-1)
        self._pos[rows] = np.arange(len(rows))
        self.free = self._free.take(rows, axis=0)
        self.total = self._total.take(rows, axis=0)
        self.nt_free = self._nt_free.take(rows)
        self.lifetime = self._lifetime.take(rows)
        self.worker_ids = self._ids.take(rows).tolist()
        self._mu_blocked = self._mu is not None and bool(
            self._mu.take(rows).any()
        )

    def _apply(self) -> None:
        """What the cache was told since the last sync: the dirty rows'
        content, then the named workers' eligibility."""
        self.incremental_syncs += 1
        workers = self._workers
        dirty = list(self._dirty)
        self._dirty.clear()
        rows = np.asarray(dirty, dtype=np.int64)
        if dirty:
            self._write_content(rows, [workers[i] for i in dirty])
        for i in self._timed_rows:
            self._lifetime[i] = workers[i].lifetime_secs()
        mask = self._mask
        n_flips = 0
        first = len(mask)
        keep = self.keep_reserved
        for i in self._flipped:
            ok = eligible(workers[i], keep)
            if mask[i] != ok:
                mask[i] = ok
                n_flips += 1
                first = min(first, i)
        self._flipped.clear()
        if n_flips:
            # rows left or rejoined: every dense array is cut anew from the
            # rows over all workers (the content above is already in them)
            self.membership_flips += n_flips
            was = self._pos[first:].copy()
            self._cut()
            now = self._pos[first:]
            self.rows_moved_last = int(((now >= 0) & (now != was)).sum())
            self.rows_rewritten_last = int((self._pos[rows] >= 0).sum())
            return
        self.rows_moved_last = 0
        # content that moved on a worker that is no dense row (one draining
        # towards a gang) stays in the rows over all alone
        at = self._pos[rows]
        rows, at = rows[at >= 0], at[at >= 0]
        self.free[at] = self._free[rows]
        self.nt_free[at] = self._nt_free[rows]
        if self._timed_rows:
            self._lifetime.take(self._rows, out=self.lifetime)
        self.rows_rewritten_last = len(at)

    # ------------------------------------------------------------------
    def gang_inputs(self, core, worker_ids) -> tuple:
        """(`gang_ok`, `group_ids`, walked) for the rows `worker_ids`: as
        `walk_gang_inputs` gives them, read from the two columns when
        `worker_ids` is this cache's own dense row list and no membership
        change waits for the next sync; a first call after a build writes
        the columns whole, and any other list is walked."""
        if (
            worker_ids is not self.worker_ids
            or self._free is None
            # a worker named since the sync may have started a gang, which
            # the walk's `is_idle` sees and the column does not
            or self._heard
        ):
            self.gang_input_walks += 1
            return (*walk_gang_inputs(core.workers, worker_ids), True)
        walked = self._idle is None
        if walked:
            self.gang_input_walks += 1
            self._write_gang_columns()
        else:
            self.gang_input_reads += 1
        rows = self._rows
        gang_ok = self._idle.take(rows).astype(np.int32)
        # groups renumbered by first appearance in the rows, as the walk
        # numbers them: the kernel takes the first group with n eligible
        # workers, so this order is part of the answer
        _, first, codes = np.unique(
            self._group.take(rows), return_index=True, return_inverse=True
        )
        rank = np.empty(len(first), dtype=np.int32)
        rank[np.argsort(first)] = np.arange(len(first), dtype=np.int32)
        return gang_ok, rank[codes], walked

    def _write_gang_columns(self) -> None:
        """Idleness and group code of every row, walked from the workers,
        who are attached to the idleness column from here on.  A worker's
        group is fixed once it schedules, so the codes are never told."""
        workers = self._workers
        n = len(workers)
        idle = self._idle = np.fromiter(
            (not w.assigned_tasks and not w.prefilled_tasks for w in workers),
            dtype=bool, count=n,
        )
        names: dict[str, int] = {}
        self._group = np.fromiter(
            (names.setdefault(w.configuration.group, len(names))
             for w in workers),
            dtype=np.int64, count=n,
        )
        for w in workers:
            w.tick_idle = idle

    # ------------------------------------------------------------------
    def reset_counters(self) -> None:
        """A measurement window starts (`Server.reset_metrics`)."""
        self.parked.clear()
        self.full_rebuilds = 0
        self.incremental_syncs = 0
        self.membership_flips = 0
        self.gang_input_walks = 0
        self.gang_input_reads = 0
        self.gang_reserved = 0
        self.gang_reserved_busy = 0

    def counters(self) -> dict:
        return {
            "full_rebuilds": self.full_rebuilds,
            "incremental_syncs": self.incremental_syncs,
            "membership_flips": self.membership_flips,
            "gang_input_walks": self.gang_input_walks,
            "gang_input_reads": self.gang_input_reads,
            "gang_reserved": self.gang_reserved,
            "gang_reserved_busy": self.gang_reserved_busy,
            "rows_rewritten_last": self.rows_rewritten_last,
            "rows_moved_last": self.rows_moved_last,
            "workers": len(self.worker_ids),
            "resources": self.n_r,
        }


def walk_gang_inputs(workers: dict, worker_ids) -> tuple:
    """The gang inputs read from the workers themselves: per row of
    `worker_ids`, `gang_ok` (1 where `Worker.is_idle`) and `group_ids`, the
    groups numbered by first appearance in the rows; int32 arrays."""
    gmap: dict[str, int] = {}
    gang_ok = []
    group_ids = []
    for wid in worker_ids:
        w = workers[wid]
        gang_ok.append(w.is_idle())
        group_ids.append(gmap.setdefault(w.configuration.group, len(gmap)))
    return (np.array(gang_ok, dtype=np.int32),
            np.array(group_ids, dtype=np.int32))


def paranoid_check(core, snapshot: DenseSnapshot, batches, rq_map,
                   resource_map, gang_ok=None, group_ids=None,
                   policy=None, gang_resv=None) -> None:
    """Assert the incremental assembly is bit-identical to from-scratch.

    Runs BOTH assemble paths on copies of the batch list (assemble sorts
    in place but pops nothing), and compares every kwargs array exactly —
    including the fused-gang inputs (gang_nodes/gang_ok/group_onehot)
    and the policy affinity matrix when the tick carries them.  Raises
    AssertionError naming the first differing array.  On a tick with gang
    rows, `gang_ok` and `group_ids` as the tick read them are first held to
    a walk over the workers, and a difference names its row, as is
    `gang_resv` (the reservation column) to the workers' `mn_reserved`.
    Debug tool:
    `hq server start --paranoid-tick N` runs this every N ticks.
    """
    from hyperqueue_tpu.scheduler.tick import Batch, assemble_solve_inputs

    if gang_ok is not None:
        for name, told, walked in zip(
            ("gang_ok", "group_ids"),
            (gang_ok, group_ids),
            walk_gang_inputs(core.workers, snapshot.worker_ids),
        ):
            told = np.asarray(told)
            assert told.shape == walked.shape, (
                f"paranoid-tick: {name} has {told.shape[0]} rows, "
                f"the snapshot {walked.shape[0]}"
            )
            (differ,) = np.nonzero(told != walked)
            assert not len(differ), (
                f"paranoid-tick: {name} diverged from the walk at row "
                f"{differ[0]} (worker {snapshot.worker_ids[differ[0]]}: "
                f"{told[differ[0]]}, walked {walked[differ[0]]})"
            )
    if gang_resv is not None:
        walked = [core.workers[w].mn_reserved for w in snapshot.worker_ids]
        assert np.asarray(gang_resv).tolist() == walked, (
            "paranoid-tick: the reservation column diverged from the walk"
        )

    def copy_batches(src):
        return [Batch(rq_id=b.rq_id, priority=b.priority, size=b.size,
                      gang_task=b.gang_task, gang_nodes=b.gang_nodes)
                for b in src]

    scratch_rows = [
        r for r in core.worker_rows(core.tick_cache.keep_reserved)
        if r.cpu_floor <= 0
    ]
    k_scratch = assemble_solve_inputs(
        scratch_rows, copy_batches(batches), rq_map, resource_map,
        gang_ok=gang_ok, group_ids=group_ids, policy=policy,
        gang_resv=gang_resv,
    )
    # key_cache=core.tick_cache: the check must exercise the SAME memoized
    # sort-key/batch-layout/needs32 path the production assemble uses, or
    # a corrupted memo would pass paranoid while feeding every real solve
    k_incr = assemble_solve_inputs(
        None, copy_batches(batches), rq_map, resource_map, dense=snapshot,
        key_cache=core.tick_cache, gang_ok=gang_ok, group_ids=group_ids,
        policy=policy, gang_resv=gang_resv,
    )
    scratch_ids = [r.worker_id for r in scratch_rows]
    assert scratch_ids == snapshot.worker_ids, (
        f"paranoid-tick: worker row order diverged "
        f"(scratch={scratch_ids[:8]}..., cache={snapshot.worker_ids[:8]}...)"
    )
    keys = set(k_scratch) | set(k_incr)
    for key in sorted(keys):
        a, b = k_scratch.get(key), k_incr.get(key)
        if key == "priorities":
            assert a == b, f"paranoid-tick: priorities diverged"
            continue
        assert (a is None) == (b is None), (
            f"paranoid-tick: key {key!r} present on one path only"
        )
        if a is None:
            continue
        a, b = np.asarray(a), np.asarray(b)
        if key == "lifetime" and a.shape == b.shape:
            # lifetime is wall-clock-derived for time-limited workers: the
            # cache stamped it at sync() and the scratch rows re-evaluate
            # it here, so crossing a 1-second boundary in between yields a
            # legitimate off-by-one — everything else must be exact
            assert np.abs(a.astype(np.int64) - b.astype(np.int64)).max(
                initial=0
            ) <= 1, (
                "paranoid-tick: lifetime diverged beyond clock granularity"
            )
            continue
        assert np.array_equal(a, b), (
            f"paranoid-tick: array {key!r} diverged between incremental "
            f"and from-scratch assembly"
        )
