"""Server core state.

Reference: crates/tako/src/internal/server/core.rs:42-62 — the single source
of truth mutated only by the reactor on the single-threaded server loop:
task map, worker map, interning maps, ready queues, id counters. Purity of
the scheduler (a function of a snapshot of this state) is what makes the TPU
offload possible; nothing here holds locks.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

from hyperqueue_tpu.ids import IdCounter
from hyperqueue_tpu.resources.map import ResourceIdMap, ResourceRqMap
from hyperqueue_tpu.resources.request import ResourceRequestVariants
from hyperqueue_tpu.scheduler.queues import TaskQueues
from hyperqueue_tpu.scheduler.tick import WorkerRow
from hyperqueue_tpu.scheduler.tick_cache import (
    TickPhaseStats,
    TickStateCache,
    eligible,
)
from hyperqueue_tpu.server.lazy import LazyStore
from hyperqueue_tpu.server.task import Task, TaskState
from hyperqueue_tpu.server.worker import Worker
from hyperqueue_tpu.utils.flight import FlightRecorder
from hyperqueue_tpu.utils.trace import TaskTraceStore

GANG_DRAIN_MODES = ("idle", "busy")


@dataclass
class Core:
    tasks: dict[int, Task] = field(default_factory=dict)
    workers: dict[int, Worker] = field(default_factory=dict)
    resource_map: ResourceIdMap = field(default_factory=ResourceIdMap)
    rq_map: ResourceRqMap = field(default_factory=ResourceRqMap)
    queues: TaskQueues = field(default_factory=TaskQueues)
    worker_id_counter: IdCounter = field(default_factory=IdCounter)
    # multi-node gang tasks waiting for enough workers, in priority order
    mn_queue: list[int] = field(default_factory=list)
    # which workers drain for which pending gang, task id -> worker ids:
    # the inverse of `Worker.mn_reserved` over `workers`, written by
    # `reserve_mn` and `forget_mn_reservation` alone, so that lifting a
    # gang's reservations visits its workers and no other
    mn_reservations: dict[int, set[int]] = field(default_factory=dict)
    # what the fused gang phase looked at so far: entries of `mn_queue`
    # examined and workers visited to lift reservations, summed over ticks
    # (`hq server stats`; a tick's own two are on its `gangs/rows` span)
    mn_examined_total: int = 0
    mn_swept_total: int = 0
    scheduling_needed: bool = False
    # restart fencing base for THIS boot: n_prior_boots * the generation
    # stride (task.py INSTANCE_GENERATION_STRIDE), set by journal restore.
    # Every restored task re-issued (not reattached) is fenced to at least
    # this, so no instance a crashed boot issued in its lost journal tail
    # can collide with a re-issue (0 on a fresh server: nothing to fence)
    instance_fence_floor: int = 0
    # (rq_id, variant) -> (wire entries, n_nodes); rq interning is
    # append-only so entries never change within a Core
    entries_cache: dict = field(default_factory=dict)
    # (rq_id, variant) -> (has_all, [(resource_id, amount)]) memo for
    # variant_amounts (per-assignment hot path)
    amounts_cache: dict = field(default_factory=dict)
    # persistent dense tick snapshot, updated by dirty-tracking deltas
    # instead of rebuilt per tick (scheduler/tick_cache.py)
    tick_cache: TickStateCache = field(default_factory=TickStateCache)
    # per-phase tick latency breakdown, recorded by reactor.schedule and
    # surfaced through `hq server stats`
    tick_stats: TickPhaseStats = field(default_factory=TickPhaseStats)
    # debug: every N ticks, assert the incremental assembly is
    # bit-identical to a from-scratch one (0 = off; --paranoid-tick N)
    paranoid_tick: int = 0
    # fused-solve mode (--scheduler greedy-fused): multi-node gangs become
    # all-or-nothing column groups inside the dense solve (scheduler/tick.py
    # gang rows) instead of the host-side reservation drain
    fused_solve: bool = False
    # what a waiting gang does to busy members on the fused path
    # (`--gang-drain`, set through `set_gang_drain`): "idle", the kernel
    # holds idle members within one solve and nothing drains a busy one;
    # "busy", a gang that cannot start reserves n members across ticks
    # (reactor.fused_gang_reserve) and they drain, staying dense rows
    gang_drain: str = "idle"
    # two-stage async tick pipeline (scheduler/pipeline.TickPipeline) when
    # the server started with --tick-pipeline; None = synchronous ticks
    tick_pipeline: object = None
    # weighted scheduling objective (scheduler/policy.PolicyState) when the
    # server started with --policy-file; None = flat placement-count
    # objective. Only consulted on the fused dense path.
    policy: object = None
    tick_counter: int = 0
    # bumped on every change of the schedulable-worker SET (connect,
    # disconnect, gang reservation/claim/release, drain), through
    # bump_membership alone: decision.py's capability memo, the pipeline's
    # idle signature and the tick cache compare it.  Row CONTENT changes
    # (free/nt_free) reach the tick cache from Worker.assign/unassign.
    membership_epoch: int = 0
    # flight recorder: ring of per-tick DecisionRecords + control-plane
    # events (utils/flight.py); reactor.schedule records into it and the
    # explain/flight-recorder/trace RPCs read it
    flight: FlightRecorder = field(default_factory=FlightRecorder)
    # per-task distributed traces (utils/trace.py TaskTraceStore): spans
    # from client submit through worker spawn to completion commit are
    # assembled here and queried by the task_trace RPC / `hq task trace`
    traces: TaskTraceStore = field(default_factory=TaskTraceStore)
    # rq_id -> (membership_epoch, amount_capable, lifetime_ok) memo for
    # decision.classify_class (pure in the worker set per class)
    capable_memo: dict = field(default_factory=dict)
    # jobs paused via `hq job pause`: their READY tasks are held out of the
    # scheduler queues (paused_held[job_id] = task ids) until resume
    paused_jobs: set[int] = field(default_factory=set)
    paused_held: dict[int, set[int]] = field(default_factory=dict)
    # unmaterialized lazy array tasks (server/lazy.py): chunked array
    # submits register O(chunks) records here; the queues materialize
    # per-task state only at dispatch/prefill time
    lazy: LazyStore = field(default_factory=LazyStore)

    def __post_init__(self) -> None:
        # the queues consult the lazy store for batch sizing and
        # materializing takes; takes need the core for task creation
        self.queues.bind_lazy(self.lazy, self)

    def bump_membership(self, worker: Worker | None = None) -> None:
        """The schedulable-worker set changed.  A site that flips ONE
        worker's eligibility (mn_task, mn_reserved, draining) names it, once
        a worker, before or after the flip: the tick cache then moves that
        row alone.  With no worker named (a connect, a disconnect, anything
        else) the cache walks every worker and rebuilds its rows
        (`hq_tick_cache_full_rebuilds_total`)."""
        self.membership_epoch += 1
        self.tick_cache.membership_changed(worker)

    def set_gang_drain(self, mode: str) -> None:
        """Choose what a waiting gang does to busy members on the fused
        path (`hq server start --gang-drain`): "idle" or "busy".  Under
        "busy" a reserved worker stays a dense row of the tick snapshot and
        its reservation is a column of it (TickStateCache.tell_reserved)."""
        if mode not in GANG_DRAIN_MODES:
            raise ValueError(
                f"--gang-drain must be one of {GANG_DRAIN_MODES}, not {mode!r}"
            )
        if mode != self.gang_drain:
            self.gang_drain = mode
            self.tick_cache.keep_reserved = mode == "busy"
            self.bump_membership()  # every row's eligibility is read anew

    def reserve_mn(self, worker: Worker, task_id: int) -> None:
        """Set `worker.mn_reserved` (0 lifts the reservation): the one place
        the field is written, so `mn_reservations` stays its inverse and the
        flip is told to the tick cache by name: as a write of its
        reservation column, and but under `--gang-drain busy` (where the
        row stays a dense row) as a membership flip (the row leaves the
        dense rows)."""
        if worker.mn_reserved == task_id:
            return
        self.forget_mn_reservation(worker)
        if task_id:
            self.mn_reservations.setdefault(task_id, set()).add(
                worker.worker_id
            )
        worker.mn_reserved = task_id
        self.tick_cache.tell_reserved(worker)
        if self.gang_drain != "busy":
            self.bump_membership(worker)

    def forget_mn_reservation(self, worker: Worker) -> None:
        """Take `worker` out of `mn_reservations`, its field left as it is:
        what `reserve_mn` does first, and all a worker that has left
        `workers` needs."""
        held = self.mn_reservations.get(worker.mn_reserved)
        if held is not None:
            held.discard(worker.worker_id)
            if not held:
                del self.mn_reservations[worker.mn_reserved]

    def intern_rqv(self, rqv: ResourceRequestVariants) -> int:
        return self.rq_map.get_or_create(rqv)

    def worker_rows(self, keep_reserved: bool = False) -> list[WorkerRow]:
        """Snapshot rows for the tick; excludes workers that run a gang,
        workers reserved for one and workers draining toward a graceful
        stop.  `keep_reserved` keeps the reserved ones, as the snapshot
        does under `--gang-drain busy` (`paranoid_check` compares the
        two); the path that solves these rows has no mask for them."""
        return [
            WorkerRow(
                worker_id=w.worker_id,
                free=w.free,
                nt_free=w.nt_free,
                lifetime_secs=w.lifetime_secs(),
                total=w.resources.amounts,
                cpu_floor=w.cpu_floor(),
            )
            for w in self.workers.values()
            if eligible(w, keep_reserved)
        ]

    def variant_amounts(
        self, rq_id: int, variant: int, worker=None
    ) -> list[tuple[int, int]]:
        """[(resource_id, amount)] of the chosen variant for accounting.

        ALL-policy entries take the WORKER's whole pool (reference
        solver.rs:120-124 amount_or_none_if_all), so `worker` must be passed
        whenever the request could contain one — assign and release then
        stay symmetric because the pool size is static per worker.

        Classes without ALL entries (the overwhelming majority) get their
        amount list memoized per (rq_id, variant): this is called once per
        assignment on the apply path, and rebuilding the list dominated
        the tick's apply phase at 1M x 1k (callers treat it read-only).
        """
        key = (rq_id, variant)
        cached = self.amounts_cache.get(key)
        if cached is None:
            from hyperqueue_tpu.resources.request import AllocationPolicy

            entries = self.rq_map.get_variants(rq_id).variants[variant].entries
            if any(e.policy is AllocationPolicy.ALL for e in entries):
                cached = (True, None)
            else:
                cached = (
                    False, [(e.resource_id, e.amount) for e in entries]
                )
            self.amounts_cache[key] = cached
        has_all, static = cached
        if not has_all:
            return static
        from hyperqueue_tpu.resources.request import AllocationPolicy

        rqv = self.rq_map.get_variants(rq_id)
        return [
            (
                e.resource_id,
                worker.resources.amount(e.resource_id)
                if worker is not None
                and e.policy is AllocationPolicy.ALL
                else e.amount,
            )
            for e in rqv.variants[variant].entries
        ]

    def sanity_check(self) -> None:
        """Debug invariant walk (reference core.rs:274-430)."""
        self.queues.sanity_check()
        for task in self.tasks.values():
            if task.state is TaskState.WAITING:
                assert task.unfinished_deps > 0, task
            if task.state in (TaskState.ASSIGNED, TaskState.RUNNING):
                assert task.assigned_worker in self.workers or task.mn_workers
        reserved: dict[int, set[int]] = {}
        for worker in self.workers.values():
            if worker.mn_reserved:
                reserved.setdefault(worker.mn_reserved, set()).add(
                    worker.worker_id
                )
            for rid, amount in enumerate(worker.free):
                assert 0 <= amount <= worker.resources.amount(rid), (
                    worker.worker_id,
                    rid,
                    amount,
                )
            for task_id in worker.assigned_tasks:
                task = self.tasks.get(task_id)
                assert task is not None and task.assigned_worker == worker.worker_id
            levels: dict[int, int] = {}
            for task_id in worker.prefilled_tasks:
                task = self.tasks.get(task_id)
                assert (
                    task is not None
                    and task.prefilled
                    and task.assigned_worker == worker.worker_id
                ), task_id
                level = task.priority[0]
                levels[level] = levels.get(level, 0) + 1
            # the per-level view the displacement pass trusts is a recount
            held = worker.prefilled_tasks
            assert (
                held.level_counts() == levels
                and held.lowest == min(levels, default=math.inf)
            ), (worker.worker_id, held, levels)
            # and so is the tick snapshot's idleness, where it keeps one
            if worker.tick_idle is not None:
                assert worker.tick_idle[worker.tick_row] == (
                    not worker.assigned_tasks and not held
                ), worker.worker_id
        # and its reservation column, where the rows hold it
        assert self.tick_cache.reservations_told(self.workers), "reserved"
        assert reserved == self.mn_reservations, (
            reserved, self.mn_reservations
        )
