"""Server-side view of a connected worker.

Reference: crates/tako/src/internal/server/worker.rs:30-63 — tracks assigned
tasks, free resources (dense, mirrors the solver's columns), capability
checks, time-limit and heartbeat state. The free/nt_free fields are exactly
the WorkerRow the tick snapshot copies out (scheduler/tick.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import TYPE_CHECKING

from hyperqueue_tpu.utils.constants import INF_TIME
from hyperqueue_tpu.resources.descriptor import ResourceDescriptor
from hyperqueue_tpu.resources.map import ResourceIdMap
from hyperqueue_tpu.resources.worker_resources import WorkerResources
from hyperqueue_tpu.utils import clock

if TYPE_CHECKING:
    import numpy as np


@dataclass
class WorkerConfiguration:
    descriptor: ResourceDescriptor
    hostname: str = "localhost"
    group: str = "default"
    heartbeat_secs: float = 8.0
    time_limit_secs: float = 0.0  # 0 = unlimited
    idle_timeout_secs: float = 0.0
    on_server_lost: str = "stop"  # stop | finish-running | reconnect
    # with on_server_lost=reconnect: give up after this many seconds of
    # failed reconnect attempts (0 = keep retrying forever)
    reconnect_timeout_secs: float = 60.0
    overview_interval_secs: float = 0.0
    # Scheduler only plans tasks here while at least min_utilization x cpus
    # would be busy afterwards — all-or-nothing per tick (reference worker
    # configuration.rs:52, enforced in solver.rs:479-518 add_min_utilization;
    # used by autoalloc so allocation-spawned workers pack-or-idle).
    min_utilization: float = 0.0
    listen_address: str = ""
    # autoalloc linkage: batch manager + allocation id (HQ_ALLOC_ID env)
    manager: str = "none"
    manager_job_id: str = ""
    alloc_id: str = ""
    # warm runner pool width: -1 = auto-size to CPU capacity, 0 = disable
    # (every task spawns through the in-loop asyncio path)
    runner_pool: int = -1
    # bounded coalescing delay of the uplink send drainer: completions
    # within the window share one frame (0 = send-as-ready)
    uplink_flush_secs: float = 0.002
    # federation: home shard this worker was lent FROM after a coordinator
    # redirect (-1 = not a borrowed worker); lets the borrowing shard
    # count its borrowed pool in `hq server stats`
    lent_from: int = -1

    def to_wire(self) -> dict:
        return {
            "descriptor": self.descriptor.to_dict(),
            "hostname": self.hostname,
            "group": self.group,
            "heartbeat_secs": self.heartbeat_secs,
            "time_limit_secs": self.time_limit_secs,
            "idle_timeout_secs": self.idle_timeout_secs,
            "on_server_lost": self.on_server_lost,
            "reconnect_timeout_secs": self.reconnect_timeout_secs,
            "overview_interval_secs": self.overview_interval_secs,
            "min_utilization": self.min_utilization,
            "listen_address": self.listen_address,
            "manager": self.manager,
            "manager_job_id": self.manager_job_id,
            "alloc_id": self.alloc_id,
            "runner_pool": self.runner_pool,
            "uplink_flush_secs": self.uplink_flush_secs,
            "lent_from": self.lent_from,
        }

    @classmethod
    def from_wire(cls, data: dict) -> "WorkerConfiguration":
        return cls(
            descriptor=ResourceDescriptor.from_dict(data["descriptor"]),
            hostname=data.get("hostname", "localhost"),
            group=data.get("group", "default"),
            heartbeat_secs=data.get("heartbeat_secs", 8.0),
            time_limit_secs=data.get("time_limit_secs", 0.0),
            idle_timeout_secs=data.get("idle_timeout_secs", 0.0),
            on_server_lost=data.get("on_server_lost", "stop"),
            reconnect_timeout_secs=data.get("reconnect_timeout_secs", 60.0),
            overview_interval_secs=data.get("overview_interval_secs", 0.0),
            min_utilization=data.get("min_utilization", 0.0),
            listen_address=data.get("listen_address", ""),
            manager=data.get("manager", "none"),
            manager_job_id=data.get("manager_job_id", ""),
            alloc_id=data.get("alloc_id", ""),
            runner_pool=data.get("runner_pool", -1),
            uplink_flush_secs=data.get("uplink_flush_secs", 0.002),
            lent_from=data.get("lent_from", -1),
        )


class PrefilledTasks(set):
    """The ids of the tasks prefilled on one worker, with how many of them
    sit at each user-priority level (`task.priority[0]`, fixed at submit).

    A set to every reader; `add` and `discard` are the only ways in and out
    and take the task's level, so the per-level count cannot drift from the
    ids (the set's other mutators refuse).  `lowest` is the lowest level
    held, above every priority when nothing is held: the displacement pass
    (reactor._prefill_displace) compares it before it looks at any task.
    Core.sanity_check recounts.  When the set goes empty or stops being so,
    it tells its worker (`Worker.tell_idle`): the worker's idleness may
    have flipped."""

    __slots__ = ("_levels", "lowest", "worker")

    def __init__(self) -> None:
        super().__init__()
        self._levels: dict[int, int] = {}
        self.lowest: float = math.inf
        self.worker: Worker | None = None  # the owner, set by Worker

    def add(self, task_id: int, level: int) -> None:
        if task_id in self:
            return
        set.add(self, task_id)
        self._levels[level] = self._levels.get(level, 0) + 1
        if level < self.lowest:
            self.lowest = level
        if len(self) == 1 and self.worker is not None:
            self.worker.tell_idle()

    def discard(self, task_id: int, level: int) -> None:
        if task_id not in self:
            return
        set.discard(self, task_id)
        if not self and self.worker is not None:
            self.worker.tell_idle()
        left = self._levels[level] - 1
        if left:
            self._levels[level] = left
        else:
            del self._levels[level]
            if level == self.lowest:
                self.lowest = min(self._levels, default=math.inf)

    def level_counts(self) -> dict[int, int]:
        return dict(self._levels)

    def _refuse(self, *args, **kwargs):
        raise TypeError("PrefilledTasks changes through add and discard only")

    update = remove = pop = clear = _refuse
    difference_update = intersection_update = _refuse
    symmetric_difference_update = _refuse
    __ior__ = __iand__ = __isub__ = __ixor__ = _refuse


@dataclass
class Worker:
    worker_id: int
    configuration: WorkerConfiguration
    resources: WorkerResources
    started_at: float = field(default_factory=clock.monotonic)

    # dense scheduling state (the tick snapshot reads these directly)
    free: list[int] = field(default_factory=list)
    nt_free: int = 0
    assigned_tasks: set[int] = field(default_factory=set)
    # tasks pushed beyond current capacity (queue on the worker; no resource
    # accounting until they report running)
    prefilled_tasks: PrefilledTasks = field(default_factory=PrefilledTasks)
    # multi-node: task id this worker is running a gang for (0 = none)
    mn_task: int = 0
    # multi-node: pending gang task this worker is DRAINING for (0 = none).
    # A reserved worker takes no new sn work (excluded from the dense solve
    # and prefill) so it converges to idle and the gang can eventually claim
    # it even under a continuous stream of small tasks (anti-starvation; the
    # reference achieves this inside one MILP via per-group count variables
    # plus blocking variables, solver.rs:177-209,479-518).
    mn_reserved: int = 0
    last_heartbeat: float = field(default_factory=clock.monotonic)
    last_overview: dict = field(default_factory=dict)
    # gauge/counter samples piggybacked on the worker's last overview
    # message; fanned out (with a `worker` label) by the server's metrics
    # collect hook for the cluster-wide Prometheus view
    last_metrics: list = field(default_factory=list)
    # the worker is going away deliberately (`hq worker stop`, idle/time
    # limit): its tasks requeue WITHOUT a crash-counter increment
    # (reference gateway.rs CrashLimit doc: stops don't count)
    clean_stop: bool = False
    # graceful drain (ISSUE 13): the worker is masked out of the solve,
    # prefill and gang selection (a membership mask like mn_reserved) so it
    # converges to idle; running tasks finish normally, then the server
    # stops it. Set by `hq worker stop --drain` and the elasticity
    # controller's scale-down path; every flip of this, of mn_task or of
    # mn_reserved MUST be told to the core BY NAME,
    # `core.bump_membership(worker)`: the tick snapshot moves only the rows
    # it is told of (an unnamed bump is legal and rebuilds every row).
    draining: bool = False
    # content counter of the dense scheduling state (free/nt_free): every
    # mutation bumps it, and assign/unassign are the only such mutation
    # funnel.  Tests and debugging may compare it; no tick walks it: the
    # tick snapshot is TOLD which rows moved, through the two fields below
    epoch: int = 0
    # this worker's row in the tick snapshot's rows over all connected
    # workers, and the dirty set of the cache that holds those rows
    # (scheduler/tick_cache.TickStateCache attaches both when it builds the
    # rows; None = not attached, the build will read this worker whole).
    # Every mutation of free/nt_free MUST add the row there, or the cache
    # serves a stale row: assign/unassign do
    tick_row: int = -1
    tick_dirty: set | None = field(default=None, repr=False, compare=False)
    # the same cache's idleness column over those rows, attached only while
    # the cache keeps one (from the first tick with gang rows to the next
    # build; None otherwise).  `tell_idle` is its one writer: assign/
    # unassign and PrefilledTasks.add/discard call it where their set goes
    # empty or stops being so
    tick_idle: np.ndarray | None = field(
        default=None, repr=False, compare=False
    )

    def __post_init__(self) -> None:
        self.prefilled_tasks.worker = self

    @classmethod
    def create(
        cls,
        worker_id: int,
        configuration: WorkerConfiguration,
        resource_map: ResourceIdMap,
    ) -> "Worker":
        resources = WorkerResources.from_descriptor(
            configuration.descriptor, resource_map
        )
        worker = cls(
            worker_id=worker_id,
            configuration=configuration,
            resources=resources,
        )
        worker.free = list(resources.amounts)
        worker.nt_free = resources.task_max_count()
        return worker

    @property
    def group(self) -> str:
        return self.configuration.group

    def lifetime_secs(self) -> int:
        limit = self.configuration.time_limit_secs
        if limit <= 0:
            return int(INF_TIME)
        remaining = limit - (clock.monotonic() - self.started_at)
        return max(int(remaining), 0)

    def cpu_floor(self) -> int:
        """Cpu fractions this tick must still fill for min_utilization.

        floor = ceil(mu x all_cpus) - used_cpus = mu x all - (all - free);
        0 for normal workers or once enough is already running (reference
        solver.rs:493-498). Resource id 0 is the cpus column by convention
        (reference CPU_RESOURCE_ID)."""
        mu = self.configuration.min_utilization
        if mu <= 0.001 or not self.free:
            return 0
        all_cpus = self.resources.amount(0)
        if all_cpus <= 0:
            return 0
        import math

        floor = math.ceil(mu * all_cpus) - (all_cpus - self.free[0])
        return max(floor, 0)

    def assign(self, task_id: int, amounts: list[tuple[int, int]]) -> None:
        """amounts: [(resource_id, fraction_amount)] of the chosen variant."""
        self.assigned_tasks.add(task_id)
        for rid, amount in amounts:
            if rid < len(self.free):
                self.free[rid] -= amount
        self.nt_free -= 1
        self.epoch += 1
        if self.tick_dirty is not None:
            self.tick_dirty.add(self.tick_row)
        if len(self.assigned_tasks) == 1:
            self.tell_idle()

    def unassign(self, task_id: int, amounts: list[tuple[int, int]]) -> None:
        self.assigned_tasks.discard(task_id)
        for rid, amount in amounts:
            if rid < len(self.free):
                self.free[rid] += amount
        self.nt_free += 1
        self.epoch += 1
        if self.tick_dirty is not None:
            self.tick_dirty.add(self.tick_row)
        if not self.assigned_tasks:
            self.tell_idle()

    def tell_idle(self) -> None:
        """assigned_tasks or prefilled_tasks went empty or stopped being so:
        write the worker's idleness into the tick snapshot's column, where
        one is attached.  `mn_task` has no part in it: a worker that runs a
        gang is no dense row, and the column is read at dense rows only."""
        idle = self.tick_idle
        if idle is not None:
            idle[self.tick_row] = (
                not self.assigned_tasks and not self.prefilled_tasks
            )

    def is_idle(self) -> bool:
        return (
            not self.assigned_tasks
            and not self.prefilled_tasks
            and self.mn_task == 0
        )
