"""In-memory test environment for core/reactor/scheduler tests.

Mirrors the reference tier-1 infra (crates/tako/src/internal/tests/utils/):
TestComm captures outgoing messages, builders create tasks/workers tersely,
and every step can re-validate core invariants via sanity_check.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from hyperqueue_tpu.ids import make_task_id
from hyperqueue_tpu.models.greedy import GreedyCutScanModel
from hyperqueue_tpu.resources.amount import FRACTIONS_PER_UNIT
from hyperqueue_tpu.resources.descriptor import (
    ResourceDescriptor,
    ResourceDescriptorItem,
)
from hyperqueue_tpu.resources.request import (
    ResourceRequest,
    ResourceRequestEntry,
    ResourceRequestVariants,
)
from hyperqueue_tpu.server import reactor
from hyperqueue_tpu.server.core import Core
from hyperqueue_tpu.server.task import Task
from hyperqueue_tpu.server.worker import Worker, WorkerConfiguration


@dataclass
class TestComm:
    compute: list[tuple[int, list[dict]]] = field(default_factory=list)
    cancels: list[tuple[int, list[int]]] = field(default_factory=list)
    retracts: list[tuple[int, list[tuple[int, int]]]] = field(default_factory=list)
    scheduling_asked: int = 0

    def send_compute(self, worker_id, tasks):
        self.compute.append((worker_id, tasks))

    def send_cancel(self, worker_id, task_ids):
        self.cancels.append((worker_id, task_ids))

    def send_retract(self, worker_id, task_refs):
        self.retracts.append((worker_id, task_refs))

    def ask_for_scheduling(self):
        self.scheduling_asked += 1

    def assigned_by_worker(self) -> dict[int, list[int]]:
        out: dict[int, list[int]] = {}
        for wid, tasks in self.compute:
            out.setdefault(wid, []).extend(t["id"] for t in tasks)
        return out


@dataclass
class TestEvents:
    started: list[int] = field(default_factory=list)
    restarted: list[int] = field(default_factory=list)
    finished: list[int] = field(default_factory=list)
    failed: list[tuple[int, str]] = field(default_factory=list)
    canceled: list[int] = field(default_factory=list)
    workers_new: list[int] = field(default_factory=list)
    workers_lost: list[tuple[int, str]] = field(default_factory=list)

    def on_task_started(self, task_id, instance_id, worker_ids, variant=0,
                        wtrace=None):
        self.started.append(task_id)

    def on_task_restarted(self, task_id):
        self.restarted.append(task_id)

    def on_task_finished(self, task_id, wtrace=None):
        self.finished.append(task_id)

    def on_task_failed(self, task_id, message, wtrace=None):
        self.failed.append((task_id, message))

    def on_task_canceled(self, task_id):
        self.canceled.append(task_id)

    def on_worker_new(self, worker):
        self.workers_new.append(worker.worker_id)

    def on_worker_lost(self, worker_id, reason):
        self.workers_lost.append((worker_id, reason))


def displace_workers() -> dict:
    """hq_prefill_displace_workers_total so far, by outcome."""
    from hyperqueue_tpu.utils.metrics import REGISTRY

    counter = REGISTRY.get("hq_prefill_displace_workers_total")
    return {o: counter.labels(o).value for o in ("skipped", "scanned")}


# Default scheduling model for TestEnv; test modules that parametrize over
# backends (test_scheduler_golden.py) monkeypatch this so reactor-level
# cases exercise the swapped model too.
DEFAULT_MODEL = GreedyCutScanModel()


class TestEnv:
    __test__ = False  # not a pytest test class

    def __init__(self, model=None):
        self.core = Core()
        self.comm = TestComm()
        self.events = TestEvents()
        self.model = model or DEFAULT_MODEL
        self._task_seq = 0

    # --- builders -----------------------------------------------------
    def worker(self, cpus=4, gpus=0, group="default", time_limit=0.0) -> Worker:
        items = [ResourceDescriptorItem.range("cpus", 0, cpus - 1)]
        if gpus:
            items.append(
                ResourceDescriptorItem.list("gpus", [str(i) for i in range(gpus)])
            )
        config = WorkerConfiguration(
            descriptor=ResourceDescriptor(items=tuple(items)),
            group=group,
            time_limit_secs=time_limit,
        )
        w = Worker.create(
            self.core.worker_id_counter.next(), config, self.core.resource_map
        )
        reactor.on_new_worker(self.core, self.comm, self.events, w)
        return w

    def rqv(self, cpus=1, gpus=0.0, n_nodes=0, min_time=0.0, variants=None):
        if variants is not None:
            return ResourceRequestVariants(variants=tuple(variants))
        return ResourceRequestVariants.single(
            self.rq(cpus=cpus, gpus=gpus, n_nodes=n_nodes, min_time=min_time)
        )

    def rq(self, cpus=1, gpus=0.0, n_nodes=0, min_time=0.0):
        if n_nodes:
            return ResourceRequest(n_nodes=n_nodes, min_time_secs=min_time)
        entries = [
            ResourceRequestEntry(
                self.core.resource_map.get_or_create("cpus"),
                int(cpus * FRACTIONS_PER_UNIT),
            )
        ]
        if gpus:
            entries.append(
                ResourceRequestEntry(
                    self.core.resource_map.get_or_create("gpus"),
                    int(gpus * FRACTIONS_PER_UNIT),
                )
            )
        return ResourceRequest(entries=tuple(entries), min_time_secs=min_time)

    def submit(self, n=1, rqv=None, deps=(), priority=(0, 0), job=1, body=None,
               crash_limit=None):
        """Create n tasks; returns their ids."""
        if rqv is None:
            rqv = self.rqv()
        rq_id = self.core.intern_rqv(rqv)
        extra = {} if crash_limit is None else {"crash_limit": crash_limit}
        tasks = []
        for _ in range(n):
            self._task_seq += 1
            tasks.append(
                Task(
                    task_id=make_task_id(job, self._task_seq),
                    rq_id=rq_id,
                    priority=priority,
                    deps=tuple(deps),
                    body=body or {},
                    **extra,
                )
            )
        reactor.on_new_tasks(self.core, self.comm, tasks)
        return [t.task_id for t in tasks]

    # --- actions ------------------------------------------------------
    def schedule(self, prefill: bool = False) -> int:
        """Prefill defaults OFF for deterministic assignment assertions;
        dedicated prefill tests pass True (the real server always prefills)."""
        n = reactor.schedule(
            self.core, self.comm, self.events, self.model, prefill=prefill
        )
        self.core.sanity_check()
        return n

    def start_all_assigned(self, include_prefilled: bool = False):
        """Worker acks: report ASSIGNED tasks as running.

        Prefilled tasks are skipped by default — a real worker only starts
        them once resources free up; reporting them running while the box is
        full would simulate an impossible ordering.
        """
        from hyperqueue_tpu.server.task import TaskState

        for task in list(self.core.tasks.values()):
            if task.state is TaskState.ASSIGNED and (
                include_prefilled or not task.prefilled
            ):
                reactor.on_task_running(
                    self.core, self.events, task.task_id, task.instance_id
                )

    def finish(self, task_id):
        task = self.core.tasks[task_id]
        reactor.on_task_finished(
            self.core, self.comm, self.events, task_id, task.instance_id
        )
        self.core.sanity_check()

    def fail(self, task_id, message="boom"):
        task = self.core.tasks[task_id]
        reactor.on_task_failed(
            self.core, self.comm, self.events, task_id, task.instance_id, message
        )
        self.core.sanity_check()

    def lose_worker(self, worker_id, clean=False):
        """clean=True simulates a deliberate stop (hq worker stop /
        idle-timeout / time-limit) — crash counters are not charged."""
        if clean:
            self.core.workers[worker_id].clean_stop = True
        reactor.on_remove_worker(
            self.core, self.comm, self.events, worker_id,
            "stopped" if clean else "connection lost",
        )
        self.core.sanity_check()

    def start_drain(self, worker_ids) -> list[int]:
        """`Server.start_drain` itself, on a stand-in for the server."""
        import types

        from hyperqueue_tpu.server.bootstrap import Server

        host = types.SimpleNamespace(
            core=self.core, comm=self.comm, _draining={},
            emit_event=lambda *a, **k: None,
        )
        return Server.start_drain(host, worker_ids)

    def cancel(self, task_ids):
        out = reactor.on_cancel_tasks(self.core, self.comm, self.events, task_ids)
        self.core.sanity_check()
        return out

    def state(self, task_id):
        return self.core.tasks[task_id].state
