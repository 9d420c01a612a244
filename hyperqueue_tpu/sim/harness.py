"""Simulation harness: the real Server under a virtual clock.

One :class:`Simulation` boots the production ``Server`` (reactor,
scheduler tick, journal + snapshot + restore, lazy store, autoalloc
controller) on a :class:`~hyperqueue_tpu.sim.loop.SimEventLoop`, wires
thousands of :class:`SimWorker`s and a :class:`SimClient` to it through
in-memory duplex streams, drives a synthetic workload at virtual arrival
times under a seeded :class:`FaultSchedule`, and checks invariants
continuously.  Single-threaded by construction: the PR 9/12 escape
hatches (``client_plane="reactor"``, ``journal_plane="reactor"``,
``fanout_senders=0``) plus ``solver_watchdog_timeout=0`` pin every plane
to the one virtual loop, so a run is a deterministic function of
(workload, seed, schedule).

Server kill -9 is modeled honestly in-process: the incarnation's event
tap is severed, the journal appender is abandoned with its unflushed
buffer discarded (``Journal.kill``), every server task and connection is
torn down abruptly, and a NEW ``Server`` object restores from the journal
file — driving the same restore/reattach/stream-replay choreography the
process-level chaos tests exercise, thousands of times faster.

Determinism contract: two runs with the same (workload, seed, schedule)
in the same interpreter produce bit-identical journal files and
decision-record streams.  Across interpreter invocations set
``PYTHONHASHSEED`` — a handful of str-set iterations in the server are
hash-order dependent.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import logging
import random
import shutil
import tempfile
import time as _walltime
from dataclasses import dataclass, field
from pathlib import Path

from hyperqueue_tpu.server.bootstrap import Server
from hyperqueue_tpu.sim.client import SimClient, SimSubmitStream
from hyperqueue_tpu.sim.faults import FaultSchedule
from hyperqueue_tpu.sim.invariants import InvariantMonitor, InvariantViolation
from hyperqueue_tpu.sim.loop import SimClock, SimEventLoop
from hyperqueue_tpu.sim.transport import duplex
from hyperqueue_tpu.sim.worker import SimWorker
from hyperqueue_tpu.sim.workloads import Workload
from hyperqueue_tpu.utils import chaos, clock, serverdir
from hyperqueue_tpu.utils import trace as trace_mod
from hyperqueue_tpu.utils.metrics import REGISTRY

logger = logging.getLogger("hq.sim")

# chunk size the harness streams arrays at (mirrors the CLI default)
CHUNK_SIZE = 16384


class SimKilled(asyncio.CancelledError):
    """Raised through a chaos action="kill" site to unwind the stack the
    way SIGKILL would: nothing after the injection point runs on the dead
    incarnation (and the task ends 'cancelled', never 'errored')."""


@dataclass
class SimResult:
    seed: int
    workload: str
    n_tasks: int
    makespan: float            # virtual seconds to quiescence
    wall_s: float              # real seconds the run took
    server_boots: int
    audit: dict
    decision_digest: str
    journal_digest: str
    decisions: list = field(repr=False, default_factory=list)
    violations: list = field(default_factory=list)
    # real per-tick scheduler latencies (ms), collected BEFORE decision
    # records are normalized (normalization strips wall timings)
    tick_ms: list = field(repr=False, default_factory=list)
    # PolicyState.stats() harvested at quiescence when the server ran
    # with a --policy-file (None on the flat objective)
    policy: dict | None = None
    # TickStats.shares() of the final incarnation — the per-phase half of
    # the PR 19 per-plane / per-phase attribution
    tick_shares: dict = field(repr=False, default_factory=dict)

    @property
    def virtual_tasks_per_wall_s(self) -> float:
        return self.n_tasks / self.wall_s if self.wall_s > 0 else 0.0


def _normalize_decision(record: dict) -> dict:
    """A decision record minus its perf_counter-measured fields (real CPU
    timings differ run-to-run by construction; everything semantic —
    virtual stamps included — must be bit-identical)."""
    out = {k: v for k, v in record.items()
           if k not in ("duration_ms", "phases")}
    solver = out.get("solver")
    if isinstance(solver, dict):
        out["solver"] = {
            k: v for k, v in solver.items()
            if k not in ("solve_ms", "inflight_ms", "dispatched_at_wall",
                         "mapped_at_wall")
        }
    return out


def _digest(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True, default=repr).encode()
    ).hexdigest()


class Simulation:
    def __init__(
        self,
        workload: Workload,
        seed: int = 0,
        n_workers: int = 16,
        worker_cpus: int = 4,
        worker_groups: int = 1,
        faults: FaultSchedule | None = None,
        server_dir: Path | None = None,
        scheduler: str = "greedy-numpy",
        schedule_min_delay: float = 0.01,
        heartbeat_secs: float = 8.0,
        reattach_timeout: float = 5.0,
        restore_delay: float = 1.0,
        horizon: float | None = None,
        flight_ticks: int = 1 << 20,
        chunk_size: int = CHUNK_SIZE,
        server_kwargs: dict | None = None,
    ):
        self.workload = workload
        self.seed = seed
        self.n_workers = n_workers
        self.worker_cpus = worker_cpus
        self.worker_groups = max(worker_groups, 1)
        self.faults = faults or FaultSchedule(seed=seed, events=[])
        self.scheduler = scheduler
        self.schedule_min_delay = schedule_min_delay
        self.heartbeat_secs = heartbeat_secs
        self.reattach_timeout = reattach_timeout
        self.restore_delay = restore_delay
        # hard virtual deadline: a scenario that cannot quiesce inside it
        # is reported as a hang instead of spinning forever
        self.horizon = horizon or max(
            self.workload.horizon_hint * 4 + 3600.0, 3600.0
        )
        self.flight_ticks = flight_ticks
        self.chunk_size = max(int(chunk_size), 1)
        self.server_kwargs = dict(server_kwargs or {})

        self._own_dir = server_dir is None
        self.server_dir = Path(server_dir or tempfile.mkdtemp(
            prefix="hq-sim-"
        ))
        self.journal_path = self.server_dir / "journal.bin"

        self.loop: SimEventLoop | None = None
        self.monitor = InvariantMonitor(self)
        self.server: Server | None = None
        self.server_boots = 0
        self.workers: dict[str, SimWorker] = {}
        self.client = SimClient(self, "driver")
        self.expected_tasks: dict[int, int] = {}
        self._server_links: list = []
        self._server_down = None       # asyncio.Event, created in run()
        self._next_restore_delay = self.restore_delay
        self._stopping = False
        self._decisions: list[dict] = []
        self._tick_ms: list[float] = []
        self._event_tap_task = None
        self._fault_tasks: list = []
        self.wall_s = 0.0

    # --- connection points (SimWorker / SimClient call these) -----------
    def connect_worker(self, name: str):
        if self.server is None:
            raise ConnectionError("server is down")
        a, b = duplex(self.loop, name=f"w-{name}")
        self._server_links.append(a.link)
        self.server.accept_worker(b.reader, b.writer)
        return a

    def connect_client(self, name: str):
        if self.server is None:
            raise ConnectionError("server is down")
        a, b = duplex(self.loop, name=f"c-{name}")
        self._server_links.append(a.link)
        self.server.accept_client(b.reader, b.writer)
        return a

    # --- server lifecycle ------------------------------------------------
    async def start_server(self) -> Server:
        kwargs = dict(
            server_dir=self.server_dir,
            host="sim-host",
            disable_client_auth=True,
            disable_worker_auth=True,
            scheduler=self.scheduler,
            schedule_min_delay=self.schedule_min_delay,
            journal_path=self.journal_path,
            reattach_timeout=self.reattach_timeout,
            solver_watchdog_timeout=0.0,
            flight_recorder_ticks=self.flight_ticks,
            client_plane="reactor",
            journal_plane="reactor",
            fanout_senders=0,
            memory_transport=True,
        )
        kwargs.update(self.server_kwargs)
        server = Server(**kwargs)
        await server.start()
        self.server = server
        self.server_boots += 1
        self._server_links = []
        # tap the journaled event stream into the invariant monitor
        tap: asyncio.Queue = asyncio.Queue()
        server._event_listeners.append(tap)
        self._event_tap_task = self.loop.create_task(self._drain_tap(tap))
        if server.n_boots > 1:
            # a restore: every pre-crash promise must hold on this
            # incarnation (ack-implies-durable)
            self.monitor.check_restored_server(server)
        return server

    async def _drain_tap(self, tap: asyncio.Queue) -> None:
        while True:
            record = await tap.get()
            self.monitor.on_event(record)

    def _collect_decisions(self, server: Server) -> None:
        for r in server.core.flight.ticks():
            dur = r.get("duration_ms")
            if isinstance(dur, (int, float)):
                self._tick_ms.append(float(dur))
            self._decisions.append(_normalize_decision(r))

    def _kill_server_now(self) -> None:
        """kill -9 the current incarnation, synchronously: everything
        after this instant is lost exactly as with a process SIGKILL."""
        server = self.server
        if server is None:
            return
        self.server = None
        self._collect_decisions(server)
        # sever visibility first: nothing the dying incarnation does past
        # this point may reach the monitor, subscribers, or the journal
        server._event_listeners.clear()
        server._subscribers.clear()
        if self._event_tap_task is not None:
            self._event_tap_task.cancel()
            self._event_tap_task = None
        if server.journal is not None:
            server.journal.kill()   # unflushed tail is LOST
            server.journal = None
        server.jplane = None
        for t in (list(server._tasks) + list(server._client_tasks)
                  + list(server._conn_tasks)):
            t.cancel()
        if server.autoalloc is not None:
            server.autoalloc.stop()
        if server._metrics_hook is not None:
            REGISTRY.remove_collect_hook(server._metrics_hook)
            server._metrics_hook = None
        for link in self._server_links:
            link.abort()
        self._server_links = []
        if self._server_down is not None:
            self._server_down.set()
        logger.info("sim: server killed at t=%.3f", clock.monotonic())

    def chaos_kill_handler(self) -> None:
        """utils/chaos action="kill" in-process: kill the server NOW and
        unwind the injecting call stack (a real SIGKILL never returns)."""
        self._kill_server_now()
        raise SimKilled("chaos kill")

    async def kill_server(self, restore_after: float | None = None) -> None:
        self._next_restore_delay = (
            restore_after if restore_after is not None else self.restore_delay
        )
        self._kill_server_now()
        await asyncio.sleep(0)

    async def _server_supervisor(self) -> None:
        """Restore a killed server after the configured delay — the
        operator/systemd half of the crash choreography."""
        while True:
            await self._server_down.wait()
            self._server_down.clear()
            if self._stopping:
                return
            await asyncio.sleep(self._next_restore_delay)
            self._next_restore_delay = self.restore_delay
            if self._stopping:
                return
            await self.start_server()
            logger.info("sim: server restored at t=%.3f", clock.monotonic())

    # --- workers ----------------------------------------------------------
    def add_worker(self, name: str | None = None, **kwargs) -> SimWorker:
        name = name or f"w{len(self.workers)}"
        group = kwargs.pop(
            "group", f"g{len(self.workers) % self.worker_groups}"
        )
        worker = SimWorker(
            self, name,
            n_cpus=kwargs.pop("n_cpus", self.worker_cpus),
            group=group,
            heartbeat_secs=kwargs.pop("heartbeat_secs", self.heartbeat_secs),
            **kwargs,
        )
        self.workers[name] = worker
        worker.start()
        return worker

    # --- fault driver ----------------------------------------------------
    async def _drive_faults(self) -> None:
        for event in self.faults.events:
            if event.kind == "chaos_rule":
                continue  # pre-installed as at_t rules (see run())
            delay = event.at - clock.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            # apply concurrently: a 30 s partition window must not push
            # every later fault 30 s off its scheduled instant
            t = self.loop.create_task(self._apply_fault(event))
            self._fault_tasks.append(t)

    async def _apply_fault(self, event) -> None:
        logger.info("sim fault: %s", event.describe())
        if event.kind == "server_kill":
            await self.kill_server(restore_after=event.delay)
            return
        if event.kind == "clock_skew":
            clock.get().skew += event.delta
            return
        worker = self.workers.get(event.target)
        if worker is None or worker.dead:
            return
        if event.kind == "worker_kill":
            worker.kill()
            if event.delay >= 0:
                await asyncio.sleep(event.delay)
                if not self._stopping:
                    worker.revive()
        elif event.kind == "partition":
            worker.partition(True)
            await asyncio.sleep(event.duration)
            worker.partition(False)
        elif event.kind == "straggler":
            worker.speed = event.factor
            await asyncio.sleep(event.duration)
            worker.speed = 1.0
        else:
            raise ValueError(f"unknown fault kind {event.kind!r}")

    def _chaos_plan(self) -> chaos.FaultPlan | None:
        """One FaultPlan holding every chaos_rule event as a
        schedule-driven (at_t-gated) rule."""
        rules = []
        epoch = clock.get().epoch
        for event in self.faults.events:
            if event.kind != "chaos_rule":
                continue
            rule = dict(event.rule)
            rule.setdefault("at_t", epoch + event.at)
            rules.append(rule)
        if not rules:
            return None
        return chaos.FaultPlan({"seed": self.seed, "rules": rules})

    # --- workload driver -------------------------------------------------
    async def _drive_workload(self) -> None:
        submits = sorted(
            enumerate(self.workload.submits), key=lambda p: (p[1].at, p[0])
        )
        for i, spec in submits:
            delay = spec.at - clock.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            await self._submit_spec(i, spec)

    async def _submit_spec(self, i: int, spec) -> None:
        """Exactly-once submission through the chunked-stream plane: a
        submit whose ack was lost to a crash replays by (uid, index)
        instead of duplicating the job."""
        desc = spec.job_desc
        header = {k: v for k, v in desc.items()
                  if k not in ("array", "tasks")}
        stream = SimSubmitStream(self.client, uid=f"sim-{self.seed}-{i}",
                                 header=header)
        array = desc.get("array")
        if array is not None and array.get("id_range") and \
                not array.get("entries"):
            lo, hi = array["id_range"]
            cursors = list(range(lo, hi, self.chunk_size))
            for j, start in enumerate(cursors):
                chunk = dict(array)
                chunk["id_range"] = [start, min(start + self.chunk_size, hi)]
                await stream.send_chunk(
                    array=chunk, last=(j == len(cursors) - 1)
                )
        elif array is not None:
            await stream.send_chunk(array=array, last=True)
        else:
            await stream.send_chunk(tasks=desc.get("tasks") or [],
                                    last=True)
        job_id = stream.job_id
        self.expected_tasks[job_id] = (
            self.expected_tasks.get(job_id, 0) + spec.n_tasks
        )

    # --- drain helper (scenario surface) ---------------------------------
    async def drain_worker(self, worker: SimWorker,
                           timeout: float = 60.0) -> None:
        """Gracefully drain one worker through the real RPC, recording the
        drain instant for the no-new-assignments invariant."""
        wid = worker.worker_id
        self.monitor.on_drain_started(wid, clock.monotonic())
        await self.client.worker_stop([wid], drain=True, timeout=timeout)

    # --- main -------------------------------------------------------------
    def run(self) -> SimResult:
        """Build the loop, run the scenario to quiescence, audit, tear
        down.  Synchronous wrapper — the whole simulation lives inside."""
        t_wall = _walltime.perf_counter()
        self.loop = SimEventLoop()
        asyncio.set_event_loop(self.loop)
        sim_clock = SimClock(self.loop)
        prev_clock = clock.install(sim_clock)
        uid_rng = random.Random(f"uids:{self.seed}")
        token = lambda n: "%0*x" % (n * 2, uid_rng.getrandbits(n * 8))  # noqa: E731
        prev_sd_tokens = serverdir.set_token_source(token)
        prev_tr_tokens = trace_mod.set_token_source(token)
        prev_plan = chaos._PLAN
        chaos.install_plan(self._chaos_plan())
        chaos.set_kill_handler(self.chaos_kill_handler)
        result = None
        try:
            result = self.loop.run_until_complete(
                asyncio.wait_for(self._main(), timeout=self.horizon)
            )
            return result
        finally:
            chaos.set_kill_handler(None)
            chaos.install_plan(prev_plan)
            serverdir.set_token_source(prev_sd_tokens)
            trace_mod.set_token_source(prev_tr_tokens)
            clock.install(prev_clock)
            try:
                self._drain_loop()
            finally:
                try:
                    self.loop.close()
                finally:
                    asyncio.set_event_loop(None)
            self.wall_s = _walltime.perf_counter() - t_wall
            if result is not None:
                result.wall_s = self.wall_s
            if self._own_dir:
                shutil.rmtree(self.server_dir, ignore_errors=True)

    def _drain_loop(self) -> None:
        """Unwind every pending task inside the loop before closing it:
        an abandoned scenario (timeout, violation) must not leak tasks
        whose finalizers would run against a closed loop at GC time."""
        if self.loop is None or self.loop.is_closed():
            return
        self._stopping = True
        if self.server is not None:
            self._kill_server_now()
        pending = [
            t for t in asyncio.all_tasks(self.loop) if not t.done()
        ]
        for t in pending:
            t.cancel()
        if pending:
            try:
                self.loop.run_until_complete(
                    asyncio.gather(*pending, return_exceptions=True)
                )
            except Exception:  # noqa: BLE001 - teardown is best-effort
                pass

    async def _main(self) -> SimResult:
        self._server_down = asyncio.Event()
        await self.start_server()
        supervisor = self.loop.create_task(self._server_supervisor())
        for i in range(self.n_workers):
            self.add_worker()
        fault_task = self.loop.create_task(self._drive_faults())
        await self._drive_workload()
        # quiesce: every submitted job's tasks terminal
        await self.client.job_wait(sorted(self.expected_tasks))
        # let trailing uplinks/events/retries settle, then a clean stop
        await asyncio.sleep(max(self.heartbeat_secs, 2.0))
        makespan = clock.monotonic()
        self._stopping = True
        fault_task.cancel()
        for t in self._fault_tasks:
            t.cancel()
        supervisor.cancel()
        self.client.close()
        for worker in self.workers.values():
            if not worker.dead:
                worker.dead = True
                if worker._task is not None:
                    worker._task.cancel()
                if worker._link is not None:
                    worker._link.close()
        # let the closed worker links unwind their connection handlers
        # (worker-lost events journal BEFORE the journal closes below)
        await asyncio.sleep(0.05)
        server = self.server
        audit = {}
        policy_stats = None
        tick_shares = {}
        if server is not None:
            self._collect_decisions(server)
            # the core's own invariant walk, per-worker prefill index
            # included, on the state every fault of the run left behind
            server.core.sanity_check()
            if server.core.policy is not None:
                policy_stats = server.core.policy.stats()
            try:
                tick_shares = server.core.tick_stats.shares()
            except Exception:  # noqa: BLE001 - telemetry only
                tick_shares = {}
            if self._event_tap_task is not None:
                self._event_tap_task.cancel()
            server._event_listeners.clear()
            await server.shutdown()
            self.server = None
        # violations raised inside loop CALLBACKS (worker timers) land in
        # the loop's exception handler, not here — the recorded list is
        # the reliable channel, so re-raise the first one now
        if self.monitor.violations:
            raise InvariantViolation(self.monitor.violations[0])
        audit = self.monitor.final_check(
            self.journal_path, self.expected_tasks,
            expect_failed=self.workload.expect_failed,
        )
        journal_digest = hashlib.sha256(
            self.journal_path.read_bytes()
        ).hexdigest()
        return SimResult(
            seed=self.seed,
            workload=self.workload.name,
            n_tasks=self.workload.n_tasks,
            makespan=makespan,
            wall_s=0.0,  # stamped by run()'s caller via wall_s attr
            server_boots=self.server_boots,
            audit=audit,
            decision_digest=_digest(self._decisions),
            journal_digest=journal_digest,
            decisions=self._decisions,
            violations=list(self.monitor.violations),
            tick_ms=self._tick_ms,
            policy=policy_stats,
            tick_shares=tick_shares,
        )


def run_scenario(
    workload: Workload,
    seed: int = 0,
    n_workers: int = 16,
    faults: FaultSchedule | None = None,
    **kwargs,
) -> SimResult:
    """One-call scenario runner (the CLI and tests use this)."""
    sim = Simulation(
        workload, seed=seed, n_workers=n_workers, faults=faults, **kwargs
    )
    return sim.run()


def bisect_failure(
    make_sim,
    faults: FaultSchedule,
) -> tuple[int, list[str]]:
    """Shrink a failing schedule to its minimal failing prefix.

    ``make_sim(schedule) -> Simulation``; returns (k, descriptions of the
    minimal prefix).  Runs O(log n) full simulations."""
    from hyperqueue_tpu.sim.faults import bisect_minimal_prefix
    from hyperqueue_tpu.sim.loop import SimDeadlockError

    def fails(k: int) -> bool:
        sim = make_sim(faults.prefix(k))
        try:
            sim.run()
            return False
        except (InvariantViolation, SimDeadlockError, asyncio.TimeoutError):
            return True

    k = bisect_minimal_prefix(fails, len(faults))
    return k, faults.prefix(k).describe()
