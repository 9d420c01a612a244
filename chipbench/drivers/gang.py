"""The `gang` driver: the production tick with multi-node tasks riding the
device solve as gang rows, over a backlog of single-node filler.

The `tick` driver's loop with the fused gang phase of `reactor._tick` around
it, by the functions `_tick` itself calls: per tick
`reactor.fused_gang_rows` (the head of `core.mn_queue` as gang rows) ->
`TickStateCache.sync -> create_batches` + the gang rows ->
`reactor.fused_gang_inputs` (`gang_ok`, `group_ids` aligned to the snapshot)
-> `run_tick` -> `reactor._apply_fused_gangs` (the gang sentinels) and the
single-node assignments applied, with the model the server builds for
`--scheduler tpu` (`GreedyCutScanModel(backend="jax")`, device-resident
state, no pipeline, no paranoid guard).  The gangs are real `Task` objects in
`core.tasks` and `core.mn_queue`, submitted through `reactor.on_new_tasks`;
a finished gang is released by `reactor._release_task_resources`.

From the `tick` driver, by import: the program state, the `Cluster` (the
filler's churn and record), the comparison with the reference and the audit
of what holds whatever the order.  Added here: the workers' groups and the
gangs' request classes, the gangs' side of the cluster (submitted, started,
ended, replaced), of the comparison (every started gang's member set) and of
the audit (`gang_split`, `gang_shared`, `gang_overtaken`).

Set-up is: the world, the program's state with the filler alone, the fill
tick and one tick per delta-upload bucket at the full worker bucket (the
campaign's filler was submitted first); then that first wave finishes, the
gangs are submitted and ticks run with nothing ending until one starts no
gang (16 gangs a tick until the nodes without gpus are taken: the worker
rows fall through both worker buckets, every tick a full upload); one tick
per delta-upload bucket at the lower worker bucket; then the traffic's
settle steps.  A run on the chip whose set-up did not meet every upload
program the window can meet ends without a result.
"""

from __future__ import annotations

import dataclasses
import gc
import statistics
import time

import numpy as np

from chipbench import generate_gang, manifest, spans
from chipbench.drivers import tick as tick_driver

TASK_MASK = tick_driver.TASK_MASK
GANG_JOB = 2
HOST_PHASES = tick_driver.HOST_PHASES + ("gangs",)
GANG_GROUPS_COUNTER = "hq_solve_gang_groups"


class _Comm:
    """What `reactor.on_new_tasks` asks of its comm: nothing is sent here."""

    def ask_for_scheduling(self) -> None:
        pass


def build_program_state(world, config):
    """The `tick` driver's program state, the workers in their groups, and
    one multi-node request class per gang size.  Returns (core, rq_ids,
    worker_ids, gang_rq: nodes -> request class)."""
    from hyperqueue_tpu.resources.request import (
        ResourceRequest,
        ResourceRequestVariants,
    )

    core, rq_ids, worker_ids = tick_driver.build_program_state(world, config)
    for worker_id, group in zip(worker_ids, world.worker_group.tolist()):
        core.workers[worker_id].configuration.group = f"alloc-{group:03d}"
    gang_rq = {
        n: core.intern_rqv(ResourceRequestVariants(
            variants=(ResourceRequest(n_nodes=n),)))
        for n in sorted(set(world.gang_nodes.tolist()))
    }
    return core, rq_ids, worker_ids, gang_rq


class Cluster(tick_driver.Cluster):
    """The `tick` driver's cluster, and the gangs: submitted, started, ended
    and replaced, with a record of each beside the filler's."""

    def __init__(self, world, core, rq_ids, seed, gang_rq):
        super().__init__(world, core, rq_ids, seed)
        self.gang_rq = gang_rq
        self.gang_rng = np.random.default_rng([int(seed), 10])
        self.gang_nodes: list = []    # nodes of every gang there ever was
        self.running_gangs: list = []
        # per tick: [started (gang, worker ids), ended gangs, nodes of the
        # gangs that arrived]
        self.gang_log: list = []
        self.comm = _Comm()
        self.submit_s = 0.0   # spent inside `reactor.on_new_tasks`

    def submit_gangs(self, nodes) -> None:
        from hyperqueue_tpu.ids import make_task_id
        from hyperqueue_tpu.server import reactor
        from hyperqueue_tpu.server.task import Task

        tasks = []
        for n in nodes:
            tasks.append(Task(
                task_id=make_task_id(GANG_JOB, len(self.gang_nodes)),
                rq_id=self.gang_rq[n],
                priority=(self.world.gang_prio, 0),
            ))
            self.gang_nodes.append(n)
        t = time.perf_counter()
        reactor.on_new_tasks(self.core, self.comm, tasks)
        self.submit_s += time.perf_counter() - t

    def apply(self, assignments, phases=None) -> list:
        """The gang sentinels through the reactor's own function, the rest
        as the `tick` driver applies them; returns the single-node part."""
        from hyperqueue_tpu.ids import make_task_id
        from hyperqueue_tpu.server import reactor
        from hyperqueue_tpu.utils import clock

        sentinels: dict = {}
        for task_id, worker_id, _rq_id, variant in assignments:
            if variant == -1:
                sentinels.setdefault(task_id & TASK_MASK, []).append(worker_id)
        single, _n_gangs = reactor._apply_fused_gangs(
            self.core, assignments, {}, clock.now(), phases)
        super().apply(single)
        # the program's own placements: the gangs that now run, on the
        # workers the solve named; one the reactor refused (it stays queued)
        # is an answer nobody can account for
        tasks = self.core.tasks
        started = [(g, members) for g, members in sentinels.items()
                   if tasks[make_task_id(GANG_JOB, g)].mn_workers]
        self.refused = len(sentinels) - len(started)
        self.gang_log.append([started, [], []])
        self.running_gangs.extend(g for g, _members in started)
        return single

    def churn(self, share: float, on_workers: int | None = None,
              gang_share: float = 0.0, arrive=()) -> None:
        """The filler's churn; then a share of the running gangs ends, and
        every gang the last tick started is replaced at the queue's tail by
        a new ready gang of its size (behind them `arrive`: the campaign's
        gangs, the one time they are submitted)."""
        from hyperqueue_tpu.ids import make_task_id
        from hyperqueue_tpu.server import reactor
        from hyperqueue_tpu.server.task import TaskState

        super().churn(share, on_workers)
        running = self.running_gangs
        n = len(running)
        k = min(n, max(1, int(round(gang_share * n)))) if gang_share else 0
        ended = []
        for i in (sorted(self.gang_rng.choice(n, size=k, replace=False)
                         .tolist(), reverse=True) if k else []):
            gang = running[i]
            running[i] = running[-1]
            running.pop()
            task = self.core.tasks.pop(make_task_id(GANG_JOB, gang))
            reactor._release_task_resources(self.core, task)
            task.state = TaskState.FINISHED
            ended.append(gang)
        arrived = [self.gang_nodes[g] for g, _m in self.gang_log[-1][0]]
        arrived.extend(arrive)
        self.submit_gangs(arrived)
        self.gang_log[-1][1:] = [ended, arrived]


def compare_with_reference(world, log, gang_log, rq_ids, worker_ids,
                           reference_cls):
    """The `tick` driver's replay, with the gangs: the reference also has to
    start, tick by tick, the gangs the program started, on the same
    workers.  The reference is told which gangs ended and which arrived, as
    it is told which tasks finished."""
    row_of = {w: i for i, w in enumerate(worker_ids)}
    no_match = np.asarray([[-1, 0, 0, 1]], dtype=np.int64)
    # every gang arrives through the record, the campaign's first ones too
    world = dataclasses.replace(world, gang_nodes=world.gang_nodes[:0])

    class Replay:
        def __init__(self, w):
            self.ref = reference_cls(w)
            self.i = -1

        def tick(self):
            self.i += 1
            cells, taken = self.ref.tick()
            want = sorted((g, sorted(m)) for g, m in self.ref.last_gangs)
            got = sorted((g, sorted(row_of.get(w, -1) for w in members))
                         for g, members in gang_log[self.i][0])
            return (cells, taken) if want == got else (no_match, taken)

        def finish(self, finished):
            return self.ref.finish(finished, gang_log[self.i][1])

        def arrive(self, task_ids, levels):
            self.ref.arrive(task_ids, levels, gang_log[self.i][2])

    return tick_driver.compare_with_reference(
        world, log, rq_ids, worker_ids, Replay)


def audit_gangs(world, log, gang_log, worker_ids, rows_per_tick) -> dict:
    """What the configuration guarantees of gangs whatever the order of the
    scan, read from the program's own placements and the world alone:
    `gang_split` (a started gang whose members are not its n, or lie in two
    groups), `gang_shared` (a member that ran something or belonged to a
    gang when the gang started, or took a task while in it), `gang_overtaken`
    (a gang that started in a tick in which a gang ahead of it among the
    tick's rows, no larger than it, did not)."""
    row_of = {w: i for i, w in enumerate(worker_ids)}
    group = world.worker_group
    nodes: list = []       # of every gang there ever was, as they arrive
    queue: list = []
    tasks_on = np.zeros(len(worker_ids), dtype=np.int64)
    gang_on = np.full(len(worker_ids), -1, dtype=np.int64)
    where: dict = {}                          # running filler task -> row
    members_of: dict = {}
    split = shared = overtaken = 0
    for (assignments, finished), (started, ended, arrived) in zip(
            log, gang_log):
        rows = queue[:rows_per_tick]
        began = {g for g, _members in started}
        for g, members in started:
            members = [row_of.get(w, -1) for w in members]
            known = [r for r in members if r >= 0]
            if (g >= len(nodes) or len(set(members)) != nodes[g]
                    or len(known) != len(members)
                    or len(set(group[known].tolist())) != 1):
                split += 1
            shared += int(((tasks_on[known] > 0) | (gang_on[known] >= 0)).sum())
            gang_on[known] = g
            members_of[g] = known
            if g in rows:
                ahead = rows[: rows.index(g)]
                overtaken += any(a not in began and nodes[a] <= nodes[g]
                                 for a in ahead if g < len(nodes))
            else:
                overtaken += 1  # not among the tick's rows at all
        for task_id, worker_id, _rq_id, _variant in assignments:
            row = row_of.get(worker_id)
            if row is None:
                continue  # `answers_unknown` has counted it
            shared += int(gang_on[row] >= 0)
            tasks_on[row] += 1
            where[task_id & TASK_MASK] = row
        for t in finished:
            row = where.pop(t, None)
            if row is not None:
                tasks_on[row] -= 1
        for g in ended:
            gang_on[members_of.pop(g, [])] = -1
        queue = [g for g in queue if g not in began]
        queue.extend(range(len(nodes), len(nodes) + len(arrived)))
        nodes.extend(arrived)
    return {"gang_split": split, "gang_shared": shared,
            "gang_overtaken": overtaken}


def counter_value(name: str):
    """A counter of the program's registry; None where it has none such."""
    from hyperqueue_tpu.utils.metrics import REGISTRY

    counter = REGISTRY.get(name)
    return None if counter is None else counter.labels().value


def run(ctx) -> dict:
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel, _bucket
    from hyperqueue_tpu.parallel.resident import _ROW_BUCKET_FLOOR
    from hyperqueue_tpu.scheduler.tick import create_batches, run_tick
    from hyperqueue_tpu.server import reactor

    if not hasattr(reactor, "fused_gang_rows"):
        raise SystemExit(
            "chipbench: this program's fused gang phase cannot be called "
            "(no reactor.fused_gang_rows): the cell cannot run on it")
    config, traffic = ctx.cell["config"], ctx.cell["traffic"]
    rows_per_tick = int(traffic["gang_rows_per_tick"])
    if not (reactor.MAX_FUSED_GANG_ROWS == rows_per_tick
            == int(config["gangs"]["rows_per_tick"])):
        raise SystemExit(
            f"chipbench: the cell states {rows_per_tick} gang rows a tick, "
            f"the program sends {reactor.MAX_FUSED_GANG_ROWS}")
    world = generate_gang.world(config, traffic, ctx.seed, ctx.scale)
    core, rq_ids, worker_ids, gang_rq = build_program_state(world, config)
    cluster = Cluster(world, core, rq_ids, ctx.seed, gang_rq)
    model_cls = spans.annotated_model(GreedyCutScanModel) if ctx.trace \
        else GreedyCutScanModel
    # a rehearsal solves on the host, or (`"backend": "jax"` in its scale)
    # runs the device path on the CPU backend
    backend = (ctx.scale or {}).get("backend", "numpy") if ctx.rehearse \
        else "jax"
    model = model_cls(backend=backend)
    wanted_backend = ("device-jax",) if backend == "jax" \
        else ("host-native", "host-numpy")
    ann = spans.annotate
    solves_by_backend: dict = {}
    refused = 0
    rows_seen: list = []
    gc_settings = spans.server_gc_settings()
    spans.gc_as_server_starts(gc_settings)

    def tick():
        nonlocal refused
        phases: dict = {}
        t0 = time.perf_counter()
        with ann("chipbench/gang_rows"):
            gang_rows = reactor.fused_gang_rows(core, phases) \
                if core.mn_queue else []
        t0a = time.perf_counter()
        with ann("chipbench/snapshot"):
            snap = core.tick_cache.sync(core)
        t1 = time.perf_counter()
        with ann("chipbench/batches"):
            batches = create_batches(core.queues) + gang_rows
        t2 = time.perf_counter()
        gang_ok = group_ids = None
        if gang_rows:
            with ann("chipbench/gang_inputs"):
                gang_ok, group_ids = reactor.fused_gang_inputs(
                    core, snap.worker_ids, phases)
        with ann("chipbench/run_tick"):
            out = run_tick(
                core.queues, None, core.rq_map, core.resource_map, model,
                batches=batches, dense=snap, phases=phases,
                key_cache=core.tick_cache,
                gang_ok=gang_ok, group_ids=group_ids,
            )
        t3 = time.perf_counter()
        with ann("chipbench/apply"):
            single = cluster.apply(out, phases)
        t4 = time.perf_counter()
        phases.update(snapshot=(t1 - t0a) * 1e3, batches=(t2 - t1) * 1e3,
                      apply=(t4 - t3) * 1e3 - phases.get("gangs/apply", 0.0),
                      total=(t4 - t0) * 1e3)
        backend = model.last_backend
        solves_by_backend[backend] = solves_by_backend.get(backend, 0) + 1
        refused += cluster.refused
        rows_seen.append(len(snap.worker_ids))
        cluster.started(single)
        return phases

    share = float(traffic["churn_per_tick"])
    gang_share = float(traffic["gang_finish_per_tick"])
    uploads_met: set = set()   # (worker bucket, row bucket or "full")

    def note_upload(before):
        stats = model.resident_stats()
        if "full_uploads" not in stats:
            return  # a host solve (a rehearsal) uploads nothing
        bucket = _bucket(stats["dirty_rows_last"], _ROW_BUCKET_FLOOR)
        delta = stats["delta_uploads"] > before.get("delta_uploads", 0)
        uploads_met.add((stats["rows_per_device"],
                         bucket if delta else "full"))

    def row_buckets(pw: int) -> list:
        """The row buckets of a delta upload at worker bucket `pw`."""
        return [_ROW_BUCKET_FLOOR << i
                for i in range((pw // 2 // _ROW_BUCKET_FLOOR).bit_length())]

    def warm_delta_buckets():
        """One tick per delta-upload bucket at the worker bucket the rows
        are in: one running task finishes on each of so many workers and no
        gang ends, so the rows stay and so many are dirty (and those a gang
        row held last tick: the aim allows for as many again)."""
        pw = model._worker_bucket(rows_seen[-1])
        besides = 0
        for bucket in row_buckets(pw):
            for _attempt in range(4):
                n = max(1, bucket * 3 // 4 - besides)
                before = model.resident_stats()
                cluster.churn(share, on_workers=n)
                tick()
                note_upload(before)
                dirty = model.resident_stats().get("dirty_rows_last", n)
                besides = max(0, dirty - n)
                if backend != "jax" or (pw, bucket) in uploads_met:
                    break

    # -- set-up ---------------------------------------------------------------
    # the filler alone: fill, every delta bucket at the full worker bucket
    before = model.resident_stats()
    tick()
    note_upload(before)
    warm_delta_buckets()
    # the gangs arrive: ticks with nothing ending until one starts no gang
    # (the filler's first wave finishes as they do, so the gangs, whose rows
    # come first, find every node idle and the filler takes what they leave)
    cluster.churn(1.0, arrive=world.gang_nodes.tolist())
    for _ in range(int(traffic["fill_ticks_at_most"])):
        before = model.resident_stats()
        tick()
        note_upload(before)
        if not cluster.gang_log[-1][0]:
            break
        cluster.churn(share, on_workers=1)
    warm_delta_buckets()
    for n_ticks, settle_share, settle_gang_share in (ctx.scale or {}).get(
            "settle", traffic["settle"]):
        for _ in range(int(n_ticks)):
            cluster.churn(float(settle_share),
                          gang_share=float(settle_gang_share))
            before = model.resident_stats()
            tick()
            note_upload(before)
    top = model._worker_bucket(len(worker_ids))
    uploads_not_met = sorted(
        f"{pw}:{k}" for pw in (top, top // 2)
        for k in ["full"] + row_buckets(pw) if (pw, k) not in uploads_met)
    if backend == "jax" and {(top, "full"), (top // 2, "full"),
                             (top, top // 2)} - uploads_met:
        # what the window does meet: at both worker buckets the rows wander
        # between a full upload, at the upper one a delta of its largest
        # row bucket.  (A smaller delta needs the rows of two ticks to
        # agree but for a few, and gangs end between any two: the row
        # buckets that the gang rows' held workers put out of reach in
        # set-up are named in the run note, and a compile in the window is
        # a failed check either way.)
        raise SystemExit(
            "chipbench: set-up did not meet the upload programs the window "
            f"meets; not met: {uploads_not_met}")
    cluster.churn(share, gang_share=gang_share)
    spans.gc_as_server_started(gc_settings)
    shapes_warm = model.shape_allocations
    uploads0 = model.resident_stats()
    cache0 = core.tick_cache.counters()
    started0 = counter_value(GANG_GROUPS_COUNTER)
    first_window_tick = len(cluster.log)
    rows_in_setup = len(rows_seen)
    ctx.setup_done()

    # -- the window -----------------------------------------------------------
    ticks: list = []
    compiles0 = ctx.compiles.count
    solves0 = dict(solves_by_backend)
    submit_s0 = cluster.submit_s
    host = spans.HostReading()
    t_start = time.perf_counter()
    t_end = t_start + ctx.seconds
    ctx.window_opens(t_start)
    while True:
        ticks.append(tick())
        with ann("chipbench/churn"):
            cluster.churn(share, gang_share=gang_share)
        ctx.window_tick()
        if time.perf_counter() >= t_end:
            break
    window_s = time.perf_counter() - t_start
    host_in_window = host.delta()
    ctx.window_closed()
    uploads1 = model.resident_stats()
    cache1 = core.tick_cache.counters()
    started1 = counter_value(GANG_GROUPS_COUNTER)
    compiles_in_window = ctx.compiles.count - compiles0
    in_window = {k: v - solves0.get(k, 0) for k, v in solves_by_backend.items()}
    failed = sum(v for k, v in in_window.items() if k not in wanted_backend)
    new_shapes = model.shape_allocations - shapes_warm
    memory_peak = ctx.memory_peak()
    gc.unfreeze()
    core = model = cluster.core = None  # the program's state is freed

    # -- the comparison -------------------------------------------------------
    t = time.perf_counter()
    compared = compare_with_reference(
        world, cluster.log, cluster.gang_log, rq_ids, worker_ids,
        manifest.reference(config["reference"]),
    )
    audited = tick_driver.audit_placements(
        world, cluster.log, rq_ids, worker_ids)
    audited_gangs = audit_gangs(
        world, cluster.log, cluster.gang_log, worker_ids, rows_per_tick)
    reference_s = time.perf_counter() - t
    total = np.asarray([p["total"] for p in ticks])
    window_rows = rows_seen[rows_in_setup:]
    window_gangs = cluster.gang_log[first_window_tick:]
    checks = [
        ("ticks_mismatched", compared["ticks_mismatched"], 0),
        ("rows_overcommitted", audited["rows_overcommitted"], 0),
        ("tasks_out_of_order", audited["tasks_out_of_order"], 0),
        ("priority_inversions", audited["priority_inversions"], 0),
        ("answers_unknown", audited["answers_unknown"] + refused, 0),
        ("gang_split", audited_gangs["gang_split"], 0),
        ("gang_shared", audited_gangs["gang_shared"], 0),
        ("gang_overtaken", audited_gangs["gang_overtaken"], 0),
        ("solves_off_device", failed, 0),
        ("compiles_in_window", compiles_in_window, 0),
        ("new_shapes_in_window", new_shapes, 0),
    ]
    observed = {
        "tick_phases_ms": ticks,
        "host_phases": HOST_PHASES,
        "device_phases": tick_driver.DEVICE_PHASES,
        "uploads_before": uploads0,
        "uploads_after": uploads1,
        "cache_before": cache0,
        "cache_after": cache1,
        "ticks": len(ticks),
        "extents": {
            "B": world.class_needs.shape[0] * world.n_priorities
            + rows_per_tick,
            "V": world.class_needs.shape[1],
            # the rows of a solve: the workers that run no gang
            "W": int(statistics.median(window_rows)),
            "R": world.worker_total.shape[1],
        },
        "groups": int(world.worker_group.max()) + 1,
        "gang_rows": rows_per_tick,
        "kernel_module": "greedy_cut_scan_impl",
    }
    if started0 is not None and started1 is not None:
        observed["gangs_started_in_window"] = started1 - started0

    def spread(values):
        return [min(values), statistics.median(values), max(values)]

    return {
        "attempted": len(ticks),
        "failed": failed,
        "window_s": window_s,
        "end_to_end": {
            "tick_ms_p50": float(np.percentile(total, 50)),
            "tick_ms_p95": float(np.percentile(total, 95)),
            "ticks_per_s": len(ticks) / window_s,
        },
        "observed": observed,
        "checks": checks,
        "memory_peak_bytes": memory_peak,
        "notes": {
            "solves_by_backend_in_window": in_window,
            "solves_by_backend_whole_run": solves_by_backend,
            "ticks_replayed_by_reference": compared["ticks_replayed"],
            "first_mismatch_tick": compared["first_mismatch_tick"],
            "reference_s": round(reference_s, 3),
            "host_in_window": host_in_window,
            "collector": gc_settings,
            "setup_ticks": first_window_tick,
            "upload_programs_not_met_in_setup": uploads_not_met,
            "resident": {k: uploads1.get(k) for k in (
                "full_uploads", "delta_uploads", "invalidations")},
            "phases_ms_p50": {
                key: statistics.median(p.get(key, 0.0) for p in ticks)
                for key in sorted({k for p in ticks for k in p})},
            "longest_tick_ms": max(ticks, key=lambda p: p["total"]),
            "between_ticks_s": window_s - float(total.sum()) / 1e3,
            # of which inside `reactor.on_new_tasks`, the gangs that arrive
            "gang_submit_s": cluster.submit_s - submit_s0,
            "rows_min_p50_max": spread(window_rows),
            "ticks_at_upper_worker_bucket": sum(
                r > top // 2 for r in window_rows),
            "gangs_started_a_tick_min_p50_max": spread(
                [len(g[0]) for g in window_gangs]),
            "gangs_ended_a_tick_min_p50_max": spread(
                [len(g[1]) for g in window_gangs]),
            "nodes_started_a_tick_min_p50_max": spread(
                [sum(len(m) for _g, m in g[0]) for g in window_gangs]),
            "gangs_running_at_close": len(cluster.running_gangs),
            "running_at_close": len(cluster.running),
            "assigned_in_window": sum(
                len(rec[0]) for rec in cluster.log[first_window_tick:]),
        },
    }
