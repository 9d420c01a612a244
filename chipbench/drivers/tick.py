"""The `tick` driver: the production tick over a backlog held constant.

Per tick, as `chip_smoke.py` `width` and the reactor do it:
`TickStateCache.sync -> create_batches -> run_tick -> apply`, with the model
the server builds for `--scheduler tpu` (`GreedyCutScanModel(backend="jax")`,
device-resident state, no pipeline, no paranoid guard).  Between ticks the
harness plays the cluster: a seeded share of the running tasks finishes and as
many new ready tasks arrive, so the backlog keeps its size and every tick
uploads a delta of a few dozen rows.

Set-up is: the world from the seed, the program's state built from it, the
first tick (fills the cluster, full upload, compiles), one tick for each
delta-upload bucket the window can meet, and the traffic's settle steps
(ticks under a falling churn, the last of them the window's own kind), which
bring the running mix to where the window's churn holds it.  The window then
runs whole ticks for
`--seconds` of wall clock.  Everything the program answered is kept and
compared with the plain reference once the window has closed.
"""

from __future__ import annotations

import gc
import statistics
import time
from collections import deque

import numpy as np

from chipbench import generate, manifest, spans

TASK_MASK = (1 << 32) - 1
HOST_PHASES = ("snapshot", "batches", "assemble", "solve_host_prep",
               "mapping", "apply")
DEVICE_PHASES = ("device_sync", "solve_dispatch")


def build_program_state(world, config):
    """The program's Core holding the world: real Worker objects, interned
    request classes, populated TaskQueues.  Returns (core, rq_ids,
    worker_ids)."""
    from hyperqueue_tpu.ids import make_task_id
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
    from hyperqueue_tpu.server.core import Core
    from hyperqueue_tpu.server.worker import Worker, WorkerConfiguration

    if FRACTIONS_PER_UNIT != generate.UNIT or make_task_id(1, 5) != (1 << 32) | 5:
        raise SystemExit("chipbench: the program's units or task ids changed")
    core = Core()
    rids = [core.resource_map.get_or_create(n) for n in world.resources]
    if rids != list(range(len(rids))):
        raise SystemExit(f"chipbench: resource ids {rids} are not 0..R-1")
    rq_ids = []
    for c in range(world.class_needs.shape[0]):
        variants = tuple(
            ResourceRequest(entries=tuple(
                ResourceRequestEntry(rids[r], int(a))
                for r, a in enumerate(world.class_needs[c, v]) if a > 0
            ))
            for v in range(int(world.class_variants[c]))
        )
        rq_ids.append(core.intern_rqv(ResourceRequestVariants(variants=variants)))
    if sorted(set(rq_ids)) != rq_ids:
        raise SystemExit("chipbench: request classes did not intern in order")
    add = core.queues.add
    for t, (c, p) in enumerate(zip(world.task_class.tolist(),
                                   world.task_prio.tolist())):
        add(rq_ids[c], (p, 0), (1 << 32) | t)
    kinds = config["resource_kinds"]
    worker_ids = []
    for row in (world.worker_total // generate.UNIT).tolist():
        items = []
        for name, n in zip(world.resources, row):
            if n <= 0:
                continue
            if kinds[name] == "range":
                items.append(ResourceDescriptorItem.range(name, 0, n - 1))
            elif kinds[name] == "list":
                items.append(ResourceDescriptorItem.list(
                    name, [str(i) for i in range(n)]))
            else:
                items.append(ResourceDescriptorItem.sum(name, n * generate.UNIT))
        worker = Worker.create(
            core.worker_id_counter.next(),
            WorkerConfiguration(descriptor=ResourceDescriptor(items=tuple(items))),
            core.resource_map,
        )
        core.workers[worker.worker_id] = worker
        worker_ids.append(worker.worker_id)
    slots = [core.workers[w].nt_free for w in worker_ids]
    if slots != world.worker_slots.tolist():
        raise SystemExit("chipbench: the program bounds task slots otherwise")
    return core, rq_ids, worker_ids


class Cluster:
    """What the harness does between ticks, and its record of every answer."""

    def __init__(self, world, core, rq_ids, seed):
        self.world, self.core, self.rq_ids = world, core, rq_ids
        self.rng = np.random.default_rng([int(seed), 9])
        self.running: list = []       # the program's assignments still running
        self.next_task = len(world.task_class)
        self.log: list = []           # per tick: [assignments, finished]
        # level (class * priorities + priority) of every task there ever was
        self.level_of: list = (
            world.task_class.astype(np.int64) * world.n_priorities
            + world.task_prio
        ).tolist()

    def apply(self, assignments) -> None:
        core = self.core
        workers = core.workers
        for task_id, worker_id, rq_id, variant in assignments:
            worker = workers[worker_id]
            worker.assign(task_id, core.variant_amounts(rq_id, variant, worker))

    def started(self, assignments) -> None:
        self.running.extend(assignments)
        self.log.append([assignments, ()])

    def _pick(self, share: float):
        n = len(self.running)
        k = min(n, max(1, int(round(share * n)))) if n else 0
        return sorted(self.rng.choice(n, size=k, replace=False).tolist(),
                      reverse=True) if k else []

    def _pick_on_workers(self, n_workers: int):
        """One running task on each of `n_workers` distinct workers."""
        seen, picked = set(), []
        for i in self.rng.permutation(len(self.running)).tolist():
            worker_id = self.running[i][1]
            if worker_id not in seen:
                seen.add(worker_id)
                picked.append(i)
                if len(picked) == n_workers:
                    break
        return sorted(picked, reverse=True)

    def churn(self, share: float, on_workers: int | None = None) -> None:
        """Finish running tasks and let as many new ready tasks arrive."""
        core, running = self.core, self.running
        picked = (self._pick(share) if on_workers is None
                  else self._pick_on_workers(on_workers))
        finished = []
        for i in picked:
            task_id, worker_id, rq_id, variant = running[i]
            running[i] = running[-1]
            running.pop()
            worker = core.workers[worker_id]
            worker.unassign(task_id, core.variant_amounts(rq_id, variant, worker))
            finished.append(task_id & TASK_MASK)
        # the backlog keeps its size and its mix: each task the last tick
        # placed is replaced by a new ready task of its class and priority,
        # numbered in the order of the tasks they replace
        add = core.queues.add
        t = self.next_task
        level_of = self.level_of
        n_p = self.world.n_priorities
        rq_ids = self.rq_ids
        for old in sorted(a[0] & TASK_MASK for a in self.log[-1][0]):
            level = level_of[old]
            level_of.append(level)
            add(rq_ids[level // n_p], (level % n_p, 0), (1 << 32) | t)
            t += 1
        self.next_task = t
        self.log[-1][1] = finished


def audit_placements(world, log, rq_ids, worker_ids) -> dict:
    """What the configuration guarantees whatever the order of the scan, read
    from the program's own placements and the world alone (no reference
    takes part): no worker holds more than it has of a resource or a task
    slot; within a (class, priority) level the oldest waiting tasks leave
    first; no level of a class is served while a higher level of that class
    still waits (within a tick resources only shrink, so what a higher level
    could not use a lower level of the same class cannot use either)."""
    n_p = world.n_priorities
    level_of = (world.task_class.astype(np.int64) * n_p
                + world.task_prio).tolist()
    waiting = [deque() for _ in range(world.class_needs.shape[0] * n_p)]
    for t, level in enumerate(level_of):
        waiting[level].append(t)
    row_of = {w: i for i, w in enumerate(worker_ids)}
    class_of = {rq: c for c, rq in enumerate(rq_ids)}
    used = np.zeros_like(world.worker_total)
    slots_used = np.zeros_like(world.worker_slots)
    holds: dict = {}
    overcommitted = out_of_order = inversions = unknown = 0
    for assignments, finished in log:
        by_level: dict = {}
        for task_id, worker_id, rq_id, variant in assignments:
            t = task_id & TASK_MASK
            row, c = row_of.get(worker_id), class_of.get(rq_id)
            if (row is None or c is None or t >= len(level_of) or t in holds
                    or level_of[t] // n_p != c
                    or not 0 <= variant < int(world.class_variants[c])):
                unknown += 1
                continue
            need = world.class_needs[c, variant]
            used[row] += need
            slots_used[row] += 1
            holds[t] = (row, c, variant)
            by_level.setdefault(level_of[t], []).append(t)
        overcommitted += int(((used > world.worker_total).any(axis=1)
                              | (slots_used > world.worker_slots)).sum())
        for level, tasks in by_level.items():
            queue = waiting[level]
            oldest = {queue.popleft() for _ in range(min(len(tasks), len(queue)))}
            out_of_order += len(set(tasks) - oldest)
        for level in by_level:
            c, p = divmod(level, n_p)
            inversions += any(waiting[c * n_p + q] for q in range(p + 1, n_p))
        for t in finished:
            held = holds.pop(t, None)
            if held is None:
                unknown += 1
                continue
            row, c, variant = held
            used[row] -= world.class_needs[c, variant]
            slots_used[row] -= 1
        # the traffic's rule: every task just placed is replaced by a new
        # ready task of its level, numbered in the order of those replaced
        for t in sorted(t for tasks in by_level.values() for t in tasks):
            waiting[level_of[t]].append(len(level_of))
            level_of.append(level_of[t])
    return {
        "rows_overcommitted": overcommitted,
        "tasks_out_of_order": out_of_order,
        "priority_inversions": inversions,
        "answers_unknown": unknown,
    }


def compare_with_reference(world, log, rq_ids, worker_ids, reference_cls):
    """Replay the record against the plain reference.  Returns the ticks
    whose placements differ (every tick after the first difference counts,
    since nothing after it can be vouched for) and how many were replayed."""
    ref = reference_cls(world)
    n_p = world.n_priorities
    n_v = world.class_needs.shape[1]
    n_w = world.worker_total.shape[0]
    # the level of every task there ever was, as the reference accounts it
    level_of = (world.task_class.astype(np.int64) * n_p
                + world.task_prio).tolist()
    row_of = {w: i for i, w in enumerate(worker_ids)}
    rq_of_level = np.repeat(np.asarray(rq_ids, dtype=np.int64), n_p)
    mismatched = 0
    first_bad = None
    for i, (assignments, finished) in enumerate(log):
        cells, taken = ref.tick()
        got = np.asarray(assignments, dtype=np.int64).reshape(-1, 4)
        tasks = got[:, 0] & TASK_MASK
        same = bool((tasks < len(level_of)).all())
        if same:
            levels = np.asarray([level_of[t] for t in tasks.tolist()],
                                dtype=np.int64)
            rows = np.fromiter((row_of.get(w, -1) for w in got[:, 1].tolist()),
                               dtype=np.int64, count=len(got))
            same = bool((rows >= 0).all()) and bool(
                (rq_of_level[levels] == got[:, 2]).all())
        if same:
            keys, counts = np.unique(
                (levels * n_v + got[:, 3]) * n_w + rows, return_counts=True)
            want = (cells[:, 0] * n_v + cells[:, 1]) * n_w + cells[:, 2]
            order = np.argsort(want)
            same = (np.array_equal(keys, want[order])
                    and np.array_equal(counts, cells[order, 3]))
        if same:
            want_ids = sorted(t for ids in taken.values() for t in ids)
            same = want_ids == sorted(tasks.tolist())
        unknown = ref.finish(finished) if same else 0
        if not same or unknown:
            first_bad = i
            mismatched = len(log) - i
            break
        placed = sorted(t for ids in taken.values() for t in ids)
        new_levels = [level_of[t] for t in placed]
        ref.arrive(range(len(level_of), len(level_of) + len(placed)),
                   new_levels)
        level_of.extend(new_levels)
    return {
        "ticks_mismatched": mismatched,
        "ticks_replayed": len(log) if first_bad is None else first_bad + 1,
        "first_mismatch_tick": first_bad,
    }


def run(ctx) -> dict:
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel
    from hyperqueue_tpu.scheduler.tick import create_batches, run_tick

    config, traffic = ctx.cell["config"], ctx.cell["traffic"]
    world = generate.world(config, traffic, ctx.seed, ctx.scale)
    core, rq_ids, worker_ids = build_program_state(world, config)
    cluster = Cluster(world, core, rq_ids, ctx.seed)
    model_cls = spans.annotated_model(GreedyCutScanModel) if ctx.trace \
        else GreedyCutScanModel
    model = model_cls(backend="numpy" if ctx.rehearse else "jax")
    wanted_backend = ("host-native", "host-numpy") if ctx.rehearse \
        else ("device-jax",)
    ann = spans.annotate
    solves_by_backend: dict = {}
    # the collector as the server that runs this tick in production has it
    gc_settings = spans.server_gc_settings()
    spans.gc_as_server_starts(gc_settings)

    def tick():
        phases: dict = {}
        t0 = time.perf_counter()
        with ann("chipbench/snapshot"):
            snap = core.tick_cache.sync(core)
        t1 = time.perf_counter()
        with ann("chipbench/batches"):
            batches = create_batches(core.queues)
        t2 = time.perf_counter()
        with ann("chipbench/run_tick"):
            out = run_tick(
                core.queues, None, core.rq_map, core.resource_map, model,
                batches=batches, dense=snap, phases=phases,
                key_cache=core.tick_cache,
            )
        t3 = time.perf_counter()
        with ann("chipbench/apply"):
            cluster.apply(out)
        t4 = time.perf_counter()
        phases.update(snapshot=(t1 - t0) * 1e3, batches=(t2 - t1) * 1e3,
                      apply=(t4 - t3) * 1e3, total=(t4 - t0) * 1e3)
        backend = model.last_backend
        solves_by_backend[backend] = solves_by_backend.get(backend, 0) + 1
        cluster.started(out)
        return phases

    share = float(traffic["churn_per_tick"])
    # -- set-up: fill, every delta bucket, then the window's own ticks ------
    tick()
    for rows in traffic["warm_dirty_rows"]:
        cluster.churn(share, on_workers=min(int(rows), len(worker_ids)))
        tick()
    for n_ticks, settle_share in (ctx.scale or {}).get("settle",
                                                       traffic["settle"]):
        for _ in range(int(n_ticks)):
            cluster.churn(float(settle_share))
            tick()
    cluster.churn(share)
    spans.gc_as_server_started(gc_settings)
    shapes_warm = model.shape_allocations
    uploads0 = model.resident_stats()
    first_window_tick = len(cluster.log)
    ctx.setup_done()

    # -- the window -----------------------------------------------------------
    ticks: list = []
    compiles0 = ctx.compiles.count
    solves0 = dict(solves_by_backend)
    host = spans.HostReading()
    t_start = time.perf_counter()
    t_end = t_start + ctx.seconds
    ctx.window_opens(t_start)
    while True:
        ticks.append(tick())
        with ann("chipbench/churn"):
            cluster.churn(share)
        ctx.window_tick()
        if time.perf_counter() >= t_end:
            break
    window_s = time.perf_counter() - t_start
    host_in_window = host.delta()
    ctx.window_closed()
    uploads1 = model.resident_stats()
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
        world, cluster.log, rq_ids, worker_ids,
        manifest.reference(config["reference"]),
    )
    audited = audit_placements(world, cluster.log, rq_ids, worker_ids)
    reference_s = time.perf_counter() - t
    total = np.asarray([p["total"] for p in ticks])
    checks = [
        ("ticks_mismatched", compared["ticks_mismatched"], 0),
        ("rows_overcommitted", audited["rows_overcommitted"], 0),
        ("tasks_out_of_order", audited["tasks_out_of_order"], 0),
        ("priority_inversions", audited["priority_inversions"], 0),
        ("answers_unknown", audited["answers_unknown"], 0),
        ("solves_off_device", failed, 0),
        ("compiles_in_window", compiles_in_window, 0),
        ("new_shapes_in_window", new_shapes, 0),
    ]
    return {
        "attempted": len(ticks),
        "failed": failed,
        "window_s": window_s,
        "end_to_end": {
            "tick_ms_p50": float(np.percentile(total, 50)),
            "tick_ms_p95": float(np.percentile(total, 95)),
            "ticks_per_s": len(ticks) / window_s,
        },
        "observed": {
            "tick_phases_ms": ticks,
            "host_phases": HOST_PHASES,
            "device_phases": DEVICE_PHASES,
            "uploads_before": uploads0,
            "uploads_after": uploads1,
            "ticks": len(ticks),
            "extents": {
                "B": world.class_needs.shape[0] * world.n_priorities,
                "V": world.class_needs.shape[1],
                "W": world.worker_total.shape[0],
                "R": world.worker_total.shape[1],
            },
            "kernel_module": "greedy_cut_scan_impl",
        },
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
            "host_phases_ms_p50": statistics.median(
                sum(p.get(n, 0.0) for n in HOST_PHASES) for p in ticks),
            "device_phases_ms_p50": statistics.median(
                sum(p.get(n, 0.0) for n in DEVICE_PHASES) for p in ticks),
            # a stall shows in ticks_per_s and not in the percentiles: the
            # longest tick with its phases, and the time between the ticks
            "longest_tick_ms": max(ticks, key=lambda p: p["total"]),
            "between_ticks_s": window_s - float(total.sum()) / 1e3,
            "running_at_close": len(cluster.running),
            "assigned_in_window": sum(
                len(rec[0]) for rec in cluster.log[first_window_tick:]),
        },
    }

