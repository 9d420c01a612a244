"""Benchmark: the north-star scheduling tick on real TPU hardware.

BASELINE.json: 1M ready tasks x 1k heterogeneous workers scheduled in
< 50 ms/tick (the reference's CPU MILP takes much longer at this scale; its
published claim is <0.1 ms per-task *overhead*, i.e. throughput, not a single
global solve).

The default mode times the WHOLE production tick — `scheduler.tick.run_tick`
driven from populated TaskQueues (native C++ queues when available) through
batching, snapshot build, the dense solve, and the assignment mapping loop —
exactly what `reactor.schedule` runs per tick (the reference times the same
span, scheduler/main.rs:40-46 trace_time!). `--kernel` times the jitted solve
alone.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
vs_baseline = baseline_ms / measured_ms (higher is better, >1 beats the 50 ms
target).

Run with no args on the TPU (driver does this); pass --cpu to force the
virtual CPU backend for local checks.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

BASELINE_MS = 50.0  # BASELINE.json north star


def build_instance(n_workers=1024, n_tasks=1_000_000, n_r=8, n_b=256, n_v=2,
                   seed=42):
    """1k heterogeneous workers (NUMA-ish cpu counts, GPUs on 1/4 of boxes,
    memory), 1M ready tasks spread over 256 priority-cut batches of mixed
    resource classes.

    Shapes are TPU-aligned (W=1024, R=8) — the production path
    (models/greedy.py) pads every tick the same way; unaligned layouts cost
    >70 ms on this hardware (measured W=1000/R=6 vs W=1024/R=8)."""
    from hyperqueue_tpu.ops.assign import scarcity_weights
    from hyperqueue_tpu.utils.constants import INF_TIME

    U = 10_000
    rng = np.random.default_rng(seed)
    free = np.zeros((n_workers, n_r), dtype=np.int32)
    free[:, 0] = rng.choice([32, 64, 128], size=n_workers) * U          # cpus
    gpu_boxes = rng.random(n_workers) < 0.25
    free[:, 1] = np.where(gpu_boxes, rng.choice([4, 8], size=n_workers), 0) * U
    free[:, 2] = rng.choice([256, 512, 1024], size=n_workers) * U       # mem
    free[:, 3] = rng.integers(0, 2, size=n_workers) * 4 * U             # tpus
    nt_free = np.minimum(free[:, 0] // U, 256).astype(np.int32)
    lifetime = np.full(n_workers, INF_TIME, dtype=np.int32)

    needs = np.zeros((n_b, n_v, n_r), dtype=np.int32)
    needs[:, 0, 0] = rng.choice([1, 2, 4, 8], size=n_b) * U             # cpus
    needs[:, 0, 1] = np.where(rng.random(n_b) < 0.3,
                              rng.choice([5000, U], size=n_b), 0)       # gpus
    needs[:, 0, 2] = rng.choice([1, 4, 16], size=n_b) * U               # mem
    # second variant: cpu-heavier fallback without gpu
    needs[:, 1, 0] = needs[:, 0, 0] * 2
    needs[:, 1, 2] = needs[:, 0, 2]
    sizes = rng.multinomial(
        n_tasks, np.ones(n_b) / n_b
    ).astype(np.int32)
    min_time = np.zeros((n_b, n_v), dtype=np.int32)
    scarcity = np.asarray(
        scarcity_weights(free.astype(np.int64).sum(axis=0))
    ).astype(np.float32)

    # the kernel requires float32-exact amounts (< 2^23); run the same range
    # compression the production tick path applies
    from hyperqueue_tpu.scheduler.tick import _range_compress

    needs64 = needs.astype(np.int64)
    free64 = free.astype(np.int64)
    _range_compress(needs64, free64)
    return (
        free64.astype(np.int32),
        nt_free,
        lifetime,
        needs64.astype(np.int32),
        sizes,
        min_time,
        scarcity,
    )


def build_tick_state(n_workers=1024, n_tasks=1_000_000, n_classes=128,
                     seed=42):
    """Production-shaped tick inputs: interned rq classes, priority-levelled
    TaskQueues holding n_tasks ready ids, and WorkerRow snapshots — the same
    objects `reactor.schedule` hands to run_tick."""
    from hyperqueue_tpu.ids import make_task_id
    from hyperqueue_tpu.resources.amount import FRACTIONS_PER_UNIT as U
    from hyperqueue_tpu.resources.map import ResourceIdMap, ResourceRqMap
    from hyperqueue_tpu.resources.request import (
        ResourceRequest,
        ResourceRequestEntry,
        ResourceRequestVariants,
    )
    from hyperqueue_tpu.scheduler.queues import TaskQueues
    from hyperqueue_tpu.scheduler.tick import WorkerRow
    from hyperqueue_tpu.utils.constants import INF_TIME

    rng = np.random.default_rng(seed)
    resource_map = ResourceIdMap()
    cpus = resource_map.get_or_create("cpus")
    gpus = resource_map.get_or_create("gpus")
    mem = resource_map.get_or_create("mem")

    rq_map = ResourceRqMap()
    rq_ids = []
    for _ in range(n_classes):
        n_cpus = int(rng.choice([1, 2, 4, 8]))
        entries = [ResourceRequestEntry(cpus, n_cpus * U)]
        if rng.random() < 0.3:
            entries.append(
                ResourceRequestEntry(gpus, int(rng.choice([U // 2, U])))
            )
        entries.append(
            ResourceRequestEntry(mem, int(rng.choice([1, 4, 16])) * U)
        )
        primary = ResourceRequest(entries=tuple(sorted(
            entries, key=lambda e: e.resource_id)))
        if rng.random() < 0.5:
            fallback = ResourceRequest(entries=(
                ResourceRequestEntry(cpus, 2 * n_cpus * U),
                ResourceRequestEntry(mem, primary.entries[-1].amount),
            ))
            rqv = ResourceRequestVariants(variants=(primary, fallback))
        else:
            rqv = ResourceRequestVariants.single(primary)
        rq_ids.append(rq_map.get_or_create(rqv))

    queues = TaskQueues()
    # spread 1M ready tasks over the classes with a few priority levels each
    class_of = rng.integers(0, n_classes, size=n_tasks)
    prio_of = rng.integers(0, 4, size=n_tasks)
    for t in range(n_tasks):
        queues.add(rq_ids[class_of[t]], (int(prio_of[t]), 0),
                   make_task_id(1, t))

    from hyperqueue_tpu.ids import task_id_task

    def priority_of(task_id):
        return (int(prio_of[task_id_task(task_id)]), 0)

    workers = []
    for wid in range(1, n_workers + 1):
        n_cpus = int(rng.choice([32, 64, 128]))
        free = [0] * len(resource_map)
        free[cpus] = n_cpus * U
        free[gpus] = int(rng.choice([0, 0, 0, 4, 8])) * U
        free[mem] = int(rng.choice([256, 512, 1024])) * U
        workers.append((wid, free, min(n_cpus, 256)))

    def worker_rows():
        # per-tick snapshot, as core.worker_rows() builds it
        return [
            WorkerRow(
                worker_id=wid,
                free=free,
                nt_free=nt,
                lifetime_secs=int(INF_TIME),
            )
            for wid, free, nt in workers
        ]

    return queues, worker_rows, rq_map, resource_map, priority_of


def build_core_state(n_workers=1024, n_tasks=1_000_000, n_classes=128,
                     seed=42):
    """Server-Core-backed tick state: real Worker objects (the dirty-
    tracking epoch lives on them), interned rq classes and populated
    TaskQueues — the state `reactor.schedule` actually ticks over, so the
    incremental snapshot cache (scheduler/tick_cache.py) is exercised
    exactly as in production."""
    from hyperqueue_tpu.ids import make_task_id, task_id_task
    from hyperqueue_tpu.resources.amount import FRACTIONS_PER_UNIT as U
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

    rng = np.random.default_rng(seed)
    core = Core()
    cpus = core.resource_map.get_or_create("cpus")
    gpus = core.resource_map.get_or_create("gpus")
    mem = core.resource_map.get_or_create("mem")

    rq_ids = []
    for _ in range(n_classes):
        n_cpus = int(rng.choice([1, 2, 4, 8]))
        entries = [ResourceRequestEntry(cpus, n_cpus * U)]
        if rng.random() < 0.3:
            entries.append(
                ResourceRequestEntry(gpus, int(rng.choice([U // 2, U])))
            )
        entries.append(
            ResourceRequestEntry(mem, int(rng.choice([1, 4, 16])) * U)
        )
        primary = ResourceRequest(entries=tuple(sorted(
            entries, key=lambda e: e.resource_id)))
        if rng.random() < 0.5:
            fallback = ResourceRequest(entries=(
                ResourceRequestEntry(cpus, 2 * n_cpus * U),
                ResourceRequestEntry(mem, primary.entries[-1].amount),
            ))
            rqv = ResourceRequestVariants(variants=(primary, fallback))
        else:
            rqv = ResourceRequestVariants.single(primary)
        rq_ids.append(core.intern_rqv(rqv))

    class_of = rng.integers(0, n_classes, size=n_tasks)
    prio_of = rng.integers(0, 4, size=n_tasks)
    for t in range(n_tasks):
        core.queues.add(rq_ids[class_of[t]], (int(prio_of[t]), 0),
                        make_task_id(1, t))

    for _ in range(n_workers):
        n_cpus = int(rng.choice([32, 64, 128]))
        items = [ResourceDescriptorItem.range("cpus", 0, n_cpus - 1)]
        n_gpus = int(rng.choice([0, 0, 0, 4, 8]))
        if n_gpus:
            items.append(ResourceDescriptorItem.list(
                "gpus", [str(i) for i in range(n_gpus)]
            ))
        items.append(ResourceDescriptorItem.sum(
            "mem", int(rng.choice([256, 512, 1024])) * U
        ))
        config = WorkerConfiguration(
            descriptor=ResourceDescriptor(items=tuple(items))
        )
        worker = Worker.create(
            core.worker_id_counter.next(), config, core.resource_map
        )
        core.workers[worker.worker_id] = worker

    def priority_of(task_id):
        return (int(prio_of[task_id_task(task_id)]), 0)

    return core, rq_ids, priority_of


def bench_phases(args, on_cpu, scratch=False):
    """Per-phase tick breakdown over the production Core state.

    Each measured tick runs: snapshot (cache sync or from-scratch
    WorkerRows with --scratch) -> batches -> run_tick (assemble /
    solve-dispatch / device-sync / mapping) -> apply (worker resource
    accounting, marking rows dirty like the reactor does).  Between reps
    the assignments are reverted OUTSIDE the timed span so every rep
    solves the same steady heavy-load tick.
    """
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel
    from hyperqueue_tpu.scheduler.tick import create_batches, run_tick

    core, _rq_ids, priority_of = build_core_state(
        n_workers=args.workers, n_tasks=args.tasks,
        n_classes=args.classes,
    )
    model = GreedyCutScanModel(backend="numpy" if on_cpu else "jax")

    import gc

    gc.collect()
    gc.set_threshold(100_000, 50, 25)

    def one_tick(phases):
        t0 = time.perf_counter()
        if scratch:
            rows = core.worker_rows()
            snap = None
        else:
            rows = None
            snap = core.tick_cache.sync(core)
        t1 = time.perf_counter()
        phases["snapshot"] = (t1 - t0) * 1e3
        batches = create_batches(core.queues)
        t2 = time.perf_counter()
        phases["batches"] = (t2 - t1) * 1e3
        assignments = run_tick(
            core.queues, rows, core.rq_map, core.resource_map, model,
            batches=batches, dense=snap, phases=phases,
            key_cache=None if scratch else core.tick_cache,
        )
        t3 = time.perf_counter()
        for task_id, worker_id, rq_id, variant in assignments:
            worker = core.workers[worker_id]
            worker.assign(
                task_id, core.variant_amounts(rq_id, variant, worker)
            )
        phases["apply"] = (time.perf_counter() - t3) * 1e3
        phases["total"] = (time.perf_counter() - t0) * 1e3
        return assignments

    def restore(assignments):
        for task_id, worker_id, rq_id, variant in assignments:
            worker = core.workers[worker_id]
            worker.unassign(
                task_id, core.variant_amounts(rq_id, variant, worker)
            )
            core.queues.add(rq_id, priority_of(task_id), task_id)

    warm = one_tick({})  # compile + first-population of every cache
    n_assigned = len(warm)
    restore(warm)
    rebuilds_after_warm = core.tick_cache.full_rebuilds
    shapes_after_warm = model.shape_allocations

    reps = []
    for _ in range(args.repeats):
        phases: dict = {}
        out = one_tick(phases)
        reps.append(phases)
        restore(out)

    keys = sorted({k for p in reps for k in p})
    medians = {
        k: float(np.median([p.get(k, 0.0) for p in reps])) for k in keys
    }
    steady_rebuilds = core.tick_cache.full_rebuilds - rebuilds_after_warm
    steady_shapes = model.shape_allocations - shapes_after_warm
    host_ms = sum(
        medians.get(k, 0.0)
        for k in ("snapshot", "batches", "assemble", "mapping", "apply")
    )
    return {
        "phases_ms": {k: round(v, 3) for k, v in medians.items()},
        "host_ms": round(host_ms, 3),
        "n_assigned": n_assigned,
        "steady_full_rebuilds": steady_rebuilds,
        "steady_shape_allocations": steady_shapes,
        "cache": core.tick_cache.counters(),
        "backend": model.last_backend,
        "mode": "scratch" if scratch else "incremental",
    }


def bench_full_tick(args, on_cpu):
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel
    from hyperqueue_tpu.scheduler.tick import run_tick

    queues, worker_rows, rq_map, resource_map, priority_of = build_tick_state(
        n_workers=args.workers, n_tasks=args.tasks
    )
    # the device solve, forced: a run without --cpu is a run on the chip,
    # and its number must not be a host solve under the device's name
    model = GreedyCutScanModel(backend="numpy" if on_cpu else "jax")

    # mirror the server's steady-state GC thresholds (bootstrap.Server
    # .start): default thresholds fire gen-0 collections mid-tick (~30 ms
    # spikes). Deliberately NOT freezing the 1M-task state: the production
    # server receives its tasks after startup, so old-gen collections do
    # traverse them — the bench must pay the same cost.
    import gc

    gc.collect()
    gc.set_threshold(100_000, 50, 25)

    def tick():
        return run_tick(queues, worker_rows(), rq_map, resource_map, model)

    def restore(assignments):
        # put the assigned ids back (at their original priority) so every
        # rep schedules the same steady heavy-load tick; the real server
        # would instead apply the assignments and shrink the queue
        for task_id, _worker_id, rq_id, _variant in assignments:
            queues.add(rq_id, priority_of(task_id), task_id)

    warm = tick()  # compile + warmup
    n_assigned = len(warm)
    restore(warm)

    times = []
    for _ in range(args.repeats):
        t0 = time.perf_counter()
        out = tick()
        times.append((time.perf_counter() - t0) * 1e3)
        restore(out)
    return times, n_assigned, model.last_backend


def bench_kernel(args, on_cpu):
    import jax

    from hyperqueue_tpu.ops.assign import (
        greedy_cut_scan_impl,
        greedy_cut_scan_numpy,
        host_visit_classes,
    )

    instance = build_instance(n_workers=args.workers, n_tasks=args.tasks)
    free, nt_free, lifetime, needs, sizes, min_time, scarcity = instance
    device = jax.devices()[0]
    if on_cpu:
        def tick():
            class_m, order_ids = host_visit_classes(free, needs, scarcity)
            return greedy_cut_scan_numpy(
                free, nt_free, lifetime, needs, sizes, min_time,
                class_m, order_ids,
            )
    else:
        fn = jax.jit(greedy_cut_scan_impl)
        placed = [
            jax.device_put(a, device)
            for a in (free, nt_free, lifetime, needs, sizes, min_time)
        ]

        def tick():
            class_m, order_ids = host_visit_classes(free, needs, scarcity)
            out = fn(*placed, class_m, order_ids)
            jax.block_until_ready(out)
            return out

    out = tick()  # compile + warmup
    times = []
    for _ in range(args.repeats):
        t0 = time.perf_counter()
        out = tick()
        times.append((time.perf_counter() - t0) * 1e3)
    counts = np.asarray(out[0])
    return times, int(counts.sum())


def bench_sharded_probe(args):
    """Virtual-8-device sharded solve at W=8192: the multichip scaling
    probe. Run under JAX_PLATFORMS=cpu + xla_force_host_platform_device_
    count=8.

    Two measurements, with a per-phase breakdown so MULTICHIP/BENCH
    artifacts show where the time goes instead of one opaque number:

    - raw kernel: place / compile (first call minus steady execute,
      cached across repeats) / execute / readback of the device-sliced
      counts;
    - production resident tick (MultichipModel): steady-state per-tick
      solve cost with the device-resident state engaged — assignments
      applied between ticks (so the donated free_after matches the next
      inputs), ~1% of worker rows released per tick as completion churn,
      giving per-tick dirty-row DELTA uploads instead of full (W, R)
      device_puts — plus the pipelined critical path (async dispatch +
      readback of the PREVIOUS, already-finished solve), which is the
      host-visible per-tick cost under `--tick-pipeline`.

    NOTE on the CPU mesh: the 8 "devices" are XLA host-platform threads
    sharing this machine's cores, so `execute` here is an emulation
    artifact (8-way oversubscribed CPU), not device silicon — on real
    chips the same program is the sub-millisecond kernel measured by
    --kernel. The numbers that transfer are place/upload/readback and the
    pipelined critical path."""
    import jax

    from hyperqueue_tpu.models.multichip import MultichipModel
    from hyperqueue_tpu.ops.assign import host_visit_classes
    from hyperqueue_tpu.parallel.solve import (
        make_worker_mesh,
        place_tick_inputs,
        sharded_cut_scan,
    )

    instance = build_instance(n_workers=args.workers, n_tasks=args.tasks)
    free, nt_free, lifetime, needs, sizes, min_time, scarcity = instance
    mesh = make_worker_mesh()
    n_devices = len(mesh.devices.flat)
    class_m, order_ids = host_visit_classes(free, needs, scarcity)

    phases = {}
    t0 = time.perf_counter()
    placed = place_tick_inputs(
        mesh, free, nt_free, lifetime, needs, sizes, min_time, class_m,
        order_ids,
    )
    jax.block_until_ready(placed)
    phases["place_ms"] = round((time.perf_counter() - t0) * 1e3, 3)

    t0 = time.perf_counter()
    out = sharded_cut_scan(mesh, *placed)
    jax.block_until_ready(out)
    first_call_ms = (time.perf_counter() - t0) * 1e3

    execute = []
    for _ in range(max(args.repeats, 2)):
        t0 = time.perf_counter()
        out = sharded_cut_scan(mesh, *placed)
        jax.block_until_ready(out)
        execute.append((time.perf_counter() - t0) * 1e3)
    phases["execute_ms"] = round(float(np.median(execute)), 3)
    phases["compile_ms"] = round(first_call_ms - phases["execute_ms"], 3)

    t0 = time.perf_counter()
    counts = np.asarray(out[0])  # full padded readback (the OLD cost)
    phases["readback_padded_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
    n_b, n_v, _ = needs.shape
    out2 = sharded_cut_scan(mesh, *placed)
    jax.block_until_ready(out2)
    from hyperqueue_tpu.models.greedy import _device_slicer

    t0 = time.perf_counter()
    sliced = np.asarray(
        _device_slicer(n_b, n_v, args.workers)(out2[0])
    )
    phases["readback_sliced_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
    n_assigned = int(counts.sum())
    del counts, sliced, out, out2, placed

    # --- production resident tick (the number the tick budget governs) ---
    model = MultichipModel()
    needs64 = needs.astype(np.int64)
    f = free.copy()
    nt = nt_free.copy()
    rng = np.random.default_rng(0)
    kwargs = dict(needs=needs, sizes=sizes, min_time=min_time,
                  lifetime=lifetime)
    out = model.solve(free=f, nt_free=nt, **kwargs)  # compile + full upload

    def apply_and_churn(counts_arr):
        nonlocal f, nt
        used = np.einsum("bvw,bvr->wr", counts_arr.astype(np.int64), needs64)
        f = (f - used).astype(np.int32)
        nt = (nt - counts_arr.sum(axis=(0, 1))).astype(np.int32)
        # ~1% of workers complete something: realistic per-tick churn
        rows = rng.integers(0, f.shape[0], size=max(f.shape[0] // 100, 1))
        f[rows] = free[rows]
        nt[rows] = nt_free[rows]

    apply_and_churn(out)
    resident = []
    for _ in range(args.repeats):
        t0 = time.perf_counter()
        out = model.solve(free=f, nt_free=nt, **kwargs)
        resident.append((time.perf_counter() - t0) * 1e3)
        apply_and_churn(out)
    stats = model.resident_stats()
    phases["resident_tick_ms"] = round(float(np.median(resident)), 3)
    phases["dirty_rows_last"] = stats.get("dirty_rows_last")

    # --- pipelined tick, exactly the reactor's order (map the PREVIOUS
    # solve, then dispatch this one): dispatch + wait is the host-visible
    # per-tick cost under --tick-pipeline.  On real accelerators dispatch
    # is an enqueue and wait ~0 (the device executed during inter-tick
    # host work); the CPU mesh executes sharded programs synchronously in
    # the dispatching thread, so dispatch absorbs the emulated execute ---
    dispatch_ms, wait_ms = [], []
    pending = None
    for _ in range(args.repeats + 1):
        if pending is not None:
            t0 = time.perf_counter()
            prev = pending.result()
            wait_ms.append((time.perf_counter() - t0) * 1e3)
            apply_and_churn(prev)
        t0 = time.perf_counter()
        pending = model.solve_async(free=f, nt_free=nt, **kwargs)
        dispatch_ms.append((time.perf_counter() - t0) * 1e3)
    apply_and_churn(pending.result())
    phases["pipeline_dispatch_ms"] = round(float(np.median(dispatch_ms)), 3)
    if wait_ms:
        phases["pipeline_wait_ms"] = round(float(np.median(wait_ms)), 3)
    phases["upload_bytes_total"] = stats.get("upload_bytes_total")
    return resident, n_assigned, n_devices, phases


def run_multichip_smoke() -> None:
    """Small-instance sharded-vs-single-chip parity gate: the 8-device
    mesh must produce counts bitwise identical to the single-chip host
    solve, through the PRODUCTION MultichipModel (resident device state
    engaged) across several evolving ticks."""
    import jax

    failures = []
    t0 = time.perf_counter()
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel
    from hyperqueue_tpu.models.multichip import MultichipModel

    n_devices = len(jax.devices())
    if n_devices < 2:
        print(json.dumps({
            "metric": "multichip_smoke", "ok": False,
            "failures": [f"need >= 2 devices, have {n_devices} (set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8)"],
        }))
        sys.exit(1)
    free, nt_free, lifetime, needs, sizes, min_time, _sc = build_instance(
        n_workers=64, n_tasks=2000, n_b=16
    )
    needs64 = needs.astype(np.int64)
    multi = MultichipModel()
    multi.paranoid_resident = 1  # fresh-solve cross-check each tick
    host = GreedyCutScanModel(backend="numpy")
    f, nt = free.copy(), nt_free.copy()
    ticks = 0
    for tick in range(5):
        kwargs = dict(free=f.copy(), nt_free=nt.copy(), lifetime=lifetime,
                      needs=needs, sizes=sizes, min_time=min_time)
        sharded = multi.solve(**kwargs)
        single = host.solve(**kwargs)
        if not np.array_equal(sharded, single):
            failures.append(
                f"tick {tick}: sharded counts diverge from single-chip"
            )
            break
        used = np.einsum("bvw,bvr->wr", sharded.astype(np.int64), needs64)
        f = (f - used).astype(np.int32)
        nt = (nt - sharded.sum(axis=(0, 1))).astype(np.int32)
        # one worker completes everything each tick: the delta-scatter
        # upload path must engage (a churn-free tick uploads NOTHING,
        # which the dirty-row diff handles without a scatter)
        f[tick % f.shape[0]] = free[tick % f.shape[0]]
        nt[tick % nt.shape[0]] = nt_free[tick % nt.shape[0]]
        ticks += 1
    stats = multi.resident_stats()
    if multi._mesh is False or multi._mesh is None:
        failures.append("multichip model never built a mesh")
    if stats.get("delta_uploads", 0) < 1:
        failures.append(
            f"resident delta path never engaged: {stats}"
        )
    print(json.dumps({
        "metric": "multichip_smoke",
        "ok": not failures,
        "failures": failures,
        "n_devices": n_devices,
        "ticks_compared": ticks,
        "resident": {k: stats.get(k) for k in (
            "full_uploads", "delta_uploads", "dirty_rows_last",
            "rep_cache_hits")},
        "paranoid_checks": multi.paranoid_checks,
        "total_s": round(time.perf_counter() - t0, 2),
    }))
    sys.exit(1 if failures else 0)


def run_scalability_sweep(args) -> None:
    """Worker-axis scalability sweep (ROADMAP item 1 acceptance): per-tick
    solve cost, host-native vs the sharded device path with resident
    state, at W = 1k..16k. One row per (W, backend) in
    benchmarks/results/db.jsonl.

    On a real TPU mesh the device execute is the sub-ms kernel and the
    crossover vs host-native lands at a few thousand workers; on a CPU
    host the "devices" are oversubscribed host threads, so the device
    rows carry device=cpu-mesh and the execute-dominated cost must be
    read as emulation (see bench_sharded_probe note)."""
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from common import emit

    import jax

    from hyperqueue_tpu.models.greedy import GreedyCutScanModel
    from hyperqueue_tpu.models.multichip import MultichipModel

    n_devices = len(jax.devices())
    device_kind = (
        "cpu-mesh" if jax.default_backend() == "cpu"
        else jax.devices()[0].platform
    )
    widths = [1024, 2048, 4096, 8192, 16384]
    if args.workers:
        widths = [w for w in widths if w <= args.workers]
    reps = max(min(args.repeats, 5), 2)
    rows = []
    for n_w in widths:
        free, nt_free, lifetime, needs, sizes, min_time, _sc = (
            build_instance(n_workers=n_w, n_tasks=args.tasks)
        )
        needs64 = needs.astype(np.int64)
        rng = np.random.default_rng(0)
        for backend, model in (
            ("host-native", GreedyCutScanModel(backend="numpy")),
            ("device-sharded", MultichipModel()),
        ):
            f, nt = free.copy(), nt_free.copy()
            kwargs = dict(needs=needs, sizes=sizes, min_time=min_time,
                          lifetime=lifetime)

            def apply_and_churn(counts_arr):
                nonlocal f, nt
                used = np.einsum(
                    "bvw,bvr->wr", counts_arr.astype(np.int64), needs64
                )
                f = (f - used).astype(np.int32)
                nt = (nt - counts_arr.sum(axis=(0, 1))).astype(np.int32)
                rows_i = rng.integers(
                    0, f.shape[0], size=max(f.shape[0] // 100, 1)
                )
                f[rows_i] = free[rows_i]
                nt[rows_i] = nt_free[rows_i]

            out = model.solve(free=f, nt_free=nt, **kwargs)  # warm/compile
            apply_and_churn(out)
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                out = model.solve(free=f, nt_free=nt, **kwargs)
                times.append((time.perf_counter() - t0) * 1e3)
                apply_and_churn(out)
            row = {
                "experiment": "solve_scalability",
                "n_workers": n_w,
                "n_tasks": args.tasks,
                "backend": backend,
                "device": device_kind if backend.startswith("device")
                else "host",
                "n_devices": n_devices if backend.startswith("device")
                else 1,
                "value_ms": round(float(np.median(times)), 3),
                "min_ms": round(min(times), 3),
                "max_ms": round(max(times), 3),
                "solve_backend": model.last_backend,
            }
            if backend.startswith("device"):
                stats = model.resident_stats()
                row["dirty_rows_last"] = stats.get("dirty_rows_last")
                row["delta_uploads"] = stats.get("delta_uploads")
            emit(row)
            rows.append(row)
    # crossover summary row: smallest W where the device path wins
    crossover = None
    by_w = {}
    for row in rows:
        by_w.setdefault(row["n_workers"], {})[row["backend"]] = (
            row["value_ms"]
        )
    for n_w in sorted(by_w):
        pair = by_w[n_w]
        if len(pair) == 2 and pair["device-sharded"] < pair["host-native"]:
            crossover = n_w
            break
    emit({
        "experiment": "solve_scalability",
        "n_workers": max(widths),
        "n_tasks": args.tasks,
        "backend": "crossover",
        "device": device_kind,
        "device_beats_host_at_w": crossover,
    })


def run_smoke() -> None:
    """Small-shape CPU gate, runnable inside tier-1: asserts the per-phase
    breakdown accounts for the wall tick time, that steady-state ticks
    perform zero full (W, R) rebuilds and zero new solver shape
    allocations (i.e. no recompilation), and that the incremental
    assembly is bit-identical to from-scratch on this state."""
    import argparse as _argparse

    import jax

    jax.config.update("jax_platforms", "cpu")
    small = _argparse.Namespace(workers=16, tasks=2000, classes=8, repeats=5)
    res = bench_phases(small, on_cpu=True)
    failures = []
    if res["steady_full_rebuilds"] != 0:
        failures.append(
            f"steady-state ticks performed "
            f"{res['steady_full_rebuilds']} full (W, R) rebuilds"
        )
    if res["steady_shape_allocations"] != 0:
        failures.append(
            f"steady-state ticks allocated "
            f"{res['steady_shape_allocations']} new solver shapes "
            "(would recompile on the jit path)"
        )
    ph = res["phases_ms"]
    total = ph.get("total", 0.0)
    parts = sum(v for k, v in ph.items() if k != "total")
    if abs(parts - total) > max(0.35 * total, 0.5):
        failures.append(
            f"phase breakdown ({parts:.3f} ms) does not account for the "
            f"wall tick time ({total:.3f} ms)"
        )

    # incremental-vs-scratch bit-identity on a fresh state (the same
    # check `--paranoid-tick` runs in production)
    from hyperqueue_tpu.scheduler.tick import create_batches
    from hyperqueue_tpu.scheduler.tick_cache import paranoid_check

    core, _rq_ids, _prio = build_core_state(
        n_workers=16, n_tasks=2000, n_classes=8
    )
    snap = core.tick_cache.sync(core)
    batches = create_batches(core.queues)
    try:
        paranoid_check(core, snap, batches, core.rq_map, core.resource_map)
    except AssertionError as e:
        failures.append(f"paranoid check failed: {e}")

    print(json.dumps({
        "metric": "smoke_tick",
        "ok": not failures,
        "failures": failures,
        **{k: res[k] for k in ("phases_ms", "host_ms", "n_assigned",
                               "backend", "cache")},
    }))
    sys.exit(1 if failures else 0)


def run_metrics_bench(args) -> None:
    """End-to-end metrics-plane gate: start a real server (--metrics-port 0)
    + zero-worker, scrape the Prometheus endpoint before and after a
    1k-task run, and emit tick-phase histogram summaries alongside the
    wall-clock timing — the scrape-diff is what later perf PRs report
    against. Also validates that the exposition parses and contains the
    tick-phase histograms, solver counters and per-worker gauges the
    acceptance criteria name."""
    import os
    import tempfile
    from pathlib import Path

    from hyperqueue_tpu.utils.metrics import (
        histogram_summary,
        parse_exposition,
        scrape,
    )

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    from utils_e2e import HqEnv

    n_tasks = min(args.tasks, 1000) if args.tasks else 1000
    failures = []
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        with HqEnv(Path(td)) as env:
            env.start_server("--metrics-port", "0")
            env.start_worker("--zero-worker", "--overview-interval", "0.5",
                             cpus=8)
            env.wait_workers(1)
            info = json.loads(env.command(
                ["server", "info", "--output-mode", "json"]
            ))
            port = info.get("metrics_port")
            if not port:
                # the very regression this gate guards: report it as a
                # failure JSON instead of crashing on an unscrapeable port
                print(json.dumps({
                    "metric": "metrics_scrape_1k_tasks", "ok": False,
                    "failures": ["server info reports no metrics_port"],
                }))
                sys.exit(1)
            env.command(["server", "reset-metrics"])
            before = parse_exposition(scrape("127.0.0.1", port))
            t_run = time.perf_counter()
            env.command([
                "submit", "--array", f"0-{n_tasks - 1}", "--wait", "--",
                "true",
            ], timeout=120)
            run_s = time.perf_counter() - t_run
            after_text = scrape("127.0.0.1", port)
            after = parse_exposition(after_text)

            phases = histogram_summary(after, "hq_tick_phase_seconds")
            if not phases:
                failures.append("no tick-phase histograms in the scrape")
            for required in ("hq_scheduler_ticks_total",
                             "hq_solver_failures_total",
                             "hq_workers_connected"):
                if required not in after:
                    failures.append(f"{required} missing from the scrape")
            ticks_before = sum(
                before.get("hq_scheduler_ticks_total", {})
                .get("samples", {}).values()
            )
            ticks_after = sum(
                after.get("hq_scheduler_ticks_total", {})
                .get("samples", {}).values()
            )
            if ticks_after <= ticks_before:
                failures.append("tick counter did not advance over the run")
            timeline = json.loads(env.command(
                ["job", "timeline", "last", "--output-mode", "json"]
            ))[0]
    print(json.dumps({
        "metric": "metrics_scrape_1k_tasks",
        "ok": not failures,
        "failures": failures,
        "value": round(run_s, 3),
        "unit": "s",
        "n_tasks": n_tasks,
        "ticks": int(ticks_after - ticks_before),
        "tick_phases": phases,
        "timeline_phases": timeline.get("phases"),
        "timeline_makespan": timeline.get("makespan"),
        "total_s": round(time.perf_counter() - t0, 2),
    }))
    sys.exit(1 if failures else 0)


def run_chaos_smoke() -> None:
    """One seeded kill -9/restart cycle against real processes: submit
    blocked work to a journaled server, SIGKILL it mid-job, restart it,
    let the reconnect-mode worker reattach, then assert completion + zero
    duplicate executions (each task exactly one start line, instance 0).
    The process-level gate for the fail-safe control plane
    (docs/fault_tolerance.md)."""
    import os
    import tempfile
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    from utils_e2e import HqEnv, wait_until

    failures = []
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        tmp = Path(td)
        with HqEnv(tmp) as env:
            journal = tmp / "journal.bin"
            marker = env.work_dir / "starts.txt"
            flag = env.work_dir / "flag"
            server_args = ("--journal", str(journal),
                           "--reattach-timeout", "60")
            env.start_server(*server_args)
            env.start_worker("--on-server-lost", "reconnect", cpus=4)
            env.wait_workers(1)
            env.command([
                "submit", "--array", "0-3", "--", "bash", "-c",
                f'echo "start:$HQ_TASK_ID:$HQ_INSTANCE_ID" >> {marker}; '
                f"while [ ! -f {flag} ]; do sleep 0.2; done",
            ])

            def running():
                out = json.loads(env.command(
                    ["job", "list", "--all", "--output-mode", "json"]
                ))
                return out and out[0]["counters"]["running"] == 4

            wait_until(running, timeout=30, message="tasks running")
            env.kill_process("server")
            env.start_server(*server_args)
            env.command(["server", "wait", "--timeout", "20"])
            try:
                wait_until(running, timeout=30, message="tasks reattached")
            except TimeoutError:
                failures.append("running tasks were not reattached")
            flag.touch()
            env.command(["job", "wait", "all"], timeout=60)
            out = json.loads(env.command(
                ["job", "list", "--all", "--output-mode", "json"]
            ))
            if out[0]["status"] != "finished":
                failures.append(f"job status {out[0]['status']!r}")
            starts = sorted(marker.read_text().splitlines())
            expected = sorted(f"start:{i}:0" for i in range(4))
            if starts != expected:
                failures.append(
                    f"duplicate/missing executions: {starts}"
                )
    print(json.dumps({
        "metric": "chaos_smoke",
        "ok": not failures,
        "failures": failures,
        "value": round((time.perf_counter() - t0), 2),
        "unit": "s",
    }))
    sys.exit(1 if failures else 0)


def run_slo_smoke() -> None:
    """SLO alerting gate (ISSUE 18): a chaos solve-delay breaches the
    tick-latency objective on a real server running with compressed
    alert windows (HQ_SLO_WINDOW_SCALE), the page-severity burn-rate
    alert fires (observed through `hq alerts`), the chaos plan exhausts,
    and the alert resolves. Fire/resolve latencies are recorded into
    benchmarks/results/db.jsonl (experiment slo_smoke)."""
    import os
    import tempfile
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from common import emit
    from utils_e2e import HqEnv

    # 1 h / 5 m page windows become 36 s / 3 s; evaluation every 0.3 s
    scale = 0.01
    delay_ms = 400.0      # > the 250 ms tick objective, < the 5 s watchdog
    chaos_fires = 50      # the bad era ends by exhaustion, then resolves
    plan = json.dumps({"rules": [
        {"site": "solve", "action": "delay",
         "delay_ms": delay_ms, "times": chaos_fires},
    ]})
    env_extra = {
        "HQ_SLO_WINDOW_SCALE": str(scale),
        "HQ_FAULT_PLAN": plan,
    }
    failures = []
    fired = None
    fire_s = resolve_s = None
    t_wall = time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        tmp = Path(td)
        with HqEnv(tmp) as env:
            env.start_server(env_extra=env_extra)
            env.start_worker(cpus=4)
            env.wait_workers(1)

            def alerts():
                out = json.loads(env.command(
                    ["alerts", "--output-mode", "json"]
                ))
                # no-alerts renders as a {"message": ...} record, firing
                # alerts as a list of table rows
                return out if isinstance(out, list) else []

            # bad era: every solve is delayed past the objective. Keep
            # the scheduler ticking with small arrays, polling WITHOUT
            # waiting for completion — the alert must be caught while
            # the chaos plan still has fires left.
            t0 = time.perf_counter()
            deadline = t0 + 60
            batch = 0
            while time.perf_counter() < deadline and fired is None:
                env.command([
                    "submit", "--array", "0-3", "--", "true",
                ])
                batch += 1
                hits = [a for a in alerts()
                        if a["slo"] == "tick-latency"
                        and a["state"] == "firing"]
                if hits:
                    fired = hits[0]
                    fire_s = round(time.perf_counter() - t0, 2)
            if fired is None:
                failures.append(
                    "tick-latency alert never fired under the chaos "
                    "solve-delay"
                )
            elif fired["severity"] != "page":
                failures.append(f"expected a page alert, got {fired}")

            # good era: drain the backlog (exhausting the chaos fires),
            # then the short window clears and the alert must resolve
            env.command(["job", "wait", "all"], timeout=120)
            t1 = time.perf_counter()
            deadline = t1 + 90
            while time.perf_counter() < deadline and resolve_s is None:
                if not [a for a in alerts()
                        if a["slo"] == "tick-latency"]:
                    resolve_s = round(time.perf_counter() - t1, 2)
                    break
                time.sleep(0.5)
            if fired is not None and resolve_s is None:
                failures.append(
                    "tick-latency alert never resolved after the chaos "
                    "plan exhausted"
                )
            prof_summary = profile_summary(json.loads(env.command(
                ["server", "stats", "--output-mode", "json"]
            )))

    emit({
        "experiment": "slo_smoke",
        "profile": prof_summary,
        "metric": "alert_fire_seconds",
        "value": fire_s if fire_s is not None else 0.0,
        "unit": "s",
        "params": {
            "window_scale": scale, "delay_ms": delay_ms,
            "chaos_fires": chaos_fires, "slo": "tick-latency",
        },
        "alert_resolve_seconds": resolve_s if resolve_s is not None else 0.0,
        "submit_batches": batch,
        "ok": not failures,
        "failures": failures,
        "wall_s": round(time.perf_counter() - t_wall, 2),
    })
    if not os.environ.get("HQ_BENCH_NO_DB"):
        try:
            checked, regs = check_regressions(experiment="slo_smoke")
            if regs:
                failures.append(
                    f"regress: {len(regs)} metric(s) >20% worse than "
                    f"their stored baselines: {regs}"
                )
            else:
                print(f"# regress: OK ({checked} slo_smoke metric(s) "
                      f"within 20% of baseline)", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - recorded as a failure
            failures.append(f"regress: {type(e).__name__}: {e}")
    print("slo-smoke:", "OK" if not failures else failures)
    sys.exit(1 if failures else 0)


def run_federation_smoke() -> None:
    """Federated failover gate (ISSUE 11): 2 shards + a warm standby.

    A reconnect-mode worker runs blocked tasks on shard 1; shard 1 is
    SIGKILLed mid-job. Measures the failover time — kill to the FIRST
    task completion committed by the promoted successor — and asserts
    the bound (lease detection + restore + reattach + completion). Also
    audits exactly-once: every task exactly one start line, instance 0,
    and a second submit against the promoted shard completes."""
    import os
    import tempfile
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from common import emit
    from utils_e2e import HqEnv, wait_until

    lease_timeout = 1.0
    # generous on the slow 2-core gVisor box: detection (~1-2 lease
    # timeouts) + journal restore + worker reconnect backoff (<= 5 s
    # jittered cap) + one task round trip
    bound_s = 20.0
    failures = []
    t_wall = time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        tmp = Path(td)
        with HqEnv(tmp) as env:
            marker = env.work_dir / "starts.txt"
            flag = env.work_dir / "flag"
            env.start_shard(0, 2, "--lease-timeout", str(lease_timeout))
            env.start_shard(1, 2, "--lease-timeout", str(lease_timeout))
            env.start_standby("--lease-timeout", str(lease_timeout),
                              "--no-coordinator")
            env.start_worker("--shard", "1", "--on-server-lost",
                             "reconnect", cpus=4)
            env.wait_workers(1)
            os.environ["HQ_SHARD"] = "1"
            try:
                env.command([
                    "submit", "--array", "0-3", "--", "bash", "-c",
                    f'echo "start:$HQ_TASK_ID:$HQ_INSTANCE_ID" >> {marker}'
                    f"; while [ ! -f {flag} ]; do sleep 0.2; done",
                ])
            finally:
                os.environ.pop("HQ_SHARD", None)
            wait_until(
                lambda: marker.exists()
                and len(marker.read_text().splitlines()) == 4,
                timeout=30, message="tasks running on shard 1",
            )
            flag.touch()  # tasks exit as soon as they can
            t_kill = time.perf_counter()
            env.kill_process("shard1-0")

            def first_completion() -> bool:
                try:
                    out = json.loads(env.command(
                        ["job", "list", "--all", "--output-mode", "json"],
                        timeout=30,
                    ))
                except Exception:  # noqa: BLE001 - mid-failover blips
                    return False
                return bool(out) and out[0]["counters"]["finished"] > 0

            try:
                wait_until(first_completion, timeout=bound_s + 10,
                           interval=0.1, message="successor completion")
                failover_s = time.perf_counter() - t_kill
            except TimeoutError:
                failover_s = float("inf")
                failures.append("no successor-side completion")
            env.command(["job", "wait", "all"], timeout=60)
            starts = sorted(marker.read_text().splitlines())
            if starts != sorted(f"start:{i}:0" for i in range(4)):
                failures.append(f"duplicate/missing executions: {starts}")
            # the promoted shard keeps serving: a fresh submit completes
            os.environ["HQ_SHARD"] = "1"
            try:
                env.command(["submit", "--array", "0-3", "--wait", "--",
                             "true"], timeout=60)
            except Exception as e:  # noqa: BLE001
                failures.append(f"post-promotion submit failed: {e}")
            finally:
                os.environ.pop("HQ_SHARD", None)
            stats = json.loads(env.command(
                ["server", "stats", "--shard", "1", "--output-mode",
                 "json"]
            ))
            if not (stats.get("federation") or {}).get("promoted"):
                failures.append("shard 1 is not a promoted successor")
            if failover_s != float("inf") and failover_s > bound_s:
                failures.append(
                    f"failover {failover_s:.2f}s over the {bound_s}s bound"
                )
    emit({
        "experiment": "federation_smoke",
        "metric": "failover_seconds",
        # None on the no-completion failure path: float('inf') would
        # serialize as the non-RFC-8259 token Infinity
        "value": (
            round(failover_s, 3) if failover_s != float("inf") else None
        ),
        "unit": "s",
        "params": {"shards": 2, "lease_timeout_s": lease_timeout,
                   "bound_s": bound_s, "successor": "standby"},
        "ok": not failures,
        "failures": failures,
        "wall_s": round(time.perf_counter() - t_wall, 2),
    })
    # --- regression gate: the row just stored vs its prior rows ------
    if not os.environ.get("HQ_BENCH_NO_DB"):
        try:
            checked, regs = check_regressions(experiment="federation_smoke")
            if regs:
                failures.append(
                    f"regress: {len(regs)} metric(s) >20% worse than "
                    f"their stored baselines: {regs}"
                )
            else:
                print(f"# regress: OK ({checked} federation_smoke "
                      f"metric(s) within 20% of baseline)",
                      file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - recorded as a failure
            failures.append(f"regress: {type(e).__name__}: {e}")
    sys.exit(1 if failures else 0)


def run_fleet_smoke() -> None:
    """Fleet observability gate (ISSUE 15): 2 shards + a standby running
    the lending coordinator.

    A reconnect-mode worker registers with shard 0; an array job lands
    on each shard (shard 1's requires the coordinator to LEND the
    worker over, so completion itself proves the lending path). A
    FleetFeed attached to the federation root must observe every
    shard's task-finished events EXACTLY once under the right shard
    label plus the structured lend departure, and one scrape of the
    fleet metrics proxy must cover both shards under the latency bound.
    Records a row in benchmarks/results/db.jsonl."""
    import os
    import tempfile
    import threading
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from common import emit
    from utils_e2e import HqEnv, start_fleet_proxy, wait_until

    from hyperqueue_tpu.client.fleet import FleetFeed
    from hyperqueue_tpu.utils.metrics import parse_exposition, scrape

    n_tasks = 10
    scrape_bound_s = 0.250
    failures: list[str] = []
    t_wall = time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        tmp = Path(td)
        with HqEnv(tmp) as env:
            env.start_shard(0, 2, "--lease-timeout", "2")
            env.start_shard(1, 2, "--lease-timeout", "2")
            env.start_standby("--lease-timeout", "2",
                              "--coordinator-interval", "0.25")
            env.start_worker("--shard", "0", "--on-server-lost",
                             "reconnect", cpus=2)
            env.wait_workers(1)

            feed = FleetFeed(env.server_dir, sample_interval=0.3,
                             retry_delay=0.3)
            feed.start()
            frames: list[dict] = []

            def collect() -> None:
                for frame in feed.frames(timeout=2.0):
                    frames.append(frame)

            threading.Thread(target=collect, daemon=True).start()
            wait_until(
                lambda: all(s == "up" for s in feed.states.values()),
                message="fleet feed live",
            )

            job_ids: dict[int, int] = {}
            for shard in (0, 1):
                os.environ["HQ_SHARD"] = str(shard)
                try:
                    out = env.command([
                        "submit", "--array", f"0-{n_tasks - 1}", "--",
                        "true",
                    ])
                finally:
                    os.environ.pop("HQ_SHARD", None)
                job_ids[shard] = int(out.split("job ID: ")[1].split()[0])
            # shard 1's job can only finish if the coordinator lends the
            # worker over — completion is the lending assert
            env.command(["job", "wait", "all"], timeout=120)

            def finished_events() -> dict:
                seen: dict = {}
                for frame in list(frames):
                    if frame.get("op") != "events":
                        continue
                    for rec in frame["records"]:
                        if rec.get("event") != "task-finished":
                            continue
                        key = (rec["shard"], rec["job"], rec["task"])
                        seen[key] = seen.get(key, 0) + 1
                return seen

            try:
                wait_until(
                    lambda: len(finished_events()) >= 2 * n_tasks,
                    timeout=30, message="fleet feed completeness",
                )
            except TimeoutError:
                failures.append(
                    f"feed saw {len(finished_events())} of "
                    f"{2 * n_tasks} task-finished events"
                )
            seen = finished_events()
            dups = {k: n for k, n in seen.items() if n != 1}
            if dups:
                failures.append(f"events not exactly-once: {dups}")
            for shard, job_id in job_ids.items():
                rows = [k for k in seen if k[0] == shard and k[1] == job_id]
                if len(rows) != n_tasks:
                    failures.append(
                        f"shard {shard} job {job_id}: {len(rows)} of "
                        f"{n_tasks} finishes observed under its label"
                    )
            lends = [
                rec
                for frame in list(frames) if frame.get("op") == "events"
                for rec in frame["records"]
                if rec.get("event") == "worker-lost"
                and rec.get("lent_to") is not None
            ]
            if not lends:
                failures.append("no structured lend event in the feed")

            # --- metrics proxy: parallel fan-out scrape ----------------
            scrape_s = float("inf")
            text = ""
            try:
                proxy_port = start_fleet_proxy(env.server_dir)
            except RuntimeError as e:
                failures.append(str(e))
            else:
                for _ in range(3):  # best-of-3 dampens box noise
                    t0 = time.perf_counter()
                    text = scrape("127.0.0.1", proxy_port)
                    scrape_s = min(scrape_s, time.perf_counter() - t0)
            if text:
                parsed = parse_exposition(text)
                up = parsed.get("hq_federation_shard_up", {}).get(
                    "samples", {}
                )
                for k in ("0", "1"):
                    if up.get((
                        "hq_federation_shard_up",
                        frozenset({("shard", k)}),
                    )) != 1.0:
                        failures.append(
                            f"proxy scrape missing shard {k} up"
                        )
                ticks = parsed.get("hq_scheduler_ticks_total", {}).get(
                    "samples", {}
                )
                shard_labels = {
                    dict(labels).get("shard") for _, labels in ticks
                }
                if not {"0", "1"} <= shard_labels:
                    failures.append(
                        f"proxy exposition lacks per-shard series: "
                        f"{shard_labels}"
                    )
                if scrape_s > scrape_bound_s:
                    failures.append(
                        f"proxy scrape {scrape_s * 1e3:.1f}ms over the "
                        f"{scrape_bound_s * 1e3:.0f}ms bound"
                    )
            feed.stop()
    emit({
        "experiment": "fleet_smoke",
        "metric": "proxy_scrape_seconds",
        "value": round(scrape_s, 4) if scrape_s != float("inf") else None,
        "unit": "s",
        "params": {
            "shards": 2, "tasks_per_shard": n_tasks,
            "scrape_bound_s": scrape_bound_s, "successor": "standby",
        },
        "events_observed": len(seen),
        "lend_events": len(lends),
        "ok": not failures,
        "failures": failures,
        "wall_s": round(time.perf_counter() - t_wall, 2),
    })
    # --- regression gate: the row just stored vs its prior rows ------
    if not os.environ.get("HQ_BENCH_NO_DB"):
        try:
            checked, regs = check_regressions(experiment="fleet_smoke")
            if regs:
                failures.append(
                    f"regress: {len(regs)} metric(s) >20% worse than "
                    f"their stored baselines: {regs}"
                )
            else:
                print(f"# regress: OK ({checked} fleet_smoke metric(s) "
                      f"within 20% of baseline)", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - recorded as a failure
            failures.append(f"regress: {type(e).__name__}: {e}")
    sys.exit(1 if failures else 0)


def run_reshard_smoke() -> None:
    """Elastic-resharding gate (ISSUE 17): 2 shards, a hot/idle backlog
    split, the rebalancer on, then an ONLINE third shard.

    Phase 1 (convergence): every job lands pinned on shard 0 while
    shard 1 idles behind a small pinned warmup; the standby runs
    ``--rebalance`` and must drive live migrations until the fleet's
    max/mean backlog ratio drops below the 1.5x hysteresis band.
    Measures standby-start -> convergence.

    Phase 2 (online add): ``--shards 3 --shard-id 2`` boots against the
    2-way root — the descriptor grows in place, the shard-add lands in
    the ownership log, no restart anywhere. Measures spawn -> shard 2
    serving stats. A job is then explicitly migrated onto the new shard
    and EVERY submitted task must still finish exactly once (zero loss
    across both the rebalancer's moves and the manual one)."""
    import os
    import tempfile
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from common import emit
    from utils_e2e import HqEnv, wait_until

    from hyperqueue_tpu.client.fleet import fleet_snapshot
    from hyperqueue_tpu.server.federation import _backlog
    from hyperqueue_tpu.utils.ownership import OwnershipStore

    converge_bound_s = 90.0
    add_bound_s = 45.0
    failures = []
    converge_s = float("inf")
    add_s = float("inf")
    t_wall = time.perf_counter()

    def backlog_ratio(root) -> float | None:
        samples = fleet_snapshot(root, timeout=5.0, sample_interval=0.25)
        # the rebalancer's own backlog definition (server queues PLUS
        # worker prefill queues) — measuring convergence with a narrower
        # one would declare victory on an all-prefilled hot shard
        backlogs = [
            _backlog(s) for s in samples.values() if s is not None
        ]
        if len(backlogs) < 2:
            return None
        mean = sum(backlogs) / len(backlogs)
        if mean <= 0:
            return 1.0  # all quiet: trivially converged
        return max(backlogs) / mean

    with tempfile.TemporaryDirectory() as td:
        tmp = Path(td)
        with HqEnv(tmp) as env:
            env.start_shard(0, 2, "--lease-timeout", "2.0")
            env.start_shard(1, 2, "--lease-timeout", "2.0")
            env.start_worker("--shard", "0", cpus=2)
            env.start_worker("--shard", "1", cpus=2)
            env.wait_workers(2)
            # shard 1's worker stays busy on a small pinned warmup: the
            # lending coordinator then has no idle donor, so backlog can
            # only converge through the REBALANCER's job migrations
            os.environ["HQ_SHARD"] = "1"
            try:
                env.command(["submit", "--array", "0-7", "--",
                             "sleep", "1"])
            finally:
                os.environ.pop("HQ_SHARD", None)
            os.environ["HQ_SHARD"] = "0"
            try:
                for _ in range(2):
                    env.command(["submit", "--array", "0-19", "--",
                                 "sleep", "2"])
            finally:
                os.environ.pop("HQ_SHARD", None)
            t0 = time.perf_counter()
            # pin the rebalancer control loop to a fast deterministic
            # cadence (HQ_REBALANCE_INTERVAL, server/federation.py) so
            # convergence is bounded by migration work, not by where the
            # default sampling interval happened to land
            env.start_standby("--lease-timeout", "2.0",
                              "--coordinator-interval", "0.25",
                              "--rebalance",
                              env_extra={"HQ_REBALANCE_INTERVAL": "0.25"})
            store = OwnershipStore(env.server_dir)

            def engaged() -> bool:
                m = store.load()
                return bool(m.assignments) or bool(m.verdicts)

            try:
                wait_until(engaged, timeout=converge_bound_s,
                           message="rebalancer verdict/migration")
                wait_until(
                    lambda: (backlog_ratio(env.server_dir) or 9.9) < 1.5,
                    timeout=converge_bound_s, interval=0.5,
                    message="backlog convergence below 1.5x",
                )
                converge_s = time.perf_counter() - t0
            except TimeoutError as e:
                failures.append(f"no convergence: {e}")
            moved = len(store.load().assignments)

            # --- phase 2: online shard add (N=2 -> N=3) --------------
            t1 = time.perf_counter()
            env.start_shard(2, 3, "--lease-timeout", "2.0")

            def shard2_up() -> bool:
                try:
                    stats = json.loads(env.command(
                        ["server", "stats", "--shard", "2",
                         "--output-mode", "json"], timeout=20,
                    ))
                except Exception:  # noqa: BLE001 - still booting
                    return False
                return (
                    stats.get("federation") or {}
                ).get("shard_id") == 2

            try:
                wait_until(shard2_up, timeout=add_bound_s,
                           message="shard 2 serving")
                add_s = time.perf_counter() - t1
            except TimeoutError:
                failures.append("online shard add never served")
            env.start_worker("--shard", "2", cpus=2)
            # move one job onto the shard that did not exist at submit
            # time (retry on a short cadence matched to the pinned
            # rebalancer interval: it may briefly hold the job's claim)
            migrated_to_new = False
            for _ in range(12):
                try:
                    env.command(["fleet", "migrate", "1", "2"],
                                timeout=60)
                    migrated_to_new = True
                    break
                except AssertionError:
                    time.sleep(0.5)
            if not migrated_to_new:
                failures.append("migration onto the added shard failed")
            env.command(["job", "wait", "all"], timeout=180)
            # zero task loss: every submitted task finished exactly once
            jobs = json.loads(env.command(
                ["job", "info", "all", "--output-mode", "json"],
                timeout=30,
            ))
            expected = {1: 20, 2: 8, 3: 20}
            got = {
                j["id"]: (j.get("counters") or {}).get("finished", 0)
                for j in jobs
            }
            if got != expected:
                failures.append(
                    f"task loss across resharding: finished {got}, "
                    f"expected {expected}"
                )
            status = env.command(["fleet", "status"], timeout=30)
            if "federation:" not in status:
                failures.append(f"fleet status unusable: {status!r}")
            if converge_s != float("inf") and converge_s > converge_bound_s:
                failures.append(
                    f"convergence {converge_s:.1f}s over the "
                    f"{converge_bound_s}s bound"
                )
            if add_s != float("inf") and add_s > add_bound_s:
                failures.append(
                    f"shard add {add_s:.1f}s over the {add_bound_s}s bound"
                )
    emit({
        "experiment": "reshard_smoke",
        "metric": "converge_seconds",
        "value": round(converge_s, 2) if converge_s != float("inf")
        else None,
        "unit": "s",
        "params": {"shards": 2, "ratio_band": 1.5,
                   "converge_bound_s": converge_bound_s},
        "jobs_moved": moved,
        "ok": not failures,
        "failures": failures,
        "wall_s": round(time.perf_counter() - t_wall, 2),
    })
    emit({
        "experiment": "reshard_smoke",
        "metric": "shard_add_seconds",
        "value": round(add_s, 2) if add_s != float("inf") else None,
        "unit": "s",
        "params": {"shards_before": 2, "shards_after": 3,
                   "add_bound_s": add_bound_s},
        "ok": not failures,
        "failures": failures,
        "wall_s": round(time.perf_counter() - t_wall, 2),
    })
    # --- regression gate: the rows just stored vs their prior rows ---
    if not os.environ.get("HQ_BENCH_NO_DB"):
        try:
            checked, regs = check_regressions(experiment="reshard_smoke")
            if regs:
                failures.append(
                    f"regress: {len(regs)} metric(s) >20% worse than "
                    f"their stored baselines: {regs}"
                )
            else:
                print(f"# regress: OK ({checked} reshard_smoke metric(s) "
                      f"within 20% of baseline)", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - recorded as a failure
            failures.append(f"regress: {type(e).__name__}: {e}")
    print("reshard-smoke:", "OK" if not failures else failures)
    sys.exit(1 if failures else 0)


def run_elasticity_smoke() -> None:
    """Self-healing elasticity gate (ISSUE 13): burst submit against an
    EMPTY local-handler pool.

    Clean pass: measures scale-up latency (burst submit -> first
    completion via a controller-spawned worker) and idle scale-down-to-
    floor latency (last completion -> zero active allocations), asserting
    both under generous bounds for this box. Chaos pass: the FIRST submit
    fails (injected) and the FIRST spawned worker dies at boot — the loop
    must still converge with zero failed tasks, proving backoff + crash
    accounting contain the faults."""
    import os
    import tempfile
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from common import emit
    from utils_e2e import HqEnv, wait_until

    # generous on the slow 2-core gVisor box: interpreter+import startup
    # of a spawned worker alone is ~1-2 s, the autoalloc tick is 0.4 s
    scale_up_bound_s = 30.0
    scale_down_bound_s = 30.0
    failures = []
    t_wall = time.perf_counter()

    def one_pass(tag: str, env_extra: dict) -> dict:
        with tempfile.TemporaryDirectory() as td:
            with HqEnv(Path(td)) as env:
                env.start_server(env_extra={
                    "HQ_AUTOALLOC_INTERVAL": "0.4", **env_extra,
                })
                env.command(["alloc", "add", "local", "--backlog", "2",
                             "--idle-timeout", "1.5", "--no-dry-run"])
                t0 = time.perf_counter()
                env.command(["submit", "--array", "1-16", "--",
                             "sleep", "0.1"])

                def first_completion():
                    out = json.loads(env.command(
                        ["job", "list", "--all", "--output-mode", "json"]
                    ))
                    return out and out[0]["counters"]["finished"] > 0

                wait_until(first_completion, timeout=90,
                           message=f"{tag}: first completion")
                scale_up_s = time.perf_counter() - t0
                env.command(["job", "wait", "all"], timeout=120)
                job = json.loads(env.command(
                    ["job", "list", "--all", "--output-mode", "json"]
                ))[0]
                if job["counters"]["failed"]:
                    failures.append(
                        f"{tag}: {job['counters']['failed']} failed tasks"
                    )
                t1 = time.perf_counter()

                def scaled_to_floor():
                    qs = json.loads(env.command(
                        ["alloc", "list", "--output-mode", "json"]
                    ))
                    return not [
                        a for a in qs[0]["allocations"]
                        if a["status"] in ("queued", "running")
                    ]

                wait_until(scaled_to_floor, timeout=90,
                           message=f"{tag}: scale-down to floor")
                scale_down_s = time.perf_counter() - t1
                decisions = json.loads(env.command(
                    ["alloc", "events", "--output-mode", "json"]
                ))
                return {
                    "scale_up_s": round(scale_up_s, 2),
                    "scale_down_s": round(scale_down_s, 2),
                    "verdicts": sorted({d["verdict"] for d in decisions}),
                }

    clean = one_pass("clean", {})
    if clean["scale_up_s"] > scale_up_bound_s:
        failures.append(
            f"clean scale-up {clean['scale_up_s']}s > {scale_up_bound_s}s"
        )
    if clean["scale_down_s"] > scale_down_bound_s:
        failures.append(
            f"clean scale-down {clean['scale_down_s']}s > "
            f"{scale_down_bound_s}s"
        )
    if "scale-up" not in clean["verdicts"] or \
            "scale-down" not in clean["verdicts"]:
        failures.append(f"clean verdicts incomplete: {clean['verdicts']}")

    # chaos: first submit fails, first spawned worker dies at boot —
    # the loop converges anyway (no latency bound: backoff dominates)
    plan = json.dumps({"rules": [
        {"site": "autoalloc.submit", "action": "raise", "at": 1},
        {"site": "autoalloc.spawn", "action": "raise", "at": 1},
    ]})
    chaotic = one_pass("chaos", {"HQ_FAULT_PLAN": plan})
    if "scale-up-failed" not in chaotic["verdicts"]:
        failures.append(
            f"chaos pass never recorded the injected submit failure: "
            f"{chaotic['verdicts']}"
        )

    emit({
        "experiment": "elasticity_smoke",
        "metric": "scale_up_seconds",
        "value": clean["scale_up_s"],
        "unit": "s",
        "params": {
            "tasks": 16, "backlog": 2, "idle_timeout_s": 1.5,
            "scale_up_bound_s": scale_up_bound_s,
            "scale_down_bound_s": scale_down_bound_s,
        },
        "scale_down_seconds": clean["scale_down_s"],
        "chaos": chaotic,
        "ok": not failures,
        "failures": failures,
        "wall_s": round(time.perf_counter() - t_wall, 2),
    })
    print("elasticity-smoke:", "OK" if not failures else failures)
    sys.exit(1 if failures else 0)


def run_explain_smoke() -> None:
    """Explainability gate: run a deliberately unsatisfiable and a
    satisfiable workload against a real server, assert the reason codes
    the flight recorder attributes to each, and record the solver
    status/objective trajectory in the BENCH json (ISSUE 4)."""
    import os
    import tempfile
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    from utils_e2e import HqEnv, wait_until

    failures = []
    t0 = time.perf_counter()
    trajectory = []
    with tempfile.TemporaryDirectory() as td:
        with HqEnv(Path(td)) as env:
            env.start_server()
            env.start_worker("--zero-worker", cpus=4)
            env.wait_workers(1)

            # job 1: unsatisfiable (no worker has 64 cpus) — must surface
            # no-matching-worker, and never complete
            env.command(["submit", "--cpus", "64", "--", "true"])

            def unsat_classified():
                out = json.loads(env.command(
                    ["task", "explain", "1.0", "--output-mode", "json"]
                ))
                return out.get("reason") == "no-matching-worker"

            try:
                wait_until(unsat_classified, timeout=20,
                           message="unsatisfiable task classified")
            except TimeoutError:
                failures.append(
                    "unsatisfiable task was not classified "
                    "no-matching-worker"
                )

            # job 2: satisfiable 200-task array — completes, solver ok
            env.command([
                "submit", "--array", "0-199", "--wait", "--", "true",
            ], timeout=120)

            dump = json.loads(env.command(
                ["server", "flight-recorder", "dump", "--json"]
            ))
            for rec in dump.get("ticks", []):
                trajectory.append({
                    "tick": rec["tick"],
                    "status": rec["solver"].get("status"),
                    "objective": rec["solver"].get("objective"),
                    "assigned": rec["counts"].get("assigned", 0),
                    "prefilled": rec["counts"].get("prefilled", 0),
                    "unplaced": rec["counts"].get("unplaced", 0),
                })
            reasons = {
                e["reason"]
                for rec in dump.get("ticks", [])
                for e in rec.get("unplaced", [])
            }
            if "no-matching-worker" not in reasons:
                failures.append(
                    "flight recorder never recorded no-matching-worker"
                )
            statuses = {t["status"] for t in trajectory}
            if "ok" not in statuses:
                failures.append(
                    f"no successful solve in the trajectory ({statuses})"
                )
            placed = sum(
                t["assigned"] + t["prefilled"] for t in trajectory
            )
            if placed < 200:
                failures.append(
                    f"assigned+prefilled sum to {placed} < the 200 "
                    "satisfiable tasks"
                )
            jobs = json.loads(env.command(
                ["job", "list", "--all", "--output-mode", "json"]
            ))
            sat = next(j for j in jobs if j["id"] == 2)
            if sat["status"] != "finished":
                failures.append(
                    f"satisfiable job status {sat['status']!r}"
                )
    print(json.dumps({
        "metric": "explain_smoke",
        "ok": not failures,
        "failures": failures,
        "value": round(time.perf_counter() - t0, 2),
        "unit": "s",
        "n_tick_records": len(trajectory),
        "solver_trajectory": trajectory[-40:],
    }))
    sys.exit(1 if failures else 0)


def run_throughput_smoke() -> None:
    """Scaled-down dask-comparison (200 x 8 ms sleeps, 4 lanes) against the
    in-process pool comparator AND this host's bare-spawn bound, so the
    `hq_vs_pool` ratio is tracked in every round's BENCH json. The ok gate
    uses the spawn-bound ratio: `hq_vs_pool` conflates dispatch overhead
    with the host's process-creation cost (an in-process pool never
    spawns), which varies ~100x between bare metal and container
    sandboxes — the floor-normalized ratio is the comparable number."""
    import os
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["HQ_BENCH_NO_DB"] = "1"  # scaled config: BENCH json only
    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from experiment_dask_comparison import measure_config, measure_spawn_floor

    n_tasks, seconds, cores = 200, 0.008, 4
    t0 = time.perf_counter()
    row = measure_config(n_tasks, seconds, cores, measure_spawn_floor())
    ratio_bound = row["hq_vs_spawn_bound"]
    failures = []
    if ratio_bound > 3.0:
        failures.append(
            f"hq_vs_spawn_bound {ratio_bound} > 3.0: dispatch overhead "
            "regressed far above this host's process-creation floor"
        )
    print(json.dumps({
        "metric": "throughput_smoke",
        "ok": not failures,
        "failures": failures,
        **{k: v for k, v in row.items() if k != "experiment"},
        "total_s": round(time.perf_counter() - t0, 2),
    }))
    sys.exit(1 if failures else 0)


def run_restore_smoke(args) -> None:
    """Bounded-restore gate (ISSUE 6): restore must be O(live state), not
    O(history).

    Synthesizes a journal of >= 1M completed tasks spread over many jobs
    plus one small live job, measures a FULL replay (the O(history)
    baseline), forgets the completed jobs, compacts (snapshot + GC —
    exactly the server's code path), and asserts the snapshot restore
    lands under 2 s with the journal GC'd to a bounded size. The row is
    recorded in benchmarks/results/db.jsonl so rounds are comparable."""
    import os
    import shutil
    import tempfile
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from common import emit

    from hyperqueue_tpu.events import snapshot as snapshot_mod
    from hyperqueue_tpu.events.journal import Journal
    from hyperqueue_tpu.events.restore import restore_from_journal
    from hyperqueue_tpu.server.bootstrap import Server

    n_tasks = args.tasks if args.tasks else 1_000_000
    n_jobs = max(n_tasks // 10_000, 1)
    per_job = n_tasks // n_jobs
    n_live = 5
    failures = []
    tmp = Path(tempfile.mkdtemp(prefix="hq-restore-smoke-"))
    try:
        journal = tmp / "journal.bin"
        t0 = time.perf_counter()
        j = Journal(journal)
        j.open_for_append()
        seq = 0

        def write(rec):
            nonlocal seq
            rec["seq"] = seq
            rec["time"] = 1_000.0 + seq * 1e-3
            seq += 1
            j.write(rec)

        write({"event": "server-uid", "server_uid": "bench-uid"})
        body = {"cmd": ["true"]}
        for job_id in range(1, n_jobs + 1):
            ids = list(range(per_job))
            write({"event": "job-submitted", "job": job_id,
                   "desc": {"name": f"bulk{job_id}",
                            "array": {"ids": ids, "body": body}}})
            for i in ids:
                write({"event": "task-started", "job": job_id, "task": i,
                       "instance": 0, "variant": 0, "workers": [1]})
                write({"event": "task-finished", "job": job_id, "task": i})
            write({"event": "job-completed", "job": job_id,
                   "status": "finished"})
        live_job = n_jobs + 1
        write({"event": "job-submitted", "job": live_job,
               "desc": {"name": "live",
                        "array": {"ids": list(range(n_live)),
                                  "body": body}}})
        j.close()
        journal_bytes = journal.stat().st_size
        synth_s = time.perf_counter() - t0

        # --- O(history) baseline: full replay of every event -----------
        t0 = time.perf_counter()
        server = Server(server_dir=tmp / "full", journal_path=journal)
        restore_from_journal(server)
        full_replay_s = time.perf_counter() - t0
        restored_tasks = sum(
            job.n_tasks() for job in server.jobs.jobs.values()
        )
        if restored_tasks != per_job * n_jobs + n_live:
            failures.append(
                f"full replay restored {restored_tasks} tasks, expected "
                f"{per_job * n_jobs + n_live}"
            )

        # --- forget the completed bulk, compact (server code path) ------
        for job_id in list(server.jobs.jobs):
            job = server.jobs.jobs[job_id]
            if job.is_terminated():
                del server.jobs.jobs[job_id]
        server.n_boots += 1  # as the running server would have counted
        server.journal_uids.add("bench-uid")
        state = snapshot_mod.capture_state(server)
        snapshot_mod.write_snapshot(journal, state)
        keep = set(server.jobs.jobs)
        stop_at = journal.stat().st_size
        gc_tmp = Path(str(journal) + ".gc")
        kept, dropped = Journal.gc_rewrite(
            journal, gc_tmp, keep, state["seq"], stop_at
        )
        Journal.gc_finalize(journal, gc_tmp, stop_at)
        journal_bytes_after = journal.stat().st_size
        snapshot_bytes = snapshot_mod.snapshot_path(journal).stat().st_size

        # --- O(live state) restore: snapshot + empty tail ---------------
        t0 = time.perf_counter()
        server2 = Server(server_dir=tmp / "snap", journal_path=journal)
        restore_from_journal(server2)
        restore_s = time.perf_counter() - t0
        if server2.last_restore["snapshot"] is None:
            failures.append("bounded restore did not use the snapshot")
        if len(server2.jobs.jobs) != 1 or (
            server2.jobs.jobs[live_job].n_tasks() != n_live
        ):
            failures.append(
                f"bounded restore state wrong: {server2.last_restore}"
            )
        if restore_s >= 2.0:
            failures.append(
                f"restore took {restore_s:.2f}s >= 2s after {n_tasks} "
                "completed+forgotten tasks — not O(live state)"
            )
        bound = 1 << 20
        if journal_bytes_after + snapshot_bytes >= bound:
            failures.append(
                f"journal+snapshot {journal_bytes_after + snapshot_bytes} "
                f"bytes >= {bound} after compaction — size not bounded"
            )
        if full_replay_s <= restore_s * 5:
            failures.append(
                f"full replay ({full_replay_s:.2f}s) is not demonstrably "
                f"O(history) vs the bounded restore ({restore_s:.3f}s)"
            )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    emit({
        "experiment": "restore_smoke",
        "metric": "restore_smoke",
        "ok": not failures,
        "failures": failures,
        "value": round(restore_s, 4),
        "unit": "s",
        "n_tasks": n_tasks,
        "n_jobs": n_jobs,
        "full_replay_s": round(full_replay_s, 3),
        "restore_s": round(restore_s, 4),
        "speedup": round(full_replay_s / max(restore_s, 1e-9), 1),
        "journal_bytes_before": journal_bytes,
        "journal_bytes_after": journal_bytes_after,
        "snapshot_bytes": snapshot_bytes,
        "gc_kept_records": kept,
        "gc_dropped_records": dropped,
        "synth_s": round(synth_s, 2),
    })
    sys.exit(1 if failures else 0)


def run_submit_smoke(args) -> None:
    """High-throughput submit-plane gate (ISSUE 10).

    Streams bulk array submits through the pipelined chunked ingest plane
    against a live server (journal on, one real worker executing tasks,
    plus a background trickle of small jobs keeping the scheduler
    ticking) and asserts:

    - sustained ingest >= 100k tasks/s (compact id_range chunks; an
      entries variant with per-task payloads is recorded alongside, like
      spawn_floor_ms, for honest cross-box comparison);
    - scheduler tick p95 DURING ingest within 10% (+3 ms 2-core-box noise
      floor) of the idle-ingest p95 — the connection plane must keep the
      reactor's tick latency flat;
    - a 1M-task array submit allocates O(chunks), not O(tasks),
      server-side at ingest (lazy store holds the tasks; only
      dispatch-driven materialization creates per-task records).
    """
    import json as _json
    import os
    import tempfile
    import threading
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from common import emit
    from utils_e2e import HqEnv

    from hyperqueue_tpu.client.connection import ClientSession, SubmitStream

    n_tasks = args.tasks if args.tasks else 1_000_000
    chunk = 16384
    failures = []
    results: dict = {}
    # The GATE runs ENCRYPTED (ISSUE 12): with the AEAD backend ladder
    # (transport/aead.py — native/numpy instead of the ~6 us/wire-byte
    # pure-python fallback) the sealed wire is the production
    # configuration, so the production configuration is what gets gated.
    # A plaintext burst run afterwards records the encrypted/plaintext
    # ratio as its own db.jsonl row, gated at ~15%.
    with tempfile.TemporaryDirectory() as td:
        with HqEnv(Path(td)) as env:
            env.start_server(
                "--journal", str(Path(td) / "journal.bin"),
            )
            env.start_worker(cpus=2)
            env.wait_workers(1)
            body = {"cmd": ["true"], "env": {},
                    "submit_dir": str(env.work_dir)}

            stop = threading.Event()

            def trickle() -> None:
                # small jobs at a steady cadence keep ticks flowing in
                # BOTH measurement windows
                with ClientSession(env.server_dir) as s:
                    i = 0
                    while not stop.is_set():
                        s.request({"op": "submit", "job": {
                            "name": f"trickle{i}",
                            "submit_dir": str(env.work_dir),
                            "tasks": [{"id": 0, "body": dict(body),
                                       "request": {}}],
                        }})
                        i += 1
                        stop.wait(0.05)

            th = threading.Thread(target=trickle, daemon=True)
            th.start()

            def tick_durations_after(tick_floor: int) -> list:
                dump = _json.loads(env.command(
                    ["server", "flight-recorder", "dump", "--json"]
                ))
                return [
                    t["duration_ms"] for t in dump.get("ticks", ())
                    if t.get("tick", 0) > tick_floor
                    and "duration_ms" in t
                ]

            def max_tick() -> int:
                dump = _json.loads(env.command(
                    ["server", "flight-recorder", "dump", "--json"]
                ))
                return max(
                    (t.get("tick", 0) for t in dump.get("ticks", ())),
                    default=0,
                )

            def p95(values: list) -> float:
                if not values:
                    return 0.0
                values = sorted(values)
                return values[min(len(values) - 1,
                                  int(0.95 * (len(values) - 1) + 0.5))]

            # --- pre-load a bulk backlog, THEN measure the idle window --
            # Both windows must schedule comparable work (prefill feeding
            # the worker from a deep backlog IS tick work, with or
            # without an active ingest); only then does the idle-vs-
            # during delta isolate the connection plane's perturbation.
            # (this unpaced preload doubles as the BURST ingest
            # measurement: how fast can one pipelined client stream a
            # whole n_tasks array in?)
            with ClientSession(env.server_dir) as s0:
                stream = SubmitStream(
                    s0, {"name": "preload",
                         "submit_dir": str(env.work_dir)}
                )
                t0 = time.perf_counter()
                for lo in range(0, n_tasks, chunk):
                    stream.send_chunk(array={
                        "id_range": [lo, min(lo + chunk, n_tasks)],
                        "body": body, "request": {},
                        "priority": 0, "crash_limit": 5,
                    })
                _job, preload_acked = stream.finish()
                burst_tasks_per_s = preload_acked / max(
                    time.perf_counter() - t0, 1e-9
                )
            time.sleep(1.0)  # settle
            idle_floor = max_tick()
            time.sleep(3.0)
            idle_ticks = tick_durations_after(idle_floor)
            idle_p95 = p95(idle_ticks)

            # --- sustained bulk ingest window (>= 3 s of streaming) -----
            ingest_floor = max_tick()
            # one OPEN stream appending chunks for the whole window (the
            # tentpole's open-job append path); a single job keeps the
            # backlog's priority-level shape identical to the idle
            # window, and the stream is PACED at ~1M tasks/s (10x the
            # 100k/s gate) so the window measures "tick latency at
            # sustained target ingest" rather than CPU contention from an
            # unpaced burst saturating this 2-core box (the burst rate is
            # the preload measurement above)
            total_bulk = 0
            ingest_s = 0.0
            with ClientSession(env.server_dir) as s2:
                stream = SubmitStream(
                    s2, {"name": "bulk", "submit_dir": str(env.work_dir)}
                )
                t0 = time.perf_counter()
                lo = 0
                while time.perf_counter() - t0 < 3.0:
                    stream.send_chunk(array={
                        "id_range": [lo, lo + chunk],
                        "body": body, "request": {},
                        "priority": 0, "crash_limit": 5,
                    })
                    lo += chunk
                    # pace to ~1M tasks/s
                    target = t0 + (lo / 1_000_000)
                    delay = target - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                _job, acked = stream.finish()
                total_bulk += acked
                ingest_s = time.perf_counter() - t0
            during_ticks = tick_durations_after(ingest_floor)
            during_p95 = p95(during_ticks)
            tasks_per_s = total_bulk / max(ingest_s, 1e-9)

            stats = _json.loads(env.command(
                ["server", "stats", "--output-mode", "json"]
            ))
            lazy = stats["ingest"]["lazy"]
            # per-plane/per-phase shares ride the row as metadata so
            # --regress can blame the guilty plane (ISSUE 19)
            prof_summary = profile_summary(stats)
            results.update(
                tasks_per_s=round(tasks_per_s, 1),
                burst_tasks_per_s=round(burst_tasks_per_s, 1),
                bulk_tasks=total_bulk,
                ingest_s=round(ingest_s, 3),
                chunks=lazy["chunks"],
                unmaterialized=lazy["unmaterialized"],
                materialized_total=lazy["materialized_total"],
                tick_p95_idle_ms=round(idle_p95, 3),
                tick_p95_ingest_ms=round(during_p95, 3),
                idle_tick_samples=len(idle_ticks),
                ingest_tick_samples=len(during_ticks),
                handoff_depth=stats["ingest"].get("handoff_depth", 0),
            )
            if tasks_per_s < 100_000:
                failures.append(
                    f"sustained ingest {tasks_per_s:,.0f} tasks/s < 100k"
                )
            # O(chunks) at ingest: per-task records may only come from
            # dispatch-driven materialization (bounded by what one worker
            # could possibly have been fed during the window), never from
            # ingest itself
            total_ingested = preload_acked + total_bulk
            if lazy["unmaterialized"] < 0.9 * total_ingested:
                failures.append(
                    f"only {lazy['unmaterialized']}/{total_ingested} "
                    "tasks left lazy after ingest — ingest is "
                    "materializing per-task records (O(tasks), not "
                    "O(chunks))"
                )
            budget = idle_p95 * 1.10 + 3.0  # 10% + 2-core-box noise floor
            if during_p95 > budget:
                failures.append(
                    f"tick p95 during ingest {during_p95:.2f} ms exceeds "
                    f"idle p95 {idle_p95:.2f} ms by more than 10% (+3 ms "
                    "noise floor)"
                )

            # --- entries variant (per-task payloads; recorded honestly
            # like spawn_floor_ms, not gated) -------------------------
            n_entries = min(n_tasks // 5, 200_000)
            with ClientSession(env.server_dir) as s3:
                stream = SubmitStream(
                    s3, {"name": "entries",
                         "submit_dir": str(env.work_dir)}
                )
                t0 = time.perf_counter()
                sent = 0
                echunk = 8192
                while sent < n_entries:
                    n = min(echunk, n_entries - sent)
                    stream.send_chunk(array={
                        "id_range": [sent, sent + n],
                        "entries": [f"payload-{sent + i}"
                                    for i in range(n)],
                        "body": body, "request": {},
                        "priority": 0, "crash_limit": 5,
                    })
                    sent += n
                _job, eacked = stream.finish()
                entries_s = time.perf_counter() - t0
            results["entries_tasks_per_s"] = round(
                eacked / max(entries_s, 1e-9), 1
            )
            from hyperqueue_tpu.transport.aead import WIRE_BACKEND

            results["transport"] = f"encrypted-{WIRE_BACKEND}"
            stop.set()
            th.join(timeout=5)

        # --- encrypted/plaintext ratio (ISSUE 12 satellite): the same
        # unpaced burst preload against a plaintext server; the sealed
        # wire must stay within ~15% of it on the native/numpy backends
        # (the pure-python fallback is exempt from the gate — it exists
        # for compatibility, and its ratio is recorded honestly) -------
        with HqEnv(Path(td) / "plain") as env2:
            env2.start_server(
                "--journal", str(Path(td) / "plain-journal.bin"),
                "--disable-client-authentication",
                "--disable-worker-authentication",
            )
            env2.start_worker(cpus=2)
            env2.wait_workers(1)
            body2 = {"cmd": ["true"], "env": {},
                     "submit_dir": str(env2.work_dir)}
            with ClientSession(env2.server_dir) as s4:
                stream = SubmitStream(
                    s4, {"name": "plain-burst",
                         "submit_dir": str(env2.work_dir)}
                )
                t0 = time.perf_counter()
                for lo in range(0, n_tasks, chunk):
                    stream.send_chunk(array={
                        "id_range": [lo, min(lo + chunk, n_tasks)],
                        "body": body2, "request": {},
                        "priority": 0, "crash_limit": 5,
                    })
                _job, plain_acked = stream.finish()
                plain_burst = plain_acked / max(
                    time.perf_counter() - t0, 1e-9
                )
        enc_ratio = results["burst_tasks_per_s"] / max(plain_burst, 1e-9)
        results["plaintext_burst_tasks_per_s"] = round(plain_burst, 1)
        results["encrypted_over_plaintext"] = round(enc_ratio, 4)
        ratio_failures = []
        from hyperqueue_tpu.transport.aead import WIRE_BACKEND as _WB

        if _WB != "python" and enc_ratio < 0.85:
            msg = (
                f"encrypted burst ingest is {enc_ratio:.2f}x plaintext "
                f"on the {_WB} backend (< 0.85 = outside the ~15% budget)"
            )
            ratio_failures.append(msg)
            failures.append(msg)
        emit({
            "experiment": "wire_encrypted_ratio",
            "metric": "encrypted_over_plaintext_burst",
            "ok": not ratio_failures,
            "failures": ratio_failures,
            "value": round(enc_ratio, 4),
            "unit": "x",
            "wire_backend": _WB,
            "encrypted_burst_tasks_per_s": results["burst_tasks_per_s"],
            "plaintext_burst_tasks_per_s": round(plain_burst, 1),
            "n_tasks": n_tasks,
        })
    emit({
        "experiment": "submit_smoke",
        "metric": "submit_smoke",
        "ok": not failures,
        "failures": failures,
        "value": results.get("tasks_per_s", 0.0),
        "unit": "tasks/s",
        "n_tasks": n_tasks,
        "profile": prof_summary,
        **results,
    })
    print("submit-smoke:", "OK" if not failures else failures)
    sys.exit(1 if failures else 0)


def run_trace_smoke() -> None:
    """Distributed-tracing gate (ISSUE 8): every task of a real-worker
    submit yields a complete CLOSED trace (all hops, span-sum <= wall),
    and the tracing plane costs <= 5% on the zero-worker dispatch path
    (measured traces-on vs --task-trace-capacity 0)."""
    import os
    import tempfile
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    from utils_e2e import HqEnv

    from hyperqueue_tpu.utils.trace import REQUIRED_HOPS

    failures = []
    t0 = time.perf_counter()
    n_tasks = 40

    # --- completeness: real worker, every trace closed with all hops ----
    with tempfile.TemporaryDirectory() as td:
        with HqEnv(Path(td)) as env:
            env.start_server()
            env.start_worker(cpus=4)
            env.wait_workers(1)
            env.command(
                ["submit", "--array", f"0-{n_tasks - 1}", "--wait",
                 "--", "true"],
                timeout=120,
            )
            incomplete = []
            sum_over_wall = []
            trace_ids = set()
            for i in range(n_tasks):
                out = json.loads(env.command(
                    ["task", "trace", f"1.{i}", "--output-mode", "json"]
                ))
                trace_ids.add(out["trace_id"])
                names = {s["name"] for s in out["spans"]}
                if not (out["closed"] and REQUIRED_HOPS <= names):
                    incomplete.append((i, sorted(REQUIRED_HOPS - names)))
                if out["span_sum_s"] > out["wall_s"] + 1e-6:
                    sum_over_wall.append(i)
            if incomplete:
                failures.append(
                    f"{len(incomplete)}/{n_tasks} tasks lack a complete "
                    f"closed trace (first: {incomplete[:3]})"
                )
            if sum_over_wall:
                failures.append(
                    f"span-sum exceeds wall time for tasks {sum_over_wall[:5]}"
                )
            if len(trace_ids) != 1:
                failures.append(
                    f"one submit produced {len(trace_ids)} trace ids"
                )

    # --- overhead: zero-worker dispatch, traces on vs off ---------------
    # interleaved best-of-two: scheduler-cadence noise on a loaded 2-core
    # sandbox swings single runs +-50%, so each config gets two timed
    # windows inside one warm server and the MIN is compared (the standard
    # floor-measurement trick from the dask comparator).
    #
    # The GATE runs ENCRYPTED (ISSUE 12): the AEAD backend ladder
    # (transport/aead.py) replaced the ~6 us/wire-byte pure-python seal
    # that used to drown the trace header's ~14 bytes/task in crypto, so
    # the sealed wire — the production configuration — is what gets
    # gated. The plaintext ratio is recorded informationally.
    def timed_run(extra_server_args, plaintext: bool) -> float:
        auth = (
            ("--disable-worker-authentication",
             "--disable-client-authentication")
            if plaintext else ()
        )
        with tempfile.TemporaryDirectory() as td:
            with HqEnv(Path(td)) as env:
                env.start_server(*auth, *extra_server_args)
                env.start_worker("--zero-worker", cpus=4)
                env.wait_workers(1)
                # warm-up (pool/plan caches, first-tick jit)
                env.command(["submit", "--array", "0-49", "--wait",
                             "--", "true"], timeout=120)
                best = float("inf")
                for _ in range(2):
                    t = time.perf_counter()
                    env.command(["submit", "--array", "0-499", "--wait",
                                 "--", "true"], timeout=180)
                    best = min(best, time.perf_counter() - t)
                return best

    off_flag = ("--task-trace-capacity", "0")
    on_s = min(timed_run((), False), timed_run((), False))
    off_s = min(timed_run(off_flag, False), timed_run(off_flag, False))
    on_plain_s = timed_run((), True)
    off_plain_s = timed_run(off_flag, True)
    ratio = on_s / max(off_s, 1e-9)
    plain_ratio = on_plain_s / max(off_plain_s, 1e-9)
    per_task_delta_ms = (on_s - off_s) / 500 * 1e3
    # the 5% gate, with an absolute floor so residual box noise cannot
    # fail a sub-0.1ms/task cost; the honest numbers are recorded anyway
    if ratio > 1.05 and per_task_delta_ms > 0.1:
        failures.append(
            f"tracing overhead {ratio:.3f}x ({per_task_delta_ms:.3f} "
            "ms/task) exceeds the 5% dispatch budget"
        )
    print(json.dumps({
        "metric": "trace_smoke",
        "ok": not failures,
        "failures": failures,
        "value": round(ratio, 4),
        "unit": "x",
        "n_tasks": n_tasks,
        "traces_on_s": round(on_s, 3),
        "traces_off_s": round(off_s, 3),
        "overhead_ratio": round(ratio, 4),
        "overhead_ms_per_task": round(per_task_delta_ms, 4),
        "plaintext_overhead_ratio": round(plain_ratio, 4),
        "wire_backend": __import__(
            "hyperqueue_tpu.transport.aead", fromlist=["WIRE_BACKEND"]
        ).WIRE_BACKEND,
        "note": (
            "gate runs encrypted (the production wire); the plaintext "
            "ratio is informational"
        ),
        "total_s": round(time.perf_counter() - t0, 2),
    }))
    sys.exit(1 if failures else 0)


def run_wire_smoke() -> None:
    """Wire-path micro-gate (ISSUE 12): µs/wire-byte to seal+open per
    available AEAD backend (transport/aead.py), recorded every round so
    the ~6 µs/wire-byte pure-python number stays tracked and a backend-
    selection regression (the box silently falling off the ladder) is
    caught at the source. Gate: the SELECTED backend seals 64 KiB frames
    under 1 µs/byte unless it IS the pure-python fallback."""
    import secrets
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from common import emit

    from hyperqueue_tpu.transport import aead

    sizes = (256, 4096, 65536)
    reps = {256: 60, 4096: 30, 65536: 8}
    backends: dict = {}
    for name in aead.available_backends():
        impl = aead.select_backend(name)[1]
        per_size = {}
        for size in sizes:
            key = secrets.token_bytes(32)
            nonce = secrets.token_bytes(12)
            data = secrets.token_bytes(size)
            obj = impl(key)
            ct = obj.encrypt(nonce, data, None)
            best_seal = best_open = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(reps[size]):
                    obj.encrypt(nonce, data, None)
                best_seal = min(
                    best_seal, (time.perf_counter() - t0) / reps[size]
                )
                t0 = time.perf_counter()
                for _ in range(reps[size]):
                    obj.decrypt(nonce, ct, None)
                best_open = min(
                    best_open, (time.perf_counter() - t0) / reps[size]
                )
            per_size[size] = {
                "seal_us_per_byte": round(best_seal / size * 1e6, 4),
                "open_us_per_byte": round(best_open / size * 1e6, 4),
            }
        backends[name] = per_size
    failures = []
    selected = aead.WIRE_BACKEND
    sel_64k = backends[selected][65536]["seal_us_per_byte"]
    if selected != "python" and sel_64k > 1.0:
        failures.append(
            f"selected backend {selected} seals 64KiB frames at "
            f"{sel_64k} us/byte (> 1.0) — the native wire path regressed"
        )
    emit({
        "experiment": "wire_smoke",
        "metric": "seal_us_per_byte_64k",
        "ok": not failures,
        "failures": failures,
        "value": sel_64k,
        "unit": "us/B",
        "wire_backend": selected,
        "backends": backends,
    })
    print("wire-smoke:", "OK" if not failures else failures)
    sys.exit(1 if failures else 0)


def run_saturation_smoke(args) -> None:
    """Multi-core server gate (ISSUE 12): with the ingest, journal and
    fan-out planes on their own threads and the wire encrypted, a
    saturated server must sustain MORE THAN ONE CORE of process CPU —
    the reactor is a pure scheduling loop, not the ceiling.

    Load: zero-workers churning completions (uplink decode + completion
    processing + journal commits + downlink fan-out), a subscriber
    consuming the task-event firehose (per-peer encode+seal), and two
    concurrent entries-heavy chunked ingest streams — every plane busy
    at once. Server CPU is read from /proc/<pid>/stat (utime+stime
    covers all threads), with the main-thread (reactor) vs off-loop
    split recorded.

    Box honesty: this bench box reports nproc=1 — NO process can exceed
    1.0 cores here, so on such boxes the >1-core gate is unmeasurable
    and the gate falls back to the property the refactor actually
    created: a substantial OFF-REACTOR share of server CPU (the
    pre-ISSUE-12 server ran ~95%+ of its cycles on the main thread).
    On a multi-core box the >1-core gate applies as written."""
    import os
    import tempfile
    import threading
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from common import emit
    from utils_e2e import HqEnv

    from hyperqueue_tpu.client.connection import (
        ClientSession,
        SubmitStream,
        subscribe,
    )
    from hyperqueue_tpu.transport.aead import WIRE_BACKEND

    hz = os.sysconf("SC_CLK_TCK")

    def cpu_seconds(pid: int) -> float:
        with open(f"/proc/{pid}/stat") as f:
            parts = f.read().rsplit(")", 1)[1].split()
        return (int(parts[11]) + int(parts[12])) / hz

    def thread_cpu(pid: int) -> dict:
        """tid -> cpu seconds. tid == pid is the main (reactor) thread;
        everything else is an off-loop plane (journal commit thread,
        ingest loop, fan-out senders, executor workers)."""
        out = {}
        try:
            for tid in os.listdir(f"/proc/{pid}/task"):
                with open(f"/proc/{pid}/task/{tid}/stat") as f:
                    raw = f.read()
                parts = raw.rsplit(")", 1)[1].split()
                out[tid] = (int(parts[11]) + int(parts[12])) / hz
        except OSError:
            pass
        return out

    n_tasks = 60_000
    n_cpus = os.cpu_count() or 1
    failures: list = []
    results: dict = {}
    with tempfile.TemporaryDirectory() as td:
        with HqEnv(Path(td)) as env:
            env.start_server(
                "--journal", str(Path(td) / "journal.bin"),
                "--fanout-senders", "4",
            )
            env.start_worker("--zero-worker", cpus=16)
            env.wait_workers(1)
            server_pid = env.processes[0][1].pid
            body = {"cmd": ["true"], "env": {},
                    "submit_dir": str(env.work_dir)}

            events_seen = [0]
            stop = threading.Event()

            def consume() -> None:
                try:
                    for frame in subscribe(
                        env.server_dir, filters=("task-", "job-")
                    ):
                        if frame.get("op") == "events":
                            events_seen[0] += len(frame["records"])
                        if stop.is_set():
                            return
                except Exception:  # noqa: BLE001 - teardown ends the feed
                    pass

            threading.Thread(target=consume, daemon=True).start()

            ingested = [0]
            # entries-heavy chunks: real per-task payloads, so every
            # plane does real per-byte work (client seal -> ingest open/
            # decode -> apply -> journal encode+write -> sealed ack);
            # one shared entries list keeps the CLIENT side cheap
            entries = [f"payload-{i:08d}-xxxxxxxxxxxxxxxx"
                       for i in range(4096)]

            def ingest_load(base: int) -> None:
                try:
                    with ClientSession(env.server_dir) as s:
                        stream = SubmitStream(
                            s, {"name": f"sat-ingest-{base}",
                                "submit_dir": str(env.work_dir)}
                        )
                        lo = base
                        while not stop.is_set():
                            stream.send_chunk(array={
                                "id_range": [lo, lo + 4096],
                                "entries": entries,
                                "body": body, "request": {},
                                "priority": -1, "crash_limit": 5,
                            })
                            ingested[0] += 4096
                            lo += 4096
                        stream.finish()
                except Exception:  # noqa: BLE001
                    pass

            # warm-up: pools, first ticks, jit
            env.command(
                ["submit", "--array", "0-499", "--wait", "--", "true"],
                timeout=180,
            )
            loads = [
                threading.Thread(target=ingest_load, args=(b,),
                                 daemon=True)
                for b in (10_000_000, 200_000_000)
            ]
            for th in loads:
                th.start()
            wall0 = time.perf_counter()
            cpu0 = cpu_seconds(server_pid)
            threads0 = thread_cpu(server_pid)
            env.command(
                ["submit", "--array", f"0-{n_tasks - 1}", "--wait",
                 "--", "true"],
                timeout=600,
            )
            wall = time.perf_counter() - wall0
            cpu = cpu_seconds(server_pid) - cpu0
            threads1 = thread_cpu(server_pid)
            stop.set()
            for th in loads:
                th.join(timeout=10)
            cores = cpu / max(wall, 1e-9)
            main_cpu = (
                threads1.get(str(server_pid), 0.0)
                - threads0.get(str(server_pid), 0.0)
            )
            off_loop_cpu = max(cpu - main_cpu, 0.0)
            off_share = off_loop_cpu / max(cpu, 1e-9)
            results.update(
                cores=round(cores, 3),
                server_cpu_s=round(cpu, 2),
                reactor_thread_cpu_s=round(main_cpu, 2),
                off_reactor_cpu_s=round(off_loop_cpu, 2),
                off_reactor_share=round(off_share, 3),
                nproc=n_cpus,
                wall_s=round(wall, 2),
                tasks=n_tasks,
                tasks_per_s=round(n_tasks / wall, 1),
                subscriber_events=events_seen[0],
                ingested_tasks=ingested[0],
                wire_backend=WIRE_BACKEND,
            )
            if n_cpus > 1:
                if cores <= 1.0:
                    failures.append(
                        f"server sustained {cores:.2f} cores (<= 1.0 "
                        f"with {n_cpus} CPUs): the planes are not "
                        "parallelizing"
                    )
            else:
                # 1-CPU box: >1 core is unmeasurable for ANY process;
                # gate the structural property instead and say so
                results["note"] = (
                    "nproc=1 box: the >1-core gate is unmeasurable "
                    "here; gating the off-reactor CPU share instead "
                    "(single-threaded baseline is ~0.05)"
                )
                if off_share < 0.25:
                    failures.append(
                        f"off-reactor share {off_share:.2f} < 0.25: the "
                        "journal/fanout/ingest planes are not carrying "
                        "their weight off the main thread"
                    )
    emit({
        "experiment": "saturation_smoke",
        "metric": "server_cores",
        "ok": not failures,
        "failures": failures,
        "value": results.get("cores", 0.0),
        "unit": "cores",
        **results,
    })
    print("saturation-smoke:", "OK" if not failures else failures)
    sys.exit(1 if failures else 0)



def run_sim_smoke(args) -> None:
    """Deterministic-simulator gate (ISSUE 14).

    Three parts, all on the virtual clock in THIS process (no spawns):
    a determinism pair (same seed twice -> bit-identical decision-record
    and journal digests), a scenario sweep over the synthetic workload
    shapes under seeded fault schedules, and the acceptance-scale soak —
    >= 100k virtual tasks on >= 1k simulated workers with a server
    kill -9 + restore and worker churn in the schedule, required to
    quiesce with every invariant green inside the 5-wall-minute budget.
    Records virtual-tasks-per-wall-second and per-scenario rows."""
    import os
    from pathlib import Path as _Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(_Path(__file__).resolve().parent / "benchmarks"))
    from common import emit

    from hyperqueue_tpu.sim import FaultEvent, FaultSchedule, build
    from hyperqueue_tpu.sim.harness import run_scenario

    failures = []
    t_wall = time.perf_counter()

    # --- determinism pair -------------------------------------------
    def det_run():
        wl = build("bursty", seed=42, n_tenants=3, bursts_per_tenant=2,
                   tasks_per_burst=80, window=25)
        faults = FaultSchedule(seed=42, events=[
            FaultEvent(at=5.0, kind="server_kill", delay=1.0),
            FaultEvent(at=11.0, kind="worker_kill", target="w3", delay=1.0),
        ])
        return run_scenario(wl, seed=42, n_workers=12, faults=faults)

    d1, d2 = det_run(), det_run()
    det_ok = (d1.decision_digest == d2.decision_digest
              and d1.journal_digest == d2.journal_digest)
    if not det_ok:
        failures.append("same-seed runs diverged (decision/journal digest)")

    # --- scenario sweep ---------------------------------------------
    scenarios = []
    for name, kwargs, workers in (
        ("dag", dict(layers=8, width=16), 8),
        ("gang", dict(n_gangs=6, gang_size=3, filler_tasks=300), 12),
        ("tail", dict(n_tasks=800), 12),
    ):
        wl = build(name, seed=7, **kwargs)
        names = [f"w{i}" for i in range(workers)]
        faults = FaultSchedule.generate(
            7, horizon=40.0, worker_names=names, rate=0.03, server_kills=1,
        )
        try:
            res = run_scenario(wl, seed=7, n_workers=workers, faults=faults)
            scenarios.append({
                "workload": res.workload, "n_tasks": res.n_tasks,
                "makespan_virtual_s": round(res.makespan, 2),
                "wall_s": round(res.wall_s, 2),
                "server_boots": res.server_boots,
                "finished": res.audit["finished"],
            })
            if res.audit["finished"] != wl.n_tasks:
                failures.append(f"{name}: lost tasks")
        except Exception as e:  # noqa: BLE001 - recorded as a failure
            failures.append(f"{name}: {type(e).__name__}: {e}")

    # --- fused-solve A/B (ISSUE 16): the same seeded workload under the
    # host-greedy baseline and the fused gang/lookahead scheduler.  The
    # gang-heavy and stress-dag rows are GATES: fused makespan must not
    # exceed the host baseline, every gang must start atomically (the
    # monitor's gang-atomicity invariant + the gang_starts count), and
    # fused tick p95 must stay inside the north-star budget. ---
    ab_rows = []
    ab_specs = (
        ("gang-heavy", "gang",
         dict(n_gangs=8, gang_size=4, filler_tasks=600), 8, 11, True),
        ("stress-dag", "dag", dict(layers=12, width=30), 8, 5, True),
        ("tail", "tail", dict(n_tasks=800), 12, 7, False),
    )
    for label, name, kwargs, workers, seed, gated in ab_specs:
        wl = build(name, seed=seed, **kwargs)
        try:
            base = run_scenario(wl, seed=seed, n_workers=workers,
                                scheduler="greedy-numpy")
            fused = run_scenario(wl, seed=seed, n_workers=workers,
                                 scheduler="greedy-fused")
        except Exception as e:  # noqa: BLE001 - recorded as a failure
            failures.append(f"ab:{label}: {type(e).__name__}: {e}")
            continue
        ticks = sorted(fused.tick_ms)
        p95 = ticks[min(int(len(ticks) * 0.95), len(ticks) - 1)] \
            if ticks else 0.0
        row = {
            "workload": label, "n_tasks": wl.n_tasks,
            "makespan_host_s": round(base.makespan, 2),
            "makespan_fused_s": round(fused.makespan, 2),
            "fused_vs_host": round(fused.makespan / base.makespan, 4)
            if base.makespan else 0.0,
            "gang_starts": fused.audit.get("gang_starts", 0),
            "fused_tick_p95_ms": round(p95, 3),
        }
        ab_rows.append(row)
        if gated and fused.makespan > base.makespan + 1e-6:
            failures.append(
                f"ab:{label}: fused makespan {fused.makespan:.2f}s > "
                f"host baseline {base.makespan:.2f}s"
            )
        if gated and p95 > 50.0:
            failures.append(
                f"ab:{label}: fused tick p95 {p95:.1f}ms > 50ms budget"
            )
        if name == "gang" and \
                fused.audit.get("gang_starts", 0) != kwargs["n_gangs"]:
            failures.append(
                f"ab:{label}: expected {kwargs['n_gangs']} atomic gang "
                f"starts, saw {fused.audit.get('gang_starts', 0)}"
            )

    # --- journal replay --compare-scheduler row (sim/replay.py): record
    # a gang run's journal, rebuild the workload from it, A/B the
    # schedulers on the replay ---
    replay_row = {}
    import shutil as _shutil
    import tempfile as _tempfile

    from hyperqueue_tpu.sim.replay import replay_compare

    jdir = _Path(_tempfile.mkdtemp(prefix="hq-sim-replay-"))
    try:
        wl = build("gang", seed=3, n_gangs=4, gang_size=3,
                   filler_tasks=150)
        run_scenario(wl, seed=3, n_workers=9, server_dir=jdir)
        cmp_res = replay_compare(
            jdir / "journal.bin", "greedy-numpy", "greedy-fused",
            seed=3, n_workers=9,
        )
        replay_row = {
            "makespan_host_s": round(cmp_res.makespan_a, 2),
            "makespan_fused_s": round(cmp_res.makespan_b, 2),
            "assigned_host": cmp_res.assigned_a,
            "assigned_fused": cmp_res.assigned_b,
            "summary": cmp_res.summary(),
        }
    except Exception as e:  # noqa: BLE001 - recorded as a failure
        failures.append(f"replay-compare: {type(e).__name__}: {e}")
    finally:
        _shutil.rmtree(jdir, ignore_errors=True)

    # --- acceptance soak: 100k tasks / 1k workers / kill -9 + churn --
    n_tasks = args.sim_tasks
    n_workers = args.sim_workers
    wl = build("uniform", seed=1, n_tasks=n_tasks, dur_ms=20_000)
    events = [FaultEvent(at=30.0, kind="server_kill", delay=2.0)]
    for i, t in ((1, 12.0), (7, 18.0), (13, 44.0), (200, 51.0),
                 (400, 60.0), (650, 70.0)):
        events.append(FaultEvent(
            at=t, kind="worker_kill", target=f"w{i % n_workers}", delay=2.0,
        ))
    soak_row = {}
    try:
        res = run_scenario(
            wl, seed=1, n_workers=n_workers, worker_cpus=4,
            faults=FaultSchedule(seed=1, events=events),
            horizon=4 * 3600.0, schedule_min_delay=0.5,
        )
        soak_row = {
            "n_tasks": res.n_tasks, "n_workers": n_workers,
            "makespan_virtual_s": round(res.makespan, 1),
            "wall_s": round(res.wall_s, 1),
            "virtual_tasks_per_wall_s": round(
                res.virtual_tasks_per_wall_s, 1
            ),
            "server_boots": res.server_boots,
            "executions": res.audit["executions"],
            "finished": res.audit["finished"],
        }
        if res.audit["finished"] != n_tasks:
            failures.append("soak lost tasks")
        if res.server_boots < 2:
            failures.append("soak never exercised kill -9 + restore")
        if res.wall_s > 300.0:
            failures.append(
                f"soak took {res.wall_s:.0f}s wall (> 300s budget)"
            )
    except Exception as e:  # noqa: BLE001 - recorded as a failure
        failures.append(f"soak: {type(e).__name__}: {e}")

    emit({
        "experiment": "sim_smoke",
        "metric": "virtual_tasks_per_wall_s",
        "value": soak_row.get("virtual_tasks_per_wall_s", 0.0),
        "unit": "tasks/s",
        "params": {
            "tasks": n_tasks, "workers": n_workers,
            "fault_schedule": "kill9+churn", "wall_budget_s": 300,
        },
        "determinism_ok": det_ok,
        "soak": soak_row,
        "scenarios": scenarios,
        "ab": ab_rows,
        "replay_compare": replay_row,
        "ok": not failures,
        "failures": failures,
        "wall_s": round(time.perf_counter() - t_wall, 2),
    })
    # --- regression gate: the row just stored vs its prior rows ------
    if not os.environ.get("HQ_BENCH_NO_DB"):
        try:
            checked, regs = check_regressions(experiment="sim_smoke")
            if regs:
                failures.append(
                    f"regress: {len(regs)} metric(s) >20% worse than "
                    f"their stored baselines: {regs}"
                )
            else:
                print(f"# regress: OK ({checked} sim_smoke metric(s) "
                      f"within 20% of baseline)", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - recorded as a failure
            failures.append(f"regress: {type(e).__name__}: {e}")
    print("sim-smoke:", "OK" if not failures else failures)
    sys.exit(1 if failures else 0)


def run_policy_smoke(args) -> None:
    """Weighted-objective gate (ISSUE 20): the policy brain A/B'd in the
    simulator, flat placement-count objective vs heterogeneity weights +
    runtime prediction + fairness, on the same seeded workloads.

    Legs:

    1. Model-level weighted-kernel soak: numpy twin vs the jax device
       path (resident state + paranoid fresh-solve cross-check every
       tick) on the same affinity matrix, including zero-weight hard
       exclusions — counts must be bitwise identical and excluded
       (batch, worker) pairs must never place.
    2. Bursty multi-tenant A/B (opt-in per-tenant duration scales):
       weighted makespan must be STRICTLY better and the time-averaged
       Jain fairness index must improve.
    3. Straggler-tail A/B (opt-in split long job): the weighted leg's
       predictor is seeded OFFLINE from the flat leg's journal (PR 14
       replay), and the LPT boost must strictly beat the flat makespan.
    4. Stress-dag A/B under a worker-group affinity matrix: weighted
       makespan must not regress.

    Weighted tick p95 must stay inside the 50 ms north-star budget on
    every leg. One db.jsonl row per scenario (with the PR 19 per-phase
    profile summary as blame metadata), auto-gated by --regress."""
    import os
    import tempfile
    from pathlib import Path as _Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(_Path(__file__).resolve().parent / "benchmarks"))
    from common import emit

    from hyperqueue_tpu.models.greedy import GreedyCutScanModel
    from hyperqueue_tpu.sim import build
    from hyperqueue_tpu.sim.harness import run_scenario

    failures = []
    t_wall = time.perf_counter()

    # --- leg 1: weighted kernel, numpy twin vs resident device path --
    free, nt_free, lifetime, needs, sizes, min_time, _sc = build_instance(
        n_workers=64, n_tasks=2000, n_b=16
    )
    n_b, n_w = needs.shape[0], free.shape[0]
    rng = np.random.default_rng(7)
    affinity = rng.choice(
        [0.5, 1.0, 2.0], size=(n_b, n_w)
    ).astype(np.float32)
    affinity[:2, :8] = 0.0  # zero weight = hard exclusion
    needs64 = needs.astype(np.int64)
    host = GreedyCutScanModel(backend="numpy")
    dev = GreedyCutScanModel(backend="jax")
    dev.paranoid_resident = 1  # fresh-solve cross-check every tick
    f, nt = free.copy(), nt_free.copy()
    soak_ticks = 0
    try:
        for tick in range(5):
            kwargs = dict(lifetime=lifetime, needs=needs, sizes=sizes,
                          min_time=min_time, affinity=affinity)
            a = host.solve(free=f.copy(), nt_free=nt.copy(), **kwargs)
            b = dev.solve(free=f.copy(), nt_free=nt.copy(), **kwargs)
            if not np.array_equal(a, b):
                failures.append(
                    f"soak tick {tick}: weighted numpy counts diverge "
                    f"from the device path"
                )
                break
            if a[:2, :, :8].any():
                failures.append(
                    f"soak tick {tick}: zero-weight workers received "
                    f"placements"
                )
                break
            used = np.einsum("bvw,bvr->wr", a.astype(np.int64), needs64)
            f = (f - used).astype(np.int32)
            nt = (nt - a.sum(axis=(0, 1))).astype(np.int32)
            f[tick % n_w] = free[tick % n_w]
            nt[tick % n_w] = nt_free[tick % n_w]
            soak_ticks += 1
    except Exception as e:  # noqa: BLE001 - recorded as a failure
        failures.append(f"soak: {type(e).__name__}: {e}")
    if soak_ticks and not dev.paranoid_checks:
        failures.append("soak: resident paranoid check never engaged")

    # --- A/B legs: flat objective vs the weighted policy -------------
    def write_toml(path, text):
        path.write_text(text)
        return str(path)

    def tick_p95(res) -> float:
        ticks = sorted(res.tick_ms)
        if not ticks:
            return 0.0
        return ticks[min(int(len(ticks) * 0.95), len(ticks) - 1)]

    rows = []
    with tempfile.TemporaryDirectory(prefix="hq-policy-") as td:
        tmp = _Path(td)
        # the flat leg still loads a (no-op) policy so both sides record
        # the same Jain fairness telemetry through the same code path
        flat_toml = write_toml(tmp / "flat.toml", "[fairness]\n"
                               "enabled = false\n")
        specs = []
        # bursty multi-tenant, heterogeneous per-tenant durations, all
        # bursts landing at once on a SATURATED pool (backlog far beyond
        # the prefill budgets, so the boosted batch order decides which
        # tenant's work flows to the workers every refill tick): fairness
        # + prediction must strictly improve makespan AND Jain
        specs.append(dict(
            label="bursty-hetero",
            wl=lambda: build("bursty", seed=11, n_tenants=4,
                             bursts_per_tenant=2, tasks_per_burst=150,
                             window=0.0,
                             tenant_dur_scales=[0.25, 4.0, 1.0, 0.5]),
            workers=2, groups=1, seed=11, strict=True, jain_gate=True,
            policy="[fairness]\nenabled = true\nmax_boost = 8\n"
                   "[prediction]\nenabled = true\nmax_boost = 2\n"
                   "ewma_alpha = 0.3\nseed_journal = \"{journal}\"\n",
        ))
        # straggler tail, long tasks as their own job: the journal-seeded
        # LPT boost must start the tail first and strictly win
        specs.append(dict(
            label="straggler-tail",
            wl=lambda: build("tail", seed=5, n_tasks=500, split_long=True),
            workers=8, groups=1, seed=5, strict=True, jain_gate=False,
            policy="[prediction]\nenabled = true\nmax_boost = 4\n"
                   "ewma_alpha = 0.3\nseed_journal = \"{journal}\"\n",
        ))
        # stress dag under a worker-group affinity matrix: reordering
        # the water-fill must never cost makespan
        specs.append(dict(
            label="stress-dag",
            wl=lambda: build("dag", seed=9, layers=8, width=16),
            workers=8, groups=2, seed=9, strict=False, jain_gate=False,
            policy="[affinity.\"cpus\"]\n\"g0\" = 2.0\n\"*\" = 1.0\n",
        ))
        for spec in specs:
            label = spec["label"]
            flat_dir = tmp / f"{label}-flat"
            flat_dir.mkdir()
            try:
                flat = run_scenario(
                    spec["wl"](), seed=spec["seed"],
                    n_workers=spec["workers"],
                    worker_groups=spec["groups"],
                    scheduler="greedy-fused", server_dir=flat_dir,
                    server_kwargs={"policy_file": flat_toml},
                )
                policy_toml = write_toml(
                    tmp / f"{label}.toml",
                    spec["policy"].format(
                        journal=flat_dir / "journal.bin"
                    ),
                )
                weighted = run_scenario(
                    spec["wl"](), seed=spec["seed"],
                    n_workers=spec["workers"],
                    worker_groups=spec["groups"],
                    scheduler="greedy-fused",
                    server_kwargs={"policy_file": policy_toml},
                )
            except Exception as e:  # noqa: BLE001 - recorded
                failures.append(f"{label}: {type(e).__name__}: {e}")
                continue
            p95 = tick_p95(weighted)
            jain_flat = ((flat.policy or {}).get("jain") or {}).get("avg")
            jain_w = (
                (weighted.policy or {}).get("jain") or {}
            ).get("avg")
            row = {
                "experiment": "policy_smoke",
                "workload": label,
                "scheduler": "greedy-fused",
                "metric": "weighted_makespan_s",
                "unit": "s",
                "value": round(weighted.makespan, 2),
                "makespan_flat_s": round(flat.makespan, 2),
                "weighted_vs_flat": round(
                    weighted.makespan / flat.makespan, 4
                ) if flat.makespan else 0.0,
                "jain_flat": jain_flat,
                "jain_weighted": jain_w,
                "tick_p95_ms": round(p95, 3),
                "policy": weighted.policy,
                "profile": {"planes": {}, "phases": weighted.tick_shares},
            }
            rows.append(row)
            if weighted.makespan > flat.makespan + 1e-6:
                failures.append(
                    f"{label}: weighted makespan {weighted.makespan:.2f}s"
                    f" > flat {flat.makespan:.2f}s"
                )
            elif spec["strict"] and not (
                weighted.makespan < flat.makespan - 1e-6
            ):
                failures.append(
                    f"{label}: weighted makespan {weighted.makespan:.2f}s"
                    f" not strictly better than flat "
                    f"{flat.makespan:.2f}s"
                )
            if spec["jain_gate"]:
                if jain_flat is None or jain_w is None:
                    failures.append(f"{label}: Jain telemetry missing")
                elif jain_w <= jain_flat:
                    failures.append(
                        f"{label}: Jain {jain_w} did not improve on "
                        f"flat {jain_flat}"
                    )
            if p95 > 50.0:
                failures.append(
                    f"{label}: weighted tick p95 {p95:.1f}ms > 50ms "
                    f"budget"
                )
            pred = ((weighted.policy or {}).get("prediction") or {})
            if "seed_journal" in spec["policy"] and not pred.get(
                "observations", 0
            ):
                failures.append(
                    f"{label}: predictor never observed a runtime "
                    f"(policy={weighted.policy})"
                )
    for row in rows:
        row["ok"] = not failures
        row["failures"] = failures
        emit(row)
    emit({
        "experiment": "policy_smoke",
        "metric": "policy_soak_ticks",
        "value": soak_ticks,
        "unit": "ticks",
        "paranoid_checks": dev.paranoid_checks,
        "ok": not failures,
        "failures": failures,
        "wall_s": round(time.perf_counter() - t_wall, 2),
    })
    # --- regression gate: the rows just stored vs their prior rows ---
    if not os.environ.get("HQ_BENCH_NO_DB"):
        try:
            checked, regs = check_regressions(experiment="policy_smoke")
            if regs:
                failures.append(
                    f"regress: {len(regs)} metric(s) >20% worse than "
                    f"their stored baselines: {regs}"
                )
            else:
                print(f"# regress: OK ({checked} policy_smoke metric(s) "
                      f"within 20% of baseline)", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - recorded as a failure
            failures.append(f"regress: {type(e).__name__}: {e}")
    print("policy-smoke:", "OK" if not failures else failures)
    sys.exit(1 if failures else 0)


def run_profile_smoke(args) -> None:
    """Continuous-profiling gate (ISSUE 19). Four legs:

    1. overhead: encrypted submit bursts against a server sampling at
       19 Hz vs one at ``--profile-hz 0``, interleaved best-of-3 — the
       always-on sampler must cost <= 5% of burst ingest throughput;
    2. artifacts: `hq server profile` returns non-empty folded stacks
       (written next to the run) and `hq server trace export` carries
       the per-plane ``cpu <plane>`` Perfetto counter track;
    3. profile-on-stall: a chaos solve-delay blows --stall-budget and
       the auto-dump's attached profile burst names the solve plane;
    4. blame: a deliberately grown plane share in a throwaway result db
       makes check_regressions blame exactly that plane.
    """
    import json as _json
    import os
    import tempfile
    import shutil
    from pathlib import Path

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmarks"))
    from common import emit
    from utils_e2e import HqEnv, wait_until

    from hyperqueue_tpu.client.connection import ClientSession, SubmitStream

    n_tasks = min(args.tasks or 200_000, 200_000)
    chunk = 16384
    trials = 3
    failures = []
    results: dict = {}   # numeric, stable -> stored values in db.jsonl
    diag: dict = {}      # volatile lists/dicts -> printed, never stored
    prof_summary = None
    artifact_dir = Path(tempfile.mkdtemp(prefix="hq-profile-smoke-"))
    t_wall = time.perf_counter()

    def burst(env, name: str) -> float:
        """One encrypted burst submit; returns tasks/s."""
        body = {"cmd": ["true"], "env": {},
                "submit_dir": str(env.work_dir)}
        with ClientSession(env.server_dir) as s:
            stream = SubmitStream(
                s, {"name": name, "submit_dir": str(env.work_dir)}
            )
            t0 = time.perf_counter()
            for lo in range(0, n_tasks, chunk):
                stream.send_chunk(array={
                    "id_range": [lo, min(lo + chunk, n_tasks)],
                    "body": body, "request": {},
                    "priority": 0, "crash_limit": 5,
                })
            _job, acked = stream.finish()
            return acked / max(time.perf_counter() - t0, 1e-9)

    with tempfile.TemporaryDirectory() as td:
        tmp = Path(td)
        # --- leg 1: overhead, interleaved trials against two live
        # servers (identical but for --profile-hz); no workers — the
        # burst measures the ingest plane, and execution CPU would be
        # noise both sides pay anyway
        with HqEnv(tmp / "on") as env_on, HqEnv(tmp / "off") as env_off:
            env_on.start_server("--profile-hz", "19")
            env_off.start_server("--profile-hz", "0")
            on_rates, off_rates = [], []
            for i in range(trials):
                on_rates.append(burst(env_on, f"on{i}"))
                off_rates.append(burst(env_off, f"off{i}"))
            best_on, best_off = max(on_rates), max(off_rates)
            overhead = 1.0 - best_on / max(best_off, 1e-9)
            results.update(
                profiled_burst_tasks_per_s=round(best_on, 1),
                unprofiled_burst_tasks_per_s=round(best_off, 1),
                overhead_frac=round(overhead, 4),
            )
            if overhead > 0.05:
                failures.append(
                    f"sampling profiler costs {overhead * 100:.1f}% of "
                    "burst ingest throughput (> 5% budget)"
                )

            # --- leg 2: artifacts off the profiling server -----------
            folded = env_on.command(["server", "profile"])
            folded_lines = [
                ln for ln in folded.splitlines()
                if ln and not ln.startswith("#")
            ]
            planes_seen = {ln.split(";", 1)[0] for ln in folded_lines}
            results["folded_stacks"] = len(folded_lines)
            diag["folded_planes"] = sorted(planes_seen)
            if not folded_lines:
                failures.append("`hq server profile` returned no stacks")
            if "reactor" not in planes_seen:
                failures.append(
                    "folded stacks carry no reactor-plane samples: "
                    f"{sorted(planes_seen)}"
                )
            (artifact_dir / "profile.folded").write_text(folded)

            trace_path = artifact_dir / "trace.json"
            env_on.command(["server", "trace", "export", str(trace_path)])
            trace = _json.loads(trace_path.read_text())
            cpu_events = [
                e for e in trace.get("traceEvents", ())
                if e.get("ph") == "C"
                and str(e.get("name", "")).startswith("cpu ")
            ]
            results["trace_cpu_counter_events"] = len(cpu_events)
            if not cpu_events:
                failures.append(
                    "trace export carries no profiler cpu counter track"
                )
            stats_on = _json.loads(env_on.command(
                ["server", "stats", "--output-mode", "json"]
            ))
            prof_summary = profile_summary(stats_on)
            if not (stats_on.get("profile") or {}).get("enabled"):
                failures.append("server stats reports the profiler off")

        # --- leg 3: profile-on-stall (chaos solve-delay) -------------
        plan = json.dumps({"rules": [
            {"site": "solve", "action": "delay", "delay_ms": 600, "at": 1}
        ]})
        with HqEnv(tmp / "stall") as env:
            env.start_server("--stall-budget", "0.15",
                             env_extra={"HQ_FAULT_PLAN": plan})
            env.start_worker("--zero-worker", cpus=4)
            env.wait_workers(1)
            env.command(["submit", "--array", "0-3", "--wait", "--",
                         "true"], timeout=60)

            def stalled():
                stats = _json.loads(env.command(
                    ["server", "stats", "--output-mode", "json"]
                ))
                return (stats["stalls"]["captured"] >= 1
                        and stats["stalls"])

            stalls = wait_until(stalled, timeout=20,
                                message="stall capture")
            dump = _json.loads(Path(stalls["last"]["dump"]).read_text())
            stall_planes = {
                row["plane"] for row in dump.get("profile", ())
            }
            diag["stall_profile_planes"] = sorted(stall_planes)
            if "solve" not in stall_planes:
                failures.append(
                    "stall dump's profile burst has no solve-plane "
                    f"stack (saw {sorted(stall_planes)})"
                )
            shutil.copy(stalls["last"]["dump"],
                        artifact_dir / "stall-dump.json")

        # --- leg 4: regression blame on a throwaway db ---------------
        from database import Database

        demo_db = tmp / "blame-db.jsonl"
        db = Database(demo_db)
        base_prof = {"planes": {"reactor": 0.5, "solve": 0.2},
                     "phases": {"solve_dispatch": 0.3, "mapping": 0.2}}
        slow_prof = {"planes": {"reactor": 0.5, "solve": 0.8},
                     "phases": {"solve_dispatch": 0.7, "mapping": 0.1}}
        for _ in range(3):
            db.store_emit(
                {"experiment": "profile_blame_demo",
                 "metric": "demo_tick_ms", "unit": "ms", "value": 10.0},
                metadata={"profile": base_prof},
            )
        db.store_emit(
            {"experiment": "profile_blame_demo",
             "metric": "demo_tick_ms", "unit": "ms", "value": 25.0},
            metadata={"profile": slow_prof},
        )
        _checked, regs = check_regressions(
            experiment="profile_blame_demo", db_path=demo_db
        )
        blame = (regs[0].get("blame") or {}) if regs else {}
        diag["blame"] = blame
        if not regs:
            failures.append(
                "blame demo: deliberately slowed row did not trip the "
                "regression gate"
            )
        elif blame.get("name") != "solve":
            failures.append(
                "blame demo: the deliberately grown solve plane was not "
                f"blamed (got {blame})"
            )

    emit({
        "experiment": "profile_smoke",
        "metric": "profiled_burst_tasks_per_s",
        "ok": not failures,
        "failures": failures,
        "value": results.get("profiled_burst_tasks_per_s", 0.0),
        "unit": "tasks/s",
        "n_tasks": n_tasks,
        "profile": prof_summary,
        **results,
    })
    print(f"# diag: {json.dumps(diag)}", file=sys.stderr)
    print(f"# artifacts: {artifact_dir}/profile.folded, "
          f"{artifact_dir}/trace.json, {artifact_dir}/stall-dump.json",
          file=sys.stderr)
    if not os.environ.get("HQ_BENCH_NO_DB"):
        try:
            checked, regs = check_regressions(experiment="profile_smoke")
            if regs:
                failures.append(
                    f"regress: {len(regs)} metric(s) >20% worse than "
                    f"their stored baselines: {regs}"
                )
            else:
                print(f"# regress: OK ({checked} profile_smoke metric(s) "
                      f"within 20% of baseline)", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - recorded as a failure
            failures.append(f"regress: {type(e).__name__}: {e}")
    print("profile-smoke:", "OK" if not failures else failures)
    sys.exit(1 if failures else 0)


# --- result-db regression gate (ISSUE 16) ------------------------------
# Metric direction heuristics: a regression is movement in the BAD
# direction; metrics whose direction the name/unit doesn't reveal are
# skipped rather than guessed.
_HIGHER_BETTER = ("per_s", "per_wall", "tasks_per", "throughput",
                  "vs_baseline", "speedup", "ratio_vs")
_LOWER_BETTER = ("_ms", "_s", "latency", "makespan", "wall", "overhead",
                 "p95", "p99", "restore")


def _metric_direction(name: str, unit: str = "") -> int:
    """+1 higher-is-better, -1 lower-is-better, 0 unknown (skipped)."""
    n = str(name).lower()
    for hint in _HIGHER_BETTER:
        if hint in n:
            return 1
    u = str(unit or "").lower()
    if "/s" in u or "per s" in u:
        return 1
    if u in ("ms", "us", "s", "seconds", "secs"):
        return -1
    for hint in _LOWER_BETTER:
        if hint in n:
            return -1
    return 0


def profile_summary(stats: dict) -> dict | None:
    """Compact per-plane/per-phase share summary from one `hq server
    stats` payload — stored as row metadata so `--regress` can BLAME a
    regression (ISSUE 19): name the plane/phase whose share grew most
    instead of reporting one opaque wall-clock number."""
    planes = {
        plane: row.get("cpu", 0.0)
        for plane, row in ((stats.get("profile") or {}).get("planes")
                           or {}).items()
    }
    phases = stats.get("tick_shares") or {}
    if not planes and not phases:
        return None
    return {"planes": planes, "phases": phases}


def _blame_from_profiles(cur_profile: dict | None,
                         base_profiles: list) -> dict | None:
    """Name the plane/phase whose share grew most between the newest
    row's profile summary and the median of the prior rows'."""
    import statistics

    if not cur_profile or not base_profiles:
        return None
    best = None
    for kind in ("planes", "phases"):
        cur = cur_profile.get(kind) or {}
        for name, share in cur.items():
            priors = [
                p[kind][name] for p in base_profiles
                if isinstance((p or {}).get(kind), dict)
                and isinstance(p[kind].get(name), (int, float))
            ]
            if not priors or not isinstance(share, (int, float)):
                continue
            grew = share - statistics.median(priors)
            if best is None or grew > best["grew"]:
                best = {
                    "kind": kind[:-1],  # plane / phase
                    "name": name,
                    "share": round(share, 4),
                    "baseline_share": round(statistics.median(priors), 4),
                    "grew": round(grew, 4),
                }
    if best is None or best["grew"] <= 0:
        return None
    return best


def check_regressions(window: int = 5, threshold: float = 0.20,
                      experiment: str | None = None, db_path=None):
    """Compare the newest row of every (experiment, config) group in the
    result db against the median of up to `window` prior rows.

    Returns (n_metrics_checked, regressions): each regression names the
    experiment, metric, baseline, current value, and relative change.
    Groups with fewer than 2 rows or metrics of unknown direction are
    skipped — the gate only fires on evidence."""
    import statistics
    from pathlib import Path as _Path

    sys.path.insert(0, str(_Path(__file__).resolve().parent / "benchmarks"))
    from database import Database, config_key

    db = Database(db_path) if db_path is not None else Database()
    groups: dict = {}
    for r in db.records():
        if experiment is not None and r.experiment != experiment:
            continue
        params = r.params or {}
        # a failed smoke run stores {"ok": false, "value": null,
        # "failures": [...]} — those rows are crash markers, not
        # measurements, and must not seed prior-row medians
        if params.get("ok") is False or (
            "value" in params and params.get("value") is None
        ):
            continue
        # volatile outcome fields would fork the config grouping (every
        # distinct failure list becomes its own singleton group)
        key_params = {k: v for k, v in params.items()
                      if k not in ("ok", "failures")}
        groups.setdefault(
            (r.experiment, config_key(key_params)), []
        ).append(r)

    checked = 0
    regressions = []
    for (exp, _key), rows in sorted(groups.items()):
        rows.sort(key=lambda r: r.timestamp)
        if len(rows) < 2:
            continue
        cur, base = rows[-1], rows[-(window + 1):-1]
        for name, value in sorted(cur.values.items()):
            if not isinstance(value, (int, float)) or value <= 0:
                continue
            # rows emitted as {"metric": X, "value": v} carry the real
            # metric name in params
            metric_name = (str(cur.params.get("metric"))
                           if name == "value" and cur.params.get("metric")
                           else name)
            direction = _metric_direction(
                metric_name, str(cur.params.get("unit", "")))
            if direction == 0:
                continue
            samples = [
                r.values[name] for r in base
                if isinstance(r.values.get(name), (int, float))
                and r.values[name] > 0
            ]
            if not samples:
                continue
            baseline = statistics.median(samples)
            checked += 1
            # positive = worse, for either direction
            regress = (baseline - value) / baseline * direction
            if regress > threshold:
                reg = {
                    "experiment": exp,
                    "metric": metric_name,
                    "baseline": round(baseline, 4),
                    "current": round(value, 4),
                    "change_pct": round(regress * 100, 1),
                    "n_baseline_rows": len(samples),
                }
                # regression blame (ISSUE 19): rows carrying a profile
                # summary get the guilty plane/phase named alongside
                blame = _blame_from_profiles(
                    (cur.metadata or {}).get("profile"),
                    [(r.metadata or {}).get("profile") for r in base],
                )
                if blame is not None:
                    reg["blame"] = blame
                regressions.append(reg)
    return checked, regressions


def run_regress(args) -> None:
    """`bench.py --regress`: fail (exit 1) when the newest result-db row
    of any experiment regressed >20% against the median of its last N
    prior rows.  `--regress-demo` proves the gate live: it times a small
    compute path a few times into a THROWAWAY db, re-times it with a
    deliberate slowdown injected, and asserts the gate trips on exactly
    that row (the real db is never touched)."""
    if args.regress_demo:
        import shutil
        import tempfile
        from pathlib import Path as _Path

        sys.path.insert(
            0, str(_Path(__file__).resolve().parent / "benchmarks"))
        from database import Database

        tmp = _Path(tempfile.mkdtemp(prefix="hq-regress-demo-"))
        try:
            db = Database(tmp / "db.jsonl")

            def timed_path(slow_ms: float = 0.0) -> float:
                t0 = time.perf_counter()
                acc = 0
                for i in range(100_000):
                    acc += i * i
                if slow_ms:
                    time.sleep(slow_ms / 1e3)  # the deliberate slowdown
                return (time.perf_counter() - t0) * 1e3

            for _ in range(3):
                db.store_emit({
                    "experiment": "regress_demo",
                    "metric": "demo_path_ms", "unit": "ms",
                    "value": round(timed_path(), 4),
                })
            db.store_emit({
                "experiment": "regress_demo",
                "metric": "demo_path_ms", "unit": "ms",
                "value": round(timed_path(slow_ms=50.0), 4),
            })
            checked, regs = check_regressions(
                window=args.regress_window, experiment="regress_demo",
                db_path=db.path,
            )
            print(json.dumps({
                "experiment": "regress_demo", "checked": checked,
                "tripped": bool(regs), "regressions": regs,
            }))
            if not regs:
                print("regress-demo: FAIL — slowed path did not trip "
                      "the gate", file=sys.stderr)
                sys.exit(1)
            print("regress-demo: OK (deliberately slowed path tripped "
                  "the >20% gate, as it must)")
            sys.exit(0)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    checked, regs = check_regressions(
        window=args.regress_window, experiment=args.regress_experiment,
    )
    print(json.dumps({
        "checked_metrics": checked,
        "regressions": regs,
    }))
    if regs:
        for r in regs:
            blame = r.get("blame")
            print(
                f"REGRESSION {r['experiment']}/{r['metric']}: "
                f"{r['baseline']} -> {r['current']} "
                f"({r['change_pct']}% worse, vs median of "
                f"{r['n_baseline_rows']} prior rows)"
                + (f" — blame: {blame['kind']} '{blame['name']}' share "
                   f"{blame['baseline_share']} -> {blame['share']}"
                   if blame else ""),
                file=sys.stderr,
            )
        sys.exit(1)
    print(f"regress: OK ({checked} metric(s) within 20% of their "
          f"baselines)")
    sys.exit(0)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--kernel", action="store_true",
                        help="time the jitted solve alone (legacy metric)")
    parser.add_argument("--sharded-probe", action="store_true",
                        help="virtual-8-device sharded solve at W=8192 "
                             "(set JAX_PLATFORMS=cpu + "
                             "xla_force_host_platform_device_count=8)")
    parser.add_argument("--phases", action="store_true",
                        help="per-phase tick latency breakdown over the "
                             "production Core state (incremental snapshot "
                             "cache engaged)")
    parser.add_argument("--scratch", action="store_true",
                        help="with --phases: force the legacy from-scratch "
                             "snapshot path (the pre-cache baseline)")
    parser.add_argument("--smoke", action="store_true",
                        help="small-shape CPU gate: phase breakdown sums to "
                             "wall time, zero steady-state rebuilds/"
                             "recompiles, incremental == scratch")
    parser.add_argument("--chaos-smoke", action="store_true",
                        help="one seeded kill -9/restart cycle: workers "
                             "reconnect + reattach, job completes, zero "
                             "duplicate executions")
    parser.add_argument("--slo-smoke", action="store_true",
                        help="SLO alerting gate: a chaos solve-delay "
                             "breaches the tick budget under compressed "
                             "alert windows, the burn-rate page fires in "
                             "`hq alerts` and resolves after the chaos "
                             "lifts; latencies recorded into db.jsonl")
    parser.add_argument("--metrics", action="store_true",
                        help="end-to-end metrics gate: scrape the server's "
                             "Prometheus endpoint before/after a 1k-task "
                             "run and emit tick-phase histogram summaries")
    parser.add_argument("--explain-smoke", action="store_true",
                        help="explainability gate: unsatisfiable + "
                             "satisfiable workloads, assert reason codes, "
                             "record the solver status/objective trajectory")
    parser.add_argument("--throughput-smoke", action="store_true",
                        help="scaled-down dask-comparison (200 x 8 ms): "
                             "emit hq_vs_pool + the spawn-floor-normalized "
                             "ratio so real-task dispatch overhead is "
                             "tracked every round")
    parser.add_argument("--multichip-smoke", action="store_true",
                        help="small-instance gate: the production "
                             "MultichipModel (resident device state, 8-dev "
                             "mesh) must match the single-chip host solve "
                             "bitwise across evolving ticks")
    parser.add_argument("--scalability-sweep", action="store_true",
                        help="per-tick solve cost host-native vs sharded "
                             "device path at W=1k..16k; one row per (W, "
                             "backend) in benchmarks/results/db.jsonl")
    parser.add_argument("--trace-smoke", action="store_true",
                        help="distributed-tracing gate: N real-worker "
                             "tasks all yield complete closed traces "
                             "(all hops, span-sum <= wall), tracing "
                             "overhead <= 5% on the zero-worker dispatch "
                             "path")
    parser.add_argument("--submit-smoke", action="store_true",
                        help="submit-plane gate (ISSUE 10): sustained "
                             "chunked-ingest tasks/s, tick p95 before vs "
                             "during ingest, and O(chunks) lazy "
                             "materialization at ingest")
    parser.add_argument("--wire-smoke", action="store_true",
                        help="wire-path micro-gate (ISSUE 12): µs/wire-"
                             "byte seal+open per AEAD backend "
                             "(native/numpy/python ladder)")
    parser.add_argument("--saturation-smoke", action="store_true",
                        help="multi-core server gate (ISSUE 12): "
                             "journal+fanout+ingest planes on, encrypted "
                             "wire, assert >1 core of sustained server "
                             "process CPU under saturation")
    parser.add_argument("--elasticity-smoke", action="store_true",
                        help="self-healing elasticity gate: burst submit "
                             "against an empty local-handler pool; "
                             "scale-up/scale-down latency bounds + a "
                             "FaultPlan pass (first submit fails, first "
                             "worker dies at boot) that must converge")
    parser.add_argument("--federation-smoke", action="store_true",
                        help="federated failover gate: 2 shards + warm "
                             "standby, SIGKILL shard 1 mid-job, measure "
                             "kill -> first successor-side completion, "
                             "assert the bound + exactly-once starts")
    parser.add_argument("--fleet-smoke", action="store_true",
                        help="fleet observability gate (ISSUE 15): 2 "
                             "shards + standby w/ lending coordinator, "
                             "assert fleet-feed completeness (every "
                             "shard's task events exactly once) + a "
                             "metrics-proxy scrape covering both shards "
                             "under the latency bound")
    parser.add_argument("--reshard-smoke", action="store_true",
                        help="elastic-resharding gate (ISSUE 17): "
                             "rebalancer-driven hot-shard backlog "
                             "convergence below 1.5x + online N->N+1 "
                             "shard add with zero task loss; one "
                             "db.jsonl row per metric under --regress")
    parser.add_argument("--sim-smoke", action="store_true",
                        help="deterministic-simulator gate: determinism "
                             "pair, scenario sweep, and the 100k-task/"
                             "1k-worker kill -9 + churn soak on the "
                             "virtual clock (ISSUE 14)")
    parser.add_argument("--sim-tasks", type=int, default=100_000,
                        help="soak task count for --sim-smoke")
    parser.add_argument("--sim-workers", type=int, default=1000,
                        help="soak worker count for --sim-smoke")
    parser.add_argument("--policy-smoke", action="store_true",
                        help="weighted-objective gate (ISSUE 20): "
                             "numpy-vs-device weighted-kernel soak with "
                             "zero-weight exclusions, then seeded flat-vs-"
                             "weighted A/B sims (bursty hetero, straggler "
                             "tail, stress dag) gating makespan, Jain "
                             "fairness, and tick p95; rows auto-gated by "
                             "--regress")
    parser.add_argument("--profile-smoke", action="store_true",
                        help="continuous-profiling gate (ISSUE 19): "
                             "sampler overhead <= 5% on an encrypted "
                             "submit burst, folded + Perfetto counter "
                             "artifacts, solve-plane stack in the chaos "
                             "stall dump, and regression blame naming a "
                             "deliberately slowed plane")
    parser.add_argument("--regress", action="store_true",
                        help="result-db regression gate: newest row per "
                             "(experiment, config) vs the median of its "
                             "last N prior rows; exit 1 on any metric "
                             ">20% worse in its bad direction")
    parser.add_argument("--regress-demo", action="store_true",
                        help="prove the --regress gate live: time a "
                             "path, re-time it deliberately slowed into "
                             "a throwaway db, assert the gate trips")
    parser.add_argument("--regress-window", type=int, default=5,
                        help="prior rows per config the regression gate "
                             "baselines against (median)")
    parser.add_argument("--regress-experiment", default=None,
                        help="limit --regress to one experiment name")
    parser.add_argument("--restore-smoke", action="store_true",
                        help="bounded-restore gate: restore under 2 s from "
                             "a snapshot after --tasks (default 1M) "
                             "completed+forgotten tasks, with the full-"
                             "replay O(history) baseline in the same row")
    parser.add_argument("--classes", type=int, default=128,
                        help="distinct request classes for --phases")
    parser.add_argument("--workers", type=int, default=None,
                        help="default 1024 (8192 for --sharded-probe)")
    parser.add_argument("--tasks", type=int, default=1_000_000)
    parser.add_argument("--repeats", type=int, default=30)
    args = parser.parse_args()

    if args.smoke:
        run_smoke()
        return

    if args.chaos_smoke:
        run_chaos_smoke()
        return

    if args.explain_smoke:
        run_explain_smoke()
        return

    if args.throughput_smoke:
        run_throughput_smoke()
        return

    if args.trace_smoke:
        run_trace_smoke()
        return

    if args.submit_smoke:
        run_submit_smoke(args)
        return

    if args.wire_smoke:
        run_wire_smoke()
        return

    if args.saturation_smoke:
        run_saturation_smoke(args)
        return

    if args.slo_smoke:
        run_slo_smoke()
        return

    if args.federation_smoke:
        run_federation_smoke()
        return

    if args.fleet_smoke:
        run_fleet_smoke()
        return

    if args.reshard_smoke:
        run_reshard_smoke()
        return

    if args.elasticity_smoke:
        run_elasticity_smoke()
        return

    if args.restore_smoke:
        run_restore_smoke(args)
        return

    if args.profile_smoke:
        run_profile_smoke(args)
        return

    if args.regress or args.regress_demo:
        run_regress(args)
        return

    if args.sim_smoke:
        run_sim_smoke(args)
        return

    if args.policy_smoke:
        run_policy_smoke(args)
        return

    if args.multichip_smoke:
        run_multichip_smoke()
        return

    if args.scalability_sweep:
        if args.workers is None:
            args.workers = 16384
        run_scalability_sweep(args)
        return

    if args.metrics:
        run_metrics_bench(args)
        return

    if args.workers is None:
        args.workers = 8192 if args.sharded_probe else 1024

    if args.sharded_probe:
        times, n_assigned, n_devices, probe_phases = bench_sharded_probe(args)
        median_ms = float(np.median(times))
        print(json.dumps({
            "metric": f"sharded_solve_{n_devices}dev_w{args.workers}",
            "value": round(median_ms, 3),
            "unit": "ms",
            "vs_baseline": round(BASELINE_MS / median_ms, 2),
            "device": "cpu-mesh",
            "n_devices": n_devices,
            "phases": probe_phases,
        }))
        print(f"# sharded probe assigned={n_assigned} "
              f"p50={median_ms:.2f}ms", file=sys.stderr)
        return

    # One process, one chip: this process takes the device itself and
    # starts no child that would want it.  Without --cpu a run that finds
    # no TPU fails; it does not fall back and print a device metric's name
    # over a CPU number.
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    else:
        from hyperqueue_tpu.utils.jaxdev import configure_compile_cache

        configure_compile_cache()
        if jax.default_backend() != "tpu":
            print(
                f"bench.py: no TPU (jax.default_backend() is "
                f"{jax.default_backend()!r}); pass --cpu for a local check",
                file=sys.stderr,
            )
            raise SystemExit(1)
    on_cpu = args.cpu
    device = jax.devices()[0]

    if args.phases:
        res = bench_phases(args, on_cpu, scratch=args.scratch)
        print(json.dumps({
            "metric": "tick_phases_1M_tasks_x_1k_workers",
            "value": res["host_ms"],
            "unit": "ms-host",
            "vs_baseline": round(BASELINE_MS / max(res["host_ms"], 1e-9), 2),
            "device": device.platform,
            **res,
        }))
        print(
            f"# phases mode={res['mode']} host={res['host_ms']:.2f}ms "
            f"assigned={res['n_assigned']} "
            f"rebuilds={res['steady_full_rebuilds']}",
            file=sys.stderr,
        )
        return

    solve_backend = None
    if args.kernel:
        times, n_assigned = bench_kernel(args, on_cpu)
        metric = "tick_latency_1M_tasks_x_1k_workers"
    else:
        times, n_assigned, solve_backend = bench_full_tick(args, on_cpu)
        metric = "full_tick_1M_tasks_x_1k_workers"
    median_ms = float(np.median(times))

    result = {
        "metric": metric,
        "value": round(median_ms, 3),
        "unit": "ms",
        "vs_baseline": round(BASELINE_MS / median_ms, 2),
        "device": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
    }
    if solve_backend is not None:
        result["solve_backend"] = solve_backend
    print(json.dumps(result))
    print(
        f"# device={device.platform} assigned={n_assigned} "
        f"min={min(times):.2f}ms p50={median_ms:.2f}ms max={max(times):.2f}ms",
        file=sys.stderr,
    )

    # Store the run in the durable result database so report.py's
    # `tick_latency` published number traces to an actual stored run
    # (reference benchmarks/src/benchmark/database.py; set HQ_BENCH_NO_DB=1
    # for throwaway runs).
    import os

    if not os.environ.get("HQ_BENCH_NO_DB") and median_ms > 0:
        try:
            sys.path.insert(
                0, str(__import__("pathlib").Path(__file__).parent / "benchmarks")
            )
            from database import Database

            Database().store_emit({
                "experiment": "tick-latency",
                "mode": "kernel" if args.kernel else "full-tick",
                "n_workers": args.workers,
                "n_tasks": args.tasks,
                "device": device.platform,
                "backend": solve_backend or "device-jax",
                "value_ms": round(median_ms, 3),
                "vs_baseline": round(BASELINE_MS / median_ms, 2),
                "min_ms": round(min(times), 3),
                "max_ms": round(max(times), 3),
            })
        except Exception as e:  # noqa: BLE001 - the bench must still print
            print(f"# result-db store failed: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
