"""The `shard` driver: the production tick with the solve sharded over the
chips, over a backlog held constant.

The `tick` driver's loop with the model the server builds for `--scheduler
multichip` (`MultichipModel()` then `get_mesh()`, bootstrap.py): per tick
`TickStateCache.sync -> create_batches -> run_tick -> apply`, device-resident
sharded state, no pipeline, no paranoid guard, and between ticks the same
`Cluster` playing the cluster.  The world has whole-node classes
(`generate_shard`), the program's requests for them carry upstream's policy
`all`, and the plain reference is one cluster of W rows that knows no mesh.

From the `tick` driver, by import: the program state, the `Cluster`, the
comparison with the reference and the audit of what holds whatever the order.
Added here: the model and its mesh, and after the fill tick a check that the
program itself reports the layout the deployment states (`resident_stats()`:
devices, rows a device; a program that does not ends the run without a
result); the whole-node side of the audit (`rows_overcommitted` with a
whole-node task counted as the worker's total of cpus, `whole_node_shared`);
`solves_off_mesh`; `full_uploads_in_window`; the collectives' device time
from the trace; the counter of scan steps.

Set-up is: the world, the program's state, the fill tick (full upload,
compiles), one tick per delta-upload bucket from 16 rows up to the largest
the window meets, then the traffic's settle steps.  A rehearsal runs the same
code on four virtual CPU devices.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import re
import statistics
import time

import numpy as np

from chipbench import generate_shard, manifest, spans, trace
from chipbench.drivers import tick as tick_driver

TASK_MASK = tick_driver.TASK_MASK
SHARDED_BACKEND = "device-sharded"
KERNEL_MODULE = "sharded_cut_scan_donate"
SCAN_STEPS_COUNTER = "hq_solve_scan_steps_total"
# the gathers as the compiler leaves them: on a v5e the small all-gather of a
# scan step is lowered to an all-reduce, so every collective kind counts
COLLECTIVE = re.compile(
    r"^%(all[-_]gather|all[-_]reduce|reduce[-_]scatter|all[-_]to[-_]all"
    r"|collective[-_]permute)")
# stands for `all` where the tick driver builds request entries from amounts
WHOLE = 1 << 40


def virtual_devices(n: int) -> None:
    """A rehearsal's chips: `n` devices of the CPU backend, asked for before
    that backend starts."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
    import jax

    if len(jax.devices()) < n:
        raise SystemExit(
            f"chipbench: a rehearsal of {n} chips needs {n} CPU devices, "
            f"but JAX started with {len(jax.devices())}")


def build_program_state(world, config):
    """The `tick` driver's program state, with the whole-node classes as
    requests of policy `all`.  That function makes a request entry from an
    amount alone, so it is handed the amount `WHOLE` where the class asks
    for the whole pool, and for its duration the entry made from `WHOLE` is
    the `all` entry.  Checked afterwards, class by class."""
    from unittest import mock

    # whatever binds the entry class by name at import binds the real one
    import hyperqueue_tpu.server.core  # noqa: F401
    import hyperqueue_tpu.server.worker  # noqa: F401
    from hyperqueue_tpu.resources import request

    real = request.ResourceRequestEntry

    def entry(resource_id, amount):
        if amount == WHOLE:
            return real(resource_id, 0, request.AllocationPolicy.ALL)
        return real(resource_id, amount)

    marked = dataclasses.replace(
        world, class_needs=np.where(world.class_all, WHOLE, world.class_needs))
    with mock.patch.object(request, "ResourceRequestEntry", entry):
        core, rq_ids, worker_ids = tick_driver.build_program_state(
            marked, config)
    for c, rq_id in enumerate(rq_ids):
        for v, variant in enumerate(core.rq_map.get_variants(rq_id).variants):
            whole = np.zeros(len(world.resources), dtype=bool)
            for e in variant.entries:
                whole[e.resource_id] = e.policy is request.AllocationPolicy.ALL
            if not np.array_equal(whole, world.class_all[c, v]):
                raise SystemExit(
                    f"chipbench: class {c} variant {v} is not the request "
                    "the world states")
    return core, rq_ids, worker_ids


def audit_placements(world, log, rq_ids, worker_ids) -> dict:
    """The `tick` driver's audit, and what a whole-node task adds to it.
    There a task holds its class's amounts, which for a whole-node entry are
    none; here it holds the worker's total of that resource besides, so
    `rows_overcommitted` counts it as the configuration states, and
    `whole_node_shared` counts, per tick, the workers on which a whole-node
    task runs beside another task that holds some of the same pool."""
    numbers = tick_driver.audit_placements(world, log, rq_ids, worker_ids)
    row_of = {w: i for i, w in enumerate(worker_ids)}
    class_of = {rq: c for c, rq in enumerate(rq_ids)}
    n_tasks = len(world.task_class)
    pooled = world.class_all.any(axis=(0, 1))        # (R,) pools ever asked whole
    takes = world.class_all.any(axis=2)              # (C, V) a whole-node variant
    touches = (world.class_needs[:, :, pooled] > 0).any(axis=2) | takes
    total = world.worker_total
    used = np.zeros_like(total)
    whole_held = np.zeros(len(worker_ids), dtype=np.int64)
    pool_users = np.zeros(len(worker_ids), dtype=np.int64)
    holds: dict = {}

    def account(placements, sign):
        """Add (or give back) what the tasks at (row, class, variant) hold."""
        if not placements:
            return
        row, c, v = np.asarray(placements, dtype=np.int64).T
        held = world.class_needs[c, v] + world.class_all[c, v] * total[row]
        np.add.at(used, row, sign * held)
        np.add.at(whole_held, row, sign * takes[c, v])
        np.add.at(pool_users, row, sign * touches[c, v])

    overcommitted = shared = 0
    for assignments, finished in log:
        placed = []
        for task_id, worker_id, rq_id, variant in assignments:
            t = task_id & TASK_MASK
            row, c = row_of.get(worker_id), class_of.get(rq_id)
            if (row is None or c is None or t >= n_tasks or t in holds
                    or not 0 <= variant < int(world.class_variants[c])):
                continue  # `answers_unknown` has counted it
            holds[t] = (row, c, variant)
            placed.append(t)
        account([holds[t] for t in placed], 1)
        overcommitted += int((used > total).any(axis=1).sum())
        shared += int(((whole_held > 0) & (pool_users > 1)).sum())
        account([holds.pop(t) for t in finished if t in holds], -1)
        n_tasks += len(placed)  # each is replaced by a new ready task
    numbers["rows_overcommitted"] = max(numbers["rows_overcommitted"],
                                        overcommitted)
    numbers["whole_node_shared"] = shared
    return numbers


def collective_seconds(plain: dict | None) -> float | None:
    """Device seconds of the collective operations inside the sharded
    program's calls that lie whole inside the traced span, summed over the
    devices (`trace.reduce` counts the same calls as `kernel_calls`, and
    keeps ten rows of operations, so the sum is made here)."""
    if plain is None:
        return None
    is_device = lambda p: p["name"].startswith(  # noqa: E731
        trace.DEVICE_PLANE_PREFIX)
    traced = [(start, start + dur)
              for p in plain["planes"] if not is_device(p)
              for line in p["lines"] for name, start, dur in line["events"]
              if name == trace.TRACED_SPAN]
    if not traced:
        return None
    lo, hi = traced[0][0], traced[-1][1]
    seconds = 0.0
    for plane in filter(is_device, plain["planes"]):
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        calls = sorted((start, start + dur)
                       for name, start, dur in lines.get(trace.MODULES_LINE, [])
                       if KERNEL_MODULE in name
                       and lo <= start and start + dur <= hi)
        if not calls:
            continue
        starts = np.asarray([c[0] for c in calls])
        ends = np.asarray([c[1] for c in calls])
        for name, start, dur in lines.get(trace.OPS_LINE, []):
            if COLLECTIVE.match(name):
                k = int(np.searchsorted(starts, start, side="right")) - 1
                if k >= 0 and start + dur <= ends[k]:
                    seconds += dur / 1e9
    return seconds


def scan_steps_counted():
    """The program's own count of scan steps so far; None where the program
    has no such counter."""
    from hyperqueue_tpu.utils.metrics import REGISTRY

    counter = REGISTRY.get(SCAN_STEPS_COUNTER)
    return None if counter is None else counter.labels().value


def run(ctx) -> dict:
    config, traffic = ctx.cell["config"], ctx.cell["traffic"]
    n_chips = int(config["mesh"]["chips"])
    if ctx.rehearse:
        virtual_devices(n_chips)
    from hyperqueue_tpu.models.multichip import MultichipModel
    from hyperqueue_tpu.scheduler.tick import create_batches, run_tick

    world = generate_shard.world(config, traffic, ctx.seed, ctx.scale)
    core, rq_ids, worker_ids = build_program_state(world, config)
    cluster = tick_driver.Cluster(world, core, rq_ids, ctx.seed)
    model_cls = spans.annotated_model(MultichipModel) if ctx.trace \
        else MultichipModel
    # as the server builds it for --scheduler multichip: every device the
    # process sees (a rehearsal's CPU backend may have more than the cell's)
    model = model_cls(n_devices=n_chips if ctx.rehearse else None)
    mesh = model.get_mesh()
    if not mesh or mesh.devices.size != n_chips:
        raise SystemExit(
            f"chipbench: the cell shards over {n_chips} devices, the model "
            f"built a mesh of {mesh.devices.size if mesh else 1}")
    ann = spans.annotate
    solves: dict = {}
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
        on = (model.last_backend, (model.last_device or {}).get("count"))
        solves[on] = solves.get(on, 0) + 1
        cluster.started(out)
        return phases

    share = float(traffic["churn_per_tick"])
    # -- set-up: fill, every delta bucket, then the settle steps -------------
    tick()
    # the deployment states how the state lies on the chips; the program has
    # to say so itself (`resident_stats()`), from its first solve on
    resident = model.resident_stats()
    layout = (resident.get("mesh_devices"), resident.get("rows_per_device"))
    if layout != (n_chips, -(-len(worker_ids) // n_chips)):
        raise SystemExit(
            f"chipbench: the deployment shards {len(worker_ids)} workers over "
            f"{n_chips} chips, but the program reports (devices, rows a "
            f"device) = {layout}")
    for rows in traffic["warm_dirty_rows"]:
        if int(rows) <= len(worker_ids) // 2:  # beyond it: a full upload
            cluster.churn(share, on_workers=int(rows))
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
    steps0 = scan_steps_counted()
    first_window_tick = len(cluster.log)
    ctx.setup_done()

    # -- the window -----------------------------------------------------------
    ticks: list = []
    dirty_rows: list = []
    compiles0 = ctx.compiles.count
    solves0 = dict(solves)
    host = spans.HostReading()
    t_start = time.perf_counter()
    t_end = t_start + ctx.seconds
    ctx.window_opens(t_start)
    while True:
        ticks.append(tick())
        dirty_rows.append(model.resident_stats().get("dirty_rows_last", 0))
        with ann("chipbench/churn"):
            cluster.churn(share)
        ctx.window_tick()
        if time.perf_counter() >= t_end:
            break
    window_s = time.perf_counter() - t_start
    host_in_window = host.delta()
    ctx.window_closed()
    uploads1 = model.resident_stats()
    steps1 = scan_steps_counted()
    compiles_in_window = ctx.compiles.count - compiles0
    in_window = {k: v - solves0.get(k, 0) for k, v in solves.items()}
    off_mesh = sum(v for k, v in in_window.items()
                   if k != (SHARDED_BACKEND, n_chips))
    new_shapes = model.shape_allocations - shapes_warm
    memory_peak = ctx.memory_peak()
    gc.unfreeze()
    core = model = cluster.core = None  # the program's state is freed

    # -- the comparison -------------------------------------------------------
    t = time.perf_counter()
    compared = tick_driver.compare_with_reference(
        world, cluster.log, rq_ids, worker_ids,
        manifest.reference(config["reference"]),
    )
    audited = audit_placements(world, cluster.log, rq_ids, worker_ids)
    reference_s = time.perf_counter() - t
    total = np.asarray([p["total"] for p in ticks])
    checks = [
        ("ticks_mismatched", compared["ticks_mismatched"], 0),
        ("rows_overcommitted", audited["rows_overcommitted"], 0),
        ("whole_node_shared", audited["whole_node_shared"], 0),
        ("tasks_out_of_order", audited["tasks_out_of_order"], 0),
        ("priority_inversions", audited["priority_inversions"], 0),
        ("answers_unknown", audited["answers_unknown"], 0),
        ("solves_off_mesh", off_mesh, 0),
        ("compiles_in_window", compiles_in_window, 0),
        ("new_shapes_in_window", new_shapes, 0),
        ("full_uploads_in_window",
         uploads1.get("full_uploads", 0) - uploads0.get("full_uploads", 0), 0),
    ]
    observed = {
        "tick_phases_ms": ticks,
        "host_phases": tick_driver.HOST_PHASES,
        "device_phases": tick_driver.DEVICE_PHASES,
        "uploads_before": uploads0,
        "uploads_after": uploads1,
        "ticks": len(ticks),
        "extents": {
            "B": world.class_needs.shape[0] * world.n_priorities,
            "V": world.class_needs.shape[1],
            "W": world.worker_total.shape[0],
            "R": world.worker_total.shape[1],
        },
        "mesh_devices": n_chips,
        "kernel_module": KERNEL_MODULE,
        "collective_s": collective_seconds(ctx.trace_plain),
    }
    if steps0 is not None and steps1 is not None:
        observed["scan_steps_in_window"] = steps1 - steps0
    whole_level = np.repeat(world.class_all.any(axis=(1, 2)),
                            world.n_priorities)
    window_log = cluster.log[first_window_tick:]
    return {
        "attempted": len(ticks),
        "failed": off_mesh,
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
            "solves_by_backend_and_devices_in_window": {
                f"{k[0]} x{k[1]}": v for k, v in in_window.items()},
            "ticks_replayed_by_reference": compared["ticks_replayed"],
            "first_mismatch_tick": compared["first_mismatch_tick"],
            "reference_s": round(reference_s, 3),
            "host_in_window": host_in_window,
            "collector": gc_settings,
            "setup_ticks": first_window_tick,
            "resident": {k: uploads1.get(k) for k in (
                "mesh_devices", "rows_per_device", "full_uploads",
                "delta_uploads", "invalidations")},
            "phases_ms_p50": {
                key: statistics.median(p.get(key, 0.0) for p in ticks)
                for key in sorted({k for p in ticks for k in p})},
            "longest_tick_ms": max(ticks, key=lambda p: p["total"]),
            "between_ticks_s": window_s - float(total.sum()) / 1e3,
            "dirty_rows_min_p50_max": [
                min(dirty_rows), statistics.median(dirty_rows),
                max(dirty_rows)],
            "running_at_close": len(cluster.running),
            "assigned_in_window": sum(len(rec[0]) for rec in window_log),
            "whole_node_assigned_in_window": sum(
                int(whole_level[cluster.level_of[a[0] & TASK_MASK]])
                for rec in window_log for a in rec[0]),
        },
    }

