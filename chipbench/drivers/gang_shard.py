"""The `gang_shard` driver: the production tick with multi-node tasks riding
the solve as gang rows, the solve sharded over the chips.

The `gang` driver's tick (the reactor's own fused gang functions around
`run_tick`: `fused_gang_rows -> TickStateCache.sync -> create_batches` + the
gang rows `-> fused_gang_inputs -> run_tick -> _apply_fused_gangs` and the
single-node assignments applied) with the `shard` driver's model, the one the
server builds for `--scheduler multichip` (`MultichipModel()` then
`get_mesh()`).  The worker axis is split contiguously over the chips, so a
group's idle members may lie on two of them: every gang step gathers the
per-group counts of eligible workers (`hq_gang_select_gather`), and which
members a gang takes depends on what the lower chips hold.

Nothing here but the loop that joins the two drivers.  From `gang`, by
import: the program state with its groups and gang request classes, the
`Cluster` (filler and gangs: submitted, started, ended, replaced), the
comparison with the reference (every started gang's member set) and the gang
audit.  From `shard`: the rehearsal's virtual devices, the collectives'
device time from the trace, the counter of scan steps.  From `tick`: the audit
of what holds whatever the order.

Set-up, in the `gang` driver's order: the world, the program's state with
the filler alone, the fill tick and one tick per delta-upload bucket at the
full worker bucket; that first wave finishes as the gangs arrive, and ticks
run with nothing ending until one starts no gang (16 gangs a tick until the
nodes without gpus are taken: the worker rows fall from the full bucket to
the one below, every tick a full upload); one tick per delta-upload bucket at
the bucket the rows have reached; then the traffic's settle steps.  A run on
the chip whose set-up did not meet a full upload at every worker bucket it
passed through ends without a result; a compile in the window fails the run.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from chipbench import generate_gang, manifest, spans
from chipbench.drivers import gang as gang_driver
from chipbench.drivers import shard as shard_driver
from chipbench.drivers import tick as tick_driver

Cluster = gang_driver.Cluster
compare_with_reference = gang_driver.compare_with_reference
audit_gangs = gang_driver.audit_gangs
virtual_devices = shard_driver.virtual_devices
collective_seconds = shard_driver.collective_seconds
scan_steps_counted = shard_driver.scan_steps_counted

SHARDED_BACKEND = shard_driver.SHARDED_BACKEND
KERNEL_MODULE = shard_driver.KERNEL_MODULE


def run(ctx) -> dict:
    config, traffic = ctx.cell["config"], ctx.cell["traffic"]
    n_chips = int(config["mesh"]["chips"])
    if ctx.rehearse:
        virtual_devices(n_chips)
    from hyperqueue_tpu.models.greedy import _bucket
    from hyperqueue_tpu.models.multichip import MultichipModel
    from hyperqueue_tpu.parallel.resident import _ROW_BUCKET_FLOOR
    from hyperqueue_tpu.scheduler.tick import create_batches, run_tick
    from hyperqueue_tpu.server import reactor

    if not hasattr(reactor, "fused_gang_rows"):
        raise SystemExit(
            "chipbench: this program's fused gang phase cannot be called "
            "(no reactor.fused_gang_rows): the cell cannot run on it")
    rows_per_tick = int(traffic["gang_rows_per_tick"])
    if not (reactor.MAX_FUSED_GANG_ROWS == rows_per_tick
            == int(config["gangs"]["rows_per_tick"])):
        raise SystemExit(
            f"chipbench: the cell states {rows_per_tick} gang rows a tick, "
            f"the program sends {reactor.MAX_FUSED_GANG_ROWS}")
    world = generate_gang.world(config, traffic, ctx.seed, ctx.scale)
    core, rq_ids, worker_ids, gang_rq = gang_driver.build_program_state(
        world, config)
    cluster = Cluster(world, core, rq_ids, ctx.seed, gang_rq)
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
        on = (model.last_backend, (model.last_device or {}).get("count"))
        solves[on] = solves.get(on, 0) + 1
        refused += cluster.refused
        rows_seen.append(len(snap.worker_ids))
        cluster.started(single)
        return phases

    share = float(traffic["churn_per_tick"])
    gang_share = float(traffic["gang_finish_per_tick"])
    uploads_met: set = set()   # (worker bucket, row bucket or "full")

    def note_upload(before):
        stats = model.resident_stats()
        bucket = _bucket(stats["dirty_rows_last"], _ROW_BUCKET_FLOOR)
        delta = stats["delta_uploads"] > before.get("delta_uploads", 0)
        uploads_met.add((stats["rows_per_device"] * stats["mesh_devices"],
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
                if (pw, bucket) in uploads_met:
                    break

    # -- set-up ---------------------------------------------------------------
    # the filler alone: fill, every delta bucket at the full worker bucket
    before = model.resident_stats()
    tick()
    note_upload(before)
    # the deployment states how the state lies on the chips; the program has
    # to say so itself (`resident_stats()`), from its first solve on
    resident = model.resident_stats()
    layout = (resident.get("mesh_devices"), resident.get("rows_per_device"))
    if layout != (n_chips, -(-len(worker_ids) // n_chips)):
        raise SystemExit(
            f"chipbench: the deployment shards {len(worker_ids)} workers over "
            f"{n_chips} chips, but the program reports (devices, rows a "
            f"device) = {layout}")
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
    fill_ticks = len(cluster.log)
    warm_delta_buckets()
    for n_ticks, settle_share, settle_gang_share in (ctx.scale or {}).get(
            "settle", traffic["settle"]):
        for _ in range(int(n_ticks)):
            cluster.churn(float(settle_share),
                          gang_share=float(settle_gang_share))
            before = model.resident_stats()
            tick()
            note_upload(before)
    # every worker bucket the rows passed through on their way down is one
    # the window can meet, in the full form (a row that leaves or rejoins
    # shifts every row behind it): all of them have to have been met
    buckets = sorted({model._worker_bucket(r) for r in rows_seen},
                     reverse=True)
    uploads_not_met = sorted(
        f"{pw}:{k}" for pw in buckets
        for k in ["full"] + row_buckets(pw) if (pw, k) not in uploads_met)
    if {(pw, "full") for pw in buckets} - uploads_met:
        raise SystemExit(
            "chipbench: set-up did not meet the upload programs the window "
            f"meets; not met: {uploads_not_met}")
    cluster.churn(share, gang_share=gang_share)
    spans.gc_as_server_started(gc_settings)
    shapes_warm = model.shape_allocations
    uploads0 = model.resident_stats()
    cache0 = core.tick_cache.counters()
    started0 = gang_driver.counter_value(gang_driver.GANG_GROUPS_COUNTER)
    steps0 = scan_steps_counted()
    first_window_tick = len(cluster.log)
    rows_in_setup = len(rows_seen)
    ctx.setup_done()

    # -- the window -----------------------------------------------------------
    ticks: list = []
    compiles0 = ctx.compiles.count
    solves0 = dict(solves)
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
    started1 = gang_driver.counter_value(gang_driver.GANG_GROUPS_COUNTER)
    steps1 = scan_steps_counted()
    compiles_in_window = ctx.compiles.count - compiles0
    in_window = {k: v - solves0.get(k, 0) for k, v in solves.items()}
    off_mesh = sum(v for k, v in in_window.items()
                   if k != (SHARDED_BACKEND, n_chips))
    off_device = sum(v for k, v in in_window.items()
                     if not str(k[0]).startswith("device"))
    new_shapes = model.shape_allocations - shapes_warm
    memory_peak = ctx.memory_peak()
    buckets_in_window: dict = {}
    for r in rows_seen[rows_in_setup:]:
        pw = model._worker_bucket(r)
        buckets_in_window[pw] = buckets_in_window.get(pw, 0) + 1
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
        ("solves_off_device", off_device, 0),
        ("solves_off_mesh", off_mesh, 0),
        ("compiles_in_window", compiles_in_window, 0),
        ("new_shapes_in_window", new_shapes, 0),
    ]
    groups = int(world.worker_group.max()) + 1
    observed = {
        "tick_phases_ms": ticks,
        "host_phases": gang_driver.HOST_PHASES,
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
        "groups": groups,
        "gang_rows": rows_per_tick,
        "mesh_devices": n_chips,
        "kernel_module": KERNEL_MODULE,
        "collective_s": collective_seconds(ctx.trace_plain),
    }
    if started0 is not None and started1 is not None:
        observed["gangs_started_in_window"] = started1 - started0
    if steps0 is not None and steps1 is not None:
        observed["scan_steps_in_window"] = steps1 - steps0

    def spread(values):
        return [min(values), statistics.median(values), max(values)]

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
            "gang_fill_ticks": fill_ticks,
            "upload_programs_not_met_in_setup": uploads_not_met,
            "resident": {k: uploads1.get(k) for k in (
                "mesh_devices", "rows_per_device", "full_uploads",
                "delta_uploads", "invalidations", "gang_groups_last")},
            "phases_ms_p50": {
                key: statistics.median(p.get(key, 0.0) for p in ticks)
                for key in sorted({k for p in ticks for k in p})},
            "longest_tick_ms": max(ticks, key=lambda p: p["total"]),
            "between_ticks_s": window_s - float(total.sum()) / 1e3,
            # of which inside `reactor.on_new_tasks`, the gangs that arrive
            "gang_submit_s": cluster.submit_s - submit_s0,
            "rows_min_p50_max": spread(window_rows),
            "window_ticks_by_worker_bucket": buckets_in_window,
            "worker_buckets_in_setup": buckets,
            "groups": groups,
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
