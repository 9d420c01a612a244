"""The `shared` driver: multi-node tasks and single-node tasks on the same
nodes, the production tick with `--gang-drain busy`.

The `gang` driver's loop, with the reservation step of `reactor._tick` in
it, by the functions `_tick` itself calls: per tick
`reactor.fused_gang_rows` -> `TickStateCache.sync -> create_batches` + the
gang rows -> `reactor.fused_gang_inputs` -> `reactor.fused_gang_reserve`
(the reservations, read from the snapshot's columns) -> `run_tick` (the
reservation codes ride the one packed put) -> `reactor._apply_fused_gangs`
and the single-node assignments applied.  The program's core is set to
`--gang-drain busy` through `Core.set_gang_drain`, the function the
server's bootstrap calls; a program without it cannot run the cell.

From the `gang` driver, by import: the program state with its groups and
gang request classes and the `Cluster` (the filler's churn, the gangs
submitted, started, ended and replaced, the record).  Added here: the
reservation sets of every tick, which the comparison holds to the plain
reference's beside the placements and the started gangs, and the audit of
what holds whatever the order (`gang_split`, `gang_shared`,
`gang_overtaken` restated for reservations, `reserved_fed`,
`reservation_unhonoured`).

Set-up is: the world, the program's state with the filler alone, the fill
tick and one tick per delta-upload bucket at the full worker bucket; then
the gangs arrive into the full cluster and reserve, and before a drain
ends (no gang starts, no row moves) one tick per delta-upload bucket
again, now with the gang inputs, and one full upload with them (the
residency dropped); then the traffic's settle steps.  Every window tick has
to lie at the full worker bucket: a run on the chip whose window meets
another, or whose set-up did not meet every upload program the window
meets, ends without a result.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from chipbench import generate_shared, manifest, spans
from chipbench.drivers import gang as gang_driver
from chipbench.drivers import tick as tick_driver

TASK_MASK = tick_driver.TASK_MASK
GANG_JOB = gang_driver.GANG_JOB
HOST_PHASES = gang_driver.HOST_PHASES
GANG_GROUPS_COUNTER = gang_driver.GANG_GROUPS_COUNTER
RESERVED_BUSY_COUNTER = "hq_solve_gang_reserved_busy_total"


def record_reservations(gang_resv, worker_ids):
    """One tick's record of the reservation column, as two int64 arrays
    (the reserved workers' ids, the gang task of each): nothing the
    collector tracks is kept across the window.  None for no column."""
    if gang_resv is None:
        return None
    rows = np.flatnonzero(gang_resv)
    ids = np.fromiter(map(worker_ids.__getitem__, rows.tolist()),
                      dtype=np.int64, count=len(rows))
    return ids, gang_resv[rows]


def reservation_sets(raw, row_of=None) -> dict:
    """{gang number: sorted members} from one tick's record of the
    reservation column (`record_reservations`); members as worker ids, or
    rows of `row_of`."""
    if raw is None:
        return {}
    sets: dict = {}
    for w, task in zip(*(arr.tolist() for arr in raw)):
        sets.setdefault(task & TASK_MASK, []).append(
            w if row_of is None else row_of.get(w, -1))
    return {g: sorted(m) for g, m in sets.items()}


def compare_with_reference(world, log, gang_log, resv_log, rq_ids,
                           worker_ids, reference_cls):
    """The `gang` driver's replay, with the reservations: the reference
    also has to leave, tick by tick, the reservation sets the program
    left.  `resv_log` holds per tick {gang: worker ids}."""
    import dataclasses

    row_of = {w: i for i, w in enumerate(worker_ids)}
    no_match = np.asarray([[-1, 0, 0, 1]], dtype=np.int64)
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
            want_resv = {g: sorted(m)
                         for g, m in self.ref.last_reservations.items()}
            got_resv = {g: sorted(row_of.get(w, -1) for w in m)
                        for g, m in resv_log[self.i].items()}
            same = want == got and want_resv == got_resv
            return (cells, taken) if same else (no_match, taken)

        def finish(self, finished):
            return self.ref.finish(finished, gang_log[self.i][1])

        def arrive(self, task_ids, levels):
            self.ref.arrive(task_ids, levels, gang_log[self.i][2])

    return tick_driver.compare_with_reference(
        world, log, rq_ids, worker_ids, Replay)


def audit_shared(world, log, gang_log, resv_log, worker_ids,
                 rows_per_tick) -> dict:
    """What the configuration guarantees of gangs and reservations whatever
    the order of the scan, read from the program's own placements and
    reservation sets and the world alone:
    `gang_split` (a started gang whose members are not its n, or lie in two
    groups), `gang_shared` (a member that ran something or belonged to a
    gang when the gang started, or took a task while in it),
    `gang_overtaken` (a gang that started on workers none of which was
    reserved for it, in a tick in which a gang ahead of it among the tick's
    rows, no larger than it, did not), `reserved_fed` (a single-node task
    placed on a worker reserved at the solve), `reservation_unhonoured` (a
    gang whose n reserved workers all ran nothing at the tick's start and
    that did not start on them; or a row's gang that did not start and
    holds no n reserved workers after the tick's reservations although no
    group held n idle workers free for it and some group held n workers
    free for it, free as the reservations stood when its turn came).  The
    configuration's gangs are at the filler's highest user priority, so
    nothing outranks them."""
    row_of = {w: i for i, w in enumerate(worker_ids)}
    group = world.worker_group
    n_groups = int(group.max()) + 1
    n_w = len(worker_ids)
    nodes: list = []
    queue: list = []
    tasks_on = np.zeros(n_w, dtype=np.int64)
    gang_on = np.full(n_w, -1, dtype=np.int64)
    where: dict = {}
    members_of: dict = {}
    standing: dict = {}           # gang -> rows, at the tick's start
    split = shared = overtaken = fed = unhonoured = 0

    def rows_of(ws):
        return [row_of.get(w, -1) for w in ws]

    for (assignments, finished), (started, ended, arrived), resv in zip(
            log, gang_log, resv_log):
        rows = queue[:rows_per_tick]
        began = {g: rows_of(m) for g, m in started}
        now = {g: rows_of(m) for g, m in resv.items()}
        idle = (tasks_on == 0) & (gang_on < 0)
        # a gang whose n reserved workers all ran nothing starts on them
        for g in rows:
            held = standing.get(g, [])
            if (g < len(nodes) and len(held) == nodes[g]
                    and idle[held].all()
                    and sorted(began.get(g, [])) != sorted(held)):
                unhonoured += 1
        # a row that cannot start on idle workers holds n reserved ones
        for i, g in enumerate(rows):
            if g in began or g >= len(nodes):
                continue
            # as its turn found them: an earlier row's after its own
            # turn, every other's as they stood at the tick's start
            earlier = set(rows[:i])
            taken = np.zeros(n_w, dtype=bool)
            for h in (set(now) | set(standing)) - {g}:
                held = now.get(h, []) if h in earlier else standing.get(h, [])
                taken[[r for r in held if r >= 0]] = True
            free = (gang_on < 0) & ~taken
            if (np.bincount(group[free & idle], minlength=n_groups)
                    >= nodes[g]).any():
                continue
            if (np.bincount(group[free], minlength=n_groups).max()
                    >= nodes[g] and len(now.get(g, [])) != nodes[g]):
                unhonoured += 1
        reserved = np.zeros(n_w, dtype=bool)
        for held in now.values():
            reserved[[r for r in held if r >= 0]] = True
        for g, members in began.items():
            known = [r for r in members if r >= 0]
            if (g >= len(nodes) or len(set(members)) != nodes[g]
                    or len(known) != len(members)
                    or len(set(group[known].tolist())) != 1):
                split += 1
            shared += int(((tasks_on[known] > 0) | (gang_on[known] >= 0)).sum())
            gang_on[known] = g
            members_of[g] = known
            if set(known) & set(now.get(g, [])):
                continue  # it took workers reserved for it
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
            fed += int(reserved[row])
            tasks_on[row] += 1
            where[task_id & TASK_MASK] = row
        for t in finished:
            row = where.pop(t, None)
            if row is not None:
                tasks_on[row] -= 1
        for g in ended:
            gang_on[members_of.pop(g, [])] = -1
        # what stands at the next tick's start: a started gang's is lifted
        standing = {g: m for g, m in now.items() if g not in began}
        queue = [g for g in queue if g not in began]
        queue.extend(range(len(nodes), len(nodes) + len(arrived)))
        nodes.extend(arrived)
    return {"gang_split": split, "gang_shared": shared,
            "gang_overtaken": overtaken, "reserved_fed": fed,
            "reservation_unhonoured": unhonoured}


def run(ctx) -> dict:
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel, _bucket
    from hyperqueue_tpu.parallel.resident import _ROW_BUCKET_FLOOR
    from hyperqueue_tpu.scheduler.tick import create_batches, run_tick
    from hyperqueue_tpu.server import reactor
    from hyperqueue_tpu.server.core import Core

    if not (hasattr(Core, "set_gang_drain")
            and hasattr(reactor, "fused_gang_reserve")):
        raise SystemExit(
            "chipbench: this program has no --gang-drain busy "
            "(no Core.set_gang_drain): the cell cannot run on it")
    config, traffic = ctx.cell["config"], ctx.cell["traffic"]
    rows_per_tick = int(traffic["gang_rows_per_tick"])
    if not (reactor.MAX_FUSED_GANG_ROWS == rows_per_tick
            == int(config["gangs"]["rows_per_tick"])):
        raise SystemExit(
            f"chipbench: the cell states {rows_per_tick} gang rows a tick, "
            f"the program sends {reactor.MAX_FUSED_GANG_ROWS}")
    world = generate_shared.world(config, traffic, ctx.seed, ctx.scale)
    if int(world.gang_prio) != world.n_priorities - 1:
        raise SystemExit("chipbench: the gangs must be at the top priority")
    core, rq_ids, worker_ids, gang_rq = gang_driver.build_program_state(
        world, config)
    # what `hq server start --scheduler tpu --gang-drain busy` sets
    core.fused_solve = True
    core.set_gang_drain(config["gang_drain"])
    cluster = gang_driver.Cluster(world, core, rq_ids, ctx.seed, gang_rq)
    model_cls = spans.annotated_model(GreedyCutScanModel) if ctx.trace \
        else GreedyCutScanModel
    backend = (ctx.scale or {}).get("backend", "numpy") if ctx.rehearse \
        else "jax"
    model = model_cls(backend=backend)
    wanted_backend = ("device-jax",) if backend == "jax" \
        else ("host-native", "host-numpy")
    ann = spans.annotate
    solves_by_backend: dict = {}
    refused = 0
    rows_seen: list = []
    resv_raw: list = []
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
        gang_ok = group_ids = gang_resv = None
        if gang_rows:
            with ann("chipbench/gang_inputs"):
                gang_ok, group_ids = reactor.fused_gang_inputs(
                    core, snap.worker_ids, phases)
            with ann("chipbench/gang_reserve"):
                gang_resv = reactor.fused_gang_reserve(
                    core, cluster.comm, gang_rows, snap, gang_ok, group_ids,
                    batches, phases)
        with ann("chipbench/run_tick"):
            out = run_tick(
                core.queues, None, core.rq_map, core.resource_map, model,
                batches=batches, dense=snap, phases=phases,
                key_cache=core.tick_cache,
                gang_ok=gang_ok, group_ids=group_ids, gang_resv=gang_resv,
            )
        t3 = time.perf_counter()
        with ann("chipbench/apply"):
            single = cluster.apply(out, phases)
        t4 = time.perf_counter()
        phases.update(snapshot=(t1 - t0a) * 1e3, batches=(t2 - t1) * 1e3,
                      apply=(t4 - t3) * 1e3 - phases.get("gangs/apply", 0.0),
                      total=(t4 - t0) * 1e3)
        backend_now = model.last_backend
        solves_by_backend[backend_now] = \
            solves_by_backend.get(backend_now, 0) + 1
        refused += cluster.refused
        rows_seen.append(len(snap.worker_ids))
        resv_raw.append(record_reservations(gang_resv, snap.worker_ids))
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
                         bucket if delta else "full", bool(core.mn_queue)))

    def row_buckets(pw: int) -> list:
        return [_ROW_BUCKET_FLOOR << i
                for i in range((pw // 2 // _ROW_BUCKET_FLOOR).bit_length())]

    top = model._worker_bucket(len(worker_ids))

    def warm_delta_buckets(with_gangs: bool):
        """One tick per delta-upload bucket: one running task finishes on
        each of so many workers and no gang ends, so the rows stay and so
        many are dirty (a gang that starts moves rows: it is tried again)."""
        besides = 0
        for bucket in row_buckets(top):
            for _attempt in range(6):
                n = max(1, bucket * 3 // 4 - besides)
                before = model.resident_stats()
                cluster.churn(share, on_workers=n)
                tick()
                note_upload(before)
                dirty = model.resident_stats().get("dirty_rows_last", n)
                besides = max(0, dirty - n)
                if backend != "jax" or (top, bucket, with_gangs) in \
                        uploads_met:
                    break

    # -- set-up ---------------------------------------------------------------
    # the filler alone: fill, every delta bucket at the full worker bucket
    before = model.resident_stats()
    tick()
    note_upload(before)
    warm_delta_buckets(False)
    # the gangs arrive into a full cluster and reserve busy workers: until
    # the first drain ends no gang starts and no row moves, so every delta
    # bucket, and after a dropped residency the full upload, is met with
    # the gang inputs; then the settle steps
    cluster.churn(share, arrive=world.gang_nodes.tolist())
    before = model.resident_stats()
    tick()
    note_upload(before)
    warm_delta_buckets(True)
    model.invalidate_resident()
    for n_ticks, settle_share, settle_gang_share in (ctx.scale or {}).get(
            "settle", traffic["settle"]):
        for _ in range(int(n_ticks)):
            cluster.churn(float(settle_share),
                          gang_share=float(settle_gang_share))
            before = model.resident_stats()
            tick()
            note_upload(before)
    want = {(top, "full", True)} | {(top, b, True) for b in row_buckets(top)}
    uploads_not_met = sorted(f"{pw}:{k}:{'gangs' if g else 'filler'}"
                             for pw, k, g in want - uploads_met)
    if backend == "jax" and uploads_not_met:
        raise SystemExit(
            "chipbench: set-up did not meet the upload programs the window "
            f"meets; not met: {uploads_not_met}")
    cluster.churn(share, gang_share=gang_share)
    spans.gc_as_server_started(gc_settings)
    shapes_warm = model.shape_allocations
    uploads0 = model.resident_stats()
    cache0 = core.tick_cache.counters()
    started0 = gang_driver.counter_value(GANG_GROUPS_COUNTER)
    busy0 = gang_driver.counter_value(RESERVED_BUSY_COUNTER)
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
    started1 = gang_driver.counter_value(GANG_GROUPS_COUNTER)
    busy1 = gang_driver.counter_value(RESERVED_BUSY_COUNTER)
    compiles_in_window = ctx.compiles.count - compiles0
    in_window = {k: v - solves0.get(k, 0) for k, v in solves_by_backend.items()}
    failed = sum(v for k, v in in_window.items() if k not in wanted_backend)
    new_shapes = model.shape_allocations - shapes_warm
    memory_peak = ctx.memory_peak()
    window_rows = rows_seen[rows_in_setup:]
    if backend == "jax" and min(window_rows) <= top // 2:
        raise SystemExit(
            f"chipbench: the window met {min(window_rows)} dense rows, under "
            f"the worker bucket {top} the cell is sized for")
    gc.unfreeze()
    core = model = cluster.core = None  # the program's state is freed

    # -- the comparison -------------------------------------------------------
    t = time.perf_counter()
    resv_log = [reservation_sets(raw) for raw in resv_raw]
    compared = compare_with_reference(
        world, cluster.log, cluster.gang_log, resv_log, rq_ids, worker_ids,
        manifest.reference(config["reference"]),
    )
    audited = tick_driver.audit_placements(
        world, cluster.log, rq_ids, worker_ids)
    audited_shared = audit_shared(
        world, cluster.log, cluster.gang_log, resv_log, worker_ids,
        rows_per_tick)
    reference_s = time.perf_counter() - t
    total = np.asarray([p["total"] for p in ticks])
    window_gangs = cluster.gang_log[first_window_tick:]
    window_resv = resv_log[first_window_tick:]
    checks = [
        ("ticks_mismatched", compared["ticks_mismatched"], 0),
        ("rows_overcommitted", audited["rows_overcommitted"], 0),
        ("tasks_out_of_order", audited["tasks_out_of_order"], 0),
        ("priority_inversions", audited["priority_inversions"], 0),
        ("answers_unknown", audited["answers_unknown"] + refused, 0),
        ("gang_split", audited_shared["gang_split"], 0),
        ("gang_shared", audited_shared["gang_shared"], 0),
        ("gang_overtaken", audited_shared["gang_overtaken"], 0),
        ("reserved_fed", audited_shared["reserved_fed"], 0),
        ("reservation_unhonoured",
         audited_shared["reservation_unhonoured"], 0),
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
            "W": int(statistics.median(window_rows)),
            "R": world.worker_total.shape[1],
        },
        "groups": int(world.worker_group.max()) + 1,
        "gang_rows": rows_per_tick,
        "reservations": True,
        "kernel_module": "greedy_cut_scan_impl",
    }
    if started0 is not None and started1 is not None:
        observed["gangs_started_in_window"] = started1 - started0
    if busy0 is not None and busy1 is not None:
        observed["reserved_busy_in_window"] = busy1 - busy0

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
            "gang_submit_s": cluster.submit_s - submit_s0,
            "rows_min_p50_max": spread(window_rows),
            "gangs_started_a_tick_min_p50_max": spread(
                [len(g[0]) for g in window_gangs]),
            "gangs_ended_a_tick_min_p50_max": spread(
                [len(g[1]) for g in window_gangs]),
            "reserved_a_tick_min_p50_max": spread(
                [sum(len(m) for m in r.values()) for r in window_resv]),
            "gangs_running_at_close": len(cluster.running_gangs),
            "running_at_close": len(cluster.running),
            "assigned_in_window": sum(
                len(rec[0]) for rec in cluster.log[first_window_tick:]),
        },
    }
