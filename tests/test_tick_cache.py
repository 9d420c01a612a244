"""Incremental tick-state cache: golden parity, dirty tracking, phase
stats, and the satellite regression tests that ride with the PR
(stream-writer eviction, stream placeholders, --array subsetting,
selector parsing, the pure-Python ChaCha20-Poly1305 fallback)."""

from __future__ import annotations

import random

import numpy as np
import pytest

from utils_env import TestEnv

from hyperqueue_tpu.scheduler.tick import assemble_solve_inputs, create_batches
from hyperqueue_tpu.scheduler.tick_cache import (
    paranoid_check,
    walk_gang_inputs,
)
from hyperqueue_tpu.server import reactor


def _scratch_kwargs(core):
    rows = [r for r in core.worker_rows() if r.cpu_floor <= 0]
    batches = create_batches(core.queues)
    return assemble_solve_inputs(
        rows, batches, core.rq_map, core.resource_map
    )


def _incremental_kwargs(core):
    snap = core.tick_cache.sync(core)
    assert snap is not None
    batches = create_batches(core.queues)
    return assemble_solve_inputs(
        None, batches, core.rq_map, core.resource_map, dense=snap,
        key_cache=core.tick_cache,
    )


def _assert_kwargs_equal(a, b):
    assert set(a) == set(b), (set(a), set(b))
    for key in a:
        if key == "priorities":
            assert a[key] == b[key]
            continue
        assert np.array_equal(np.asarray(a[key]), np.asarray(b[key])), key


def _assert_snapshot_is_scratch(core):
    """The cache's dense rows against rows read whole from the workers,
    array by array, and (where tasks wait) `paranoid_check` besides."""
    snap = core.tick_cache.sync(core)
    rows = core.worker_rows()
    if snap is None:
        assert not rows or any(r.cpu_floor > 0 for r in rows) or any(
            w.configuration.min_utilization > 0.001
            for w in core.workers.values()
        )
        return
    n_r = len(core.resource_map)
    assert snap.worker_ids == [r.worker_id for r in rows]
    assert isinstance(snap.worker_ids, list)
    assert snap.free.shape == snap.total.shape == (len(rows), n_r)
    assert snap.free.dtype == snap.total.dtype == np.int64
    assert snap.nt_free.dtype == snap.lifetime.dtype == np.int32
    for i, r in enumerate(rows):
        pad = [0] * (n_r - len(r.free))
        assert snap.free[i].tolist() == list(r.free) + pad, r.worker_id
        pad = [0] * (n_r - len(r.total))
        assert snap.total[i].tolist() == list(r.total) + pad, r.worker_id
        assert snap.nt_free[i] == max(r.nt_free, 0), r.worker_id
        assert abs(int(snap.lifetime[i]) - r.lifetime_secs) <= 1
    if core.queues.total_ready():
        paranoid_check(
            core, snap, create_batches(core.queues), core.rq_map,
            core.resource_map,
        )


def _assert_gang_inputs_are_walk(core):
    """The gang inputs as a tick with gang rows reads them, against the
    workers: the idleness column at the dense rows is `is_idle()` row by
    row, and the renumbered groups are the walk's."""
    cache = core.tick_cache
    snap = cache.sync(core)
    if snap is None:
        return
    gang_ok, group_ids = reactor.fused_gang_inputs(core, snap.worker_ids)
    rows = [core.workers[wid] for wid in snap.worker_ids]
    assert cache._idle.take(cache._rows).tolist() == [
        w.is_idle() for w in rows]
    assert gang_ok.tolist() == [int(w.is_idle()) for w in rows]
    want_ok, want_groups = walk_gang_inputs(core.workers, snap.worker_ids)
    assert gang_ok.tolist() == want_ok.tolist()
    assert group_ids.tolist() == want_groups.tolist()
    assert gang_ok.dtype == group_ids.dtype == np.int32


# ---------------------------------------------------------------- golden
def test_randomized_incremental_vs_scratch_golden():
    """>= 300 random mutation steps (submits, schedules, finishes, worker
    joins/leaves, resource-map widening; gangs that reserve, start and end
    through the host gang phase and through the fused one —
    `_apply_fused_gangs`, `_release_task_resources`,
    `_clear_mn_reservations` — cancelled gangs, drains, prefilled tasks
    that arrive, start and are released); after EVERY step the incremental
    snapshot must be bit-identical to a from-scratch one
    (`paranoid_check`), and the gang inputs read from its columns must be
    the walk's.  paranoid_tick=1 additionally runs the production paranoid
    check inside every schedule()."""
    env = TestEnv()
    env.core.paranoid_tick = 1
    rng = random.Random(7)
    assigned_pool: list[int] = []
    worker_ids: list[int] = []
    extra_resources = 0
    seen = {"fused": 0, "host": 0, "reserve": 0, "cancel": 0, "drain": 0,
            "prefill": 0, "unprefill": 0, "prefill_only": 0, "read": 0}

    for _ in range(4):
        worker_ids.append(env.worker(
            cpus=rng.choice([2, 4, 8]), group=rng.choice("ab")).worker_id)

    mutations = 0
    while mutations < 320:
        op = rng.random()
        if op < 0.26:
            rqv = env.rqv(
                cpus=rng.choice([1, 1, 2]),
                gpus=rng.choice([0, 0, 0, 1]),
            )
            env.submit(
                n=rng.randrange(1, 6), rqv=rqv,
                priority=(rng.randrange(0, 3), 0),
            )
            mutations += 1
        elif op < 0.42 and assigned_pool:
            # a finished gang leaves through _release_task_resources
            env.finish(assigned_pool.pop(rng.randrange(len(assigned_pool))))
            mutations += 1
        elif op < 0.52:
            gpus = rng.choice([0, 0, 2])
            worker_ids.append(env.worker(
                cpus=rng.choice([2, 4, 8]), gpus=gpus,
                group=rng.choice("ab"),
            ).worker_id)
            mutations += 1
        elif op < 0.58 and len(worker_ids) > 2:
            wid = worker_ids.pop(rng.randrange(len(worker_ids)))
            worker = env.core.workers[wid]
            gone = set(worker.assigned_tasks) | {worker.mn_task}
            env.lose_worker(wid, clean=worker.draining)
            assigned_pool[:] = [
                t for t in assigned_pool
                if t not in gone
                and env.core.tasks[t].state.value == "running"
            ]
            mutations += 1
        elif op < 0.62:
            # widen the resource map without touching any worker (a task
            # naming a fresh resource interns it)
            extra_resources += 1
            env.core.resource_map.get_or_create(f"res{extra_resources}")
            mutations += 1
        elif op < 0.74:
            # a pending gang reserves (and later releases) workers —
            # membership changes without connect/disconnect
            env.submit(
                rqv=env.rqv(n_nodes=rng.choice([2, 2, 3])),
                priority=(rng.choice([0, 5]), 0),
            )
            mutations += 1
        elif op < 0.78 and env.core.mn_queue:
            # a cancelled gang lifts its reservations
            reserved = {w.mn_reserved for w in env.core.workers.values()}
            gang = min(env.core.mn_queue, key=lambda g: g not in reserved)
            seen["cancel"] += gang in reserved
            env.cancel([gang])
            mutations += 1
        elif op < 0.82 and len(worker_ids) > 3:
            # a drain masks a worker out (Server.start_drain's own site)
            wid = rng.choice(worker_ids)
            if env.start_drain([wid]):
                seen["drain"] += 1
                mutations += 1
        elif op < 0.88:
            # the next schedules run the other gang phase
            env.core.fused_solve = not env.core.fused_solve
            mutations += 1
        _assert_snapshot_is_scratch(env.core)
        reads0 = env.core.tick_cache.gang_input_reads
        _assert_gang_inputs_are_walk(env.core)
        seen["read"] += env.core.tick_cache.gang_input_reads - reads0
        if rng.random() < 0.5 and (
            env.core.queues.total_ready() or env.core.mn_queue
        ):
            # schedule() runs the paranoid bit-identity check itself; every
            # other one prefills, and every third cancels a prefilled task,
            # so prefilled sets fill and empty
            before = {
                t for t, task in env.core.tasks.items()
                if task.state.value == "running"
            }
            flips0 = env.core.tick_cache.membership_flips
            env.schedule(prefill=mutations % 2 == 0)
            seen["prefill"] += any(
                w.prefilled_tasks for w in env.core.workers.values())
            _assert_gang_inputs_are_walk(env.core)
            # a worker that runs nothing and holds prefilled tasks first:
            # all of them are cancelled, and the last one leaving makes it
            # idle; else one task of a busy worker
            held = sorted(
                (len(w.assigned_tasks) > 0, w.worker_id,
                 sorted(w.prefilled_tasks))
                for w in env.core.workers.values() if w.prefilled_tasks
            )
            if held and mutations % 3 == 0:
                busy, _, tasks = held[0]
                seen["prefill_only"] += not busy
                env.cancel(tasks[:1] if busy else tasks)
                seen["unprefill"] += 1
                _assert_gang_inputs_are_walk(env.core)
            env.start_all_assigned()
            started = {
                t for t, task in env.core.tasks.items()
                if task.state.value == "running"
            } - before
            assigned_pool.extend(started)
            if any(env.core.tasks[t].mn_workers for t in started):
                seen["fused" if env.core.fused_solve else "host"] += 1
            seen["reserve"] += any(
                w.mn_reserved for w in env.core.workers.values())
            _assert_snapshot_is_scratch(env.core)
            _assert_gang_inputs_are_walk(env.core)
            assert env.core.tick_cache.membership_flips >= flips0
        # independent explicit comparison of both assembly paths
        if env.core.queues.total_ready() and any(
            w.mn_task == 0 and w.mn_reserved == 0 and not w.draining
            for w in env.core.workers.values()
        ):
            _assert_kwargs_equal(
                _scratch_kwargs(env.core), _incremental_kwargs(env.core)
            )
    assert mutations >= 320
    assert env.core.tick_cache.incremental_syncs > 0
    assert env.core.tick_cache.membership_flips > 0
    # a walk writes the columns after each build, and only then
    cache = env.core.tick_cache
    assert 0 < cache.gang_input_walks <= cache.full_rebuilds
    # the walk took every road it was built to take
    assert all(seen.values()), seen


def _assert_reservation_column_is_walk(core):
    """The reservation column at the dense rows against the workers'
    `mn_reserved`, and under `--gang-drain busy` a reserved worker is a
    dense row."""
    cache = core.tick_cache
    snap = cache.sync(core)
    assert cache.reservations_told(core.workers)
    if snap is None:
        return
    assert cache.reservations().tolist() == [
        core.workers[w].mn_reserved for w in snap.worker_ids]
    if core.gang_drain == "busy":
        rows = set(snap.worker_ids)
        assert all(
            w.worker_id in rows for w in core.workers.values()
            if w.mn_reserved and not w.mn_task and not w.draining)


def test_randomized_reservation_column_is_the_walk():
    """Random histories under `--gang-drain busy` (submits, schedules with
    and without prefill, finishes, gangs that reserve, start, end and are
    cancelled, workers that join, leave and drain, a switch to `idle` and
    back): after every step the told reservation column equals a walk over
    the workers, and `paranoid_tick=1` holds it to the walk inside every
    schedule() besides."""
    for seed in (3, 11):
        env = TestEnv()
        env.core.paranoid_tick = 1
        env.core.fused_solve = True
        env.core.set_gang_drain("busy")
        rng = random.Random(seed)
        running: list[int] = []
        worker_ids = [env.worker(cpus=rng.choice([2, 4]),
                                 group=rng.choice("ab")).worker_id
                      for _ in range(6)]
        reserved_seen = 0
        for step in range(160):
            op = rng.random()
            if op < 0.3:
                env.submit(n=rng.randrange(1, 6), priority=(1, 0))
            elif op < 0.42:
                env.submit(rqv=env.rqv(n_nodes=rng.choice([2, 3])),
                           priority=(1, 0))
            elif op < 0.6 and running:
                env.finish(running.pop(rng.randrange(len(running))))
            elif op < 0.66:
                worker_ids.append(env.worker(
                    cpus=rng.choice([2, 4]), group=rng.choice("ab")).worker_id)
            elif op < 0.7 and len(worker_ids) > 3:
                wid = worker_ids.pop(rng.randrange(len(worker_ids)))
                worker = env.core.workers[wid]
                gone = set(worker.assigned_tasks) | {worker.mn_task}
                env.lose_worker(wid)
                running = [t for t in running if t not in gone
                           and env.core.tasks[t].state.value == "running"]
            elif op < 0.74 and env.core.mn_queue:
                env.cancel([rng.choice(env.core.mn_queue)])
            elif op < 0.77 and len(worker_ids) > 4:
                env.start_drain([rng.choice(worker_ids)])
            elif op < 0.79:
                env.core.set_gang_drain(
                    "idle" if env.core.gang_drain == "busy" else "busy")
            _assert_reservation_column_is_walk(env.core)
            if env.core.queues.total_ready() or env.core.mn_queue:
                env.schedule(prefill=step % 2 == 0)
                env.start_all_assigned()
                running = [t for t, task in env.core.tasks.items()
                           if task.state.value == "running"]
                reserved_seen += any(
                    w.mn_reserved for w in env.core.workers.values())
                _assert_reservation_column_is_walk(env.core)
        assert reserved_seen > 3
        assert env.core.tick_cache.gang_reserved > 0


def _parents_fused_gang_hold(core, rows) -> set:
    """The soft drain of `--gang-drain idle` as `reactor._tick` walked it
    before it read the snapshot's columns."""
    hold: set = set()
    top_sn = reactor._top_sn_priority(core)
    for gb in rows:
        if top_sn is not None and top_sn[0] > gb.priority[0]:
            continue
        req = core.rq_map.get_variants(gb.rq_id).variants[0]
        groups: dict = {}
        for w in core.workers.values():
            if (w.mn_task or w.draining or w.worker_id in hold
                    or not reactor._mn_member_eligible(w, req)):
                continue
            groups.setdefault(w.group, []).append(w)
        best = max(groups.values(), key=len, default=None)
        if best is None or len(best) < gb.gang_nodes:
            continue
        best.sort(key=lambda w: (
            not w.is_idle(),
            len(w.assigned_tasks) + len(w.prefilled_tasks),
            w.worker_id,
        ))
        hold.update(w.worker_id for w in best[:gb.gang_nodes])
    return hold


@pytest.mark.parametrize("seed", [1, 4, 9, 2147483659])
def test_fused_gang_hold_reads_the_columns_and_equals_the_walk(seed):
    """Under `--gang-drain idle` the prefill-exempt hold, read from the
    snapshot's columns, is the set the walk over every worker gave, over
    random states: groups of several sizes, idle and busy workers with
    assigned and prefilled tasks, gangs of several sizes and priorities,
    some outranked, some finding no group."""
    rng = random.Random(seed)
    env = TestEnv()
    env.core.fused_solve = True
    for _ in range(rng.randrange(6, 20)):
        env.worker(cpus=rng.choice([1, 2, 4]), group=rng.choice("abc"))
    for _round in range(6):
        env.submit(n=rng.randrange(0, 12), priority=(rng.choice([0, 1]), 0))
        for _ in range(rng.randrange(1, 5)):
            env.submit(rqv=env.rqv(n_nodes=rng.choice([1, 2, 3, 5])),
                       priority=(rng.choice([0, 1, 2]), 0))
        core = env.core
        rows = reactor.fused_gang_rows(core)
        snap = core.tick_cache.sync(core)
        if rows and snap is not None:
            gang_ok, group_ids = reactor.fused_gang_inputs(
                core, snap.worker_ids)
            assert reactor.fused_gang_hold(
                core, rows, snap, gang_ok, group_ids,
                create_batches(core.queues) + rows,
            ) == _parents_fused_gang_hold(core, rows)
        env.schedule(prefill=True)
        env.start_all_assigned()
        for task in list(env.core.tasks.values()):
            if task.state.value == "running" and rng.random() < 0.3:
                env.finish(task.task_id)


# ---------------------------------------------------------- dirty tracking
def test_steady_state_zero_full_rebuilds():
    env = TestEnv()
    for _ in range(3):
        env.worker(cpus=4)
    ids = env.submit(n=30)
    env.schedule()
    rebuilds = env.core.tick_cache.full_rebuilds
    env.start_all_assigned()
    for t in ids[:8]:
        env.finish(t)
    env.schedule()
    env.schedule()
    assert env.core.tick_cache.full_rebuilds == rebuilds
    assert env.core.tick_cache.incremental_syncs >= 2


def test_connect_disconnect_trigger_rebuild():
    env = TestEnv()
    w1 = env.worker(cpus=4)
    env.submit(n=4)
    env.schedule()
    r0 = env.core.tick_cache.full_rebuilds
    w2 = env.worker(cpus=2)
    env.submit(n=1)
    env.schedule()
    assert env.core.tick_cache.full_rebuilds == r0 + 1
    assert w2.worker_id in env.core.tick_cache.worker_ids
    env.lose_worker(w1.worker_id)
    env.submit(n=1)
    env.schedule()
    assert env.core.tick_cache.full_rebuilds == r0 + 2
    assert w1.worker_id not in env.core.tick_cache.worker_ids


def _record_rows_of_every_sync(cache) -> list:
    """Wrap `cache.sync` so the dense row set after each call is kept."""
    seen: list = []
    sync = cache.sync

    def recording(core, phases=None):
        snap = sync(core, phases)
        seen.append(set(cache.worker_ids))
        return snap

    cache.sync = recording
    return seen


def test_steady_gang_churn_flips_rows_and_rebuilds_nothing():
    """A gang starts and one ends every tick (`_apply_fused_gangs`,
    `_release_task_resources`): the rows that leave and rejoin are flips,
    counted one by one, and no tick builds the rows whole."""
    env = TestEnv()
    env.core.fused_solve = True
    env.core.paranoid_tick = 1
    for i in range(12):
        env.worker(cpus=4, group="ab"[i % 2])
    gangs = [env.submit(rqv=env.rqv(n_nodes=2), priority=(1, 0))[0]
             for _ in range(3)]
    env.submit(n=4)
    env.schedule()
    env.start_all_assigned()
    running = [g for g in gangs if env.core.tasks[g].mn_workers]
    assert len(running) == 3
    cache = env.core.tick_cache
    env.schedule()  # the three gangs' rows leave
    assert len(cache.worker_ids) == 6
    rebuilds, flips = cache.full_rebuilds, cache.membership_flips
    rows = _record_rows_of_every_sync(cache)
    rows.append(set(cache.worker_ids))
    for _ in range(20):
        env.finish(running.pop(0))
        gang = env.submit(rqv=env.rqv(n_nodes=2), priority=(1, 0))[0]
        env.submit(n=1)
        env.schedule()
        env.start_all_assigned()
        assert env.core.tasks[gang].mn_workers, "the new gang starts at once"
        running.append(gang)
        # two rows rejoined, the two the last tick's gang took left
        assert cache.rows_moved_last > 0
    assert cache.full_rebuilds == rebuilds
    moved = sum(len(a ^ b) for a, b in zip(rows, rows[1:]))
    assert moved >= 40
    assert cache.membership_flips - flips == moved
    assert cache.counters()["membership_flips"] == cache.membership_flips
    _assert_snapshot_is_scratch(env.core)


def test_unnamed_bump_and_unseen_worker_build_the_rows_whole():
    env = TestEnv()
    workers = [env.worker(cpus=4) for _ in range(4)]
    env.submit(n=2)
    env.schedule()
    cache = env.core.tick_cache
    r0, f0 = cache.full_rebuilds, cache.membership_flips
    # a bump that names no worker: everything is walked, and found
    workers[1].mn_reserved = 77
    env.core.bump_membership()
    _assert_snapshot_is_scratch(env.core)
    assert workers[1].worker_id not in cache.worker_ids
    assert (cache.full_rebuilds, cache.membership_flips) == (r0 + 1, f0)
    # named: a flip, no build
    workers[1].mn_reserved = 0
    env.core.bump_membership(workers[1])
    _assert_snapshot_is_scratch(env.core)
    assert (cache.full_rebuilds, cache.membership_flips) == (r0 + 1, f0 + 1)
    # a worker put into core.workers with no word to anybody
    from hyperqueue_tpu.server.worker import Worker

    stray = Worker.create(
        env.core.worker_id_counter.next(), workers[0].configuration,
        env.core.resource_map,
    )
    env.core.workers[stray.worker_id] = stray
    _assert_snapshot_is_scratch(env.core)
    assert stray.worker_id in cache.worker_ids
    assert cache.full_rebuilds == r0 + 2
    # a named worker the rows do not hold (it joined unseen, as above, and
    # another left: the count alone would not tell)
    env.core.workers.pop(workers[3].worker_id)
    stray2 = Worker.create(
        env.core.worker_id_counter.next(), workers[0].configuration,
        env.core.resource_map,
    )
    env.core.workers[stray2.worker_id] = stray2
    env.core.bump_membership(stray2)
    _assert_snapshot_is_scratch(env.core)
    assert cache.full_rebuilds == r0 + 3
    # an epoch moved behind the cache's back
    workers[2].draining = True
    env.core.membership_epoch += 1
    _assert_snapshot_is_scratch(env.core)
    assert cache.full_rebuilds == r0 + 4
    # and a lost worker's later bookkeeping reaches no row of the new build
    gone = workers[3]
    gone.assign(424_242, [(0, 10_000)])
    _assert_snapshot_is_scratch(env.core)
    assert cache.rows_rewritten_last == 0
    assert cache.full_rebuilds == r0 + 4


@pytest.mark.parametrize("n_dirty", [0, 1, 2, 3, 16],
                         ids=lambda n: f"{n}-of-16")
def test_dirty_rows_give_scratch_arrays_whatever_their_share(n_dirty):
    """One path whatever the share of dirty rows: 1, n/8 (where a deleted
    threshold once switched to a second path), just above it, and every
    row — with workers whose `free` is shorter than the map among them."""
    env = TestEnv()
    workers = [env.worker(cpus=8, gpus=2 if i % 3 == 0 else 0)
               for i in range(16)]
    env.core.resource_map.get_or_create("fpga")
    env.submit(n=5, rqv=env.rqv(cpus=2))
    _assert_snapshot_is_scratch(env.core)
    cache = env.core.tick_cache
    rebuilds = cache.full_rebuilds
    for k, w in enumerate(workers[:n_dirty]):
        w.assign(900_000 + k, [(0, 10_000 * (k + 1))])
        if k % 2:
            w.assign(910_000 + k, [(0, 10_000)])
            w.unassign(910_000 + k, [(0, 10_000)])
    _assert_snapshot_is_scratch(env.core)
    assert cache.rows_rewritten_last == n_dirty
    assert cache.rows_moved_last == 0
    assert cache.full_rebuilds == rebuilds
    # nothing moved since: nothing is written
    _assert_snapshot_is_scratch(env.core)
    assert cache.rows_rewritten_last == 0


@pytest.mark.parametrize("fused", [False, True], ids=["host", "fused"])
def test_gang_phases_name_every_worker_they_flip(fused):
    """The server's tick for some tens of ticks under `--paranoid-tick 1`:
    the host gang phase (reserve, drain towards a gang, release, claim) and
    the fused one, with gangs ending, one cancelled while it holds
    reservations, a worker drained and one lost.  A site that flipped
    `mn_task`, `mn_reserved` or `draining` without naming the worker would
    fail the tick's own bit-identity check here, not in a deployment."""
    env = TestEnv()
    env.core.paranoid_tick = 1
    env.core.fused_solve = fused
    rng = random.Random(34)
    for i in range(10):
        env.worker(cpus=2, group="ab"[i % 2])
    cache = env.core.tick_cache
    running: list[int] = []
    reserved_seen = gangs_started = gangs_ended = 0

    def tick():
        nonlocal reserved_seen, gangs_started
        before = {t for t, task in env.core.tasks.items()
                  if task.state.value == "running"}
        env.schedule(prefill=True)
        env.start_all_assigned()
        started = {t for t, task in env.core.tasks.items()
                   if task.state.value == "running"} - before
        running.extend(started)
        gangs_started += sum(
            1 for t in started if env.core.tasks[t].mn_workers)
        reserved_seen += any(
            w.mn_reserved for w in env.core.workers.values())
        _assert_snapshot_is_scratch(env.core)

    env.submit(n=16, rqv=env.rqv(cpus=1))
    tick()
    rebuilds = cache.full_rebuilds
    for i in range(40):
        # single-node work keeps arriving, gangs of a whole group's half
        # wait for nodes that must drain first
        env.submit(n=rng.randrange(1, 5), rqv=env.rqv(cpus=1))
        if i % 3 == 0:
            env.submit(rqv=env.rqv(n_nodes=rng.choice([2, 3])),
                       priority=(rng.choice([0, 3]), 0))
        for _ in range(rng.randrange(2, 7)):
            if running:
                t = running.pop(rng.randrange(len(running)))
                gangs_ended += bool(env.core.tasks[t].mn_workers)
                env.finish(t)
        if i == 20:
            holding = {w.mn_reserved for w in env.core.workers.values()} - {0}
            if holding:
                env.cancel([holding.pop()])
        tick()
    assert cache.full_rebuilds == rebuilds
    assert gangs_started >= 5 and gangs_ended >= 3
    assert cache.membership_flips >= 2 * gangs_started
    if not fused:
        assert reserved_seen >= 5
    # a drain is a flip; a lost worker is the structural case
    w = next(w for w in env.core.workers.values() if not w.mn_task)
    assert env.start_drain([w.worker_id]) == [w.worker_id]
    tick()
    assert w.worker_id not in cache.worker_ids
    assert cache.full_rebuilds == rebuilds
    gone = set(w.assigned_tasks)
    env.lose_worker(w.worker_id, clean=True)
    running[:] = [t for t in running if t not in gone]
    tick()
    assert cache.full_rebuilds == rebuilds + 1


def test_a_flip_nobody_named_fails_the_paranoid_tick():
    env = TestEnv()
    env.core.paranoid_tick = 1
    workers = [env.worker(cpus=4) for _ in range(3)]
    env.submit(n=2)
    env.schedule()
    workers[1].mn_task = 99  # what a forgetful site would do
    env.submit(n=1)
    with pytest.raises(AssertionError, match="row order diverged"):
        env.schedule()


def test_resource_map_widening_pads_columns():
    env = TestEnv()
    env.worker(cpus=4)
    env.submit(n=2)
    env.schedule()
    old_width = env.core.tick_cache.n_r
    env.core.resource_map.get_or_create("fpga")
    env.submit(n=1)
    _assert_kwargs_equal(
        _scratch_kwargs(env.core), _incremental_kwargs(env.core)
    )
    assert env.core.tick_cache.n_r == old_width + 1
    assert np.all(env.core.tick_cache.free[:, old_width:] == 0)


def test_overcommit_negative_free_stays_bit_identical():
    """Prefill races can drive a worker's free negative; the cache must
    mirror the raw (negative) value exactly like the scratch snapshot."""
    env = TestEnv()
    w = env.worker(cpus=2)
    env.submit(n=2)
    env.schedule()
    # force over-commit directly (what a prefill race does)
    w.assign(999_001, [(0, 50_000)])
    assert w.free[0] < 0
    env.submit(n=1)
    a = _scratch_kwargs(env.core)
    b = _incremental_kwargs(env.core)
    _assert_kwargs_equal(a, b)
    row = env.core.tick_cache.worker_ids.index(w.worker_id)
    assert env.core.tick_cache.free[row, 0] < 0
    assert env.core.tick_cache.nt_free[row] >= 0  # clamped like scratch


def test_min_utilization_worker_disables_cache():
    env = TestEnv()
    w = env.worker(cpus=4)
    w.configuration.min_utilization = 0.5
    env.core.bump_membership()
    env.submit(n=3)
    assert env.core.tick_cache.sync(env.core) is None
    # the reactor must still schedule through the legacy path
    n = env.schedule()
    assert n > 0


def test_paranoid_check_detects_corruption():
    env = TestEnv()
    env.worker(cpus=4)
    env.submit(n=4)
    snap = env.core.tick_cache.sync(env.core)
    batches = create_batches(env.core.queues)
    paranoid_check(
        env.core, snap, batches, env.core.rq_map, env.core.resource_map
    )  # clean state passes
    env.core.tick_cache.free[0, 0] += 7  # corrupt without an epoch bump
    with pytest.raises(AssertionError):
        paranoid_check(
            env.core, snap, batches, env.core.rq_map, env.core.resource_map
        )


# ------------------------------------------------------------ gang inputs
def _gang_inputs(core):
    snap = core.tick_cache.sync(core)
    return reactor.fused_gang_inputs(core, snap.worker_ids)


def test_interleaved_groups_are_numbered_by_first_appearance():
    """Groups a, a, b, b, b, a in row order number 0, 0, 1, 1, 1, 0 from
    the columns as from the walk; a seventh worker whose group's name sorts
    first but appears last still numbers last."""
    env = TestEnv()
    for group in "aabbba":
        env.worker(cpus=2, group=group)
    cache = env.core.tick_cache
    assert _gang_inputs(env.core)[1].tolist() == [0, 0, 1, 1, 1, 0]
    assert (cache.gang_input_walks, cache.gang_input_reads) == (1, 0)
    gang_ok, group_ids = _gang_inputs(env.core)
    assert (cache.gang_input_walks, cache.gang_input_reads) == (1, 1)
    assert group_ids.tolist() == [0, 0, 1, 1, 1, 0]
    assert gang_ok.tolist() == [1] * 6
    env.worker(cpus=2, group="0-first-by-name")
    assert _gang_inputs(env.core)[1].tolist() == [0, 0, 1, 1, 1, 0, 2]
    assert _gang_inputs(env.core)[1].tolist() == [0, 0, 1, 1, 1, 0, 2]
    assert (cache.gang_input_walks, cache.gang_input_reads) == (2, 2)


def test_a_group_whose_first_row_leaves_moves_back_in_the_order():
    """Rows a, b, a, b: a numbers 0.  Once the first a drains, b's row comes
    first and numbers 0 — with no build and no walk."""
    env = TestEnv()
    workers = [env.worker(cpus=2, group=g) for g in "abab"]
    cache = env.core.tick_cache
    assert _gang_inputs(env.core)[1].tolist() == [0, 1, 0, 1]
    rebuilds, walks = cache.full_rebuilds, cache.gang_input_walks
    assert env.start_drain([workers[0].worker_id])
    gang_ok, group_ids = _gang_inputs(env.core)
    assert cache.worker_ids == [w.worker_id for w in workers[1:]]
    assert group_ids.tolist() == [0, 1, 0]
    assert (cache.full_rebuilds, cache.gang_input_walks) == (rebuilds, walks)
    assert group_ids.tolist() == walk_gang_inputs(
        env.core.workers, cache.worker_ids)[1].tolist()


def test_each_funnel_tells_the_idleness_column():
    """assign/unassign and PrefilledTasks.add/discard each write the
    worker's idleness where their set goes empty or stops being so; the
    column is read, never walked, after the first call."""
    env = TestEnv()
    w, other = env.worker(cpus=4), env.worker(cpus=4)
    cache = env.core.tick_cache
    assert _gang_inputs(env.core)[0].tolist() == [1, 1]
    walks = cache.gang_input_walks
    w.assign(700_001, [(0, 10_000)])
    assert _gang_inputs(env.core)[0].tolist() == [0, 1]
    w.assign(700_002, [(0, 10_000)])
    w.unassign(700_001, [(0, 10_000)])
    assert _gang_inputs(env.core)[0].tolist() == [0, 1]
    w.unassign(700_002, [(0, 10_000)])
    assert _gang_inputs(env.core)[0].tolist() == [1, 1]
    other.prefilled_tasks.add(700_003, 0)
    assert _gang_inputs(env.core)[0].tolist() == [1, 0]
    other.assign(700_004, [(0, 10_000)])
    other.prefilled_tasks.discard(700_003, 0)
    assert _gang_inputs(env.core)[0].tolist() == [1, 0]
    other.unassign(700_004, [(0, 10_000)])
    assert _gang_inputs(env.core)[0].tolist() == [1, 1]
    # prefilled alone: the set's own emptiness flips the worker
    other.prefilled_tasks.add(700_005, 1)
    other.prefilled_tasks.add(700_006, 0)
    assert _gang_inputs(env.core)[0].tolist() == [1, 0]
    other.prefilled_tasks.discard(700_005, 1)
    assert _gang_inputs(env.core)[0].tolist() == [1, 0]
    other.prefilled_tasks.discard(700_006, 0)
    assert _gang_inputs(env.core)[0].tolist() == [1, 1]
    assert cache.gang_input_walks == walks
    # a worker's list that is not the snapshot's own is walked
    gang_ok, _ = reactor.fused_gang_inputs(
        env.core, list(cache.worker_ids))
    assert gang_ok.tolist() == [1, 1]
    assert cache.gang_input_walks == walks + 1


def test_a_membership_change_not_yet_synced_is_walked():
    """A gang started on a row since the last sync: the row is still in the
    snapshot's list, and the walk's `is_idle()` sees the gang where the
    column cannot, so the call walks until the next sync."""
    env = TestEnv()
    w, _ = env.worker(cpus=4), env.worker(cpus=4)
    cache = env.core.tick_cache
    assert _gang_inputs(env.core)[0].tolist() == [1, 1]
    w.mn_task = 424_242
    env.core.bump_membership(w)
    walks = cache.gang_input_walks
    gang_ok, _ = reactor.fused_gang_inputs(env.core, cache.worker_ids)
    assert gang_ok.tolist() == [0, 1]
    assert cache.gang_input_walks == walks + 1
    assert _gang_inputs(env.core)[0].tolist() == [1]  # synced: w left
    assert cache.gang_input_walks == walks + 1


def test_a_core_without_gang_rows_never_builds_the_gang_columns():
    """Ticks that assign, prefill, finish and cancel, with no multi-node
    task: no gang input is asked, no column is built or attached, and both
    counters read 0."""
    env = TestEnv()
    env.core.fused_solve = True
    workers = [env.worker(cpus=2, group="ab"[i % 2]) for i in range(4)]
    ids = env.submit(n=30)
    for _ in range(4):
        env.schedule(prefill=True)
        env.start_all_assigned()
        running = [t for t in ids
                   if env.core.tasks[t].state.value == "running"]
        for t in running[:3]:
            env.finish(t)
    held = [t for w in workers for t in w.prefilled_tasks]
    assert held, "the ticks prefilled"
    env.cancel(held[:1])
    cache = env.core.tick_cache
    assert (cache.gang_input_walks, cache.gang_input_reads) == (0, 0)
    assert cache._idle is None and cache._group is None
    assert all(w.tick_idle is None for w in workers)
    counters = cache.counters()
    assert (counters["gang_input_walks"], counters["gang_input_reads"]) == \
        (0, 0)


def test_paranoid_check_names_the_first_gang_row_that_differs():
    env = TestEnv()
    workers = [env.worker(cpus=4, group="ab"[i % 2]) for i in range(4)]
    env.submit(n=2)
    gang_ok, group_ids = _gang_inputs(env.core)
    snap = env.core.tick_cache.sync(env.core)
    batches = create_batches(env.core.queues)
    paranoid_check(env.core, snap, batches, env.core.rq_map,
                   env.core.resource_map, gang_ok=gang_ok,
                   group_ids=group_ids)  # clean state passes
    # a prefilled task that bypassed its funnel
    set.add(workers[2].prefilled_tasks, 800_001)
    gang_ok, group_ids = _gang_inputs(env.core)
    with pytest.raises(AssertionError, match=(
            rf"gang_ok diverged from the walk at row 2 "
            rf"\(worker {workers[2].worker_id}")):
        paranoid_check(env.core, snap, batches, env.core.rq_map,
                       env.core.resource_map, gang_ok=gang_ok,
                       group_ids=group_ids)


def test_phase_stats_recorded():
    env = TestEnv()
    env.worker(cpus=4)
    env.submit(n=8)
    env.schedule()
    stats = env.core.tick_stats
    assert stats.ticks >= 1
    snap = stats.snapshot()
    for phase in ("batches", "assemble", "mapping", "total"):
        assert phase in snap["phases"], snap
    counters = env.core.tick_cache.counters()
    assert counters["full_rebuilds"] >= 1
    assert counters["workers"] == 1


def test_dense_solve_assignments_match_legacy():
    """Same queue/worker state scheduled through the cache and through
    from-scratch WorkerRows must produce identical assignments."""
    import copy

    def build():
        env = TestEnv()
        for cpus in (2, 4, 8):
            env.worker(cpus=cpus)
        env.submit(n=12, rqv=env.rqv(cpus=1), priority=(1, 0))
        env.submit(n=7, rqv=env.rqv(cpus=2), priority=(3, 0))
        return env

    env_a = build()  # cache path (default)
    env_b = build()  # legacy path: force by pretending a mu worker exists
    env_a.schedule()
    orig_sync = env_b.core.tick_cache.sync
    env_b.core.tick_cache.sync = lambda core, phases=None: None
    env_b.schedule()
    env_b.core.tick_cache.sync = orig_sync

    def placements(env):
        return sorted(
            (t.task_id, t.assigned_worker)
            for t in env.core.tasks.values()
            if t.assigned_worker
        )

    assert placements(env_a) == placements(env_b)


# ------------------------------------------------------ satellite: streams
class _DummyWriter:
    def __init__(self, *a, **k):
        self.closed = False

    def close(self):
        self.closed = True


def _make_runtime():
    from hyperqueue_tpu.resources.descriptor import (
        ResourceDescriptor,
        ResourceDescriptorItem,
    )
    from hyperqueue_tpu.server.worker import WorkerConfiguration
    from hyperqueue_tpu.worker.runtime import WorkerRuntime

    config = WorkerConfiguration(
        descriptor=ResourceDescriptor(
            items=(ResourceDescriptorItem.range("cpus", 0, 1),)
        )
    )
    return WorkerRuntime("localhost", 0, None, config)


def test_stream_writer_eviction_skips_in_use(monkeypatch):
    import hyperqueue_tpu.events.outputlog as outputlog

    monkeypatch.setattr(outputlog, "StreamWriter", _DummyWriter)
    rt = _make_runtime()
    rt.MAX_STREAM_WRITERS = 4
    held = [rt._acquire_streamer(f"/busy/{i}") for i in range(4)]
    # a 5th dir must NOT close any in-use writer: the bound is exceeded
    rt._acquire_streamer("/new/0")
    assert all(not w.closed for w in held)
    assert len(rt._streamers) == 5
    # release one: the next acquisition may evict exactly that writer
    rt._release_streamer("/busy/2")
    rt._release_streamer("/new/0")
    rt._acquire_streamer("/new/1")
    assert rt._streamers.get("/busy/2") is None or held[2].closed is False
    closed = [d for d, w in zip(["/busy/0"], held) if w.closed]
    assert "/busy/0" not in closed  # still held -> never closed


def test_stream_writer_lru_reuse_moves_to_end(monkeypatch):
    import hyperqueue_tpu.events.outputlog as outputlog

    monkeypatch.setattr(outputlog, "StreamWriter", _DummyWriter)
    rt = _make_runtime()
    a = rt._acquire_streamer("/a")
    rt._acquire_streamer("/b")
    rt._release_streamer("/a")
    rt._release_streamer("/b")
    # reuse /a: it must move to the END of the LRU order
    assert rt._acquire_streamer("/a") is a
    rt._release_streamer("/a")
    assert list(rt._streamers) == ["/b", "/a"]
    # eviction now hits /b (least recently used), not /a
    rt.MAX_STREAM_WRITERS = 2
    rt._acquire_streamer("/c")
    assert "/b" not in rt._streamers
    assert "/a" in rt._streamers


def test_stream_writer_refcount_shared_dir(monkeypatch):
    import hyperqueue_tpu.events.outputlog as outputlog

    monkeypatch.setattr(outputlog, "StreamWriter", _DummyWriter)
    rt = _make_runtime()
    w1 = rt._acquire_streamer("/shared")
    w2 = rt._acquire_streamer("/shared")
    assert w1 is w2
    assert rt._streamer_users["/shared"] == 2
    rt._release_streamer("/shared")
    assert rt._streamer_users["/shared"] == 1
    rt._release_streamer("/shared")
    assert "/shared" not in rt._streamer_users


# ----------------------------------------------- satellite: cli validation
def test_stream_task_scope_placeholder_is_submit_error(capsys):
    import argparse

    from hyperqueue_tpu.client.cli import _check_submit_placeholders

    def make_args(stream):
        return argparse.Namespace(
            cwd=None, stdout=None, stderr=None, stream=stream
        )

    with pytest.raises(SystemExit):
        _check_submit_placeholders(
            make_args("/logs/%{TASK_ID}"), is_array=True
        )
    err = capsys.readouterr().err
    assert "task-scope" in err
    # job-scope placeholders stay fine
    _check_submit_placeholders(
        make_args("/logs/%{JOB_ID}-%{SERVER_UID}"), is_array=True
    )
    # truly unknown names still only warn
    _check_submit_placeholders(make_args("/logs/%{NOPE}"), is_array=True)
    assert "WARNING: unknown placeholder" in capsys.readouterr().err


def test_array_entries_intersection_warns_and_fails(capsys):
    from hyperqueue_tpu.client.cli import _subset_array_entries

    entries = ["l0", "l1", "l2"]
    ids, values = _subset_array_entries([1, 2, 7, 9], entries)
    assert ids == [1, 2]
    assert values == ["l1", "l2"]
    assert "2 --array id(s) outside" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        _subset_array_entries([5, 6], entries)
    assert "selects no tasks" in capsys.readouterr().err
    # no --array: every entry
    ids, values = _subset_array_entries(None, entries)
    assert ids == [0, 1, 2] and values == entries


def test_parse_selector_underscores():
    from hyperqueue_tpu.client.cli import parse_selector

    assert parse_selector("1_000") == [1000]
    assert parse_selector("1-1_0") == list(range(1, 11))
    assert parse_selector("1,2_5,3-4") == [1, 25, 3, 4]
    for bad in ("_5", "5_", "1-_5", "x_y", "nope"):
        with pytest.raises(SystemExit):
            parse_selector(bad)


# ------------------------------------------- satellite: chacha fallback
def test_pure_python_chacha_rfc8439_vectors():
    from hyperqueue_tpu.transport._chacha import ChaCha20Poly1305

    key = bytes(range(0x80, 0xA0))
    nonce = bytes([7, 0, 0, 0, 0x40, 0x41, 0x42, 0x43, 0x44, 0x45, 0x46,
                   0x47])
    aad = bytes([0x50, 0x51, 0x52, 0x53, 0xC0, 0xC1, 0xC2, 0xC3, 0xC4,
                 0xC5, 0xC6, 0xC7])
    pt = (b"Ladies and Gentlemen of the class of '99: If I could offer "
          b"you only one tip for the future, sunscreen would be it.")
    sealed = ChaCha20Poly1305(key).encrypt(nonce, pt, aad)
    assert sealed[-16:] == bytes.fromhex("1ae10b594f09e26a7e902ecbd0600691")
    assert ChaCha20Poly1305(key).decrypt(nonce, sealed, aad) == pt
    tampered = sealed[:-1] + bytes([sealed[-1] ^ 1])
    with pytest.raises(ValueError):
        ChaCha20Poly1305(key).decrypt(nonce, tampered, aad)


def test_stream_seal_roundtrip_with_fallback():
    from hyperqueue_tpu.transport import _chacha
    from hyperqueue_tpu.transport.auth import StreamSeal

    key = bytes(32)
    a = StreamSeal.__new__(StreamSeal)
    a._aead = _chacha.ChaCha20Poly1305(key)
    a._counter = 0
    a._prefix = b"dirA"
    b = StreamSeal.__new__(StreamSeal)
    b._aead = _chacha.ChaCha20Poly1305(key)
    b._counter = 0
    b._prefix = b"dirA"
    for msg in (b"x", b"hello" * 100, b""):
        assert b.open(a.seal(msg)) == msg


# ------------------------------------------- the tick loop, run directly
def _steady_ticks(backend, reps=8):
    """`sync -> create_batches -> run_tick -> apply` over a small mixed
    cluster (gpu classes, a cpu-only fallback variant, four priority
    levels), the loop the benchmark's tick driver and `reactor.schedule`
    both run.  One warm tick, then `reps` ticks of the same load: what
    each placed is taken back between ticks."""
    import time

    from hyperqueue_tpu.ids import make_task_id
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel
    from hyperqueue_tpu.scheduler.tick import run_tick

    env = TestEnv()
    core = env.core
    for i in range(16):
        env.worker(cpus=(4, 8, 16)[i % 3], gpus=(0, 0, 2)[i % 3])
    classes = [
        core.intern_rqv(rqv) for rqv in (
            env.rqv(cpus=1), env.rqv(cpus=2), env.rqv(cpus=1, gpus=0.5),
            env.rqv(variants=[env.rq(cpus=2, gpus=1), env.rq(cpus=4)]),
        )
    ]
    prio = {}
    for t in range(2000):
        task_id = make_task_id(1, t)
        prio[task_id] = (t % 4, 0)
        core.queues.add(classes[t % len(classes)], prio[task_id], task_id)
    model = GreedyCutScanModel(backend=backend)

    def tick():
        phases: dict = {}
        t0 = time.perf_counter()
        snap = core.tick_cache.sync(core)
        t1 = time.perf_counter()
        batches = create_batches(core.queues)
        t2 = time.perf_counter()
        out = run_tick(
            core.queues, None, core.rq_map, core.resource_map, model,
            batches=batches, dense=snap, phases=phases,
            key_cache=core.tick_cache,
        )
        t3 = time.perf_counter()
        for task_id, worker_id, rq_id, variant in out:
            worker = core.workers[worker_id]
            worker.assign(
                task_id, core.variant_amounts(rq_id, variant, worker)
            )
        t4 = time.perf_counter()
        phases.update(snapshot=(t1 - t0) * 1e3, batches=(t2 - t1) * 1e3,
                      apply=(t4 - t3) * 1e3, total=(t4 - t0) * 1e3)
        return out, phases

    def take_back(out):
        for task_id, worker_id, rq_id, variant in out:
            worker = core.workers[worker_id]
            worker.unassign(
                task_id, core.variant_amounts(rq_id, variant, worker)
            )
            core.queues.add(rq_id, prio[task_id], task_id)

    warm, _ = tick()
    take_back(warm)
    record = {
        "core": core,
        "assigned": len(warm),
        "rebuilds_after_warm": core.tick_cache.full_rebuilds,
        "shapes_after_warm": model.shape_allocations,
        "phases": [],
    }
    for _ in range(reps):
        out, phases = tick()
        assert len(out) == len(warm)
        record["phases"].append(phases)
        take_back(out)
    record["rebuilds"] = core.tick_cache.full_rebuilds
    record["shapes"] = model.shape_allocations
    return record


@pytest.fixture(scope="module", params=["numpy", "jax"])
def steady(request):
    return _steady_ticks(request.param)


def test_tick_phases_account_for_the_tick(steady):
    """The phases a tick records are disjoint spans inside its `total`
    (nested `a/b` keys lie inside `a`): in every tick their sum does not
    exceed it, and no stretch of the tick is left without a span.  The
    second half is read from the tick with the smallest remainder: a
    stretch nothing spans shows in every tick, a busy host does not."""
    assert steady["assigned"] > 0
    remainders = []
    for phases in steady["phases"]:
        for phase in ("assemble", "mapping", "snapshot", "batches", "apply"):
            assert phase in phases, phases
        total = phases["total"]
        # the cache's own `sync` span lies inside this tick's stopwatch
        # around the call (`snapshot`): counted once
        assert 0.0 < phases["sync"] <= phases["snapshot"], phases
        parts = sum(
            v for k, v in phases.items()
            if k not in ("total", "sync") and "/" not in k
        )
        assert parts <= total + 1e-6, phases
        remainders.append((total - parts) / total)
    assert min(remainders) <= 0.35, steady["phases"]


def test_steady_ticks_rebuild_no_snapshot(steady):
    """The first tick builds the (W, R) snapshot; ticks that only assign
    and release update rows in place."""
    assert steady["rebuilds_after_warm"] == 1
    assert steady["rebuilds"] == 1
    counters = steady["core"].tick_cache.counters()
    assert counters["full_rebuilds"] == 1
    assert counters["incremental_syncs"] >= len(steady["phases"])


def test_steady_ticks_allocate_no_solver_shape(steady):
    """One bucket shape serves every steady tick: a new one would be a
    new padded buffer set and, on the jitted path, a recompilation."""
    assert steady["shapes_after_warm"] == 1
    assert steady["shapes"] == 1


def test_steady_state_assembly_equals_scratch(steady):
    """After the steady ticks the incremental snapshot of this state
    (variants, fractional gpus, priorities) still assembles what a
    from-scratch one does."""
    core = steady["core"]
    snap = core.tick_cache.sync(core)
    paranoid_check(
        core, snap, create_batches(core.queues), core.rq_map,
        core.resource_map,
    )
    _assert_kwargs_equal(_scratch_kwargs(core), _incremental_kwargs(core))
