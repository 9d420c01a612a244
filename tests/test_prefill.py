"""Proactive prefilling semantics (reference mapping.rs:159,
state.rs:4-21)."""

import math
import random
import types

import pytest

from hyperqueue_tpu.scheduler.tick import create_batches
from hyperqueue_tpu.server import reactor
from hyperqueue_tpu.server.task import TaskState

from utils_env import TestEnv, displace_workers


def test_prefill_queues_extra_tasks_on_busy_worker():
    env = TestEnv()
    w = env.worker(cpus=2)
    ids = env.submit(n=10)
    env.schedule(prefill=True)
    worker = env.core.workers[w.worker_id]
    # 2 run now (resource-accounted), the rest queue as prefilled
    assert len(worker.assigned_tasks) == 2
    assert len(worker.prefilled_tasks) == 8
    assert all(
        env.core.tasks[t].state is TaskState.ASSIGNED for t in ids
    )
    # prefilled tasks hold no resources yet
    assert worker.free[0] == 0  # the 2 real assignments took both cpus
    assert worker.nt_free == worker.resources.task_max_count() - 2


def test_prefilled_task_accounts_resources_when_running():
    env = TestEnv()
    w = env.worker(cpus=1)
    a, b = env.submit(n=2)
    env.schedule(prefill=True)
    worker = env.core.workers[w.worker_id]
    assert worker.prefilled_tasks == {b}
    env.start_all_assigned()  # a runs; b stays queued on the worker
    env.finish(a)             # cpu frees -> the worker starts b
    env.start_all_assigned(include_prefilled=True)
    # b transitioned: resources now accounted, no longer prefilled
    assert not worker.prefilled_tasks
    assert worker.assigned_tasks == {b}
    env.finish(b)
    assert worker.free == worker.resources.amounts


def test_prefill_cap_respected():
    env = TestEnv()
    env.worker(cpus=1)
    n = reactor.PREFILL_MAX + 60
    env.submit(n=n)
    env.schedule(prefill=True)
    worker = next(iter(env.core.workers.values()))
    assert len(worker.prefilled_tasks) == reactor.PREFILL_MAX
    # 1 assigned + PREFILL_MAX prefilled; the rest stay ready
    assert env.core.queues.total_ready() == n - 1 - reactor.PREFILL_MAX


def test_prefill_lost_worker_requeues_without_crash():
    env = TestEnv()
    w = env.worker(cpus=1)
    a, b = env.submit(n=2)
    env.schedule(prefill=True)
    env.lose_worker(w.worker_id)
    assert env.state(a) is TaskState.READY
    assert env.state(b) is TaskState.READY
    assert env.core.tasks[b].crash_counter == 0
    assert not env.core.tasks[b].prefilled


def test_prefill_only_capable_classes():
    env = TestEnv()
    w = env.worker(cpus=2)  # no gpus
    env.submit(n=1)  # keeps the worker busy after schedule
    gpu_ids = env.submit(n=5, rqv=env.rqv(gpus=1))
    env.schedule(prefill=True)
    worker = env.core.workers[w.worker_id]
    assert not any(t in worker.prefilled_tasks for t in gpu_ids)
    assert all(env.state(t) is TaskState.READY for t in gpu_ids)


def test_prefill_cancel_releases_cleanly():
    env = TestEnv()
    w = env.worker(cpus=1)
    a, b = env.submit(n=2)
    env.schedule(prefill=True)
    env.cancel([b])
    worker = env.core.workers[w.worker_id]
    assert not worker.prefilled_tasks
    assert env.state(b) is TaskState.CANCELED
    # cancel message went to the worker holding the prefilled task
    assert any(b in tids for _, tids in env.comm.cancels)


def test_retract_rebalances_to_idle_worker():
    env = TestEnv()
    w1 = env.worker(cpus=1)
    env.submit(n=20)
    env.schedule(prefill=True)  # all 20 land on w1 (1 running, 19 prefilled)
    w2 = env.worker(cpus=1)
    env.schedule(prefill=True)
    # nothing ready, w2 idle -> server retracts part of w1's backlog
    assert env.comm.retracts
    donor_id, victims = env.comm.retracts[0]
    assert donor_id == w1.worker_id
    assert len(victims) >= 1
    # worker acks: tasks come back and get scheduled to w2
    for t, instance in victims:
        reactor.on_retract_response(env.core, env.comm, t, True, instance)
    env.core.sanity_check()
    env.schedule(prefill=True)
    assert env.core.workers[w2.worker_id].assigned_tasks


def test_retract_response_not_ok_keeps_task():
    env = TestEnv()
    w1 = env.worker(cpus=1)
    a, b = env.submit(n=2)
    env.schedule(prefill=True)
    # worker says b already started: server keeps the prefilled bookkeeping
    task_b = env.core.tasks[b]
    task_b.retract_pending = True  # as if a retract were in flight
    reactor.on_retract_response(
        env.core, env.comm, b, False, task_b.instance_id
    )
    assert env.core.tasks[b].prefilled
    assert b in env.core.workers[w1.worker_id].prefilled_tasks


def test_reservation_prevents_big_task_starvation():
    env = TestEnv()
    w = env.worker(cpus=16)
    # a small task occupies the box first
    (occupant,) = env.submit(rqv=env.rqv(cpus=1), priority=(0, 0))
    env.schedule(prefill=True)
    env.start_all_assigned()
    # now a whole-box task at HIGH priority plus a stream of low-prio smalls
    (big,) = env.submit(rqv=env.rqv(cpus=16), priority=(5, 0), job=2)
    small = env.submit(n=30, rqv=env.rqv(cpus=1), priority=(0, 0), job=3)
    env.schedule(prefill=True)
    worker = env.core.workers[w.worker_id]
    # gap relaxation: 15 smalls may USE the 15 free cpus right now (solver
    # semantics, utilization first) — but the big task holds the prefill
    # reservation, so no further lower-priority work stacks on the drain path
    assert env.core.tasks[big].state is TaskState.ASSIGNED
    assert env.core.tasks[big].assigned_worker == w.worker_id
    assert worker.prefilled_tasks == {big}
    assert env.core.queues.total_ready() == 15  # the rest stay off the box
    env.start_all_assigned()
    # drain everything currently holding cpus -> big must start next, ahead
    # of the 15 still-ready smalls (bounded delay, no starvation)
    env.finish(occupant)
    running = [
        t for t in small
        if env.core.tasks[t].state is TaskState.RUNNING
    ]
    for t in running:
        env.finish(t)
    # box fully drained: the worker now starts the big task
    env.start_all_assigned(include_prefilled=True)
    assert env.core.tasks[big].state is TaskState.RUNNING
    assert env.core.queues.total_ready() == 15


def test_prefill_priority_order_across_classes():
    env = TestEnv()
    env.worker(cpus=1)
    low = env.submit(n=50, rqv=env.rqv(cpus=1), priority=(0, 0))
    high = env.submit(n=50, rqv=env.rqv(gpus=0, cpus=1), priority=(9, 0))
    env.schedule(prefill=True)
    # high-priority tasks must win the prefill budget
    n_high_prefilled = sum(
        1 for t in high if env.core.tasks[t].prefilled
        or env.core.tasks[t].state is TaskState.ASSIGNED
    )
    n_low_prefilled = sum(1 for t in low if env.core.tasks[t].prefilled)
    assert n_high_prefilled >= 50 - 1 or n_low_prefilled == 0


def test_retract_fires_despite_unschedulable_ready_tasks():
    """Idle capacity must trigger rebalance even while the queues still hold
    ready work nobody can run (reference retracts whenever idle capacity
    appears, worker/rpc.rs:322; previously gated on empty queues)."""
    env = TestEnv()
    w1 = env.worker(cpus=2)
    busy = env.submit(n=2)
    env.schedule(prefill=True)
    env.start_all_assigned()
    env.submit(n=40)  # builds prefilled backlog on w1
    env.schedule(prefill=True)
    assert len(w1.prefilled_tasks) >= 20
    # ready tasks that no worker can ever run keep total_ready() > 0
    env.submit(n=3, rqv=env.rqv(cpus=64))
    w2 = env.worker(cpus=2)  # fresh idle worker
    before = len(env.comm.retracts)
    env.schedule(prefill=True)
    # w2 was either fed by the solve or fed via retract from w1's backlog
    got_work = bool(w2.assigned_tasks or w2.prefilled_tasks)
    retracted = len(env.comm.retracts) > before
    assert got_work or retracted


def test_retract_skips_tasks_idle_workers_cannot_run():
    """No churn: backlog classes the idle worker cannot host stay put."""
    env = TestEnv()
    w1 = env.worker(cpus=2, gpus=2)
    busy = env.submit(n=2)
    env.schedule(prefill=True)
    env.start_all_assigned()
    env.submit(n=20, rqv=env.rqv(gpus=1))  # gpu backlog prefills onto w1
    env.schedule(prefill=True)
    assert w1.prefilled_tasks
    w2 = env.worker(cpus=2)  # no gpus: cannot host any backlog task
    before = len(env.comm.retracts)
    env.schedule(prefill=True)
    assert len(env.comm.retracts) == before


def test_prefill_spreads_across_workers():
    """Deep prefill budgets must not pile onto one worker while its peers
    run dry (least-backlog-first feeding)."""
    env = TestEnv()
    workers = [env.worker(cpus=1) for _ in range(4)]
    env.submit(n=4)
    env.schedule(prefill=True)
    env.start_all_assigned()
    env.submit(n=100)
    env.schedule(prefill=True)
    backlogs = sorted(len(w.prefilled_tasks) for w in workers)
    assert backlogs[0] >= 20, backlogs  # roughly even split of 100


# ---------------------------------------------------------------------------
# Reference test_reactor.rs steal/prefill matrix (":798-1160") ported onto
# this design's retract protocol.  Mapping notes where the designs differ:
# the reference pre-picks a redirect target and keeps the task in a
# `Retracting` state; here a retract is a plain give-it-back request — the
# task stays prefilled on the donor until the worker answers, then requeues
# and the next tick re-places it.  RejectRequest/EnableRequest
# (test_task_reject1-3, test_prefill_rejected, test_steal_rejected) have no
# server-side analog: capability is static, the server never prefills a
# class the worker cannot host (test_prefill_only_capable_classes), and a
# worker that cannot allocate *right now* parks the task in its blocked
# queue and answers retracts with ok=False
# (test_retract_response_not_ok_keeps_task).
# ---------------------------------------------------------------------------

from utils_env import TestEnv as _TestEnv


def _setup_prefill():
    """Reference setup_prefill (test_reactor.rs:778): one busy 1-cpu worker
    holding an assigned task and prefilled backlog."""
    env = _TestEnv()
    w1 = env.worker(cpus=1)
    ids = env.submit(n=3)
    env.schedule(prefill=True)
    assigned = next(t for t in ids if not env.core.tasks[t].prefilled)
    prefilled = next(t for t in ids if env.core.tasks[t].prefilled)
    return env, w1, assigned, prefilled


def _setup_retracting():
    """Reference setup_retracting (test_reactor.rs:995): a retract is in
    flight from donor w1 after idle w2 appeared.  Also returns the task
    RUNNING on the donor (reference reads it from sn_assignment)."""
    env = _TestEnv()
    w1 = env.worker(cpus=1)
    ids = env.submit(n=8)
    env.schedule(prefill=True)
    env.start_all_assigned()
    w2 = env.worker(cpus=1)
    env.schedule(prefill=True)
    pending = [t for t in ids if env.core.tasks[t].retract_pending]
    assert pending, "setup: no retract in flight"
    running = next(iter(w1.assigned_tasks))
    return env, w1, w2, pending[0], running


def test_prefill_submit_high_priority_displaces_backlog():
    """test_reactor.rs:798 (cpus=1 arm) — a strictly-higher-priority
    runnable task arriving when the worker's prefill budget is exhausted
    retracts lower-priority prefilled backlog to make room.  (With budget
    to spare the high-priority task is instead prefilled directly and the
    worker's priority-ordered blocked queue starts it first — same
    outcome, no retract needed.)"""
    from hyperqueue_tpu.server import reactor

    env = _TestEnv()
    w1 = env.worker(cpus=1)
    env.submit(n=reactor.PREFILL_MAX + 1)
    env.schedule(prefill=True)
    assert len(w1.prefilled_tasks) == reactor.PREFILL_MAX
    env.submit(n=1, priority=(10, 0), job=2)
    before = len(env.comm.retracts)
    env.schedule(prefill=True)
    assert len(env.comm.retracts) > before
    donor_id, refs = env.comm.retracts[-1]
    assert donor_id == w1.worker_id
    retracted_ids = {t for t, _ in refs}
    assert retracted_ids <= {
        t for t in env.core.tasks if env.core.tasks[t].retract_pending
    }
    # victims are the lowest-priority prefilled tasks
    assert all(env.core.tasks[t].priority[0] == 0 for t in retracted_ids)
    # once a victim answers, the next tick prefills the high-priority task
    victim = next(iter(retracted_ids))
    reactor.on_retract_response(
        env.core, env.comm, victim, True, env.core.tasks[victim].instance_id
    )
    env.schedule(prefill=True)
    high = [
        t for t, task in env.core.tasks.items()
        if task.priority == (10, 0)
    ]
    assert all(env.core.tasks[t].assigned_worker == w1.worker_id
               for t in high)


def test_prefill_submit_high_priority_unrunnable_no_churn():
    """test_reactor.rs:798 (cpus=2 arm) — DEVIATION: the reference retracts
    backlog even for a higher-priority task the worker could never run;
    here displacement only fires for classes the worker can host, so an
    impossible task causes no churn."""
    env, w1, assigned, prefilled = _setup_prefill()
    env.submit(n=1, rqv=env.rqv(cpus=2), priority=(10, 0), job=2)
    before = len(env.comm.retracts)
    env.schedule(prefill=True)
    assert len(env.comm.retracts) == before


def test_prefill_submit_same_priority_no_displacement():
    """test_reactor.rs:829 — a same-priority submit leaves the prefilled
    backlog alone (both cpus variants)."""
    for cpus in (1, 2):
        env, w1, assigned, prefilled = _setup_prefill()
        env.submit(n=1, rqv=env.rqv(cpus=cpus), job=2)
        before = len(env.comm.retracts)
        env.schedule(prefill=True)
        assert len(env.comm.retracts) == before
        assert env.core.tasks[prefilled].prefilled
        assert env.core.tasks[prefilled].assigned_worker == w1.worker_id


def test_prefill_worker_lost_requeues_all():
    """test_reactor.rs:851 — losing the worker requeues assigned and
    prefilled alike, no crash charge for the never-started backlog."""
    env, w1, assigned, prefilled = _setup_prefill()
    env.lose_worker(w1.worker_id)
    assert env.state(assigned) is TaskState.READY
    assert env.state(prefilled) is TaskState.READY
    assert env.core.tasks[prefilled].crash_counter == 0
    assert not env.core.tasks[prefilled].prefilled


def test_prefill_started_while_retract_in_flight():
    """test_reactor.rs:866 test_prefill_started_on_same_worker — the
    worker starts the prefilled task while the server's retract crosses it
    on the wire: the running report wins, the late answer is a no-op."""
    env, w1, w2, victim, _running = _setup_retracting()
    from hyperqueue_tpu.server import reactor

    task = env.core.tasks[victim]
    instance = task.instance_id
    reactor.on_task_running(env.core, env.events, victim, instance)
    assert task.state is TaskState.RUNNING
    assert not task.retract_pending
    assert not task.prefilled
    assert victim in w1.assigned_tasks  # resources accounted on start
    # the crossing answer (ok=False, as the worker started it) is a no-op
    reactor.on_retract_response(env.core, env.comm, victim, False, instance)
    assert task.state is TaskState.RUNNING
    env.finish(victim)
    assert env.state(victim) is TaskState.FINISHED


def test_steal_finished():
    """test_reactor.rs:1009 — the donor finishes the task before honoring
    the retract: finished wins, bookkeeping clean, late answer dropped."""
    env, w1, w2, victim, _running = _setup_retracting()
    from hyperqueue_tpu.server import reactor

    task = env.core.tasks[victim]
    instance = task.instance_id
    env.finish(victim)
    assert env.state(victim) is TaskState.FINISHED
    assert victim not in w1.prefilled_tasks
    assert not task.prefilled
    reactor.on_retract_response(env.core, env.comm, victim, False, instance)
    assert env.state(victim) is TaskState.FINISHED
    env.core.sanity_check()


def test_steal_running():
    """test_reactor.rs:1022 — the task starts on the donor while the
    retract is pending: it keeps running there."""
    env, w1, w2, victim, running = _setup_retracting()
    from hyperqueue_tpu.server import reactor

    env.finish(running)  # frees the cpu; the donor starts the victim
    task = env.core.tasks[victim]
    reactor.on_task_running(env.core, env.events, victim, task.instance_id)
    assert task.state is TaskState.RUNNING
    assert task.assigned_worker == w1.worker_id
    env.core.sanity_check()


def test_steal_failed():
    """test_reactor.rs:1051 — the task fails on the donor while the
    retract is pending: failure propagates, donor is clean."""
    env, w1, w2, victim, _running = _setup_retracting()
    task = env.core.tasks[victim]
    env.fail(victim)
    assert env.state(victim) is TaskState.FAILED
    assert victim not in w1.prefilled_tasks
    assert not task.prefilled and not task.retract_pending
    env.core.sanity_check()


def test_steal_cancel():
    """test_reactor.rs:1078 — cancelling mid-retract cancels on the donor
    and cleans up."""
    env, w1, w2, victim, _running = _setup_retracting()
    out = env.cancel([victim])
    assert out == [victim]
    assert env.state(victim) is TaskState.CANCELED
    assert victim not in w1.prefilled_tasks
    assert any(
        victim in tids for wid, tids in env.comm.cancels
        if wid == w1.worker_id
    )
    env.core.sanity_check()


def test_steal_source_worker_lost_task_reaches_new_worker():
    """test_reactor.rs:1096 — the donor dies mid-retract: the task must
    end up on the other worker (the reference redirects instantly; here it
    requeues and the next tick assigns it)."""
    env, w1, w2, victim, _running = _setup_retracting()
    env.lose_worker(w1.worker_id)
    task = env.core.tasks[victim]
    assert task.state is TaskState.READY
    assert not task.retract_pending
    env.schedule(prefill=True)
    assert task.assigned_worker == w2.worker_id
    env.core.sanity_check()


def test_steal_target_worker_lost_task_stays_on_donor():
    """test_reactor.rs:1141 — the idle worker that motivated the steal
    dies: the task stays with the donor; the eventual ok answer requeues
    it and it lands back on the donor."""
    env, w1, w2, victim, _running = _setup_retracting()
    from hyperqueue_tpu.server import reactor

    task = env.core.tasks[victim]
    instance = task.instance_id
    env.lose_worker(w2.worker_id)
    assert task.prefilled
    assert task.assigned_worker == w1.worker_id
    assert task.retract_pending  # the request is still out
    reactor.on_retract_response(env.core, env.comm, victim, True, instance)
    assert task.state is TaskState.READY
    env.schedule(prefill=True)
    assert task.assigned_worker == w1.worker_id
    env.core.sanity_check()


def test_displacement_retract_capped_by_worker_fit():
    """Displacement is bounded per worker by what it could absorb from the
    displacing batch (2x its simultaneous fit), not the batch's full size:
    a deep high-priority backlog must not strip every prefilled task from
    a small worker in one tick (retract/re-prefill churn)."""
    from hyperqueue_tpu.server import reactor

    env = _TestEnv()
    w1 = env.worker(cpus=4)
    # fill the worker's prefill backlog with low-priority 1-cpu tasks
    env.submit(n=reactor.PREFILL_MAX + 20)
    env.schedule(prefill=True)
    assert len(w1.prefilled_tasks) == reactor.PREFILL_MAX
    # a huge strictly-higher-priority batch of 3-cpu tasks: the worker fits
    # one at a time (4 // 3), so at most 2 retractions despite need >> 2
    env.submit(n=200, rqv=env.rqv(cpus=3), priority=(10, 0), job=2)
    before = len(env.comm.retracts)
    env.schedule(prefill=True)
    new_refs = [
        ref for _, refs in env.comm.retracts[before:] for ref in refs
    ]
    assert 0 < len(new_refs) <= 2


# ---------------------------------------------------------------------------
# ISSUE 26: the displacement pass asks each worker's per-level index before
# it reads a task.  Parity with the pass as it stood (sort everything, then
# compare), the index against a recount through every exit, and how often
# the pass engages.
# ---------------------------------------------------------------------------


def _displace_sort_everything(core, comm, per_worker_msgs, leftover_batches):
    """The pass before ISSUE 26, kept here as the reference: a victim list
    of everything prefilled on every worker, sorted, before any compare."""
    if not core.queues.total_ready():
        return
    victim_lists = {}
    for worker in core.workers.values():
        if worker.mn_task or worker.mn_reserved:
            continue
        if not worker.prefilled_tasks:
            continue
        just_sent = {
            m["id"] for m in per_worker_msgs.get(worker.worker_id, ())
        }
        victims = sorted(
            (
                core.tasks[tid]
                for tid in worker.prefilled_tasks
                if tid not in just_sent
                and not core.tasks[tid].retract_pending
            ),
            key=lambda t: t.priority,
        )
        if victims:
            victims.reverse()
            victim_lists[worker.worker_id] = victims
    if not victim_lists:
        return
    if leftover_batches is None:
        leftover_batches = create_batches(core.queues)
    retract_by_worker = {}
    retract_budget = {wid: reactor.PREFILL_MAX for wid in victim_lists}
    for batch in leftover_batches:
        if batch.size <= 0:
            continue
        rqv = core.rq_map.get_variants(batch.rq_id)
        need = batch.size
        for worker_id, victims in victim_lists.items():
            if need <= 0:
                break
            if not victims or retract_budget[worker_id] <= 0:
                continue
            worker = core.workers[worker_id]
            if not worker.resources.is_capable_of_rqv(rqv):
                continue
            allowance = min(
                retract_budget[worker_id],
                2 * reactor._rqv_fit_count(worker.resources, rqv),
            )
            while victims and need > 0 and allowance > 0:
                if victims[-1].priority[0] >= batch.priority[0]:
                    break
                victim = victims.pop()
                victim.retract_pending = True
                retract_by_worker.setdefault(worker_id, []).append(
                    (victim.task_id, victim.instance_id)
                )
                need -= 1
                allowance -= 1
                retract_budget[worker_id] -= 1
    for wid, refs in retract_by_worker.items():
        comm.send_retract(wid, refs)


def _random_world(seed: int, monkeypatch):
    """A seeded cluster in the state the displacement pass meets: workers
    of several shapes holding prefilled backlog at 1-4 user priorities,
    some of it asked back already, and a queue that holds more (some of it
    higher) after this tick's fill has sent what still fitted.  Returns
    (env, per_worker_msgs, leftover_batches)."""
    rng = random.Random(seed)
    # a small prefill depth makes the per-worker retract budget bind
    monkeypatch.setattr(reactor, "PREFILL_MAX", rng.choice((2, 4, 8, 16, 32)))
    env = _TestEnv()
    core = env.core
    for _ in range(rng.randint(2, 7)):
        env.worker(cpus=rng.choice((1, 2, 4, 8)), gpus=rng.choice((0, 0, 2)))
    classes = [env.rqv(cpus=1), env.rqv(cpus=2), env.rqv(cpus=3),
               env.rqv(cpus=1, gpus=1)]
    levels = sorted(rng.sample(range(-2, 9), rng.randint(1, 4)))
    job = 0

    def submit_some(pool, lo, hi):
        nonlocal job
        for _ in range(rng.randint(lo, hi)):
            job += 1
            env.submit(n=rng.randint(1, 60), rqv=rng.choice(classes),
                       priority=(rng.choice(pool), rng.randint(-3, 0)),
                       job=job)

    def start_what_fits(share):
        """The workers start prefilled tasks their free resources hold."""
        for worker in core.workers.values():
            for tid in sorted(worker.prefilled_tasks):
                task = core.tasks[tid]
                amounts = core.variant_amounts(
                    task.rq_id, task.assigned_variant, worker
                )
                if rng.random() < share and worker.nt_free > 0 and all(
                    worker.free[rid] >= amount for rid, amount in amounts
                ):
                    reactor.on_task_running(
                        core, env.events, tid, task.instance_id
                    )

    # the backlog: the lower levels first, so that what comes later can
    # outrank what is already prefilled
    lower = levels[:max(1, len(levels) - 1)]
    for _ in range(2):
        submit_some(lower, 2, 6)
        env.schedule(prefill=True)
        env.start_all_assigned()
    # some of it finishes, some backlog starts, some is being asked back
    running = [t for t in core.tasks.values() if t.state is TaskState.RUNNING]
    for task in rng.sample(running, len(running) // 2):
        env.finish(task.task_id)
    start_what_fits(0.5)
    prefilled = [t for t in core.tasks.values() if t.prefilled]
    for task in rng.sample(prefilled, len(prefilled) // 5):
        task.retract_pending = True
    # this tick: new work at every level, and the fill pass has sent what
    # the freed budgets allow (those tasks are `just_sent`)
    submit_some(levels, 1, 5)
    per_worker_msgs: dict = {}
    _, leftover = reactor._prefill_fill(
        core, 0.0, per_worker_msgs, None, None, set()
    )
    # the fill only ever sends what outranks the rest of the queue; hold
    # the pass to its rule for any message list: some of the settled
    # backlog counts as sent this tick too
    for worker in core.workers.values():
        for tid in sorted(worker.prefilled_tasks):
            if rng.random() < 0.15:
                per_worker_msgs.setdefault(worker.worker_id, []).append(
                    {"id": tid}
                )
    core.sanity_check()
    return env, per_worker_msgs, leftover


def _both_passes(env, per_worker_msgs, leftover):
    """(retracts of the reference, retracts of the pass, workers scanned)
    on the same state: the reference runs first and its marks are undone."""
    ref_comm = types.SimpleNamespace(
        retracts=[], send_retract=lambda w, r: ref_comm.retracts.append((w, r))
    )
    _displace_sort_everything(env.core, ref_comm, per_worker_msgs, leftover)
    for _wid, refs in ref_comm.retracts:
        for tid, _instance in refs:
            env.core.tasks[tid].retract_pending = False
    sizes = [b.size for b in leftover or ()]
    before = len(env.comm.retracts)
    counts = displace_workers()
    reactor._prefill_displace(env.core, env.comm, per_worker_msgs, leftover)
    assert [b.size for b in leftover or ()] == sizes
    scanned = displace_workers()["scanned"] - counts["scanned"]
    return ref_comm.retracts, env.comm.retracts[before:], scanned


PARITY_SEEDS = list(range(1, 41))


@pytest.mark.parametrize("seed", PARITY_SEEDS)
def test_displacement_sends_what_the_sort_everything_pass_sent(
        seed, monkeypatch):
    env, per_worker_msgs, leftover = _random_world(seed, monkeypatch)
    expected, got, _scanned = _both_passes(env, per_worker_msgs, leftover)
    assert got == expected  # same workers, same (task, instance), same order
    pending = {t for t, task in env.core.tasks.items() if task.retract_pending}
    assert {t for _w, refs in got for t, _i in refs} <= pending
    env.core.sanity_check()


def test_parity_worlds_cover_what_the_pass_has_to_decide(monkeypatch):
    """The seeded worlds are no empty comparison: they retract, they skip,
    they meet just-sent and asked-back tasks and binding budgets."""
    retracting = skipping_all = partly = budget_bound = 0
    with_just_sent = with_pending = 0
    for seed in PARITY_SEEDS:
        with monkeypatch.context() as patch:
            env, msgs, leftover = _random_world(seed, patch)
            expected, got, scanned = _both_passes(env, msgs, leftover)
            holders = [w for w in env.core.workers.values()
                       if w.prefilled_tasks]
            retracting += bool(got)
            skipping_all += bool(holders) and scanned == 0
            partly += 0 < scanned < len(holders)
            budget_bound += any(
                len(refs) >= reactor.PREFILL_MAX for _w, refs in got
            )
            sent = {m["id"] for ms in msgs.values() for m in ms}
            with_just_sent += any(
                sent & set(w.prefilled_tasks) for w in holders
            )
            with_pending += any(
                env.core.tasks[t].retract_pending
                for w in holders for t in w.prefilled_tasks
            )
    assert retracting >= 10 and skipping_all >= 5 and partly >= 3
    assert budget_bound >= 2 and with_just_sent >= 10 and with_pending >= 10


def _recount(env) -> None:
    """Each worker's per-level view equals a recount from its ids."""
    for worker in env.core.workers.values():
        levels: dict = {}
        for tid in worker.prefilled_tasks:
            level = env.core.tasks[tid].priority[0]
            levels[level] = levels.get(level, 0) + 1
        assert worker.prefilled_tasks.level_counts() == levels
        assert worker.prefilled_tasks.lowest == min(levels, default=math.inf)
        assert sum(levels.values()) == len(worker.prefilled_tasks)


def _prefilled_at_two_levels():
    """One busy 1-cpu worker, prefilled with 6 tasks at level 0 (job 1)
    and 6 at level 3 (job 2)."""
    env = _TestEnv()
    w = env.worker(cpus=1)
    low = env.submit(n=7, priority=(0, 0), job=1)
    env.schedule(prefill=True)
    env.start_all_assigned()
    high = env.submit(n=6, priority=(3, 0), job=2)
    env.schedule(prefill=True)
    assert w.prefilled_tasks.level_counts() == {0: 6, 3: 6}
    return env, w, low, high


def _answer_retracts(env, ok: bool) -> int:
    n = 0
    for _wid, refs in env.comm.retracts:
        for tid, instance in refs:
            reactor.on_retract_response(env.core, env.comm, tid, ok, instance)
            n += 1
    env.comm.retracts.clear()
    return n


def _exit_started(env, w, low, high):
    running = next(iter(w.assigned_tasks))
    env.finish(running)
    tid = next(t for t in high if t in w.prefilled_tasks)
    reactor.on_task_running(
        env.core, env.events, tid, env.core.tasks[tid].instance_id
    )
    assert tid in w.assigned_tasks
    return {tid}


def _exit_finished_while_prefilled(env, w, low, high):
    tid = next(t for t in low if t in w.prefilled_tasks)
    env.finish(tid)
    return {tid}


def _exit_failed(env, w, low, high):
    tid = next(t for t in high if t in w.prefilled_tasks)
    env.fail(tid)
    return {tid}


def _exit_cancelled(env, w, low, high):
    tids = [t for t in low if t in w.prefilled_tasks][:3]
    env.cancel(tids)
    return set(tids)


def _exit_retract_ok(env, w, low, high):
    tid = next(t for t in low if t in w.prefilled_tasks)
    task = env.core.tasks[tid]
    task.retract_pending = True
    reactor.on_retract_response(env.core, env.comm, tid, True,
                                task.instance_id)
    assert task.state is TaskState.READY
    return {tid}


def _exit_retract_not_ok(env, w, low, high):
    tid = next(t for t in low if t in w.prefilled_tasks)
    task = env.core.tasks[tid]
    task.retract_pending = True
    reactor.on_retract_response(env.core, env.comm, tid, False,
                                task.instance_id)
    assert tid in w.prefilled_tasks  # it started racing: it stays
    return set()


def _exit_worker_lost(env, w, low, high):
    env.lose_worker(w.worker_id)
    assert not env.core.workers
    assert not any(t.prefilled for t in env.core.tasks.values())
    other = env.worker(cpus=1)
    env.schedule(prefill=True)  # the requeued backlog prefills anew
    assert other.prefilled_tasks.level_counts() == {3: 5}  # reserved
    return None  # the lost worker's record went with it


def _exit_worker_drained(env, w, low, high):
    gone = set(w.prefilled_tasks)
    assert env.start_drain([w.worker_id]) == [w.worker_id]
    assert _answer_retracts(env, ok=True) == len(gone)
    return gone


def _exit_job_paused_and_recalled(env, w, low, high):
    gone = {t for t in high if t in w.prefilled_tasks}
    _held, retracts = reactor.pause_jobs(env.core, env.comm, [2])
    assert retracts == len(gone)
    assert _answer_retracts(env, ok=True) == len(gone)
    assert env.core.paused_held[2] >= gone
    return gone


@pytest.mark.parametrize("leave", [
    _exit_started, _exit_finished_while_prefilled, _exit_failed,
    _exit_cancelled, _exit_retract_ok, _exit_retract_not_ok,
    _exit_worker_lost, _exit_worker_drained, _exit_job_paused_and_recalled,
], ids=lambda f: f.__name__[len("_exit_"):])
def test_prefill_index_equals_a_recount_after_every_exit(leave):
    env, w, low, high = _prefilled_at_two_levels()
    _recount(env)
    held = set(w.prefilled_tasks)
    gone = leave(env, w, low, high)
    if gone is not None:
        assert set(w.prefilled_tasks) == held - gone
    _recount(env)
    env.core.sanity_check()
    # and the index keeps in step through the ticks that follow
    env.schedule(prefill=True)
    _recount(env)


def test_prefilled_tasks_reads_like_the_set_it_was():
    from hyperqueue_tpu.server.worker import PrefilledTasks

    held = PrefilledTasks()
    held.add(7, 0)
    held.add(9, 2)
    held.add(7, 0)          # a second add counts nothing twice
    held.discard(8, 0)      # nor does a discard of what is not there
    assert held == {7, 9} and len(held) == 2 and 9 in held
    assert sorted(held) == [7, 9] and held & {9, 11} == {9}
    assert held.level_counts() == {0: 1, 2: 1} and held.lowest == 0
    held.discard(7, 0)
    assert held.level_counts() == {2: 1} and held.lowest == 2
    held.add(5, -1)
    assert held.lowest == -1
    held.discard(5, -1)
    held.discard(9, 2)
    assert not held and held.lowest == math.inf  # below nothing queued
    # no other way in or out: the per-level view cannot drift
    held.add(3, 1)
    for change in (lambda: held.update({4}), held.pop, held.clear,
                   lambda: held.remove(3), lambda: held.__ior__({4}),
                   lambda: held.difference_update({3})):
        with pytest.raises(TypeError):
            change()
    assert held == {3} and held.level_counts() == {1: 1}


class _CountingTasks(dict):
    """core.tasks with its look-ups counted."""

    reads = 0

    def __getitem__(self, key):
        self.reads += 1
        return super().__getitem__(key)

    def get(self, key, default=None):
        self.reads += 1
        return super().get(key, default)


def test_displacement_skips_every_worker_under_one_priority_level():
    env = _TestEnv()
    workers = [env.worker(cpus=1) for _ in range(3)]
    env.submit(n=3 * (reactor.PREFILL_MAX + 1) + 50)
    env.schedule(prefill=True)
    assert all(len(w.prefilled_tasks) == reactor.PREFILL_MAX for w in workers)
    assert env.core.queues.total_ready() == 50
    for _ in range(3):
        before = displace_workers()
        env.schedule(prefill=True)
        after = displace_workers()
        assert after["skipped"] - before["skipped"] == 3
        assert after["scanned"] == before["scanned"]
    # the pass alone, over a core whose task look-ups are counted
    env.core.tasks = _CountingTasks(env.core.tasks)
    reactor._prefill_displace(env.core, env.comm, {}, None)
    assert env.core.tasks.reads == 0
    assert not env.comm.retracts
    # one task that outranks the backlog: now the workers are scanned, and
    # the outcome of test_prefill_submit_high_priority_displaces_backlog
    # holds
    env.core.tasks = dict(env.core.tasks)
    (high,) = env.submit(n=1, priority=(10, 0), job=2)
    before = displace_workers()
    env.schedule(prefill=True)
    after = displace_workers()
    assert after["scanned"] - before["scanned"] == 3
    assert after["skipped"] == before["skipped"]
    (donor_id, refs), = env.comm.retracts
    assert donor_id == workers[0].worker_id and len(refs) == 1
    (victim, instance), = refs
    assert env.core.tasks[victim].priority[0] == 0
    reactor.on_retract_response(env.core, env.comm, victim, True, instance)
    env.schedule(prefill=True)
    assert env.core.tasks[high].assigned_worker == donor_id
    # the high task is prefilled now and nothing queued outranks the rest
    before = displace_workers()
    env.schedule(prefill=True)
    assert displace_workers()["scanned"] == before["scanned"]


def test_displacement_scans_only_workers_holding_something_outranked(
        monkeypatch):
    """Two levels: the worker whose backlog is all at the queued level is
    passed over, the one holding lower work is scanned, and only tasks
    below the queued level are read."""
    depth = 8
    monkeypatch.setattr(reactor, "PREFILL_MAX", depth)
    env = _TestEnv()
    w_low, w_high = env.worker(cpus=1), env.worker(cpus=1)
    env.submit(n=2 * (depth + 1), priority=(5, 0), job=1)
    env.schedule(prefill=True)
    env.start_all_assigned()
    # w_low loses its backlog and takes level-0 work in its place
    env.cancel(list(w_low.prefilled_tasks))
    env.submit(n=depth, priority=(0, 0), job=2)
    env.schedule(prefill=True)
    assert w_low.prefilled_tasks.level_counts() == {0: depth}
    assert w_high.prefilled_tasks.level_counts() == {5: depth}
    env.submit(n=4, priority=(5, 0), job=3)
    before = displace_workers()
    env.core.tasks = _CountingTasks(env.core.tasks)
    reactor._prefill_displace(env.core, env.comm, {}, None)
    after = displace_workers()
    assert after["scanned"] - before["scanned"] == 1
    assert after["skipped"] - before["skipped"] == 1
    assert env.core.tasks.reads == depth  # w_low's tasks, once each
    (donor_id, refs), = env.comm.retracts
    assert donor_id == w_low.worker_id and len(refs) == 2  # 2 x fit of 1
