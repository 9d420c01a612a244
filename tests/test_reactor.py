"""Reactor + scheduling semantics tests (tier-1 equivalent).

Modeled on reference crates/tako/src/internal/tests/test_reactor.rs and
test_scheduler_sn.rs/test_scheduler_mn.rs: dependency counting, assignment,
worker loss with crash counters, cancellation propagation, gang scheduling.
"""

import pytest

from hyperqueue_tpu.server.task import TaskState

from utils_env import TestEnv


def test_simple_assign_and_finish():
    env = TestEnv()
    env.worker(cpus=4)
    (t1,) = env.submit()
    assert env.state(t1) is TaskState.READY
    assert env.schedule() == 1
    assert env.state(t1) is TaskState.ASSIGNED
    env.start_all_assigned()
    assert env.state(t1) is TaskState.RUNNING
    env.finish(t1)
    assert env.state(t1) is TaskState.FINISHED
    assert env.events.finished == [t1]
    # worker resources fully returned
    w = next(iter(env.core.workers.values()))
    assert w.free == w.resources.amounts
    assert not w.assigned_tasks


def test_dependencies_gate_readiness():
    env = TestEnv()
    env.worker(cpus=4)
    (a,) = env.submit()
    (b,) = env.submit(deps=[a])
    (c,) = env.submit(deps=[a, b])
    assert env.state(b) is TaskState.WAITING
    env.schedule()
    env.start_all_assigned()
    env.finish(a)
    assert env.state(b) is TaskState.READY
    assert env.state(c) is TaskState.WAITING
    env.schedule()
    env.start_all_assigned()
    env.finish(b)
    assert env.state(c) is TaskState.READY


def test_resources_limit_concurrency():
    env = TestEnv()
    env.worker(cpus=4)
    ids = env.submit(n=10, rqv=env.rqv(cpus=2))
    assert env.schedule() == 2  # only 2 x 2cpu fit on 4 cpus
    assigned = [t for t in ids if env.state(t) is TaskState.ASSIGNED]
    assert len(assigned) == 2
    env.start_all_assigned()
    env.finish(assigned[0])
    assert env.schedule() == 1


def test_failure_cancels_consumers():
    env = TestEnv()
    env.worker()
    (a,) = env.submit()
    (b,) = env.submit(deps=[a])
    (c,) = env.submit(deps=[b])
    env.schedule()
    env.start_all_assigned()
    env.fail(a)
    assert env.state(a) is TaskState.FAILED
    assert env.state(b) is TaskState.CANCELED
    assert env.state(c) is TaskState.CANCELED
    assert env.events.failed[0][0] == a
    assert set(env.events.canceled) == {b, c}


def test_worker_lost_requeues_and_crash_limit():
    env = TestEnv()
    w = env.worker(cpus=4)
    (t,) = env.submit()
    env.schedule()
    env.start_all_assigned()
    instance0 = env.core.tasks[t].instance_id
    env.lose_worker(w.worker_id)
    # task went back to waiting->ready with a bumped instance
    assert env.state(t) is TaskState.READY
    assert env.core.tasks[t].crash_counter == 1
    assert env.core.tasks[t].instance_id == instance0 + 1

    # crash it until the limit (default 5)
    for _ in range(4):
        w = env.worker(cpus=4)
        env.schedule()
        env.start_all_assigned()
        env.lose_worker(w.worker_id)
    assert env.state(t) is TaskState.FAILED


def test_assigned_but_not_running_does_not_count_as_crash():
    env = TestEnv()
    w = env.worker(cpus=4)
    (t,) = env.submit()
    env.schedule()
    env.lose_worker(w.worker_id)  # never reported running
    assert env.state(t) is TaskState.READY
    assert env.core.tasks[t].crash_counter == 0


def test_stale_instance_messages_ignored():
    env = TestEnv()
    w = env.worker(cpus=4)
    (t,) = env.submit()
    env.schedule()
    env.start_all_assigned()
    old_instance = env.core.tasks[t].instance_id
    env.lose_worker(w.worker_id)
    env.worker(cpus=4)
    env.schedule()
    from hyperqueue_tpu.server import reactor

    # stale "finished" from the dead incarnation must be dropped
    reactor.on_task_finished(env.core, env.comm, env.events, t, old_instance)
    assert env.state(t) is not TaskState.FINISHED


def test_cancel_ready_and_running():
    env = TestEnv()
    env.worker(cpus=1)
    a, b = env.submit(n=2)
    env.schedule()  # only a assigned (1 cpu)
    env.start_all_assigned()
    out = env.cancel([a, b])
    assert set(out) == {a, b}
    assert env.state(a) is TaskState.CANCELED
    assert env.state(b) is TaskState.CANCELED
    # running task got a cancel message to its worker
    assert any(a in tids for _, tids in env.comm.cancels)


def test_recall_asks_for_a_tick_to_place_what_waited():
    """A migration's recall frees its tasks' resources: the next tick must
    come by itself, to place another job's task that waited for them."""
    from hyperqueue_tpu.server import reactor

    env = TestEnv()
    env.worker(cpus=1)
    (a,) = env.submit(job=1)
    env.schedule()
    env.start_all_assigned()
    (b,) = env.submit(job=2)
    env.schedule()
    assert env.state(b) is TaskState.READY
    reactor.pause_jobs(env.core, env.comm, [1])
    asked = env.comm.scheduling_asked
    assert reactor.recall_tasks(env.core, env.comm, [a]) == 1
    assert env.comm.scheduling_asked > asked
    env.schedule()
    assert env.state(b) is TaskState.ASSIGNED
    assert reactor.recall_tasks(env.core, env.comm, [a]) == 0


def test_priorities_respected():
    env = TestEnv()
    env.worker(cpus=1)
    (low,) = env.submit(priority=(0, 0))
    (high,) = env.submit(priority=(5, 0))
    env.schedule()
    assert env.state(high) is TaskState.ASSIGNED
    assert env.state(low) is TaskState.READY


def test_variants_fall_back():
    env = TestEnv()
    env.worker(cpus=4)  # no gpus
    rqv = env.rqv(variants=[env.rq(gpus=1), env.rq(cpus=2)])
    (t,) = env.submit(rqv=rqv)
    env.schedule()
    assert env.state(t) is TaskState.ASSIGNED
    task = env.core.tasks[t]
    assert task.assigned_variant == 1  # gpu variant impossible


def test_gang_scheduling_all_or_nothing():
    env = TestEnv()
    env.worker(cpus=2, group="g1")
    env.worker(cpus=2, group="g1")
    (t,) = env.submit(rqv=env.rqv(n_nodes=3))
    env.schedule()
    assert env.state(t) is TaskState.READY  # only 2 workers in the group
    env.worker(cpus=2, group="g1")
    env.schedule()
    assert env.state(t) is TaskState.ASSIGNED
    task = env.core.tasks[t]
    assert len(task.mn_workers) == 3
    # compute message went to the root only, carrying the node list
    (wid, msgs), = env.comm.compute
    assert wid == task.mn_workers[0]
    assert msgs[0]["node_ids"] == list(task.mn_workers)
    # gang workers refuse other work while reserved
    ids = env.submit(n=4)
    env.schedule()
    assert all(env.state(i) is TaskState.READY for i in ids)


def test_gang_non_root_loss_keeps_running_on_root():
    """Reference reactor.rs RunningMultiNode ws.retain (CHANGELOG v0.25.1):
    a RUNNING gang that loses a NON-root member keeps running on the root
    with the member dropped — the user's launcher decides what a dead node
    means."""
    env = TestEnv()
    workers = [env.worker(cpus=2, group="g1") for _ in range(3)]
    (t,) = env.submit(rqv=env.rqv(n_nodes=3))
    env.schedule()
    env.start_all_assigned()
    task = env.core.tasks[t]
    root, mid, last = task.mn_workers
    instance = task.instance_id
    env.lose_worker(mid)
    assert env.state(t) is TaskState.RUNNING
    assert task.mn_workers == (root, last)
    assert task.crash_counter == 0
    assert task.instance_id == instance  # same incarnation keeps running
    # the task still completes normally on the survivors
    env.finish(t)
    assert env.state(t) is TaskState.FINISHED
    for w in env.core.workers.values():
        assert w.mn_task == 0


def test_gang_root_loss_tears_down_and_requeues():
    """Root loss while RUNNING tears the gang down, cancels on survivors,
    and requeues with the crash counter charged."""
    env = TestEnv()
    workers = [env.worker(cpus=2, group="g1") for _ in range(2)]
    (t,) = env.submit(rqv=env.rqv(n_nodes=2))
    env.schedule()
    env.start_all_assigned()
    task = env.core.tasks[t]
    root, member = task.mn_workers
    env.lose_worker(root)
    assert env.state(t) is TaskState.READY
    assert task.crash_counter == 1
    assert task.mn_workers == ()
    # the surviving member was told to cancel and is free again
    assert any(t in tids for wid, tids in env.comm.cancels if wid == member)
    assert all(w.mn_task == 0 for w in env.core.workers.values())


def test_never_restart_fails_even_on_clean_stop():
    """Reference reactor.rs:166 — a NeverRestart task running on a lost
    worker fails regardless of the loss reason, OUTSIDE the
    reason.is_failure() gate that exempts deliberate stops."""
    env = TestEnv()
    w = env.worker(cpus=4)
    (t,) = env.submit(crash_limit=-1)
    env.schedule()
    env.start_all_assigned()
    env.lose_worker(w.worker_id, clean=True)
    assert env.state(t) is TaskState.FAILED

    # but an ASSIGNED (never ran) never-restart task just requeues
    env = TestEnv()
    w = env.worker(cpus=4)
    (t,) = env.submit(crash_limit=-1)
    env.schedule()
    env.lose_worker(w.worker_id, clean=True)
    assert env.state(t) is TaskState.READY


def test_never_restart_gang_root_clean_loss_fails():
    env = TestEnv()
    [env.worker(cpus=2, group="g1") for _ in range(2)]
    (g,) = env.submit(rqv=env.rqv(n_nodes=2), crash_limit=-1)
    env.schedule()
    env.start_all_assigned()
    root = env.core.tasks[g].mn_workers[0]
    env.lose_worker(root, clean=True)
    assert env.state(g) is TaskState.FAILED


def test_clean_stop_does_not_charge_crash_counter():
    env = TestEnv()
    w = env.worker(cpus=4)
    (t,) = env.submit()
    env.schedule()
    env.start_all_assigned()
    env.lose_worker(w.worker_id, clean=True)
    assert env.state(t) is TaskState.READY
    assert env.core.tasks[t].crash_counter == 0


def test_worker_added_after_submit_triggers_assignment():
    env = TestEnv()
    ids = env.submit(n=3)
    assert env.schedule() == 0
    env.worker(cpus=4)
    assert env.schedule() == 3
    assert all(env.state(i) is TaskState.ASSIGNED for i in ids)


def test_gang_assigned_teardown_cancels_survivors():
    """Losing a non-root member while the gang is still ASSIGNED (compute
    message in flight to the root) must cancel on the surviving workers —
    otherwise the root launches a stale instance alongside the requeued one."""
    env = TestEnv()
    [env.worker(cpus=2, group="g1") for _ in range(3)]
    (t,) = env.submit(rqv=env.rqv(n_nodes=3))
    env.schedule()
    task = env.core.tasks[t]
    assert env.state(t) is TaskState.ASSIGNED
    root, mid, last = task.mn_workers
    env.lose_worker(mid)
    assert env.state(t) is TaskState.READY
    canceled_on = {wid for wid, _ in env.comm.cancels}
    assert root in canceled_on and last in canceled_on
    assert mid not in canceled_on


def test_gang_ineligible_short_lifetime_workers_never_chosen():
    """Workers without enough remaining lifetime for the gang's min_time are
    never picked as members (reference worker.rs is_capable_to_run)."""
    env = TestEnv()
    # group g1: enough workers but all about to expire
    [env.worker(cpus=2, group="g1", time_limit=5.0) for _ in range(3)]
    # group g2: long-lived workers
    long_lived = [env.worker(cpus=2, group="g2") for _ in range(3)]
    (t,) = env.submit(rqv=env.rqv(n_nodes=3, min_time=60.0))
    env.schedule()
    task = env.core.tasks[t]
    assert env.state(t) is TaskState.ASSIGNED
    assert set(task.mn_workers) == {w.worker_id for w in long_lived}


def test_gang_under_resourced_group_stays_pending():
    env = TestEnv()
    [env.worker(cpus=2, group="g1", time_limit=5.0) for _ in range(3)]
    (t,) = env.submit(rqv=env.rqv(n_nodes=3, min_time=60.0))
    env.schedule()
    assert env.state(t) is TaskState.READY
    # expiring workers must not be reserved for a gang they can never host
    assert all(w.mn_reserved == 0 for w in env.core.workers.values())


def test_gang_wins_workers_under_sn_stream():
    """A pending gang reserves draining workers and eventually claims them,
    even though same-priority sn tasks keep arriving (anti-starvation)."""
    env = TestEnv()
    workers = [env.worker(cpus=1, group="g1") for _ in range(2)]
    # saturate both workers with running sn tasks
    busy = env.submit(n=2)
    env.schedule()
    env.start_all_assigned()
    assert all(env.state(i) is TaskState.RUNNING for i in busy)
    (g,) = env.submit(rqv=env.rqv(n_nodes=2))
    for round_no in range(20):
        # continuous stream: one new small task per tick
        env.submit(n=1)
        env.schedule(prefill=True)
        if env.state(g) is TaskState.ASSIGNED:
            break
        # both workers must be draining for the gang from the first tick
        assert all(w.mn_reserved == g for w in workers), round_no
        # finish whatever is running, freeing capacity for the next tick
        for task in list(env.core.tasks.values()):
            if task.state is TaskState.RUNNING:
                env.finish(task.task_id)
    assert env.state(g) is TaskState.ASSIGNED
    assert all(w.mn_task == g for w in workers)
    assert all(w.mn_reserved == 0 for w in workers)


def test_gang_defers_to_higher_priority_sn():
    """Reservation must not hold workers while strictly-higher-priority sn
    work is pending (priority interleaving, reference solver.rs:479-518)."""
    env = TestEnv()
    [env.worker(cpus=1, group="g1") for _ in range(2)]
    busy = env.submit(n=2)
    env.schedule()
    env.start_all_assigned()
    (g,) = env.submit(rqv=env.rqv(n_nodes=2), priority=(0, 0))
    env.submit(n=4, priority=(5, 0))
    env.schedule()
    assert all(w.mn_reserved == 0 for w in env.core.workers.values())
    # once the high-priority stream is gone, the gang reserves again
    for task in list(env.core.tasks.values()):
        if task.state is TaskState.RUNNING:
            env.finish(task.task_id)
    for _ in range(10):
        env.schedule()
        for task in list(env.core.tasks.values()):
            if task.state is TaskState.RUNNING:
                env.finish(task.task_id)
            elif task.state is TaskState.ASSIGNED and not task.prefilled:
                from hyperqueue_tpu.server import reactor as _r
                _r.on_task_running(
                    env.core, env.events, task.task_id, task.instance_id
                )
        if env.state(g) in (TaskState.ASSIGNED, TaskState.FINISHED):
            break
    assert env.state(g) in (
        TaskState.ASSIGNED,
        TaskState.RUNNING,
        TaskState.FINISHED,
    )


def test_gang_cancel_clears_reservations():
    env = TestEnv()
    workers = [env.worker(cpus=1, group="g1") for _ in range(2)]
    busy = env.submit(n=2)
    env.schedule()
    env.start_all_assigned()
    (g,) = env.submit(rqv=env.rqv(n_nodes=2))
    env.schedule()
    assert all(w.mn_reserved == g for w in workers)
    env.cancel([g])
    assert env.state(g) is TaskState.CANCELED
    assert all(w.mn_reserved == 0 for w in workers)
    # workers accept sn work again
    ids = env.submit(n=2)
    for t in busy:
        env.finish(t)
    env.schedule()
    assert all(env.state(i) is TaskState.ASSIGNED for i in ids)


def test_gang_reserves_despite_older_same_priority_job():
    """Production priorities are (user_priority, -job_id); an older sn job's
    tuple strictly outranks a newer gang's, but only the USER priority may
    suppress reservation."""
    env = TestEnv()
    workers = [env.worker(cpus=1, group="g1") for _ in range(2)]
    busy = env.submit(n=2, priority=(0, -1), job=1)
    env.schedule()
    env.start_all_assigned()
    env.submit(n=6, priority=(0, -1), job=1)  # pending sn stream, job 1
    (g,) = env.submit(rqv=env.rqv(n_nodes=2), priority=(0, -2), job=2)
    env.schedule()
    assert all(w.mn_reserved == g for w in workers)


def test_unschedulable_high_priority_sn_does_not_block_gang():
    """A ready sn task no worker can ever run must not suppress gang
    reservations, no matter its priority."""
    env = TestEnv()
    workers = [env.worker(cpus=1, group="g1") for _ in range(2)]
    busy = env.submit(n=2)
    env.schedule()
    env.start_all_assigned()
    env.submit(n=1, rqv=env.rqv(cpus=64), priority=(9, 0))  # impossible
    (g,) = env.submit(rqv=env.rqv(n_nodes=2))
    env.schedule()
    assert all(w.mn_reserved == g for w in workers)


def test_gang_reservation_released_when_group_shrinks():
    """If the reserved group loses eligibility (a member dies), the surviving
    reservations must lift so those workers rejoin sn scheduling."""
    env = TestEnv()
    w1 = env.worker(cpus=1, group="g1")
    w2 = env.worker(cpus=1, group="g1")
    busy = env.submit(n=2)
    env.schedule()
    env.start_all_assigned()
    (g,) = env.submit(rqv=env.rqv(n_nodes=2))
    env.schedule()
    assert w1.mn_reserved == g and w2.mn_reserved == g
    env.lose_worker(w2.worker_id)
    env.schedule()
    assert w1.mn_reserved == 0
    # w1 accepts sn work again (w2's requeued task or the new one)
    ids = env.submit(n=1)
    for t in busy:
        task = env.core.tasks[t]
        if task.state is TaskState.RUNNING and task.assigned_worker == w1.worker_id:
            env.finish(t)
    env.schedule()
    assert w1.assigned_tasks, "released worker must accept sn work again"


def test_gang_reservation_retract_sent_once():
    env = TestEnv()
    workers = [env.worker(cpus=1, group="g1") for _ in range(2)]
    busy = env.submit(n=2)
    env.schedule(prefill=True)
    env.start_all_assigned()
    env.submit(n=10)
    env.schedule(prefill=True)  # builds prefilled backlog on the workers
    assert any(w.prefilled_tasks for w in workers)
    (g,) = env.submit(rqv=env.rqv(n_nodes=2))
    before = len(env.comm.retracts)
    env.schedule(prefill=True)
    after_first = len(env.comm.retracts)
    assert after_first > before  # backlog stolen back at reservation time
    env.schedule(prefill=True)
    env.schedule(prefill=True)
    assert len(env.comm.retracts) == after_first  # not re-sent every tick


def _walked_reservations(core) -> dict:
    """`core.mn_reservations` as a walk over the workers gives it."""
    walked: dict = {}
    for w in core.workers.values():
        if w.mn_reserved:
            walked.setdefault(w.mn_reserved, set()).add(w.worker_id)
    return walked


def _assert_index_is_the_walk(core):
    assert core.mn_reservations == _walked_reservations(core)
    assert all(
        wid in core.workers
        for held in core.mn_reservations.values() for wid in held
    )


def _busy_group(env, n, group="g1"):
    """`n` one-cpu workers of one group, each running a task."""
    workers = [env.worker(cpus=1, group=group) for _ in range(n)]
    busy = env.submit(n=n)
    env.schedule()
    env.start_all_assigned()
    return workers, busy


def test_reservation_index_follows_reserve_retarget_release_and_start():
    env = TestEnv()
    (w1, w2, w3), busy = _busy_group(env, 3)
    (g,) = env.submit(rqv=env.rqv(n_nodes=2))
    env.schedule()  # host phase: the two lowest ids of the busy group drain
    assert env.core.mn_reservations == {g: {w1.worker_id, w2.worker_id}}
    _assert_index_is_the_walk(env.core)
    # w3 falls idle: the reservation moves to it and to one busy worker
    env.finish(next(iter(w3.assigned_tasks)))
    env.schedule()
    assert env.core.mn_reservations == {g: {w3.worker_id, w1.worker_id}}
    assert w2.mn_reserved == 0
    _assert_index_is_the_walk(env.core)
    # higher-priority single-node work outranks the gang: released
    env.submit(n=4, priority=(5, 0))
    env.schedule()
    assert env.core.mn_reservations == {}
    _assert_index_is_the_walk(env.core)
    # that work runs out; the gang reserves again, then starts on its workers
    for _ in range(8):
        env.start_all_assigned()
        for task in list(env.core.tasks.values()):
            if task.state is TaskState.RUNNING:
                env.finish(task.task_id)
        env.schedule()
        _assert_index_is_the_walk(env.core)
        if env.state(g) is TaskState.ASSIGNED:
            break
    assert env.state(g) is TaskState.ASSIGNED
    assert env.core.mn_reservations == {}


def test_reservation_index_follows_cancel_pause_resume_and_disconnect():
    from hyperqueue_tpu.server import reactor

    env = TestEnv()
    workers, busy = _busy_group(env, 4)
    (ga,) = env.submit(rqv=env.rqv(n_nodes=2), job=2)
    (gb,) = env.submit(rqv=env.rqv(n_nodes=2), job=3)
    env.schedule()  # each gang drains two workers of its own
    ids = [w.worker_id for w in workers]
    assert env.core.mn_reservations == {ga: set(ids[:2]), gb: set(ids[2:])}
    _assert_index_is_the_walk(env.core)
    env.cancel([ga])
    assert env.core.mn_reservations == {gb: set(ids[2:])}
    _assert_index_is_the_walk(env.core)
    reactor.pause_jobs(env.core, env.comm, [3])
    assert env.core.mn_reservations == {} and env.core.mn_queue == []
    _assert_index_is_the_walk(env.core)
    reactor.resume_jobs(env.core, env.comm, [3])
    env.schedule()
    assert env.core.mn_reservations == {gb: set(ids[:2])}
    _assert_index_is_the_walk(env.core)
    # a reserved worker disconnects: the index forgets it with the worker
    env.lose_worker(ids[0])
    assert env.core.mn_reservations == {gb: {ids[1]}}
    _assert_index_is_the_walk(env.core)
    env.schedule()  # three workers left: the gang re-targets among them
    assert len(env.core.mn_reservations[gb]) == 2
    _assert_index_is_the_walk(env.core)
    for wid in list(env.core.workers):
        env.lose_worker(wid)
        _assert_index_is_the_walk(env.core)
    assert env.core.mn_reservations == {}


def _sorted_as_the_parent_did(core, queue):
    """What `append` + the parent's stable sort left in `mn_queue`."""
    queue.sort(key=lambda t: core.tasks[t].priority, reverse=True)


@pytest.mark.parametrize("seed", [1, 2, 3, 5, 8, 2147483659])
def test_ready_gang_is_inserted_where_the_sort_left_it(seed):
    import random

    rng = random.Random(seed)
    env = TestEnv()
    gang = env.rqv(n_nodes=2)
    expected: list[int] = []
    for step in range(300):
        if expected and rng.random() < 0.15:
            gone = rng.choice(expected)
            env.cancel([gone])
            expected.remove(gone)
        else:
            # three user levels and few scheduler levels: many ties
            priority = (rng.randrange(3), -rng.randrange(4))
            (task_id,) = env.submit(rqv=gang, priority=priority)
            expected.append(task_id)
            _sorted_as_the_parent_did(env.core, expected)
        assert env.core.mn_queue == expected, step


def test_ready_gangs_and_resumed_jobs_keep_the_sorts_order():
    import random

    from hyperqueue_tpu.ids import task_id_job
    from hyperqueue_tpu.server import reactor

    rng = random.Random(13)
    env = TestEnv()
    gang = env.rqv(n_nodes=2)
    expected: list[int] = []
    held: dict[int, list[int]] = {}
    for step in range(300):
        roll = rng.random()
        job = rng.randrange(1, 4)
        if roll < 0.08 and job not in held:
            reactor.pause_jobs(env.core, env.comm, [job])
            held[job] = [t for t in expected if task_id_job(t) == job]
            expected = [t for t in expected if task_id_job(t) != job]
        elif roll < 0.16 and job in held:
            reactor.resume_jobs(env.core, env.comm, [job])
            expected += sorted(held.pop(job))
            _sorted_as_the_parent_did(env.core, expected)
        else:
            priority = (rng.randrange(3), -job)
            (task_id,) = env.submit(rqv=gang, priority=priority, job=job)
            if job in held:
                held[job].append(task_id)
            else:
                expected.append(task_id)
                _sorted_as_the_parent_did(env.core, expected)
        assert env.core.mn_queue == expected, step
    assert held or len(expected) > 100


def _parents_fused_gang_rows(core):
    """`fused_gang_rows` as it was before the queue was indexed: every entry
    walked, the workers swept for each row and each dead entry."""
    from hyperqueue_tpu.scheduler.tick import Batch
    from hyperqueue_tpu.server import reactor

    def sweep(task_id):
        for w in core.workers.values():
            if w.mn_reserved == task_id:
                core.reserve_mn(w, 0)

    rows, remaining = [], []
    for task_id in core.mn_queue:
        task = core.tasks.get(task_id)
        if task is None or task.is_done:
            sweep(task_id)
            continue
        remaining.append(task_id)
        if len(rows) < reactor.MAX_FUSED_GANG_ROWS:
            sweep(task_id)
            rqv = core.rq_map.get_variants(task.rq_id)
            rows.append(Batch(
                rq_id=task.rq_id, priority=task.priority, size=1,
                gang_task=task_id, gang_nodes=rqv.variants[0].n_nodes,
            ))
    core.mn_queue = remaining
    return rows


def _queue_with_reservations_and_dead_entries():
    """Three gangs that a host-phase tick reserved two workers each, then
    eighteen gangs ahead of them and thousands behind; of the reserved, the
    first is a row, the second is dead deep in the queue, the third lives
    beyond the rows; dead entries at the head and in the tail."""
    env = TestEnv()
    _busy_group(env, 6)
    gang = env.rqv(n_nodes=2)
    reserved = env.submit(n=3, rqv=gang, priority=(3, 0))
    env.schedule()
    assert set(env.core.mn_reservations) == set(reserved)
    ahead = env.submit(n=18, rqv=gang, priority=(5, 0))
    behind = env.submit(n=3000, rqv=gang, priority=(1, 0))
    env.cancel([ahead[7]])  # so that reserved[0] is the sixteenth row
    from hyperqueue_tpu.server.task import TaskState as State

    dead = [ahead[0], ahead[3], reserved[1], behind[1500]]
    for task_id in dead[1:]:
        env.core.tasks[task_id].state = State.CANCELED
    del env.core.tasks[dead[0]]
    return env, reserved, ahead, behind, dead


def test_fused_rows_read_the_head_and_equal_the_parents_rows():
    from hyperqueue_tpu.server import reactor

    env, reserved, ahead, behind, dead = (
        _queue_with_reservations_and_dead_entries())
    then, *_ = _queue_with_reservations_and_dead_entries()
    assert then.core.mn_queue == env.core.mn_queue
    rows_then = _parents_fused_gang_rows(then.core)
    rows = reactor.fused_gang_rows(env.core)
    assert rows == rows_then and len(rows) == reactor.MAX_FUSED_GANG_ROWS
    assert rows[-1].gang_task == reserved[0]
    # the same workers stay reserved: the live gang beyond the rows keeps
    # its two, the row's and the dead gang's are lifted
    assert env.core.mn_reservations == then.core.mn_reservations
    assert set(env.core.mn_reservations) == {reserved[2]}
    _assert_index_is_the_walk(env.core)
    assert ({w.worker_id: w.mn_reserved for w in env.core.workers.values()}
            == {w.worker_id: w.mn_reserved
                for w in then.core.workers.values()})
    # 16 live rows and the two dead entries among them were looked at, and
    # the four workers that held a reservation to lift, of 3 021 and 6
    assert env.core.mn_examined_total == 18
    assert env.core.mn_swept_total == 4
    # the live entries are the parent's, in its order; the dead entries
    # beyond the head wait there and are no row
    live = [t for t in env.core.mn_queue if t not in dead]
    assert live == then.core.mn_queue
    assert [t for t in env.core.mn_queue if t in dead] == dead[2:]
    assert not {r.gang_task for r in rows} & set(dead)
    # the next tick finds nothing dead at the head and nothing to lift
    assert reactor.fused_gang_rows(env.core) == rows
    assert env.core.mn_examined_total == 18 + 16
    assert env.core.mn_swept_total == 4


def test_dead_entry_is_dropped_when_it_reaches_the_head():
    from hyperqueue_tpu.server import reactor

    env = TestEnv()
    gang = env.rqv(n_nodes=2)
    queued = env.submit(n=4000, rqv=gang)
    gone = queued[2000]
    env.core.tasks[gone].state = TaskState.CANCELED
    rows = reactor.fused_gang_rows(env.core)
    assert [r.gang_task for r in rows] == queued[:16]
    assert (env.core.mn_examined_total, env.core.mn_swept_total) == (16, 0)
    assert gone in env.core.mn_queue and len(env.core.mn_queue) == 4000
    # the gangs ahead of it start, sixteen a tick, until it is at the head
    del env.core.mn_queue[:1990]
    rows = reactor.fused_gang_rows(env.core)
    assert [r.gang_task for r in rows] == queued[1990:2000] + queued[2001:2007]
    assert gone not in env.core.mn_queue and len(env.core.mn_queue) == 2009
    assert (env.core.mn_examined_total, env.core.mn_swept_total) == (33, 0)


def test_fused_tick_lifts_the_reservation_a_host_tick_left():
    env = TestEnv()
    workers, busy = _busy_group(env, 2)
    (g,) = env.submit(rqv=env.rqv(n_nodes=2))
    env.schedule()  # host phase
    assert all(w.mn_reserved == g for w in workers)
    env.core.fused_solve = True
    env.schedule()  # fused: the gang is a row, nobody drains for it
    assert all(w.mn_reserved == 0 for w in workers)
    assert env.core.mn_reservations == {}
    assert (env.core.mn_examined_total, env.core.mn_swept_total) == (1, 2)
    assert len(env.core.tick_cache.sync(env.core).worker_ids) == 2
    env.schedule()
    assert (env.core.mn_examined_total, env.core.mn_swept_total) == (2, 2)
    for t in busy:
        env.finish(t)
    env.schedule()
    assert env.state(g) is TaskState.ASSIGNED


def test_mn_task_fail_releases_gang():
    """test_reactor.rs:472 — a gang task failing mid-run frees every member
    and propagates the failure."""
    env = TestEnv()
    workers = [env.worker(cpus=2, group="g1") for _ in range(3)]
    (g,) = env.submit(rqv=env.rqv(n_nodes=3))
    (child,) = env.submit(deps=[g])
    env.schedule()
    env.start_all_assigned()
    assert env.state(g) is TaskState.RUNNING
    env.fail(g, "gang exploded")
    assert env.state(g) is TaskState.FAILED
    assert env.state(child) is TaskState.CANCELED
    assert all(w.mn_task == 0 for w in workers)
    # members accept new work again
    ids = env.submit(n=3)
    env.schedule()
    assert all(env.state(t) is TaskState.ASSIGNED for t in ids)


def test_mn_task_cancel_releases_gang_and_notifies_members():
    """test_reactor.rs:497 — cancelling a running gang cancels on its
    workers and frees them."""
    env = TestEnv()
    workers = [env.worker(cpus=2, group="g1") for _ in range(2)]
    (g,) = env.submit(rqv=env.rqv(n_nodes=2))
    env.schedule()
    env.start_all_assigned()
    out = env.cancel([g])
    assert out == [g]
    assert env.state(g) is TaskState.CANCELED
    assert all(w.mn_task == 0 for w in workers)
    canceled_on = {wid for wid, tids in env.comm.cancels if g in tids}
    assert canceled_on == {w.worker_id for w in workers}


def test_prefilled_task_failure_accounts_cleanly():
    """test_reactor.rs:950 — a prefilled task that starts and fails must
    fully release its (deferred-then-assigned) resources."""
    env = TestEnv()
    w = env.worker(cpus=1)
    a, b = env.submit(n=2)
    env.schedule(prefill=True)
    env.start_all_assigned()
    # b is prefilled behind a
    task_b = env.core.tasks[b]
    assert task_b.prefilled
    env.finish(a)
    # worker reports b running, then failing
    from hyperqueue_tpu.server import reactor

    reactor.on_task_running(env.core, env.events, b, task_b.instance_id)
    assert not task_b.prefilled  # resources accounted on start
    env.fail(b)
    assert env.state(b) is TaskState.FAILED
    assert w.free == w.resources.amounts
    assert not w.assigned_tasks and not w.prefilled_tasks


def test_retract_in_flight_source_worker_lost():
    """test_reactor.rs:1096 — the donor dies while a retract is pending:
    the task requeues via worker loss and the stale retract answer (ok or
    not) must be ignored."""
    from hyperqueue_tpu.server import reactor

    env = TestEnv()
    w1 = env.worker(cpus=1)
    busy = env.submit(n=1)
    env.schedule(prefill=True)
    env.start_all_assigned()
    backlog = env.submit(n=10)
    env.schedule(prefill=True)
    assert w1.prefilled_tasks
    env.worker(cpus=1)  # idle worker triggers a retract
    env.schedule(prefill=True)
    pending = [
        t for t in backlog if env.core.tasks[t].retract_pending
    ]
    assert pending
    victim = pending[0]
    old_instance = env.core.tasks[victim].instance_id
    env.lose_worker(w1.worker_id)
    task = env.core.tasks[victim]
    assert task.state is TaskState.READY
    assert not task.retract_pending
    assert task.instance_id == old_instance + 1
    # stale retract answers (old instance) arrive after the loss: no-ops
    reactor.on_retract_response(
        env.core, env.comm, victim, True, old_instance
    )
    assert task.state is TaskState.READY
    assert task.instance_id == old_instance + 1
    reactor.on_retract_response(
        env.core, env.comm, victim, False, old_instance
    )
    assert task.state is TaskState.READY
    assert task.instance_id == old_instance + 1


def test_stale_retract_answer_after_reprefill_ignored():
    """The killer race: a retract answer from a DEAD placement must not
    steal the task off the worker it was since re-prefilled onto."""
    from hyperqueue_tpu.server import reactor

    env = TestEnv()
    w1 = env.worker(cpus=1)
    env.submit(n=1)
    env.schedule(prefill=True)
    env.start_all_assigned()
    backlog = env.submit(n=10)
    env.schedule(prefill=True)
    w2 = env.worker(cpus=1)
    env.schedule(prefill=True)  # retract sent to w1 for some backlog
    pending = [t for t in backlog if env.core.tasks[t].retract_pending]
    assert pending
    victim = pending[0]
    retracted_instance = env.core.tasks[victim].instance_id
    # occupy w2 so the requeued victim will be re-PREFILLED, not directly
    # assigned
    env.submit(n=1)
    env.schedule(prefill=False)
    env.start_all_assigned()
    assert not w2.is_idle()
    # w1 answers ok=True: task requeues and gets re-prefilled on the next
    # tick
    reactor.on_retract_response(
        env.core, env.comm, victim, True, retracted_instance
    )
    env.schedule(prefill=True)
    task = env.core.tasks[victim]
    assert task.prefilled
    new_worker = task.assigned_worker
    instance = task.instance_id
    assert instance == retracted_instance + 1
    # a duplicate/late answer from the OLD placement (old instance) must
    # NOT touch the new one
    reactor.on_retract_response(
        env.core, env.comm, victim, True, retracted_instance
    )
    assert task.assigned_worker == new_worker
    assert task.instance_id == instance
    assert task.prefilled


# ---------------------------------------------------------------------------
# test_scheduler_mn.rs:89/139/195/261 — gang scheduling orders and packing
# (mn batches live in core.mn_queue here, not TaskQueues — the reference's
# mn batch-structure cases test_mn_task_batches1/2 have no direct analog;
# their scheduling OUTCOMES are pinned below instead)
# ---------------------------------------------------------------------------

def test_mn_simple_priority_order_and_refill():
    """schedule_mn_simple: four 2-node gangs over five workers — the two
    highest-priority gangs run on disjoint pairs; finishing one admits the
    next-highest."""
    env = TestEnv()
    for _ in range(5):
        env.worker(cpus=5)
    t1 = env.submit(rqv=env.rqv(n_nodes=2), priority=(1, 0))[0]
    t2 = env.submit(rqv=env.rqv(n_nodes=2), priority=(2, 0))[0]
    t3 = env.submit(rqv=env.rqv(n_nodes=2), priority=(3, 0))[0]
    t4 = env.submit(rqv=env.rqv(n_nodes=2), priority=(4, 0))[0]
    env.schedule()
    ws3 = env.core.tasks[t3].mn_workers
    ws4 = env.core.tasks[t4].mn_workers
    assert len(ws3) == 2 and len(ws4) == 2
    assert not set(ws3) & set(ws4)
    assert env.state(t2) in (TaskState.READY, TaskState.WAITING)
    assert env.state(t1) in (TaskState.READY, TaskState.WAITING)
    env.finish(t3)
    env.schedule()
    assert len(env.core.tasks[t2].mn_workers) == 2


def test_mn_reserve_sequential_gangs():
    """schedule_mn_reserve: gangs of 3, 2, 3 nodes at descending priority
    over three 1-cpu workers run strictly in priority order as each
    finishes."""
    env = TestEnv()
    for _ in range(3):
        env.worker(cpus=1)
    t1 = env.submit(rqv=env.rqv(n_nodes=3), priority=(10, 0))[0]
    t2 = env.submit(rqv=env.rqv(n_nodes=2), priority=(5, 0))[0]
    t3 = env.submit(rqv=env.rqv(n_nodes=3), priority=(0, 0))[0]
    env.schedule()
    assert len(env.core.tasks[t1].mn_workers) == 3
    assert env.core.tasks[t2].mn_workers == ()
    env.finish(t1)
    env.schedule()
    assert len(env.core.tasks[t2].mn_workers) == 2
    assert env.core.tasks[t3].mn_workers == ()
    env.finish(t2)
    env.schedule()
    assert len(env.core.tasks[t3].mn_workers) == 3
    env.finish(t3)
    for w in env.core.workers.values():
        assert w.mn_task == 0


def test_mn_fill_all_gangs_at_once():
    """schedule_mn_fill: gangs of 3+5+1+2 nodes exactly cover 11 workers in
    one tick."""
    env = TestEnv()
    for _ in range(11):
        env.worker(cpus=2)
    tasks = [
        env.submit(rqv=env.rqv(n_nodes=n))[0] for n in (3, 5, 1, 2)
    ]
    env.schedule()
    for t in tasks:
        assert env.state(t) is TaskState.ASSIGNED, t
    assert all(w.mn_task != 0 for w in env.core.workers.values())


def test_mn_sleep_wakeup_at_once():
    """mn_sleep_wakeup_at_once: the unsatisfiable high-priority gang waits
    while a smaller lower-priority one starts the same tick."""
    env = TestEnv()
    env.worker(cpus=4)
    env.worker(cpus=1)
    t1 = env.submit(rqv=env.rqv(n_nodes=4), priority=(10, 0))[0]
    t2 = env.submit(rqv=env.rqv(n_nodes=2), priority=(1, 0))[0]
    env.schedule()
    assert env.core.tasks[t1].mn_workers == ()
    assert len(env.core.tasks[t2].mn_workers) == 2


# ---------------------------------------------------------------------------
# test_scheduler_mn.rs:315-356 test_schedule_mn_and_sn1-4
# ---------------------------------------------------------------------------

def test_mn_and_sn_priority_matrix():
    """Gang-vs-single-node priority: the higher priority side wins both
    workers; at equal priority the gang goes first (reference mn_and_sn3);
    with a spare worker both run (mn_and_sn4)."""
    # sn1: gang@2 beats sn@1 -> gang runs, sn waits
    env = TestEnv()
    env.worker(cpus=4)
    env.worker(cpus=4)
    g = env.submit(rqv=env.rqv(n_nodes=2), priority=(2, 0))[0]
    s = env.submit(rqv=env.rqv(cpus=4), priority=(1, 0))[0]
    env.schedule()
    assert len(env.core.tasks[g].mn_workers) == 2
    assert env.state(s) is not TaskState.ASSIGNED

    # sn2: sn@2 beats gang@1 -> sn assigned, gang waits
    env = TestEnv()
    env.worker(cpus=4)
    env.worker(cpus=4)
    g = env.submit(rqv=env.rqv(n_nodes=2), priority=(1, 0))[0]
    s = env.submit(rqv=env.rqv(cpus=4), priority=(2, 0))[0]
    env.schedule()
    assert env.core.tasks[g].mn_workers == ()
    assert env.state(s) is TaskState.ASSIGNED

    # sn3: equal priority -> the gang wins the pair
    env = TestEnv()
    env.worker(cpus=4)
    env.worker(cpus=4)
    g = env.submit(rqv=env.rqv(n_nodes=2), priority=(1, 0))[0]
    s = env.submit(rqv=env.rqv(cpus=4), priority=(1, 0))[0]
    env.schedule()
    assert len(env.core.tasks[g].mn_workers) == 2
    assert env.state(s) is not TaskState.ASSIGNED

    # sn4: three workers -> gang takes two, sn the third
    env = TestEnv()
    env.worker(cpus=4)
    env.worker(cpus=3)
    env.worker(cpus=4)
    g = env.submit(rqv=env.rqv(n_nodes=2), priority=(1, 0))[0]
    s = env.submit(rqv=env.rqv(cpus=4), priority=(1, 0))[0]
    env.schedule()
    assert len(env.core.tasks[g].mn_workers) == 2
    assert env.state(s) is TaskState.ASSIGNED


def test_gang_defers_to_any_higher_priority_sn_class():
    """Deference scans every strictly-higher-user-priority sn class, not
    just the single top tuple: here the TOP class is unschedulable on the
    gang's workers but a middle class is, and it must still win them."""
    env = TestEnv()
    env.worker(cpus=4)
    env.worker(cpus=4)
    env.worker(cpus=1, gpus=1)
    # top-priority class: gpu-only, cannot use the gang's 4-cpu workers
    env.submit(rqv=env.rqv(gpus=1), priority=(5, 0))
    # middle class CAN use them and outranks the gang
    s = env.submit(rqv=env.rqv(cpus=4), priority=(4, 0))[0]
    g = env.submit(rqv=env.rqv(n_nodes=2), priority=(3, 0))[0]
    env.schedule()
    assert env.state(s) is TaskState.ASSIGNED
    assert env.core.tasks[g].mn_workers == ()


def test_default_compact_scheduling():
    """reference tests/test_server.py test_server_compact_scheduling: the
    default placement packs small tasks onto few workers (8 one-cpu tasks
    over 8 four-cpu workers land on exactly 2) instead of spreading."""
    env = TestEnv()
    for _ in range(8):
        env.worker(cpus=4)
    tasks = env.submit(n=8)
    env.schedule()
    assigned = [
        t for t in env.core.tasks.values() if t.assigned_worker
    ]
    assert len(assigned) == len(tasks)  # nothing stranded
    assert len({t.assigned_worker for t in assigned}) == 2
