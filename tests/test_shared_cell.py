"""The `shared-1k` deployment at a test's size: multi-node tasks and
single-node tasks on the same nodes under `--gang-drain busy`, against the
benchmark's plain reference.

`run_tick` with the numpy backend and with `backend="jax"` on the CPU, fed
by the reactor's fused gang functions and its reservation step as
`reactor._tick` and the `shared` driver feed them, over some tens of ticks
of churn with gangs reserving, draining, starting and ending, must equal
`chipbench/reference/shared_plain.py` tick by tick (placements, every
started gang's member set, every tick's reservation sets); every control
has to fail.  Also here, through `reactor.schedule` itself: a gang that
starves under `--gang-drain idle` behind a deep single-node stream starts
under `busy` once its reserved members drain; a reserved worker's prefilled
backlog is retracted once and it is prefilled no more; a reservation moves
no row; the option reaches the core from the command line.
"""

import functools

import numpy as np
import pytest

from chipbench import control_shared, generate_shared, manifest
from chipbench.drivers import gang as gang_driver
from chipbench.drivers import shared as shared_driver
from chipbench.drivers import tick as tick_driver
from chipbench.reference import shared_plain
from hyperqueue_tpu.models.greedy import GreedyCutScanModel
from hyperqueue_tpu.scheduler.tick import create_batches, run_tick
from hyperqueue_tpu.server import reactor
from hyperqueue_tpu.server.core import Core
from hyperqueue_tpu.server.task import TaskState
from hyperqueue_tpu.utils.metrics import REGISTRY
from utils_env import TestEnv

CELL = "shared-1k.reserve"
SCALE = {"workers": 192, "groups": 4, "ready_tasks": 8000, "ready_gangs": 160}
# a high churn, so that reserved workers drain within a test's ticks
SHARE, GANG_SHARE = 0.25, 0.15


def record(model, seed, n_ticks, scale=SCALE, share=SHARE):
    """`n_ticks` production ticks of `model` under `--gang-drain busy` over
    the cell's world at a test's size: the filler alone for one tick, then
    the gangs arrive.  Returns (world, log, gang_log, resv_log, rq_ids,
    worker_ids, backends)."""
    cell = manifest.cell(CELL)
    world = generate_shared.world(cell["config"], cell["traffic"], seed,
                                  scale)
    core, rq_ids, worker_ids, gang_rq = gang_driver.build_program_state(
        world, cell["config"])
    core.fused_solve = True
    core.set_gang_drain("busy")
    cluster = gang_driver.Cluster(world, core, rq_ids, seed, gang_rq)
    backends, resv_log = set(), []
    for i in range(n_ticks):
        rows = reactor.fused_gang_rows(core) if core.mn_queue else []
        snap = core.tick_cache.sync(core)
        batches = create_batches(core.queues) + rows
        gang_ok = group_ids = resv = None
        if rows:
            gang_ok, group_ids = reactor.fused_gang_inputs(
                core, snap.worker_ids)
            resv = reactor.fused_gang_reserve(
                core, cluster.comm, rows, snap, gang_ok, group_ids, batches)
            assert resv.tolist() == [
                core.workers[w].mn_reserved for w in snap.worker_ids]
        resv_log.append(shared_driver.reservation_sets(
            shared_driver.record_reservations(resv, snap.worker_ids)))
        out = run_tick(
            core.queues, None, core.rq_map, core.resource_map, model,
            batches=batches, dense=snap, key_cache=core.tick_cache,
            gang_ok=gang_ok, group_ids=group_ids, gang_resv=resv,
        )
        cluster.started(cluster.apply(out))
        assert cluster.refused == 0
        assert core.tick_cache.reservations_told(core.workers)
        backends.add(model.last_backend)
        cluster.churn(share, gang_share=GANG_SHARE if i else 0.0,
                      arrive=() if i else world.gang_nodes.tolist())
    return (world, cluster.log, cluster.gang_log, resv_log, rq_ids,
            worker_ids, backends)


def compare(recorded, **reference_kwargs):
    world, log, gang_log, resv_log, rq_ids, worker_ids, _b = recorded
    return shared_driver.compare_with_reference(
        world, log, gang_log, resv_log, rq_ids, worker_ids,
        functools.partial(shared_plain.Reference, **reference_kwargs))


@pytest.fixture(scope="module")
def numpy_run():
    return record(GreedyCutScanModel(backend="numpy"), seed=2147483701,
                  n_ticks=40)


def test_numpy_ticks_equal_the_plain_reference(numpy_run):
    world, log, gang_log, resv_log, rq_ids, worker_ids, backends = numpy_run
    assert backends <= {"host-native", "host-numpy"}
    numbers = compare(numpy_run)
    assert numbers["ticks_mismatched"] == 0, numbers
    assert numbers["ticks_replayed"] == len(log) == 40
    # gangs reserved busy workers, and some started on what drained
    assert sum(bool(resv) for resv in resv_log) >= 25
    drained = [
        g for i, tick in enumerate(gang_log[2:], start=2)
        for g, members in tick[0]
        if sorted(members) == resv_log[i].get(g)]
    assert len(drained) >= 3
    assert sum(len(a) for a, _f in log[2:]) > 200  # the filler ran beside
    audited = tick_driver.audit_placements(world, log, rq_ids, worker_ids)
    assert set(audited.values()) == {0}, audited
    shared = shared_driver.audit_shared(
        world, log, gang_log, resv_log, worker_ids, 16)
    assert set(shared.values()) == {0}, shared


def test_jax_on_the_cpu_equals_numpy_and_the_reference(numpy_run):
    device = record(GreedyCutScanModel(backend="jax"), seed=2147483701,
                    n_ticks=40)
    assert device[6] == {"device-jax"}
    assert device[1] == numpy_run[1]  # every assignment and finish
    assert device[2] == numpy_run[2]  # every gang start, end and arrival
    assert device[3] == numpy_run[3]  # every tick's reservation sets
    assert compare(device)["ticks_mismatched"] == 0


@pytest.mark.parametrize("backend,workers,groups,seed", [
    ("numpy", 128, 4, 11), ("numpy", 256, 4, 2147483659),
    ("jax", 128, 4, 5), ("jax", 256, 8, 3100000007)])
def test_other_widths_and_seeds_equal_the_plain_reference(
        backend, workers, groups, seed):
    recorded = record(
        GreedyCutScanModel(backend=backend), seed, n_ticks=24,
        scale={"workers": workers, "groups": groups,
               "ready_tasks": 50 * workers, "ready_gangs": 80})
    numbers = compare(recorded)
    assert numbers["ticks_mismatched"] == 0, numbers
    assert any(tick[0] for tick in recorded[2][2:])
    assert any(recorded[3])


@pytest.mark.parametrize("broken", [
    {"reserve": False}, {"feed_reserved": True}, {"lift_each_tick": True},
    {"groups": "any_group"}],
    ids=["no-reservation", "reserved-fed", "lifted-each-tick",
         "groups-ignored"])
def test_reference_control_mismatches(numpy_run, broken):
    assert compare(numpy_run, **broken)["ticks_mismatched"] > 0


@pytest.mark.parametrize("control", [c for c in control_shared.CONTROLS if c])
def test_stand_in_controls_show_in_their_number(control):
    numbers = control_shared.shared_control(
        manifest.cell(CELL), seed=5, n_window=40,
        scale={**SCALE, "settle": [[20, SHARE, GANG_SHARE]]}, control=control)
    assert numbers["ticks_mismatched"] > 0
    assert numbers[control_shared.CONTROLS[control][1]] > 0, numbers


def test_sound_stand_in_reads_zero_everywhere():
    numbers = control_shared.shared_control(
        manifest.cell(CELL), seed=5, n_window=40,
        scale={**SCALE, "settle": [[20, SHARE, GANG_SHARE]]}, control=None)
    assert {k: v for k, v in numbers.items() if v} == {
        "ticks_replayed": 61}, numbers


# -- through reactor.schedule -------------------------------------------------
def _shared_env(drain, model=None, n_workers=4):
    """A fused core over `n_workers` two-cpu workers of one group, each
    running two tasks of a deep single-node stream, and a gang of all of
    them at the stream's priority."""
    env = TestEnv(model=model or GreedyCutScanModel(backend="numpy"))
    env.core.fused_solve = True
    env.core.set_gang_drain(drain)
    workers = [env.worker(cpus=2, group="alloc-0") for _ in range(n_workers)]
    env.submit(n=2 * n_workers, job=2, priority=(1, -2))
    env.schedule()
    env.start_all_assigned()
    (gang,) = env.submit(rqv=env.rqv(n_nodes=n_workers), job=1,
                         priority=(1, -1))
    return env, workers, gang


def _stream(env, workers, gang, ticks, prefill=False):
    """Per tick: one new stream task a worker, one running task a tick
    finishes; returns the tick the gang started at, or None."""
    for tick in range(ticks):
        env.submit(n=len(workers), job=2, priority=(1, -2))
        env.schedule(prefill=prefill)
        if env.state(gang) is TaskState.ASSIGNED:
            return tick
        env.start_all_assigned()
        running = sorted(t.task_id for t in env.core.tasks.values()
                         if t.state is TaskState.RUNNING and not t.mn_workers)
        if running:
            env.finish(running[tick % len(running)])
    return None


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_a_gang_that_starves_under_idle_starts_under_busy(backend):
    env, workers, gang = _shared_env(
        "idle", GreedyCutScanModel(backend=backend))
    assert _stream(env, workers, gang, 30) is None  # the stream refills all
    env, workers, gang = _shared_env(
        "busy", GreedyCutScanModel(backend=backend))
    started = _stream(env, workers, gang, 30)
    assert started is not None and started >= 4   # after a drain
    assert env.core.tasks[gang].mn_workers == tuple(
        w.worker_id for w in workers)
    assert all(w.mn_reserved == 0 for w in workers)
    assert env.core.mn_reservations == {}


def test_reserved_workers_stay_rows_and_take_no_stream_task():
    env, workers, gang = _shared_env("busy")
    cache = env.core.tick_cache
    rebuilds = cache.full_rebuilds
    env.submit(n=4, job=2, priority=(1, -2))
    env.schedule()
    assert all(w.mn_reserved == gang for w in workers)
    assert env.core.mn_reservations == {gang: {w.worker_id for w in workers}}
    # the reservation is a column: every worker is still a dense row
    assert cache.sync(env.core).worker_ids == [w.worker_id for w in workers]
    assert cache.full_rebuilds == rebuilds and cache.membership_flips == 0
    assert cache.reservations().tolist() == [gang] * 4
    # a task finishes: the slot it leaves stays empty
    (first, *_rest) = sorted(workers[0].assigned_tasks)
    env.finish(first)
    env.schedule()
    assert len(workers[0].assigned_tasks) == 1
    assert env.core.tick_cache.gang_reserved == 4
    assert env.core.tick_cache.gang_reserved_busy >= 4


def test_reserved_workers_prefill_is_retracted_once_and_not_refilled():
    env = TestEnv(model=GreedyCutScanModel(backend="numpy"))
    env.core.fused_solve = True
    env.core.set_gang_drain("busy")
    workers = [env.worker(cpus=2, group="alloc-0") for _ in range(4)]
    env.submit(n=8, job=2, priority=(1, -2))
    env.schedule()
    env.start_all_assigned()
    env.submit(n=12, job=2, priority=(1, -2))
    env.schedule(prefill=True)  # a backlog queued on every worker
    assert all(w.prefilled_tasks for w in workers)
    (gang,) = env.submit(rqv=env.rqv(n_nodes=4), job=1, priority=(1, -1))
    before = len(env.comm.retracts)
    env.schedule(prefill=True)
    assert all(w.mn_reserved == gang for w in workers)
    retracted = env.comm.retracts[before:]
    assert sorted(wid for wid, _refs in retracted) == sorted(
        w.worker_id for w in workers)
    held = {w.worker_id: set(w.prefilled_tasks) for w in workers}
    env.submit(n=12, job=2, priority=(1, -2))
    env.schedule(prefill=True)
    env.schedule(prefill=True)
    assert len(env.comm.retracts) == before + len(retracted)  # sent once
    assert {w.worker_id: set(w.prefilled_tasks) for w in workers} == held


def test_the_counters_count_what_was_reserved():
    reserved = REGISTRY.get("hq_solve_gang_reserved_total").labels()
    busy = REGISTRY.get("hq_solve_gang_reserved_busy_total").labels()
    before = (reserved.value, busy.value)
    env, workers, gang = _shared_env("busy")
    env.schedule()
    assert reserved.value - before[0] == 4
    assert busy.value - before[1] == 4
    assert {"gangs/reserve", "gangs/rows", "gangs/inputs"} <= set(
        env.core.tick_stats.last_ms)


def test_an_outranked_gang_lifts_its_reservation():
    env, workers, gang = _shared_env("busy")
    env.schedule()
    assert env.core.mn_reservations == {gang: {w.worker_id for w in workers}}
    env.submit(n=1, job=3, priority=(5, -3))
    env.schedule()
    assert env.core.mn_reservations == {}
    assert all(w.mn_reserved == 0 for w in workers)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_a_gang_pushed_out_of_the_rows_lifts_its_reservation(backend):
    """Two gangs reserve the whole cluster, then a row's worth of gangs of a
    higher priority comes ahead of them in the queue: the two are no rows
    any more, so what they held is lifted, the new head reserves and drains
    it, and gangs keep starting (held, the cluster would idle for good:
    no row could see a reserved worker)."""
    env = TestEnv(model=GreedyCutScanModel(backend=backend))
    env.core.fused_solve = True
    env.core.set_gang_drain("busy")
    workers = [env.worker(cpus=2, group="alloc-0") for _ in range(4)]
    env.submit(n=8, job=2, priority=(1, -2))
    env.schedule()
    env.start_all_assigned()
    low = env.submit(n=2, rqv=env.rqv(n_nodes=2), job=1, priority=(1, -1))
    env.schedule()
    assert set(env.core.mn_reservations) == set(low)
    assert all(w.mn_reserved for w in workers)
    high = env.submit(n=reactor.MAX_FUSED_GANG_ROWS,
                      rqv=env.rqv(n_nodes=2), job=3, priority=(2, -3))
    started = []
    for tick in range(24):
        env.submit(n=len(workers), job=2, priority=(1, -2))
        env.schedule()
        assert not set(env.core.mn_reservations) & set(low)
        started = [t for t in high if env.state(t) in (
            TaskState.ASSIGNED, TaskState.RUNNING)]
        if len(started) == 2:
            break
        env.start_all_assigned()
        running = sorted(t.task_id for t in env.core.tasks.values()
                         if t.state is TaskState.RUNNING and not t.mn_workers)
        if running:
            env.finish(running[tick % len(running)])
    assert started == high[:2]  # the whole cluster, drained for the head
    assert all(env.state(t) is TaskState.READY for t in low)


def test_the_reference_lifts_what_a_gang_that_is_no_row_holds():
    cell = manifest.cell(CELL)
    world = generate_shared.world(cell["config"], cell["traffic"], 5, SCALE)
    ref = shared_plain.Reference(world)
    ref.resv[:3] = 7   # gang 7 is no row of this tick
    ref.resv[3:5] = 8
    ref._reserve([(8, 2)], ref._group_order())
    assert ref.resv[:3].tolist() == [shared_plain.NONE] * 3
    assert ref.resv[3:5].tolist() == [8, 8]   # a row's stands


def test_paranoid_tick_holds_the_reservation_column_to_the_walk():
    env, workers, gang = _shared_env("busy")
    env.core.paranoid_tick = 1
    for _ in range(3):
        env.submit(n=2, job=2, priority=(1, -2))
        env.schedule()
    assert env.core.mn_reservations


def test_gang_drain_option_reaches_the_core(tmp_path):
    from hyperqueue_tpu.client import cli
    from hyperqueue_tpu.server.bootstrap import Server

    args = cli.build_parser().parse_args(
        ["server", "start", "--gang-drain", "busy"])
    assert args.gang_drain == "busy"
    assert cli.build_parser().parse_args(
        ["server", "start"]).gang_drain == "idle"
    server = Server(server_dir=tmp_path, scheduler="greedy-fused",
                    gang_drain="busy")
    assert server.core.gang_drain == "busy"
    assert server.core.tick_cache.keep_reserved
    with pytest.raises(ValueError):
        Server(server_dir=tmp_path / "b", scheduler="greedy-numpy",
               gang_drain="busy")
    with pytest.raises(ValueError):
        Core().set_gang_drain("sometimes")


def test_a_program_without_the_option_cannot_run_the_cell(
        capsys, monkeypatch):
    from chipbench import run as run_py

    monkeypatch.delattr(Core, "set_gang_drain")
    with pytest.raises(SystemExit) as ended:
        run_py.main(["--workload", CELL, "--seed", "7", "--seconds", "1",
                     "--rehearse", "--scale", '{"workers": 64}'])
    assert "--gang-drain" in str(ended.value)
    assert capsys.readouterr().out == ""


def test_server_stats_carry_the_gang_drain_block(tmp_path):
    import asyncio

    from hyperqueue_tpu.server.bootstrap import Server

    server = Server(server_dir=tmp_path, scheduler="greedy-fused",
                    gang_drain="busy")
    server.core.tick_cache.gang_reserved = 7
    server.core.tick_cache.gang_reserved_busy = 5
    stats = asyncio.run(server._client_server_stats({}))
    assert stats["gang_drain"] == {
        "mode": "busy", "reserved_total": 7, "reserved_busy_total": 5}
