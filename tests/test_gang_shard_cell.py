"""The `gang-16k` deployment at a test's size: the production tick with
multi-node tasks riding the solve as gang rows, the solve sharded over four
(virtual) devices, against the benchmark's plain reference, the numpy model
and the one-chip kernel.

`MultichipModel` through `run_tick`, fed by the reactor's fused gang functions
as `reactor._tick` and the `gang_shard` driver feed it, over some tens of
ticks with gangs starting and ending (so the dense rows are re-indexed and
groups straddle the shards' boundaries) must equal
`chipbench/reference/gang_plain.py` tick by tick, and the numpy model too; a
gang whose group has idle members on two shards takes its first n in global
row order, bit for bit what the one-chip kernel gives; every control fails in
its number, the one that selects shard by shard included.  Also here: the
sharded gang program names its second gather; the gang inputs' spans fire and
their counter counts the bytes of the three arrays.
"""

import functools

import numpy as np
import pytest

import chip_smoke
from chipbench import control_gang_shard, generate_gang, manifest
from chipbench.drivers import gang_shard as gang_shard_driver
from chipbench.drivers import tick as tick_driver
from chipbench.reference import gang_local_groups, gang_plain
from hyperqueue_tpu.models.greedy import GreedyCutScanModel
from hyperqueue_tpu.models.multichip import MultichipModel
from hyperqueue_tpu.scheduler.tick import create_batches, run_tick
from hyperqueue_tpu.server import reactor
from utils_env import TestEnv

pytestmark = pytest.mark.multichip

CELL = "gang-16k.campaign"
# 16 groups of 32; wide enough that the 16 gang rows of a tick leave the
# filler its gpu nodes
SCALE = {"workers": 512, "groups": 16, "ready_tasks": 20000,
         "ready_gangs": 400}
SHARE, GANG_SHARE = 0.05, 0.1


def record(model, seed, n_ticks, scale=SCALE):
    """`n_ticks` production ticks of `model` over the cell's world at a
    test's size: the filler alone for one tick, then the gangs arrive.
    Returns (world, log, gang_log, rq_ids, worker_ids, backends, rows)."""
    cell = manifest.cell(CELL)
    world = generate_gang.world(cell["config"], cell["traffic"], seed, scale)
    core, rq_ids, worker_ids, gang_rq = \
        gang_shard_driver.gang_driver.build_program_state(
            world, cell["config"])
    cluster = gang_shard_driver.Cluster(world, core, rq_ids, seed, gang_rq)
    backends, rows_seen = set(), []
    for i in range(n_ticks):
        rows = reactor.fused_gang_rows(core) if core.mn_queue else []
        snap = core.tick_cache.sync(core)
        gang_ok = group_ids = None
        if rows:
            gang_ok, group_ids = reactor.fused_gang_inputs(
                core, snap.worker_ids)
        out = run_tick(
            core.queues, None, core.rq_map, core.resource_map, model,
            batches=create_batches(core.queues) + rows, dense=snap,
            key_cache=core.tick_cache, gang_ok=gang_ok, group_ids=group_ids,
        )
        cluster.started(cluster.apply(out))
        assert cluster.refused == 0
        backends.add(model.last_backend)
        rows_seen.append(list(snap.worker_ids))
        cluster.churn(SHARE, gang_share=GANG_SHARE if i else 0.0,
                      arrive=() if i else world.gang_nodes.tolist())
    return (world, cluster.log, cluster.gang_log, rq_ids, worker_ids,
            backends, rows_seen)


def compare(recorded, reference=gang_plain.Reference, **reference_kwargs):
    world, log, gang_log, rq_ids, worker_ids = recorded[:5]
    return gang_shard_driver.compare_with_reference(
        world, log, gang_log, rq_ids, worker_ids,
        functools.partial(reference, **reference_kwargs))


@pytest.fixture(scope="module")
def sharded_run():
    return record(MultichipModel(n_devices=4), seed=2147483701, n_ticks=30)


def straddling_starts(recorded, n_shards=4):
    """Started gangs whose members lay on more than one shard of the solve
    that placed them (the dense rows of that tick, padded to the worker
    bucket, split contiguously)."""
    _world, _log, gang_log, _rq, _workers, _backends, rows_seen = recorded
    bucket = MultichipModel(n_devices=n_shards)._worker_bucket
    found = 0
    for (started, _ended, _arrived), rows in zip(gang_log, rows_seen):
        per_shard = bucket(len(rows)) // n_shards
        at = {w: i // per_shard for i, w in enumerate(rows)}
        found += sum(len({at[w] for w in members}) > 1
                     for _g, members in started)
    return found


def test_sharded_ticks_equal_the_plain_reference(sharded_run):
    world, log, gang_log, rq_ids, worker_ids, backends, rows = sharded_run
    assert backends == {"device-sharded"}
    numbers = compare(sharded_run)
    assert numbers["ticks_mismatched"] == 0, numbers
    assert numbers["ticks_replayed"] == len(log) == 30
    started = [g for tick in gang_log for g, _members in tick[0]]
    ended = [g for tick in gang_log for g in tick[1]]
    assert len(started) > 40 and len(ended) > 10
    sizes = {len(members) for tick in gang_log for _g, members in tick[0]}
    assert sizes >= {2, 4, 8, 16}  # 32 would need a whole group idle
    # the rows left and rejoined, and gangs took members from two shards
    assert len({len(r) for r in rows}) > 10
    assert straddling_starts(sharded_run) > 0
    # the filler ran beside them
    assert sum(len(a) for a, _f in log[2:]) > 200
    audited = tick_driver.audit_placements(world, log, rq_ids, worker_ids)
    assert set(audited.values()) == {0}, audited
    gangs = gang_shard_driver.audit_gangs(world, log, gang_log, worker_ids, 16)
    assert set(gangs.values()) == {0}, gangs


def test_numpy_model_equals_sharded_and_reference(sharded_run):
    host = record(GreedyCutScanModel(backend="numpy"), seed=2147483701,
                  n_ticks=30)
    assert host[5] <= {"host-native", "host-numpy"}
    assert host[1] == sharded_run[1]  # every assignment and finish
    assert host[2] == sharded_run[2]  # every gang start, end and arrival
    assert compare(host)["ticks_mismatched"] == 0


@pytest.mark.parametrize("workers,groups,seed", [
    (256, 8, 11), (1024, 16, 3300000007)])
def test_other_widths_and_seeds_equal_the_plain_reference(
        workers, groups, seed):
    recorded = record(
        MultichipModel(n_devices=4), seed, n_ticks=14,
        scale={"workers": workers, "groups": groups,
               "ready_tasks": 40 * workers, "ready_gangs": 200})
    assert recorded[5] == {"device-sharded"}
    numbers = compare(recorded)
    assert numbers["ticks_mismatched"] == 0, numbers
    assert any(tick[0] for tick in recorded[2])


@pytest.mark.parametrize("broken", [
    {"groups": "any_group"}, {"hold": False}, {"late_gang_ends": True}],
    ids=["groups-ignored", "no-hold", "gang-ends-a-tick-late"])
def test_reference_control_mismatches(sharded_run, broken):
    assert compare(sharded_run, **broken)["ticks_mismatched"] > 0


def test_reference_that_selects_shard_by_shard_mismatches(sharded_run):
    """The program's run is not what a selection that ignores the other
    chips would give: the comparison tells the two apart."""
    assert compare(sharded_run, reference=gang_local_groups.Reference)[
        "ticks_mismatched"] > 0


@pytest.mark.parametrize("control", [c for c in control_gang_shard.CONTROLS
                                     if c])
def test_stand_in_controls_show_in_their_number(control):
    numbers = control_gang_shard.gang_shard_control(
        manifest.cell(CELL), seed=5, n_ticks=30, scale=SCALE, control=control)
    assert numbers["ticks_mismatched"] > 0, numbers
    assert numbers[control_gang_shard.CONTROLS[control]] > 0, numbers


def test_sound_stand_in_reads_zero_everywhere():
    numbers = control_gang_shard.gang_shard_control(
        manifest.cell(CELL), seed=5, n_ticks=30, scale=SCALE, control=None)
    assert {k: v for k, v in numbers.items() if v} == {
        "ticks_replayed": 30}, numbers


# -- a gang whose group lies on two shards -----------------------------------
def one_chip_kernel(model, prep):
    """The one-chip jitted kernel on the same padded inputs, fresh uploads."""
    return GreedyCutScanModel._fresh_device_counts(model, prep)


def four_groups_env(model):
    """A fused core under 32 workers in four groups of 8, a group a shard."""
    env = TestEnv(model=model)
    env.core.fused_solve = True
    for group in "abcd":
        for _ in range(8):
            env.worker(cpus=4, group=group)
    return env


def test_gang_takes_members_from_two_shards_as_the_one_chip_kernel_does():
    """32 workers in four groups of 8 lie a group a shard.  A 4-node gang
    starts on the first group and its members leave the dense rows; re-indexed,
    the second group's eight idle workers lie on shards 0 and 1, four each,
    and a 6-node gang, which the first group's four idle workers cannot hold,
    takes its first six in global row order: four from shard 0, two from
    shard 1."""
    model = chip_smoke.checked(MultichipModel, one_chip_kernel)(n_devices=4)
    env = four_groups_env(model)
    worker_ids = list(env.core.workers)
    first = env.submit(rqv=env.rqv(n_nodes=4), job=1, priority=(2, -1))[0]
    env.schedule()
    model.verify()
    assert env.core.tasks[first].mn_workers == tuple(worker_ids[:4])
    env.start_all_assigned()
    rows = list(env.core.tick_cache.sync(env.core).worker_ids)
    assert rows == worker_ids[4:]  # the members left; 28 rows, bucket 32
    per_shard = model._worker_bucket(len(rows)) // 4
    second = env.submit(rqv=env.rqv(n_nodes=6), job=1, priority=(2, -2))[0]
    env.submit(n=12, rqv=env.rqv(cpus=1), job=2, priority=(1, -3))
    env.schedule()
    model.verify()
    assert model.last_backend == "device-sharded"
    assert model.solves_checked == 2  # both bit for bit the one-chip kernel
    members = env.core.tasks[second].mn_workers
    assert members == tuple(worker_ids[8:14])
    assert [rows.index(w) // per_shard for w in members] == [0] * 4 + [1] * 2
    # and the numpy model places the same
    host = four_groups_env(GreedyCutScanModel(backend="numpy"))
    a = host.submit(rqv=host.rqv(n_nodes=4), job=1, priority=(2, -1))[0]
    host.schedule()
    host.start_all_assigned()
    b = host.submit(rqv=host.rqv(n_nodes=6), job=1, priority=(2, -2))[0]
    host.schedule()
    assert (host.core.tasks[a].mn_workers, host.core.tasks[b].mn_workers) \
        == (env.core.tasks[first].mn_workers, members)


# -- what the PR adds to the program -------------------------------------------
def test_sharded_gang_program_names_its_second_gather():
    from hyperqueue_tpu.ops.assign import host_visit_classes, scarcity_weights
    from hyperqueue_tpu.parallel import solve

    n_w, n_r, n_b, n_v, n_g = 16, 4, 4, 1, 4
    free = np.full((n_w, n_r), 40_000, np.int32)
    needs = np.zeros((n_b, n_v, n_r), np.int32)
    needs[1:, 0, 0] = 10_000
    scarcity = np.asarray(
        scarcity_weights(free.astype(np.int64).sum(axis=0))
    ).astype(np.float32)
    class_m, order_ids = host_visit_classes(free, needs, scarcity)
    mesh = solve.make_worker_mesh(4)
    lowered = solve.sharded_cut_scan_donate.lower(
        mesh, free, np.full(n_w, 4, np.int32),
        np.full(n_w, 2**31 - 1, np.int32),
        solve.pack_batch_table(
            needs, np.asarray([1, 5, 5, 5], np.int32),
            np.zeros((n_b, n_v), np.int32), order_ids),
        class_m, extents=needs.shape,
        gang_nodes=np.asarray([6, 0, 0, 0], np.int32),
        gang_ok=np.ones(n_w, np.int32),
        group_onehot=np.eye(n_g, dtype=np.int32)[np.arange(n_w) // 4],
    )
    assert solve.GANG_SELECT_GATHER in lowered.as_text(debug_info=True)
    gathers = [line for line in lowered.compile().as_text().splitlines()
               if " all-gather(" in line and "metadata" in line]
    assert any(solve.GANG_SELECT_GATHER in g for g in gathers)
    assert any(solve.WATER_FILL_GATHER in g for g in gathers)


def test_gang_input_spans_fire_and_the_counter_counts_the_three_arrays():
    model = MultichipModel(n_devices=4)
    seen = []
    real = model._gang_inputs

    def spy(prep):
        seen.append(sum(arr.nbytes for _name, arr, _kind in real(prep)))
        return real(prep)
    model._gang_inputs = spy
    env = four_groups_env(model)
    env.submit(n=4, rqv=env.rqv(cpus=1), job=2, priority=(1, -3))
    env.schedule()  # no gang waits: no gang span, no gang byte
    assert not {"assemble/gang", "solve_host_prep/gang"} & set(
        env.core.tick_stats.last_ms)
    assert model.resident_stats()["gang_input_bytes_total"] == 0
    env.submit(rqv=env.rqv(n_nodes=3), job=1, priority=(2, -1))
    env.schedule()
    phases = env.core.tick_stats.last_ms
    assert {"gangs/inputs", "assemble/gang", "solve_host_prep/gang"} <= set(
        phases)
    assert phases["assemble"] >= phases["assemble/gang"] > 0
    assert phases["solve_host_prep"] >= phases["solve_host_prep/gang"] > 0
    stats = model.resident_stats()
    # gang_nodes (pb,), gang_ok (pw,) and the (pw, pg) one-hot, int32
    pw, pb, pg = 32, 8, 4
    assert seen[-1] == 4 * (pb + pw + pw * pg)
    assert stats["gang_input_bytes_total"] == sum(seen) == seen[-1]
    assert stats["gang_groups_last"] == pg


def test_metrics_refresh_exports_the_gang_input_counter(tmp_path):
    from hyperqueue_tpu.server.bootstrap import Server
    from hyperqueue_tpu.utils.metrics import REGISTRY

    server = Server(server_dir=tmp_path, scheduler="multichip")
    assert server.core.fused_solve is True
    model = server.model.model if hasattr(server.model, "model") \
        else server.model
    from __graft_entry__ import ClusterState

    state = ClusterState(64, 400, core=server.core)
    reactor.schedule(server.core, state.comm, state.events, server.model,
                     prefill=True)
    assert state.gang_placed()
    want = model.resident_stats()["gang_input_bytes_total"]
    assert want > 0
    server._collect_metrics()
    assert REGISTRY.get("hq_solve_gang_input_bytes_total").labels().value \
        == want
    assert REGISTRY.get("hq_solve_gang_input_groups").labels().value == \
        model.resident_stats()["gang_groups_last"]
