"""The `shard-16k` deployment at a test's size: the production tick with the
solve sharded over four (virtual) devices, whole-node classes in the mix,
against the benchmark's plain reference and against the numpy model.

`MultichipModel` through `run_tick` over some tens of ticks of churn must
equal `chipbench/reference/shard_plain.py` tick by tick (counts per class,
priority, variant and worker, and the task ids taken); the same world through
`GreedyCutScanModel(backend="numpy")` must equal both; and the comparison has
to fail when the reference reads `all` as 21 cpus or sees rows a tick late.
"""

import copy

import numpy as np
import pytest

from chipbench import control_shard, generate_shard, manifest
from chipbench.drivers import shard as shard_driver
from chipbench.drivers import tick as tick_driver
from chipbench.reference import shard_plain
from hyperqueue_tpu.models.greedy import GreedyCutScanModel
from hyperqueue_tpu.models.multichip import MultichipModel
from hyperqueue_tpu.scheduler.tick import create_batches, run_tick

pytestmark = pytest.mark.multichip

CELL = "shard-16k.backlog"
SHARE = 0.05


def small_cell(priority_levels=2):
    """The cell with fewer priority levels: 112 batches, bucket 128."""
    cell = copy.deepcopy(manifest.cell(CELL))
    cell["config"]["priority_levels"] = priority_levels
    cell["traffic"]["churn_per_tick"] = SHARE
    return cell


def record(model, cell, seed, workers, n_ticks):
    """`n_ticks` production ticks of `model` over the cell's world under its
    churn.  Returns (world, log, rq_ids, worker_ids, backends)."""
    world = generate_shard.world(
        cell["config"], cell["traffic"], seed,
        {"workers": workers, "ready_tasks": 40 * workers})
    core, rq_ids, worker_ids = shard_driver.build_program_state(
        world, cell["config"])
    cluster = tick_driver.Cluster(world, core, rq_ids, seed)
    backends = set()
    for _ in range(n_ticks):
        out = run_tick(
            core.queues, None, core.rq_map, core.resource_map, model,
            batches=create_batches(core.queues),
            dense=core.tick_cache.sync(core), key_cache=core.tick_cache,
        )
        cluster.apply(out)
        cluster.started(out)
        backends.add(model.last_backend)
        cluster.churn(SHARE)
    return world, cluster.log, rq_ids, worker_ids, backends


@pytest.fixture(scope="module")
def sharded_run():
    cell = small_cell()
    model = MultichipModel(n_devices=4)
    return cell, record(model, cell, seed=2147483701, workers=128, n_ticks=30)


def compare(recorded, **reference_kwargs):
    world, log, rq_ids, worker_ids, _backends = recorded

    def reference(world):
        return shard_plain.Reference(world, **reference_kwargs)

    return tick_driver.compare_with_reference(
        world, log, rq_ids, worker_ids, reference)


def test_sharded_ticks_equal_the_plain_reference(sharded_run):
    _cell, recorded = sharded_run
    world, log, rq_ids, worker_ids, backends = recorded
    assert backends == {"device-sharded"}
    numbers = compare(recorded)
    assert numbers["ticks_mismatched"] == 0, numbers
    assert numbers["ticks_replayed"] == len(log) == 30
    audited = shard_driver.audit_placements(world, log, rq_ids, worker_ids)
    assert set(audited.values()) == {0}, audited
    # whole-node tasks were placed after the fill tick too, beside others
    whole = np.repeat(world.class_all.any(axis=(1, 2)), world.n_priorities)
    levels = (world.task_class.astype(int) * world.n_priorities
              + world.task_prio).tolist()
    for assignments, _finished in log:
        levels += [levels[t] for t in
                   sorted(a[0] & tick_driver.TASK_MASK for a in assignments)]
    later = [whole[levels[a[0] & tick_driver.TASK_MASK]]
             for assignments, _f in log[1:] for a in assignments]
    assert any(later) and not all(later)


def test_numpy_model_equals_sharded_and_reference(sharded_run):
    cell, sharded = sharded_run
    host = record(GreedyCutScanModel(backend="numpy"), cell,
                  seed=2147483701, workers=128, n_ticks=30)
    assert host[4] <= {"host-native", "host-numpy"}
    assert host[1] == sharded[1]  # every assignment and finish, tick by tick
    assert compare(host)["ticks_mismatched"] == 0


@pytest.mark.parametrize("workers,seed", [(64, 11), (256, 2147483659)])
def test_other_widths_and_seeds_equal_the_plain_reference(workers, seed):
    recorded = record(MultichipModel(n_devices=4), small_cell(), seed,
                      workers, n_ticks=12)
    assert recorded[4] == {"device-sharded"}
    assert compare(recorded)["ticks_mismatched"] == 0


@pytest.mark.parametrize("broken", [{"whole_node": "as_21_cpus"},
                                    {"stale_rows": True}],
                         ids=["all-as-21-cpus", "rows-a-tick-late"])
def test_reference_control_mismatches(sharded_run, broken):
    _cell, recorded = sharded_run
    assert compare(recorded, **broken)["ticks_mismatched"] > 0


def test_without_whole_node_classes_shard_plain_is_tick_plain():
    """On a world with no whole-node class the two references are one."""
    from chipbench import control

    cell = manifest.cell("hetero-1k.backlog-1m")
    world, log, rq_ids, worker_ids = control.stand_in_log(
        cell, seed=3, n_ticks=10,
        scale={"workers": 64, "ready_tasks": 20000}, control=None)
    world.class_all = np.zeros(world.class_needs.shape, dtype=bool)
    numbers = tick_driver.compare_with_reference(
        world, log, rq_ids, worker_ids, shard_plain.Reference)
    assert numbers["ticks_mismatched"] == 0, numbers


@pytest.mark.parametrize("control", ["all_as_21_cpus", "stale_rows"])
def test_stand_in_controls_fail_the_comparison(control):
    numbers = control_shard.shard_control(
        small_cell(), seed=5, n_ticks=10,
        scale={"workers": 64, "ready_tasks": 8000}, control=control)
    assert numbers["ticks_mismatched"] > 0


def test_scan_steps_are_counted_per_solve():
    """`hq_solve_scan_steps_total` rises by live batches x variants a solve."""
    from hyperqueue_tpu.utils.metrics import REGISTRY

    counter = REGISTRY.get("hq_solve_scan_steps_total").labels()
    before = counter.value
    cell = small_cell()
    world = record(GreedyCutScanModel(backend="numpy"), cell, seed=3,
                   workers=64, n_ticks=3)[0]
    live = world.class_needs.shape[0] * world.n_priorities  # every level waits
    assert counter.value - before == 3 * live * world.class_needs.shape[1]


def test_the_gathers_carry_their_names_in_the_compiled_program():
    from hyperqueue_tpu.ops.assign import host_visit_classes, scarcity_weights
    from hyperqueue_tpu.parallel import solve

    rng = np.random.default_rng(0)
    n_w, n_r, n_b, n_v = 16, 4, 4, 2
    free = (rng.integers(0, 8, size=(n_w, n_r)) * 10_000).astype(np.int32)
    needs = (rng.integers(0, 3, size=(n_b, n_v, n_r)) * 5_000).astype(np.int32)
    scarcity = np.asarray(
        scarcity_weights(free.astype(np.int64).sum(axis=0))
    ).astype(np.float32)
    class_m, order_ids = host_visit_classes(free, needs, scarcity)
    mesh = solve.make_worker_mesh(4)
    # the program `shard-16k.backlog` runs, as MultichipModel calls it
    text = solve.sharded_cut_scan_donate.lower(
        mesh, free, np.full(n_w, 4, np.int32),
        np.full(n_w, 2**31 - 1, np.int32),
        solve.pack_batch_table(
            needs, np.full(n_b, 5, np.int32),
            np.zeros((n_b, n_v), np.int32), order_ids),
        class_m, extents=needs.shape,
    ).compile().as_text()
    gathers = [line for line in text.splitlines()
               if " all-gather(" in line and "metadata" in line]
    assert gathers and all(solve.WATER_FILL_GATHER in g for g in gathers)
