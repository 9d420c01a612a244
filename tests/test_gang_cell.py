"""The `gang-1k` deployment at a test's size: the production tick with
multi-node tasks riding the dense solve as gang rows, against the benchmark's
plain reference.

`run_tick` with the numpy backend and with `backend="jax"` on the CPU, fed by
the reactor's fused gang functions as `reactor._tick` and the `gang` driver
feed it, over some tens of ticks of churn with gangs starting and ending,
must equal `chipbench/reference/gang_plain.py` tick by tick (counts per
class, priority, variant and worker, the task ids taken, and every started
gang's member set); the comparison has to fail when the reference ignores
groups, holds nothing, or frees a finished gang's workers a tick late.  Also
here: the extracted gang functions give `_tick` the rows and inputs it built
inline before; the device schedulers run fused; the new counters count.
"""

import functools

import numpy as np
import pytest

from chipbench import control_gang, generate_gang, manifest
from chipbench.drivers import gang as gang_driver
from chipbench.drivers import tick as tick_driver
from chipbench.reference import gang_plain
from hyperqueue_tpu.models.greedy import GreedyCutScanModel
from hyperqueue_tpu.scheduler.tick import Batch, create_batches, run_tick
from hyperqueue_tpu.server import reactor
from hyperqueue_tpu.utils.metrics import REGISTRY
from utils_env import TestEnv

CELL = "gang-1k.rigid"
# wide enough that the 16 gang rows of a tick leave the filler its gpu nodes
SCALE = {"workers": 192, "groups": 4, "ready_tasks": 8000, "ready_gangs": 160}
SHARE, GANG_SHARE = 0.05, 0.15


def record(model, seed, n_ticks, scale=SCALE):
    """`n_ticks` production ticks of `model` over the cell's world at a
    test's size: the filler alone for one tick, then the gangs arrive.
    Returns (world, log, gang_log, rq_ids, worker_ids, backends)."""
    cell = manifest.cell(CELL)
    world = generate_gang.world(cell["config"], cell["traffic"], seed, scale)
    core, rq_ids, worker_ids, gang_rq = gang_driver.build_program_state(
        world, cell["config"])
    cluster = gang_driver.Cluster(world, core, rq_ids, seed, gang_rq)
    backends = set()
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
        cluster.churn(SHARE, gang_share=GANG_SHARE if i else 0.0,
                      arrive=() if i else world.gang_nodes.tolist())
    return world, cluster.log, cluster.gang_log, rq_ids, worker_ids, backends


def compare(recorded, **reference_kwargs):
    world, log, gang_log, rq_ids, worker_ids, _backends = recorded
    return gang_driver.compare_with_reference(
        world, log, gang_log, rq_ids, worker_ids,
        functools.partial(gang_plain.Reference, **reference_kwargs))


@pytest.fixture(scope="module")
def numpy_run():
    return record(GreedyCutScanModel(backend="numpy"), seed=2147483701,
                  n_ticks=40)


def test_numpy_ticks_equal_the_plain_reference(numpy_run):
    world, log, gang_log, rq_ids, worker_ids, backends = numpy_run
    assert backends <= {"host-native", "host-numpy"}
    numbers = compare(numpy_run)
    assert numbers["ticks_mismatched"] == 0, numbers
    assert numbers["ticks_replayed"] == len(log) == 40
    # gangs of every size started and ended, in most ticks
    started = [g for tick in gang_log for g, _members in tick[0]]
    ended = [g for tick in gang_log for g in tick[1]]
    assert len(started) > 40 and len(ended) > 20
    sizes = {len(members) for tick in gang_log for _g, members in tick[0]}
    assert sizes == {2, 4, 8, 16, 32}
    # and the filler ran beside them, on workers that no gang could take
    assert sum(len(a) for a, _f in log[2:]) > 200
    assert sum(bool(tick[0]) for tick in gang_log) > 20
    audited = tick_driver.audit_placements(world, log, rq_ids, worker_ids)
    assert set(audited.values()) == {0}, audited
    gangs = gang_driver.audit_gangs(world, log, gang_log, worker_ids, 16)
    assert set(gangs.values()) == {0}, gangs


def test_jax_on_the_cpu_equals_numpy_and_the_reference(numpy_run):
    device = record(GreedyCutScanModel(backend="jax"), seed=2147483701,
                    n_ticks=40)
    assert device[5] == {"device-jax"}
    assert device[1] == numpy_run[1]  # every assignment and finish
    assert device[2] == numpy_run[2]  # every gang start, end and arrival
    assert compare(device)["ticks_mismatched"] == 0


@pytest.mark.parametrize("backend,workers,groups,seed", [
    ("numpy", 128, 4, 11), ("numpy", 256, 4, 2147483659),
    ("jax", 128, 4, 5), ("jax", 256, 8, 3100000007)])
def test_other_widths_and_seeds_equal_the_plain_reference(
        backend, workers, groups, seed):
    recorded = record(
        GreedyCutScanModel(backend=backend), seed, n_ticks=16,
        scale={"workers": workers, "groups": groups,
               "ready_tasks": 50 * workers, "ready_gangs": 80})
    numbers = compare(recorded)
    assert numbers["ticks_mismatched"] == 0, numbers
    assert any(tick[0] for tick in recorded[2])


@pytest.mark.parametrize("broken", [
    {"groups": "any_group"}, {"hold": False}, {"late_gang_ends": True}],
    ids=["groups-ignored", "no-hold", "gang-ends-a-tick-late"])
def test_reference_control_mismatches(numpy_run, broken):
    assert compare(numpy_run, **broken)["ticks_mismatched"] > 0


@pytest.mark.parametrize("control", [c for c in control_gang.CONTROLS if c])
def test_stand_in_controls_show_in_their_number(control):
    numbers = control_gang.gang_control(
        manifest.cell(CELL), seed=5, n_ticks=14, scale=SCALE, control=control)
    assert numbers["ticks_mismatched"] > 0
    assert numbers[control_gang.CONTROLS[control][1]] > 0, numbers


def test_sound_stand_in_reads_zero_everywhere():
    numbers = control_gang.gang_control(
        manifest.cell(CELL), seed=5, n_ticks=14, scale=SCALE, control=None)
    assert {k: v for k, v in numbers.items() if v} == {
        "ticks_replayed": 14}, numbers


# -- the extracted gang phase ------------------------------------------------
class _Recorder(GreedyCutScanModel):
    """Keeps what `_tick` hands the solve."""

    def __init__(self):
        super().__init__(backend="numpy")
        self.seen = []

    def _dispatch(self, *args, **kwargs):
        self.seen.append({k: kwargs.get(k) for k in (
            "gang_nodes", "gang_ok", "group_onehot", "priorities")})
        return super()._dispatch(*args, **kwargs)


def _fused_env():
    model = _Recorder()
    env = TestEnv(model=model)
    env.core.fused_solve = True
    for group in ("a", "a", "b", "b", "b", "a"):
        env.worker(cpus=4, group=group)
    env.submit(n=3, rqv=env.rqv(cpus=4), job=9, priority=(2, -1))
    env.schedule()          # three workers run a task
    env.start_all_assigned()
    gangs = [env.submit(rqv=env.rqv(n_nodes=n), job=1, priority=(2, -2))[0]
             for n in (2, 3, 2)]
    env.submit(n=20, rqv=env.rqv(cpus=1), job=2, priority=(1, -3))
    return env, model, gangs


def _rows_and_inputs_as_tick_built_them_inline(core, worker_ids):
    """The fused gang phase as `reactor._tick` had it inline (PR 30)."""
    rows = []
    for task_id in core.mn_queue:
        task = core.tasks.get(task_id)
        if task is None or task.is_done:
            continue
        if len(rows) < reactor.MAX_FUSED_GANG_ROWS:
            rqv = core.rq_map.get_variants(task.rq_id)
            rows.append(Batch(
                rq_id=task.rq_id, priority=task.priority, size=1,
                gang_task=task_id, gang_nodes=rqv.variants[0].n_nodes))
    gmap, gang_ok, group_ids = {}, [], []
    for wid in worker_ids:
        w = core.workers[wid]
        gang_ok.append(1 if w.is_idle() else 0)
        group_ids.append(gmap.setdefault(w.group, len(gmap)))
    return rows, gang_ok, group_ids


def test_extracted_functions_give_tick_the_rows_and_inputs_it_built():
    env, model, gangs = _fused_env()
    core = env.core
    worker_ids = list(core.workers)
    want_rows, want_ok, want_groups = \
        _rows_and_inputs_as_tick_built_them_inline(core, worker_ids)
    phases: dict = {}
    rows = reactor.fused_gang_rows(core, phases)
    assert rows == want_rows and [b.gang_task for b in rows] == gangs
    assert core.mn_queue == gangs  # they stay queued until applied
    gang_ok, group_ids = reactor.fused_gang_inputs(core, worker_ids, phases)
    assert (gang_ok.tolist(), group_ids.tolist()) == (want_ok, want_groups)
    assert gang_ok.dtype == group_ids.dtype == np.int32
    assert sum(gang_ok) == 3 and group_ids.tolist() == [0, 0, 1, 1, 1, 0]
    assert {"gangs", "gangs/rows", "gangs/inputs"} <= set(phases)
    assert phases["gangs"] >= phases["gangs/rows"] + phases["gangs/inputs"]
    # and `_tick` hands the solve exactly these
    model.seen.clear()
    env.schedule()
    (seen,) = model.seen
    n_gang_rows = int((np.asarray(seen["gang_nodes"]) > 0).sum())
    assert n_gang_rows == len(want_rows)
    assert sorted(np.asarray(seen["gang_nodes"])[
        np.asarray(seen["gang_nodes"]) > 0].tolist()) == [2, 2, 3]
    assert np.asarray(seen["gang_ok"]).tolist() == want_ok
    assert np.asarray(seen["group_onehot"]).argmax(axis=1).tolist() == \
        want_groups
    # the 3-node gang found no group with three idle workers and held two
    placed = {t: core.tasks[t].mn_workers for t in gangs}
    assert [len(placed[t]) for t in gangs] == [2, 0, 0]
    assert {"gangs/apply", "gangs/rows", "gangs/inputs"} <= set(
        core.tick_stats.last_ms)


def test_gang_rows_and_held_rows_are_counted():
    rows = REGISTRY.get("hq_solve_gang_rows_total").labels()
    held = REGISTRY.get("hq_solve_gang_held_total").labels()
    started = REGISTRY.get("hq_solve_gang_groups").labels()
    before = (rows.value, held.value, started.value)
    env, _model, _gangs = _fused_env()
    env.schedule()
    assert (rows.value - before[0], held.value - before[1],
            started.value - before[2]) == (3, 2, 1)


def test_tick_without_a_waiting_gang_walks_no_worker_for_it():
    env = TestEnv(model=GreedyCutScanModel(backend="numpy"))
    env.core.fused_solve = True
    env.worker(cpus=4)
    env.submit(n=2, rqv=env.rqv(cpus=1))
    assert env.schedule() == 2
    assert "gangs" not in env.core.tick_stats.last_ms


@pytest.mark.parametrize("scheduler,fused", [
    ("tpu", True), ("multichip", True), ("greedy-fused", True),
    ("greedy-numpy", False), ("auto", False)])
def test_which_schedulers_run_the_fused_tick(tmp_path, monkeypatch,
                                             scheduler, fused):
    import jax

    from hyperqueue_tpu.models.multichip import MultichipModel
    from hyperqueue_tpu.server.bootstrap import Server

    if scheduler == "tpu":  # what bootstrap would build on the chip
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    server = Server(server_dir=tmp_path, scheduler=scheduler)
    assert server.core.fused_solve is fused
    base = server.model.model if hasattr(server.model, "model") \
        else server.model
    if scheduler == "tpu":
        assert type(base) is GreedyCutScanModel and base.backend == "jax"
    elif scheduler == "multichip":
        assert isinstance(base, MultichipModel)
    elif scheduler == "greedy-fused":
        assert base.backend == "numpy"


def test_one_chip_gang_selection_is_named_in_the_compiled_program():
    from hyperqueue_tpu.ops import assign

    n_w, n_b = 16, 4
    free = np.full((n_w, 2), 40_000, np.int32)
    needs = np.zeros((n_b, 1, 2), np.int32)
    needs[1:, 0, 0] = 10_000
    class_m, order_ids = assign.host_visit_classes(
        free, needs, np.asarray([0.5, 0.5], np.float32))
    import jax

    text = jax.jit(assign.greedy_cut_scan_impl).lower(
        free, np.full(n_w, 4, np.int32), np.full(n_w, 2**31 - 1, np.int32),
        needs, np.asarray([1, 5, 5, 5], np.int32),
        np.zeros((n_b, 1), np.int32), class_m, order_ids,
        gang_nodes=np.asarray([4, 0, 0, 0], np.int32),
        gang_ok=np.ones(n_w, np.int32),
        group_onehot=np.eye(4, dtype=np.int32)[np.arange(n_w) // 4],
    ).as_text(debug_info=True)
    assert assign.GANG_SELECT_SCOPE in text
