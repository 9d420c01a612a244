"""The `shard` cell's own checks, on the CPU (four virtual devices).

What `python -m chipbench.selfcheck` does for the cells it knows by driver
name, for this cell: the tiny rehearsal runs end to end, correct, and prints
no metric; the generator gives the same world for the same seed and the same
sizes for every seed; each check the driver adds can come out above its
limit; and the new metric readers read what they say, or nothing.
"""

import json
import os

# the rehearsal's four chips, asked for before the CPU backend starts
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import (  # noqa: E402
    control_shard,
    generate,
    generate_shard,
    manifest,
    shard_cost,
)
from chipbench import run as run_py  # noqa: E402
from chipbench.drivers import shard as shard_driver  # noqa: E402
from chipbench.drivers import tick as tick_driver  # noqa: E402

CELL = "shard-16k.backlog"
TINY = {"workers": 64, "ready_tasks": 16000, "settle": [[6, 0.05]]}
MASK = tick_driver.TASK_MASK


def rehearse(capsys, seconds=1.0, seed=2**31 + 11):
    run_py.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                 str(seconds), "--rehearse", "--scale", json.dumps(TINY)])
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])


def test_tiny_rehearsal_is_correct_and_prints_no_metric(capsys):
    line, note = rehearse(capsys)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "metrics" not in line
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    assert {"whole_node_shared", "solves_off_mesh", "ticks_mismatched",
            "full_uploads_in_window"} <= set(line["checks"])
    assert note["resident"]["mesh_devices"] == 4
    assert note["resident"]["rows_per_device"] == 16
    assert list(note["solves_by_backend_and_devices_in_window"]) == [
        "device-sharded x4"]


def test_generator_same_seed_same_world_every_seed_same_sizes():
    cell = manifest.cell(CELL)
    scale = {"workers": 32, "ready_tasks": 4000}
    a = generate_shard.world(cell["config"], cell["traffic"], 2**31 + 5, scale)
    b = generate_shard.world(cell["config"], cell["traffic"], 2**31 + 5, scale)
    c = generate_shard.world(cell["config"], cell["traffic"], 7, scale)
    fields = ("worker_total", "class_needs", "class_all", "task_class",
              "task_prio")
    assert all(np.array_equal(getattr(a, f), getattr(b, f)) for f in fields)
    assert not np.array_equal(a.task_class, c.task_class)
    assert generate.shape_signature(a) == generate.shape_signature(c)
    # 56 classes, 8 of them whole-node with one variant; a seventh of the tasks
    whole = a.class_all.any(axis=(1, 2))
    assert a.class_needs.shape == (56, 2, 3) and whole.sum() == 8
    assert (a.class_variants[whole] == 1).all()
    assert not a.class_needs[a.class_all].any()
    assert int(c.class_all.any(axis=(1, 2)).sum()) == 8
    assert abs(whole[a.task_class].mean() - 1 / 7) < 0.01


def test_full_size_world_states_what_the_file_says():
    cell = manifest.cell(CELL)
    config = cell["config"]
    assert cell["chips"] == config["mesh"]["chips"] == 4
    assert config["workers"]["count"] == 4 * config["mesh"]["rows_per_chip"]
    assert {"dag_edges", "numa_groups", "gangs", "time_limits"} <= set(config)
    assert len(config["source"]) <= 200
    world = generate_shard.world(config, cell["traffic"], 3,
                                 {"ready_tasks": 2000})
    assert world.worker_total.shape == (16384, 3)
    assert world.class_needs.shape[0] * world.n_priorities == 224
    warm = [r for r in cell["traffic"]["warm_dirty_rows"]]
    assert warm == sorted(warm) and warm[0] <= 16 and 2048 < warm[-1] <= 4096


# -- each check the driver adds can fail ------------------------------------
@pytest.fixture(scope="module")
def sound_record():
    cell = manifest.cell(CELL)
    return control_shard.stand_in_log(
        cell, seed=5, n_ticks=8,
        scale={"workers": 64, "ready_tasks": 16000}, control=None)


def test_audit_passes_a_sound_record(sound_record):
    numbers = shard_driver.audit_placements(*sound_record)
    assert set(numbers.values()) == {0}, numbers
    assert "whole_node_shared" in numbers


def test_whole_node_task_beside_another_fails_the_audit(sound_record):
    world, log, rq_ids, worker_ids = sound_record
    log = [[list(a), list(f)] for a, f in log]
    whole = world.class_all.any(axis=(1, 2))[world.task_class]
    # the fill tick places whole-node tasks alone; a later tick's task with
    # an amount of cpus is moved onto a worker that one of them still holds
    k, i, other = next((k, i, a) for k, (placed, _f) in enumerate(log)
                       for i, a in enumerate(placed) if not whole[a[0] & MASK])
    gone = {t for _placed, finished in log[:k] for t in finished}
    on_whole = next(a for a in log[0][0]
                    if whole[a[0] & MASK] and a[0] & MASK not in gone)
    log[k][0][i] = (other[0], on_whole[1], other[2], other[3])
    numbers = shard_driver.audit_placements(world, log, rq_ids, worker_ids)
    assert numbers["whole_node_shared"] > 0
    assert numbers["rows_overcommitted"] > 0
    # the tick driver's audit alone counts no cpus for a whole-node task
    assert tick_driver.audit_placements(
        world, log, rq_ids, worker_ids)["rows_overcommitted"] == 0


def test_solves_off_the_mesh_are_failed_operations(capsys, monkeypatch):
    from hyperqueue_tpu.models.multichip import MultichipModel

    # every solve reports the single-chip backend's name
    monkeypatch.setattr(MultichipModel, "_device_backend_name", "device-jax")
    line, _note = rehearse(capsys)
    assert line["correct"] is False
    assert line["checks"]["solves_off_mesh"]["value"] == line["attempted"]
    assert line["failed"] == line["attempted"]
    assert line["checks"]["ticks_mismatched"]["value"] == 0  # same placements


def test_program_that_does_not_report_its_layout_ends_the_run(capsys,
                                                               monkeypatch):
    """The parent commit's program: `resident_stats()` without
    `mesh_devices` and `rows_per_device`.  The run ends after the fill
    tick, non-zero, with no result."""
    from hyperqueue_tpu.parallel.resident import DeviceResidency

    real = DeviceResidency.stats

    def stats(self):
        return {k: v for k, v in real(self).items()
                if k not in ("mesh_devices", "rows_per_device")}
    monkeypatch.setattr(DeviceResidency, "stats", stats)
    with pytest.raises(SystemExit) as exit_info:
        rehearse(capsys)
    assert exit_info.value.code not in (0, None)
    assert "rows a device" in str(exit_info.value.code)


def test_full_upload_in_the_window_is_not_correct(capsys, monkeypatch):
    from hyperqueue_tpu.parallel import resident

    monkeypatch.setattr(resident, "FULL_UPLOAD_FRACTION", 0.0)
    line, _note = rehearse(capsys)
    assert line["correct"] is False
    assert line["checks"]["full_uploads_in_window"]["value"] > 0


def test_altered_answer_is_not_correct(capsys, monkeypatch):
    from hyperqueue_tpu.scheduler import tick

    real = tick.run_tick

    def run_tick(queues, workers, rq_map, resource_map, model, **kwargs):
        out = real(queues, workers, rq_map, resource_map, model, **kwargs)
        if len(out) >= 2 and out[0][1] != out[-1][1]:
            task_id, _worker, rq_id, variant = out[0]
            out[0] = (task_id, out[-1][1], rq_id, variant)
        return out
    monkeypatch.setattr(tick, "run_tick", run_tick)
    line, _note = rehearse(capsys)
    assert line["correct"] is False
    assert line["checks"]["ticks_mismatched"]["value"] > 0


@pytest.mark.parametrize("control", ["all_as_21_cpus", "stale_rows"])
def test_controls_fail_the_comparison(control):
    numbers = control_shard.shard_control(
        manifest.cell(CELL), seed=3, n_ticks=10,
        scale={"workers": 64, "ready_tasks": 16000}, control=control)
    assert numbers["ticks_mismatched"] > 0


# -- the new readers ---------------------------------------------------------
NEW_METRICS = ("collective_ms", "shard_scan_roofline", "scan_steps_per_tick")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_with_nothing_to_read_gives_nothing(name):
    read = manifest.metric_reader(name)
    assert read({}) is None
    assert read({"trace": None, "ticks": 0, "collective_s": None}) is None


def test_every_metric_of_the_cell_has_its_reader():
    cell = manifest.cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= names and "cut_scan_roofline" not in names
    for name in names:
        assert manifest.metric_reader(name)({}) is None
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tick_ms_p50", "tick_ms_p95", "ticks_per_s", "setup_s"}


def _toy_trace():
    def device(n, shift):
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_sharded_cut_scan_donate", 100 + shift, 400],
                ["jit_slice_live", 520 + shift, 20],
                ["jit_sharded_cut_scan_donate", 900 + shift, 400]]},
            {"name": "XLA Ops", "events": [
                ["%all-gather.3", 120 + shift, 30],
                ["%reduce-window.1", 160 + shift, 200],
                ["%all-reduce.4", 380 + shift, 30],   # as a v5e lowers it
                ["%all-gather.9", 525 + shift, 10],   # outside the program
                ["%all-gather.3", 920 + shift, 30]]}]}   # call cut by the span
    return {"planes": [device(0, 0), device(1, 5), {
        "name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["chipbench/traced", 50, 1000]]}]}]}


def test_collective_time_by_hand():
    seconds = shard_driver.collective_seconds(_toy_trace())
    assert seconds == pytest.approx(4 * 30e-9)
    assert shard_driver.collective_seconds(None) is None
    assert shard_driver.collective_seconds({"planes": []}) is None
    from chipbench import trace

    reduced = trace.reduce(_toy_trace(), shard_driver.KERNEL_MODULE)
    assert reduced["kernel_calls"] == 2 and reduced["devices"] == 2
    observed = {"collective_s": seconds, "trace": reduced}
    assert manifest.metric_reader("collective_ms")(observed) == \
        pytest.approx(60e-6)


def test_shard_cost_and_roofline_at_the_cell_size():
    cost = shard_cost.shard_scan_cost(B=224, V=2, W=16384, R=3, D=4)
    steps, rows = 448, 4096
    assert cost["ops"] == steps * rows * (4 * 3 + 7 + 3 * 3)
    assert cost["ici_bytes"] == 4 * steps * 16 * 4
    assert cost["bytes"] > 4 * steps * rows  # the counts, at least
    seconds, bound = shard_cost.least_seconds(cost, "TPU v5 lite")
    assert bound == "bytes" and 5e-6 < seconds < 5e-5
    with pytest.raises(KeyError):
        shard_cost.least_seconds(cost, "no such chip")
    observed = {
        "extents": {"B": 224, "V": 2, "W": 16384, "R": 3}, "mesh_devices": 4,
        "device_kind": "TPU v5 lite",
        "trace": {"kernel_calls": 8, "kernel_s": 8 * 0.05},
    }
    share = manifest.metric_reader("shard_scan_roofline")(observed)
    assert share == pytest.approx(100 * seconds / 0.05) and 0 < share < 100
    observed["scan_steps_in_window"], observed["ticks"] = 4480, 10
    assert manifest.metric_reader("scan_steps_per_tick")(observed) == 448
