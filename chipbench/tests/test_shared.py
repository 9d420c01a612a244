"""The `shared` cell's own checks, on the CPU.

The tiny rehearsal runs end to end, correct, and prints no metric, on the
host backend and on the device path; the generator gives the same cluster
whatever the seed and orders only the tasks and gangs by it; each number
the driver adds can come out above its limit; whole runs with the
reservation dropped, a reserved worker fed, or the solves off the device
end not correct; a program without `--gang-drain busy` ends the run with no
result; every control fails; and the new readers read what they say, or
nothing.
"""

import json

import numpy as np
import pytest

from chipbench import (control_shared, generate_shared, manifest,
                       shared_cost)
from chipbench import run as run_py
from chipbench.drivers import shared as shared_driver

CELL = "shared-1k.reserve"
TINY = {"workers": 256, "groups": 4, "ready_tasks": 16000, "ready_gangs": 200,
        "settle": [[30, 0.05, 0.1]]}
SMALL = {**TINY, "settle": [[20, 0.2, 0.15]]}


def rehearse(capsys, seconds=1.0, seed=2**31 + 11, **scale):
    run_py.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                 str(seconds), "--rehearse", "--scale",
                 json.dumps({**TINY, **scale})])
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])


def test_tiny_rehearsal_is_correct_and_prints_no_metric(capsys):
    line, note = rehearse(capsys)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "metrics" not in line
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    assert {"reserved_fed", "reservation_unhonoured", "gang_split",
            "gang_shared", "gang_overtaken", "ticks_mismatched",
            "solves_off_device"} <= set(line["checks"])
    assert note["reserved_a_tick_min_p50_max"][1] > 0
    assert note["assigned_in_window"] > 0
    assert note["ticks_replayed_by_reference"] == \
        note["setup_ticks"] + line["attempted"]
    assert "gangs/reserve" in note["phases_ms_p50"]


def test_rehearsal_on_the_device_path_meets_its_upload_programs(capsys):
    line, note = rehearse(capsys, backend="jax")
    assert line["correct"] is True, line
    assert list(note["solves_by_backend_in_window"]) == ["device-jax"]
    assert note["upload_programs_not_met_in_setup"] == []
    assert note["resident"]["full_uploads"] > 0


def test_the_cluster_does_not_depend_on_the_seed():
    cell = manifest.cell(CELL)
    scale = {"workers": 128, "groups": 4, "ready_tasks": 4000,
             "ready_gangs": 100}
    a = generate_shared.world(cell["config"], cell["traffic"], 2**31 + 5, scale)
    b = generate_shared.world(cell["config"], cell["traffic"], 2**31 + 5, scale)
    c = generate_shared.world(cell["config"], cell["traffic"], 7, scale)
    fields = ("worker_total", "worker_slots", "class_needs", "task_class",
              "task_prio", "worker_group", "gang_nodes")
    assert all(np.array_equal(getattr(a, f), getattr(b, f)) for f in fields)
    # the seed orders the gangs, nothing else
    assert all(np.array_equal(getattr(a, f), getattr(c, f))
               for f in fields if f != "gang_nodes")
    assert not np.array_equal(a.gang_nodes, c.gang_nodes)
    assert sorted(a.gang_nodes.tolist()) == sorted(c.gang_nodes.tolist())
    assert generate_shared.shape_signature(a) == \
        generate_shared.shape_signature(c)
    # one node shape an allocation
    for g in range(4):
        rows = a.worker_total[a.worker_group == g]
        assert (rows == rows[0]).all()


def test_full_size_world_states_what_the_file_says():
    cell = manifest.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and traffic["driver"] == "shared"
    assert config["gang_drain"] == "busy" and config["scheduler"] == "tpu"
    assert len(config["source"]) <= 200
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "shared-1k")
    assert set(entry["reduced"]) == set(config["reduced_from_source"]) == {
        "dag_edges", "numa_groups", "time_limits"}
    assert entry["source"] == config["source"]
    assert {"reserved_exclusive", "reservation_honoured", "gang_atomic",
            "gang_group", "gang_exclusive", "gang_fifo", "solver",
            "order"} <= set(config["guarantees"])
    world = generate_shared.world(config, traffic, 3, {"ready_tasks": 4000})
    assert world.worker_total.shape == (1024, 3)
    assert world.class_needs.shape == (64, 2, 3)
    assert np.bincount(world.worker_group).tolist() == [64] * 16
    shapes = generate_shared.group_shapes(config, 16) // 10_000
    assert sorted(np.bincount(shapes[:, 0])[[42, 96, 128]].tolist()) == \
        [5, 5, 6]
    assert (shapes[:, 1] == 0).sum() == 10
    # most of the filler asks for no gpu: it lands on any node
    no_gpu = world.class_needs[world.task_class, 0, 1] == 0
    assert no_gpu.mean() > 0.6
    sizes, counts = np.unique(world.gang_nodes, return_counts=True)
    assert sizes.tolist() == [2, 4, 8, 16, 32] and counts.sum() == 4096
    assert world.gang_prio == world.n_priorities - 1
    assert traffic["gang_rows_per_tick"] == config["gangs"]["rows_per_tick"]


# -- each number the driver adds can fail -------------------------------------
@pytest.fixture(scope="module")
def sound_record():
    world, log, gang_log, resv_log, rq_ids, worker_ids = \
        control_shared.stand_in_log(manifest.cell(CELL), seed=5,
                                    n_window=30, scale=SMALL)
    return world, log, gang_log, resv_log, rq_ids, worker_ids


def audit(world, log, gang_log, resv_log, _rq_ids, worker_ids):
    return shared_driver.audit_shared(world, log, gang_log, resv_log,
                                      worker_ids, 16)


def copy_of(record):
    world, log, gang_log, resv_log, rq_ids, worker_ids = record
    return (world, [[list(a), list(f)] for a, f in log],
            [[[(g, list(m)) for g, m in s], list(e), list(n)]
             for s, e, n in gang_log],
            [{g: list(m) for g, m in r.items()} for r in resv_log],
            rq_ids, worker_ids)


def test_audit_passes_a_sound_record(sound_record):
    assert set(audit(*sound_record).values()) == {0}
    assert shared_driver.compare_with_reference(
        *sound_record, manifest.reference("shared_plain")
    )["ticks_mismatched"] == 0


def test_task_on_a_reserved_worker_is_fed(sound_record):
    record = copy_of(sound_record)
    _world, log, _gang_log, resv_log = record[:4]
    k = next(k for k in range(len(log)) if resv_log[k] and log[k][0])
    member = next(iter(resv_log[k].values()))[0]
    task_id, _worker, rq_id, variant = log[k][0][0]
    log[k][0][0] = (task_id, member, rq_id, variant)
    assert audit(*record)["reserved_fed"] >= 1


def test_a_dropped_reservation_is_unhonoured(sound_record):
    record = copy_of(sound_record)
    resv_log = record[3]
    k = next(k for k in range(2, len(resv_log)) if resv_log[k])
    resv_log[k].clear()
    numbers = audit(*record)
    assert numbers["reservation_unhonoured"] >= 1
    assert shared_driver.compare_with_reference(
        *record, manifest.reference("shared_plain"))["ticks_mismatched"] > 0


def test_reservation_sets_read_back_from_the_column():
    column = np.asarray([0, (2 << 32) | 7, 0, (2 << 32) | 7])
    raw = shared_driver.record_reservations(column, [11, 12, 13, 14])
    assert [arr.dtype for arr in raw] == [np.int64, np.int64]
    assert shared_driver.reservation_sets(raw) == {7: [12, 14]}
    assert shared_driver.record_reservations(None, [11]) is None
    assert shared_driver.reservation_sets(raw, {12: 1, 14: 3}) == {7: [1, 3]}
    assert shared_driver.reservation_sets(None) == {}


# -- whole runs with the timed path broken underneath -------------------------
def test_reservation_dropped_is_not_correct(capsys, monkeypatch):
    from hyperqueue_tpu.server import reactor

    real = reactor.fused_gang_reserve

    def no_reservation(core, *args, **kwargs):
        resv = real(core, *args, **kwargs)
        for task_id in list(core.mn_reservations):
            reactor._clear_mn_reservations(core, task_id)
        return np.zeros_like(resv)
    monkeypatch.setattr(reactor, "fused_gang_reserve", no_reservation)
    line, _note = rehearse(capsys)
    assert line["correct"] is False
    assert line["checks"]["ticks_mismatched"]["value"] > 0


def test_reserved_worker_fed_is_not_correct(capsys, monkeypatch):
    from hyperqueue_tpu.scheduler import tick

    real = tick.reservation_codes
    # the solve is told of no reservation, the driver records them
    monkeypatch.setattr(tick, "reservation_codes",
                        lambda resv, batches: real(resv * 0, batches))
    line, _note = rehearse(capsys)
    assert line["correct"] is False
    assert line["checks"]["reserved_fed"]["value"] > 0
    assert line["checks"]["ticks_mismatched"]["value"] > 0


def test_solves_off_the_device_are_failed_operations(capsys, monkeypatch):
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel

    monkeypatch.setattr(GreedyCutScanModel, "_device_backend_name",
                        "device-elsewhere")
    line, _note = rehearse(capsys, backend="jax")
    assert line["correct"] is False
    assert line["checks"]["solves_off_device"]["value"] == line["attempted"]


def test_program_without_gang_drain_ends_the_run(capsys, monkeypatch):
    """The parent commit's program has no `--gang-drain`: the run ends at
    once, non-zero, with no result."""
    from hyperqueue_tpu.server import reactor

    monkeypatch.delattr(reactor, "fused_gang_reserve")
    with pytest.raises(SystemExit) as exit_info:
        rehearse(capsys)
    assert exit_info.value.code not in (0, None)
    assert "--gang-drain" in str(exit_info.value.code)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("control", [c for c in control_shared.CONTROLS if c])
def test_controls_fail_the_comparison(control):
    numbers = control_shared.shared_control(
        manifest.cell(CELL), seed=3, n_window=30, scale=SMALL,
        control=control)
    assert numbers["ticks_mismatched"] > 0
    assert numbers[control_shared.CONTROLS[control][1]] > 0, numbers


def test_the_rows_rehearsal_reads_the_dense_rows():
    rows = control_shared.dense_rows(manifest.cell(CELL), seed=3,
                                     n_window=20, scale=SMALL)
    assert 0 < rows["rows_min"] <= rows["rows_p50"] <= rows["rows_max"] <= 256
    assert rows["reserved_p50"] > 0


# -- the new readers ---------------------------------------------------------
NEW_METRICS = ("gang_reserve_ms", "reserved_busy_per_tick",
               "shared_scan_roofline")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_with_nothing_to_read_gives_nothing(name):
    read = manifest.metric_reader(name)
    assert read({}) is None
    assert read({"trace": None, "ticks": 10,
                 "tick_phases_ms": [{"total": 1}]}) is None


def test_every_metric_of_the_cell_has_its_reader():
    cell = manifest.cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    gang = {m["name"] for m in manifest.cell("gang-1k.rigid")["per_layer"]}
    assert names == gang | set(NEW_METRICS)
    for name in names:
        assert manifest.metric_reader(name)({}) is None
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tick_ms_p50", "tick_ms_p95", "ticks_per_s", "setup_s"}


def test_readers_read_what_the_driver_passes():
    observed = {
        "ticks": 4,
        "tick_phases_ms": [{"gangs/reserve": 0.1}, {"gangs/reserve": 0.3},
                           {"gangs/reserve": 0.2}, {"total": 9.0}],
        "reserved_busy_in_window": 400,
    }
    assert manifest.metric_reader("gang_reserve_ms")(observed) == \
        pytest.approx(0.15)
    assert manifest.metric_reader("reserved_busy_per_tick")(observed) == 100


def test_shared_cost_and_roofline_at_the_cell_size():
    from chipbench import gang_cost

    plain = gang_cost.gang_scan_cost(B=272, V=2, W=930, R=3, G=16,
                                     gang_rows=16)
    cost = shared_cost.shared_scan_cost(B=272, V=2, W=930, R=3, G=16,
                                        gang_rows=16)
    assert cost["ops"] == plain["ops"] + 272 * 2 * 930 + 16 * (8 * 930 + 32)
    assert cost["bytes"] == plain["bytes"] + 4 * 930
    seconds, _bound = shared_cost.least_seconds(cost, "TPU v5 lite")
    observed = {
        "extents": {"B": 272, "V": 2, "W": 930, "R": 3}, "groups": 16,
        "gang_rows": 16, "device_kind": "TPU v5 lite", "reservations": True,
        "trace": {"kernel_calls": 8, "kernel_s": 8 * 0.002},
    }
    share = manifest.metric_reader("shared_scan_roofline")(observed)
    assert share == pytest.approx(100 * seconds / 0.002) and 0 < share < 100
    assert manifest.metric_reader("shared_scan_roofline")(
        {**observed, "reservations": False}) is None
