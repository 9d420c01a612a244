"""The `gang` cell's own checks, on the CPU.

What `python -m chipbench.selfcheck` does for the cells it knows by driver
name, for this cell: the tiny rehearsal runs end to end, correct, and prints
no metric; the generator gives the same world for the same seed and the same
sizes for every seed; each check the driver adds can come out above its
limit; whole runs with the timed path broken underneath end not correct; and
the new metric readers read what they say, or nothing.
"""

import json

import numpy as np
import pytest

from chipbench import control_gang, gang_cost, generate_gang, manifest
from chipbench import run as run_py
from chipbench.drivers import gang as gang_driver
from chipbench.drivers import tick as tick_driver

CELL = "gang-1k.rigid"
# wide enough that the 16 gang rows of a tick leave the filler its gpu nodes
TINY = {"workers": 256, "groups": 4, "ready_tasks": 16000, "ready_gangs": 200,
        "settle": [[10, 0.05, 0.1]]}
SMALL = {k: TINY[k] for k in ("workers", "groups", "ready_tasks",
                              "ready_gangs")}
MASK = tick_driver.TASK_MASK


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
    assert {"gang_split", "gang_shared", "gang_overtaken", "ticks_mismatched",
            "solves_off_device", "answers_unknown"} <= set(line["checks"])
    assert note["gangs_started_a_tick_min_p50_max"][2] > 0
    assert note["assigned_in_window"] > 0  # the filler ran beside the gangs
    assert note["ticks_replayed_by_reference"] == \
        note["setup_ticks"] + line["attempted"]


def test_rehearsal_on_the_device_path_meets_its_upload_programs(capsys):
    line, note = rehearse(capsys, backend="jax")
    assert line["correct"] is True, line
    assert list(note["solves_by_backend_in_window"]) == ["device-jax"]
    assert note["resident"]["full_uploads"] > 0
    assert not any(name.endswith(":full")
                   for name in note["upload_programs_not_met_in_setup"])


def test_generator_same_seed_same_world_every_seed_same_sizes():
    cell = manifest.cell(CELL)
    scale = {"workers": 64, "groups": 4, "ready_tasks": 4000,
             "ready_gangs": 100}
    a = generate_gang.world(cell["config"], cell["traffic"], 2**31 + 5, scale)
    b = generate_gang.world(cell["config"], cell["traffic"], 2**31 + 5, scale)
    c = generate_gang.world(cell["config"], cell["traffic"], 7, scale)
    fields = ("worker_total", "class_needs", "task_class", "task_prio",
              "worker_group", "gang_nodes")
    assert all(np.array_equal(getattr(a, f), getattr(b, f)) for f in fields)
    assert not np.array_equal(a.gang_nodes, c.gang_nodes)
    assert generate_gang.shape_signature(a) == generate_gang.shape_signature(c)
    # 16 workers a group: gangs of 32 cannot exist there and are left out
    assert set(a.gang_nodes.tolist()) == {2, 4, 8, 16}
    assert np.bincount(a.worker_group).tolist() == [16] * 4


def test_full_size_world_states_what_the_file_says():
    cell = manifest.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and traffic["driver"] == "gang"
    assert len(config["source"]) <= 200
    reduced = set(config["reduced_from_source"])
    assert reduced == {"dag_edges", "numa_groups", "time_limits"}
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "gang-1k")
    assert set(entry["reduced"]) == reduced and entry["source"] == \
        config["source"]
    assert {"gang_atomic", "gang_group", "gang_exclusive", "gang_fifo",
            "solver", "order"} <= set(config["guarantees"])
    world = generate_gang.world(config, traffic, 3, {"ready_tasks": 2000})
    assert world.worker_total.shape == (1024, 3)
    assert world.class_needs.shape == (20, 1, 3)
    assert (world.class_needs[:, 0, 1] > 0).all()  # every class asks a gpu
    assert world.class_needs.shape[0] * world.n_priorities + 16 == 96
    assert np.bincount(world.worker_group).tolist() == [64] * 16
    sizes, counts = np.unique(world.gang_nodes, return_counts=True)
    assert sizes.tolist() == [2, 4, 8, 16, 32] and counts.sum() == 4096
    assert counts.tolist() == [1434, 1229, 819, 409, 205]
    assert world.gang_prio == world.n_priorities - 1
    assert traffic["gang_rows_per_tick"] == config["gangs"]["rows_per_tick"] \
        == 16
    assert int((world.worker_total[:, 1] == 0).sum()) in (614, 615)


# -- each check the driver adds can fail ------------------------------------
@pytest.fixture(scope="module")
def sound_record():
    return control_gang.stand_in_log(
        manifest.cell(CELL), seed=5, n_ticks=12,
        scale=SMALL, control=None)


def audit(world, log, gang_log, _rq_ids, worker_ids):
    return gang_driver.audit_gangs(world, log, gang_log, worker_ids, 16)


def copy_of(record):
    world, log, gang_log, rq_ids, worker_ids = record
    return (world, [[list(a), list(f)] for a, f in log],
            [[[(g, list(m)) for g, m in s], list(e), list(n)]
             for s, e, n in gang_log], rq_ids, worker_ids)


def test_audit_passes_a_sound_record(sound_record):
    assert set(audit(*sound_record).values()) == {0}
    assert gang_driver.compare_with_reference(
        *sound_record, manifest.reference("gang_plain")
    )["ticks_mismatched"] == 0


def test_gang_short_of_a_member_or_in_two_groups_is_split(sound_record):
    record = copy_of(sound_record)
    world, _log, gang_log = record[:3]
    k = next(k for k, tick in enumerate(gang_log) if tick[0])
    gang_log[k][0][0][1].pop()
    assert audit(*record)["gang_split"] == 1
    record = copy_of(sound_record)
    gang_log = record[2]
    g, members = gang_log[k][0][0]
    group = world.worker_group[members[0] - 1]
    taken = {w for _g, m in gang_log[k][0] for w in m}
    other = next(w for w in record[4] if world.worker_group[w - 1] != group
                 and w not in taken)
    members[-1] = other
    assert audit(*record)["gang_split"] == 1
    assert gang_driver.compare_with_reference(
        *record, manifest.reference("gang_plain"))["ticks_mismatched"] > 0


def test_task_on_a_gang_member_is_shared(sound_record):
    record = copy_of(sound_record)
    _world, log, gang_log = record[:3]
    k = next(k for k, tick in enumerate(gang_log) if tick[0])
    member = gang_log[k][0][0][1][0]
    j = next(j for j in range(k, len(log)) if log[j][0])
    task_id, _worker, rq_id, variant = log[j][0][0]
    log[j][0][0] = (task_id, member, rq_id, variant)
    assert audit(*record)["gang_shared"] >= 1


def test_gang_past_a_smaller_waiting_one_is_overtaken(sound_record):
    record = copy_of(sound_record)
    gang_log = record[2]
    # the first gang a tick started is struck from the record: a later
    # one, no smaller, then started past a waiting gang
    k, (g, members) = next(
        (k, tick[0][0]) for k, tick in enumerate(gang_log)
        if len(tick[0]) > 1
        and max(len(m) for _g, m in tick[0][1:]) >= len(tick[0][0][1]))
    del gang_log[k][0][0]
    gang_log[k][2] = gang_log[k][2][1:]
    assert audit(*record)["gang_overtaken"] >= 1


# -- whole runs with the timed path broken underneath -------------------------
def test_gang_rows_left_out_is_not_correct(capsys, monkeypatch):
    from hyperqueue_tpu.server import reactor

    monkeypatch.setattr(reactor, "fused_gang_rows",
                        lambda core, phases=None: [])
    line, _note = rehearse(capsys)
    assert line["correct"] is False
    assert line["checks"]["ticks_mismatched"]["value"] > 0


def test_altered_member_is_not_correct(capsys, monkeypatch):
    from hyperqueue_tpu.scheduler import tick

    real = tick.run_tick

    def run_tick(queues, workers, rq_map, resource_map, model, **kwargs):
        out = real(queues, workers, rq_map, resource_map, model, **kwargs)
        gang = [i for i, a in enumerate(out) if a[3] == -1]
        if gang:
            task_id, worker_id, rq_id, _v = out[gang[-1]]
            taken = {a[1] for a in out}
            other = next((w for w in kwargs["dense"].worker_ids
                          if w not in taken), worker_id)
            out[gang[-1]] = (task_id, other, rq_id, -1)
        return out
    monkeypatch.setattr(tick, "run_tick", run_tick)
    line, _note = rehearse(capsys)
    assert line["correct"] is False
    assert line["checks"]["ticks_mismatched"]["value"] > 0
    assert (line["checks"]["answers_unknown"]["value"]
            + line["checks"]["gang_split"]["value"]
            + line["checks"]["gang_shared"]["value"]) > 0


def test_gang_applied_on_a_busy_worker_is_not_correct(capsys, monkeypatch):
    from hyperqueue_tpu.server.worker import Worker

    # every worker passes for idle: the solve offers busy ones to the gang
    # rows and the reactor's validation lets them through
    monkeypatch.setattr(Worker, "is_idle", lambda self: self.mn_task == 0)
    line, _note = rehearse(capsys)
    assert line["correct"] is False
    assert line["checks"]["gang_shared"]["value"] > 0


def test_solves_off_the_device_are_failed_operations(capsys, monkeypatch):
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel

    monkeypatch.setattr(GreedyCutScanModel, "_device_backend_name",
                        "device-elsewhere")
    line, _note = rehearse(capsys, backend="jax")
    assert line["correct"] is False
    assert line["checks"]["solves_off_device"]["value"] == line["attempted"]
    assert line["failed"] == line["attempted"]
    assert line["checks"]["ticks_mismatched"]["value"] == 0  # same placements


def test_program_without_the_gang_functions_ends_the_run(capsys, monkeypatch):
    """The parent commit's program: the fused gang phase inline in `_tick`.
    The run ends at once, non-zero, with no result."""
    from hyperqueue_tpu.server import reactor

    monkeypatch.delattr(reactor, "fused_gang_rows")
    with pytest.raises(SystemExit) as exit_info:
        rehearse(capsys)
    assert exit_info.value.code not in (0, None)
    assert "fused_gang_rows" in str(exit_info.value.code)


@pytest.mark.parametrize("control", [c for c in control_gang.CONTROLS if c])
def test_controls_fail_the_comparison(control):
    numbers = control_gang.gang_control(
        manifest.cell(CELL), seed=3, n_ticks=12,
        scale=SMALL, control=control)
    assert numbers["ticks_mismatched"] > 0
    assert numbers[control_gang.CONTROLS[control][1]] > 0


# -- the new readers ---------------------------------------------------------
NEW_METRICS = ("gang_phase_ms", "gangs_started_per_tick",
               "cache_rebuilds_per_tick", "full_uploads_per_tick",
               "gang_scan_roofline")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_with_nothing_to_read_gives_nothing(name):
    read = manifest.metric_reader(name)
    assert read({}) is None
    # a program that lacks the span or the counter
    assert read({"trace": None, "ticks": 10, "tick_phases_ms": [{"total": 1}],
                 "uploads_before": {"backend": "x"},
                 "uploads_after": {"backend": "x"},
                 "cache_before": {"workers": 1},
                 "cache_after": {"workers": 1}}) is None


def test_every_metric_of_the_new_cells_has_its_reader():
    cell = manifest.cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= names and "cut_scan_roofline" not in names
    assert {"tick_host_ms", "device_wait_ms", "upload_ms", "launch_ms",
            "counts_wait_ms", "state_readback_ms", "upload_bytes_per_tick",
            "readback_bytes_per_tick", "kernel_ms", "device_idle_pct.tick",
            "compact_answer_pct"} <= names
    for name in names:
        assert manifest.metric_reader(name)({}) is None
    ends = {"tick_ms_p50", "tick_ms_p95", "ticks_per_s", "setup_s"}
    assert {m["name"] for m in cell["end_to_end"]} == ends
    flat = manifest.cell("flat-1k.backlog-1m")
    hetero = manifest.cell("hetero-1k.backlog-1m")
    assert flat["chips"] == 1 and flat["traffic"]["driver"] == "tick"
    assert flat["config"]["reference"] == "tick_plain"
    for kind in ("end_to_end", "per_layer"):
        assert [m["name"] for m in flat[kind]] == \
            [m["name"] for m in hetero[kind]]


def test_readers_read_what_the_driver_passes():
    observed = {
        "ticks": 4,
        "tick_phases_ms": [{"gangs": 1.0}, {"gangs": 3.0}, {"gangs": 2.0},
                           {"total": 9.0}],
        "uploads_before": {"full_uploads": 10}, "uploads_after":
        {"full_uploads": 13},
        "cache_before": {"full_rebuilds": 5}, "cache_after":
        {"full_rebuilds": 9},
        "gangs_started_in_window": 26,
    }
    assert manifest.metric_reader("gang_phase_ms")(observed) == 1.5
    assert manifest.metric_reader("full_uploads_per_tick")(observed) == 0.75
    assert manifest.metric_reader("cache_rebuilds_per_tick")(observed) == 1.0
    assert manifest.metric_reader("gangs_started_per_tick")(observed) == 6.5


def test_gang_cost_and_roofline_at_the_cell_size():
    from chipbench import kernel_cost

    plain = kernel_cost.cut_scan_cost(B=96, V=1, W=426, R=3)
    cost = gang_cost.gang_scan_cost(B=96, V=1, W=426, R=3, G=16, gang_rows=16)
    assert cost["ops"] == plain["ops"] + 16 * (426 * 12 + 32)
    assert cost["bytes"] == plain["bytes"] + 4 * (2 * 426 + 96)
    seconds, bound = gang_cost.least_seconds(cost, "TPU v5 lite")
    assert bound == "bytes" and 1e-7 < seconds < 1e-5
    observed = {
        "extents": {"B": 96, "V": 1, "W": 426, "R": 3}, "groups": 16,
        "gang_rows": 16, "device_kind": "TPU v5 lite",
        "trace": {"kernel_calls": 8, "kernel_s": 8 * 0.002},
    }
    share = manifest.metric_reader("gang_scan_roofline")(observed)
    assert share == pytest.approx(100 * seconds / 0.002) and 0 < share < 100
