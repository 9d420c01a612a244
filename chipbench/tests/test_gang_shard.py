"""The `gang_shard` cell's own checks, on the CPU (four virtual devices).

What `python -m chipbench.selfcheck` does for the cells it knows by driver
name, for this cell: the tiny rehearsal runs end to end, correct, and prints
no metric; the full-size world states what the file says; whole runs with the
timed path broken underneath end not correct; the parent's program ends the
run at once with no result; every control fails the comparison, the one that
selects shard by shard included; and the new metric readers read what they
say, or nothing.
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
    control_gang_shard,
    gang_shard_cost,
    generate_gang,
    kernel_cost,
    manifest,
)
from chipbench import run as run_py  # noqa: E402

CELL = "gang-16k.campaign"
# 16 groups of 32: wide enough that the 16 gang rows of a tick leave the
# filler its gpu nodes, and that groups straddle the four shards
TINY = {"workers": 512, "groups": 16, "ready_tasks": 20000,
        "ready_gangs": 400, "settle": [[8, 0.05, 0.05]]}
SMALL = {k: TINY[k] for k in ("workers", "groups", "ready_tasks",
                              "ready_gangs")}


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
    assert set(line["checks"]) == {
        "ticks_mismatched", "rows_overcommitted", "tasks_out_of_order",
        "priority_inversions", "answers_unknown", "gang_split", "gang_shared",
        "gang_overtaken", "solves_off_device", "solves_off_mesh",
        "compiles_in_window", "new_shapes_in_window"}
    assert list(note["solves_by_backend_and_devices_in_window"]) == [
        "device-sharded x4"]
    assert note["resident"]["mesh_devices"] == 4
    assert note["resident"]["gang_groups_last"] == note["groups"] == 16
    assert note["gangs_started_a_tick_min_p50_max"][2] > 0
    assert note["assigned_in_window"] > 0  # the filler ran beside the gangs
    assert note["ticks_replayed_by_reference"] == \
        note["setup_ticks"] + line["attempted"]
    # the rows fell through the worker buckets and each met its full upload
    assert note["worker_buckets_in_setup"] == [512, 256]
    assert not any(name.endswith(":full")
                   for name in note["upload_programs_not_met_in_setup"])
    assert sum(note["window_ticks_by_worker_bucket"].values()) == \
        line["attempted"]
    assert {"gangs/inputs", "assemble/gang", "solve_host_prep/gang"} <= set(
        note["phases_ms_p50"])


def test_full_size_world_states_what_the_file_says():
    cell = manifest.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == config["mesh"]["chips"] == 4
    assert traffic["driver"] == "gang_shard"
    assert config["workers"]["count"] == 4 * config["mesh"]["rows_per_chip"]
    assert len(config["source"]) <= 200
    reduced = set(config["reduced_from_source"])
    assert reduced == {"dag_edges", "numa_groups", "time_limits"}
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "gang-16k")
    assert set(entry["reduced"]) == reduced and entry["source"] == \
        config["source"]
    small = manifest.cell("gang-1k.rigid")["config"]
    assert config["reference"] == small["reference"] == "gang_plain"
    # no guarantee is weakened: gang-1k's, word for word but the solver's,
    # and the sharded cell's "no placement shows the split"
    for key, text in small["guarantees"].items():
        assert key == "solver" or config["guarantees"][key] == text
    assert "device-sharded" in config["guarantees"]["solver"]
    assert "knows no mesh" in config["guarantees"]["sharding"]
    for key in ("classes", "gangs", "priority_levels", "resources"):
        assert config[key] == small[key]
    world = generate_gang.world(config, traffic, 3, {"ready_tasks": 2000})
    assert world.worker_total.shape == (16384, 3)
    assert world.class_needs.shape == (20, 1, 3)
    assert world.class_needs.shape[0] * world.n_priorities + 16 == 96
    assert np.bincount(world.worker_group).tolist() == [64] * 256
    sizes, counts = np.unique(world.gang_nodes, return_counts=True)
    assert sizes.tolist() == [2, 4, 8, 16, 32] and counts.sum() == 16384
    assert counts.tolist() == [5735, 4915, 3277, 1638, 819]
    assert world.gang_prio == world.n_priorities - 1
    assert (traffic["ready_gangs"], traffic["ready_tasks"]) == (
        16384, 1_000_000)
    assert (traffic["gang_finish_per_tick"], traffic["churn_per_tick"]) == (
        0.005, 0.01)
    assert traffic["gang_rows_per_tick"] == config["gangs"]["rows_per_tick"] \
        == 16
    assert 9820 <= int((world.worker_total[:, 1] == 0).sum()) <= 9840
    assert all(len(step) == 3 for step in traffic["settle"])


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


def test_selection_that_ignores_the_other_chips_is_not_correct(capsys,
                                                               monkeypatch):
    """The fault `local_groups` stands for, committed by the program: every
    chip ranks a group's eligible workers among its own rows alone."""
    from hyperqueue_tpu.ops import assign
    from hyperqueue_tpu.parallel import solve

    def select(elig, group_onehot, n, per_group_total=None,
               same_group_before=0):
        return assign._gang_select_local(
            elig, group_onehot, n, per_group_total=per_group_total)
    monkeypatch.setattr(solve, "_gang_select_local", select)
    solve.sharded_cut_scan_donate.clear_cache()
    try:
        line, _note = rehearse(capsys)
    finally:
        monkeypatch.undo()
        solve.sharded_cut_scan_donate.clear_cache()
    assert line["correct"] is False
    assert line["checks"]["ticks_mismatched"]["value"] > 0
    assert (line["checks"]["gang_split"]["value"]
            + line["checks"]["answers_unknown"]["value"]) > 0


def test_solves_off_the_mesh_are_failed_operations(capsys, monkeypatch):
    from hyperqueue_tpu.models.multichip import MultichipModel

    # every solve reports the single-chip backend's name
    monkeypatch.setattr(MultichipModel, "_device_backend_name", "device-jax")
    line, _note = rehearse(capsys)
    assert line["correct"] is False
    assert line["checks"]["solves_off_mesh"]["value"] == line["attempted"]
    assert line["checks"]["solves_off_device"]["value"] == 0
    assert line["failed"] == line["attempted"]
    assert line["checks"]["ticks_mismatched"]["value"] == 0  # same placements


def test_program_without_the_gang_functions_ends_the_run(capsys, monkeypatch):
    """A program whose fused gang phase cannot be called (before PR 31).
    The run ends at once, non-zero, with no result."""
    from hyperqueue_tpu.server import reactor

    monkeypatch.delattr(reactor, "fused_gang_rows")
    with pytest.raises(SystemExit) as exit_info:
        rehearse(capsys)
    assert exit_info.value.code not in (0, None)
    assert "fused_gang_rows" in str(exit_info.value.code)


def test_parent_without_the_driver_ends_the_run(capsys, monkeypatch, tmp_path):
    """The parent commit has no `gang_shard` driver: `run.py` ends at once
    naming the file it cannot find, non-zero, with no result."""
    monkeypatch.setattr(manifest, "HERE", tmp_path)
    with pytest.raises(SystemExit) as exit_info:
        manifest.driver("gang_shard")
    assert "gang_shard.py not found" in str(exit_info.value.code)


@pytest.mark.parametrize("control", [c for c in control_gang_shard.CONTROLS
                                     if c])
def test_controls_fail_the_comparison(control):
    numbers = control_gang_shard.gang_shard_control(
        manifest.cell(CELL), seed=3, n_ticks=30, scale=SMALL, control=control)
    assert numbers["ticks_mismatched"] > 0
    assert numbers[control_gang_shard.CONTROLS[control]] > 0


def test_sound_stand_in_passes():
    numbers = control_gang_shard.gang_shard_control(
        manifest.cell(CELL), seed=3, n_ticks=30, scale=SMALL, control=None)
    assert {k: v for k, v in numbers.items() if v} == {"ticks_replayed": 30}


# -- the new readers ---------------------------------------------------------
NEW_METRICS = ("gang_inputs_ms", "gang_input_bytes_per_tick",
               "gang_shard_scan_roofline")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_with_nothing_to_read_gives_nothing(name):
    read = manifest.metric_reader(name)
    assert read({}) is None
    # the parent's program: `gangs/inputs` alone of the three spans, no
    # counter of the gang inputs' bytes
    assert read({"trace": None, "ticks": 10,
                 "tick_phases_ms": [{"total": 1, "gangs/inputs": 0.2}],
                 "uploads_before": {"full_uploads": 1},
                 "uploads_after": {"full_uploads": 2}}) is None


def test_every_metric_of_the_new_cell_has_its_reader():
    cell = manifest.cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= names
    assert not {"cut_scan_roofline", "gang_scan_roofline",
                "shard_scan_roofline"} & names
    assert {"tick_host_ms", "device_wait_ms", "upload_ms", "launch_ms",
            "counts_wait_ms", "state_readback_ms", "upload_bytes_per_tick",
            "readback_bytes_per_tick", "puts_per_tick", "compact_answer_pct",
            "kernel_ms", "device_idle_pct.tick", "collective_ms",
            "scan_steps_per_tick", "gang_phase_ms", "gangs_started_per_tick",
            "cache_rebuilds_per_tick", "full_uploads_per_tick"} <= names
    for name in names:
        assert manifest.metric_reader(name)({}) is None
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tick_ms_p50", "tick_ms_p95", "ticks_per_s", "setup_s"}
    small = {m["name"] for m in manifest.cell("gang-1k.rigid")["per_layer"]}
    assert {"gang_inputs_ms", "gang_input_bytes_per_tick"} <= small
    assert "gang_shard_scan_roofline" not in small


def test_readers_read_what_the_drivers_pass():
    observed = {
        "ticks": 4,
        "tick_phases_ms": [
            {"gangs/inputs": 1.0, "assemble/gang": 2.0,
             "solve_host_prep/gang": 3.0},
            {"gangs/inputs": 1.0, "assemble/gang": 4.0,
             "solve_host_prep/gang": 3.0},
            {"total": 9.0}, {"total": 9.0}],
        "uploads_before": {"gang_input_bytes_total": 1000},
        "uploads_after": {"gang_input_bytes_total": 41000},
    }
    assert manifest.metric_reader("gang_inputs_ms")(observed) == 3.0
    assert manifest.metric_reader("gang_input_bytes_per_tick")(observed) == \
        10000.0


def test_gang_shard_cost_and_roofline_at_the_cell_size():
    rows = 1725  # a quarter of 6 900 workers that run no gang
    plain = kernel_cost.cut_scan_cost(B=96, V=1, W=rows, R=3)
    cost = gang_shard_cost.gang_shard_scan_cost(
        B=96, V=1, W=6900, R=3, G=256, D=4, gang_rows=16)
    assert cost["ops"] == plain["ops"] + 16 * (rows * 12 + 512)
    # one idleness mark and one group number a worker, not the one-hot
    assert cost["bytes"] == plain["bytes"] + 4 * (2 * rows + 96)
    assert cost["bytes"] < 4 * rows * 256
    # both gathers: 16 class sums a step, 256 group counts a gang row
    assert cost["ici_bytes"] == 4 * 96 * 16 * 4 + 4 * 16 * 256 * 4
    seconds, bound = gang_shard_cost.least_seconds(cost, "TPU v5 lite")
    assert bound == "bytes" and 5e-7 < seconds < 5e-6
    observed = {
        "extents": {"B": 96, "V": 1, "W": 6900, "R": 3}, "groups": 256,
        "gang_rows": 16, "mesh_devices": 4, "device_kind": "TPU v5 lite",
        "trace": {"kernel_calls": 8, "kernel_s": 8 * 0.01},
    }
    read = manifest.metric_reader("gang_shard_scan_roofline")
    share = read(observed)
    assert share == pytest.approx(100 * seconds / 0.01) and 0 < share < 100
    # neither of the drivers it was joined from passes enough for it
    assert read({k: v for k, v in observed.items() if k != "gang_rows"}) \
        is None
    assert read({k: v for k, v in observed.items() if k != "mesh_devices"}) \
        is None
