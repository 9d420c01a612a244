"""`correct` has to be able to come out false.

The controls at a size a test run can hold; for each fault a cell can have,
a whole run (everything but the harness's look for a chip) with the timed
path broken underneath; and each number of the audit of the program's own
placements shown to fail on a record altered by hand.
"""

import json

import pytest

from chipbench import control, manifest
from chipbench import run as run_py
from chipbench.drivers import tick as tick_driver

TICK = "hetero-1k.backlog-1m"
SIM = "flat-1k.drain"
SIM_SCALE = {"workers": 32, "tasks": 100000}
TICK_SCALE = {"workers": 64, "ready_tasks": 20000, "settle": [[10, 0.01]]}
MASK = tick_driver.TASK_MASK


def rehearse(capsys, workload, scale, seconds=1.0, seed=11):
    """A run on the host backends; returns its last line."""
    run_py.main(["--workload", workload, "--seed", str(seed), "--seconds",
                 str(seconds), "--rehearse", "--scale", json.dumps(scale)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,scale", [(TICK, TICK_SCALE),
                                            (SIM, SIM_SCALE)])
def test_sound_run_is_correct(capsys, workload, scale):
    line = rehearse(capsys, workload, scale)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(c["value"] == 0 for c in line["checks"].values())


@pytest.mark.parametrize("which", ["stale_rows", "float32_nofix"])
def test_tick_control_fails(which):
    numbers = control.tick_control(manifest.cell(TICK), seed=3, n_ticks=15,
                                   scale=TICK_SCALE, control=which)
    assert numbers["ticks_mismatched"] > 0


def test_sim_control_fails():
    numbers = control.sim_control(manifest.cell(SIM), seed=3, seconds=1.0,
                                  every=50, rehearse=True, scale=SIM_SCALE)
    assert numbers["finished_twice"] > 0


# -- faults planted under a whole run of the tick cell ----------------------
def _state_unchanged(real):
    def run_tick(queues, *args, **kwargs):
        # solves, drops every placement and puts the tasks back
        for task_id, _w, rq_id, _v in real(queues, *args, **kwargs):
            queues.add(rq_id, (0, 0), task_id)
        return []
    return run_tick


def _half_the_batches(real):
    def create_batches(queues):
        batches = real(queues)
        return batches[1::2]
    return create_batches


def _answer_altered(real):
    def run_tick(queues, workers, rq_map, resource_map, model, **kwargs):
        out = real(queues, workers, rq_map, resource_map, model, **kwargs)
        if len(out) >= 2 and out[0][1] != out[-1][1]:
            task_id, _worker, rq_id, variant = out[0]
            out[0] = (task_id, out[-1][1], rq_id, variant)
        return out
    return run_tick


@pytest.mark.parametrize("name,wrap", [
    ("run_tick", _state_unchanged),
    ("create_batches", _half_the_batches),
    ("run_tick", _answer_altered),
], ids=["state-unchanged", "half-the-batches", "answer-altered"])
def test_tick_fault_is_not_correct(capsys, monkeypatch, name, wrap):
    from hyperqueue_tpu.scheduler import tick

    monkeypatch.setattr(tick, name, wrap(getattr(tick, name)))
    line = rehearse(capsys, TICK, TICK_SCALE)
    assert line["correct"] is False, line
    assert line["checks"]["ticks_mismatched"]["value"] > 0


# -- faults planted under a whole run of the served cell ---------------------
def test_sim_state_unchanged_never_saturates(capsys, monkeypatch):
    """A tick that places nothing: no task ever runs, and the run ends
    without a result."""
    from hyperqueue_tpu.server import reactor

    monkeypatch.setattr(reactor, "run_tick", _state_unchanged(reactor.run_tick))
    with pytest.raises((SystemExit, Exception)):
        rehearse(capsys, SIM, SIM_SCALE)


def test_sim_answer_altered_is_not_correct(capsys, monkeypatch):
    """A task's completion recorded twice where it is produced."""
    from hyperqueue_tpu.server import reactor

    real = reactor.on_task_finished
    seen = {"n": 0}

    def twice(core, comm, events, task_id, instance_id, wtrace=None):
        real(core, comm, events, task_id, instance_id, wtrace)
        seen["n"] += 1
        if seen["n"] % 40 == 0:
            events.on_task_finished(task_id, wtrace=wtrace)
    monkeypatch.setattr(reactor, "on_task_finished", twice)
    line = rehearse(capsys, SIM, SIM_SCALE)
    assert line["correct"] is False, line
    assert line["checks"]["finished_twice"]["value"] > 0


# -- the audit of the program's own placements -------------------------------
@pytest.fixture(scope="module")
def sound_record():
    return control.stand_in_log(manifest.cell(TICK), seed=5, n_ticks=12,
                                scale=TICK_SCALE, control=None)


def _copy(log):
    return [[list(a), list(f)] for a, f in log]


def test_audit_passes_a_sound_record(sound_record):
    world, log, rq_ids, worker_ids = sound_record
    numbers = tick_driver.audit_placements(world, log, rq_ids, worker_ids)
    assert numbers == {"rows_overcommitted": 0, "tasks_out_of_order": 0,
                       "priority_inversions": 0, "answers_unknown": 0}


def _levels(world):
    return (world.task_class.astype(int) * world.n_priorities
            + world.task_prio).tolist()


def _overcommitted(world, log):
    """Every placement of the fill tick lands on one worker."""
    log[0][0] = [(t, log[0][0][0][1], rq, v) for t, _w, rq, v in log[0][0]]
    return "rows_overcommitted"


def _youngest_first(world, log):
    """A placed task gives way to the youngest waiting task of its level."""
    levels = _levels(world)
    task_id, worker, rq, v = log[0][0][0]
    level = levels[task_id & MASK]
    youngest = max(t for t, lv in enumerate(levels) if lv == level)
    log[0][0][0] = ((1 << 32) | youngest, worker, rq, v)
    log[0][1] = [t for t in log[0][1] if t != task_id & MASK]
    return "tasks_out_of_order"


def _lower_level_served(world, log):
    """A placed task gives way to the oldest task a level below it."""
    levels = _levels(world)
    for i, (task_id, worker, rq, v) in enumerate(log[0][0]):
        level = levels[task_id & MASK]
        if level % world.n_priorities:
            lower = levels.index(level - 1)
            log[0][0][i] = ((1 << 32) | lower, worker, rq, v)
            log[0][1] = [t for t in log[0][1] if t != task_id & MASK]
            return "priority_inversions"
    raise AssertionError("the fill tick placed nothing above level 0")


def _task_that_never_was(world, log):
    task_id, worker, rq, v = log[0][0][0]
    log[0][0][0] = ((1 << 32) | (len(world.task_class) + 10**6), worker, rq, v)
    return "answers_unknown"


@pytest.mark.parametrize("alter", [_overcommitted, _youngest_first,
                                   _lower_level_served, _task_that_never_was])
def test_audit_fails_an_altered_record(sound_record, alter):
    world, log, rq_ids, worker_ids = sound_record
    log = _copy(log)
    number = alter(world, log)
    numbers = tick_driver.audit_placements(world, log, rq_ids, worker_ids)
    assert numbers[number] > 0, numbers
