"""The controls of a `gang` cell: its comparison has to come out as NOT
correct when what the configuration states is broken.

    python3 chipbench/control_gang.py --workload <cell> --seed <n> [<n> ...]
        [--ticks N] [--control <name>]

As in `control.py`, the plain reference, computed the control's way, is put in
the program's place (it places and starts gangs, the harness churns what it
placed and ends gangs) and the run's own comparison and audit are made
against the reference as the configuration states it.  No chip takes part.

Each control breaks one thing the configuration states, and has to show in
the number named beside it:

- `any_group` (`gang_split`): a gang's workers are taken from the whole
  cluster, so they lie in several groups;
- `busy_members` (`gang_shared`): a gang may take workers that run tasks;
- `skip_head` (`gang_overtaken`): the queue's first gang is never offered to
  a tick, and gangs no smaller start past it;
- `no_hold`, `late_gang_ends`, `stale_rows` (`ticks_mismatched`): a gang that
  cannot start holds nothing; a finished gang's workers are freed a tick
  late; what finished since the last tick is not seen (`control.py`'s).

Prints one JSON line per seed.  Exit code 0 means the control FAILED the
comparison on every seed, as it has to; 1 means it slipped through.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import generate_gang, manifest  # noqa: E402
from chipbench.drivers import gang as gang_driver  # noqa: E402
from chipbench.drivers import tick as tick_driver  # noqa: E402

# control -> (how the stand-in reference is broken, the number it must raise)
CONTROLS = {
    "any_group": ({"groups": "any_group"}, "gang_split"),
    "busy_members": ({"idle_only": False}, "gang_shared"),
    "skip_head": ({"skip_head": True}, "gang_overtaken"),
    "no_hold": ({"hold": False}, "ticks_mismatched"),
    "late_gang_ends": ({"late_gang_ends": True}, "ticks_mismatched"),
    "stale_rows": ({"stale_rows": True}, "ticks_mismatched"),
    None: ({}, None),
}


def stand_in_log(cell, seed: int, n_ticks: int, scale=None, control=None):
    """The plain reference, broken as `control` says, in the program's place
    under the cell's churn: one tick of the filler alone, then the gangs
    arrive.  Returns (world, log, gang_log, rq_ids, worker_ids) as the
    driver records them."""
    reference_cls = manifest.reference(cell["config"]["reference"])
    traffic = cell["traffic"]
    world = generate_gang.world(cell["config"], traffic, seed, scale)
    stand_in = reference_cls(world, **CONTROLS[control][0])
    stand_in.gang_queue.clear()   # they arrive after the first tick
    stand_in.n_gangs = 0
    n_p = world.n_priorities
    rq_ids = list(range(1, world.class_needs.shape[0] + 1))
    worker_ids = list(range(1, world.worker_total.shape[0] + 1))
    level_of = (world.task_class.astype(np.int64) * n_p
                + world.task_prio).tolist()
    gang_nodes: list = []
    rng = np.random.default_rng([int(seed), 9])
    gang_rng = np.random.default_rng([int(seed), 10])
    share = float(traffic["churn_per_tick"])
    gang_share = float(traffic["gang_finish_per_tick"])
    log, gang_log = [], []
    for i in range(n_ticks):
        _cells, taken = stand_in.tick()
        assignments = [
            ((1 << 32) | t, worker_ids[stand_in.running[t][0]],
             rq_ids[level // n_p], stand_in.running[t][2])
            for level, ids in taken.items() for t in ids
        ]
        started = [(g, [worker_ids[w] for w in members])
                   for g, members in stand_in.last_gangs]
        placed = sorted(t for ids in taken.values() for t in ids)
        new_levels = [level_of[t] for t in placed]
        arrived = [gang_nodes[g] for g, _m in started]
        if i == 0:
            arrived += world.gang_nodes.tolist()
        gang_nodes.extend(arrived)
        stand_in.arrive(range(len(level_of), len(level_of) + len(placed)),
                        new_levels, arrived)
        level_of.extend(new_levels)
        running = sorted(stand_in.running)
        k = min(len(running), max(1, round(share * len(running))))
        finished = [running[j] for j in
                    rng.choice(len(running), size=k, replace=False).tolist()]
        gangs = sorted(stand_in.running_gangs)
        k = min(len(gangs), max(1, round(gang_share * len(gangs)))) \
            if gangs else 0
        ended = [gangs[j] for j in gang_rng.choice(
            len(gangs), size=k, replace=False).tolist()] if k else []
        stand_in.finish(finished, ended)
        log.append([assignments, finished])
        gang_log.append([started, ended, arrived])
    return world, log, gang_log, rq_ids, worker_ids


def gang_control(cell, seed: int, n_ticks: int, scale=None,
                 control="any_group") -> dict:
    """The comparison's and the audit's numbers with the control in the
    program's place."""
    world, log, gang_log, rq_ids, worker_ids = stand_in_log(
        cell, seed, n_ticks, scale, control)
    return {
        **gang_driver.compare_with_reference(
            world, log, gang_log, rq_ids, worker_ids,
            manifest.reference(cell["config"]["reference"])),
        **tick_driver.audit_placements(world, log, rq_ids, worker_ids),
        **gang_driver.audit_gangs(
            world, log, gang_log, worker_ids,
            int(cell["traffic"]["gang_rows_per_tick"])),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--ticks", type=int, default=60)
    parser.add_argument("--control", default="any_group",
                        choices=[c for c in CONTROLS if c])
    parser.add_argument("--scale", type=json.loads, default=None)
    args = parser.parse_args(argv)
    cell = manifest.cell(args.workload)
    if cell["traffic"]["driver"] != "gang":
        raise SystemExit("these are the controls of a `gang` cell")
    number = CONTROLS[args.control][1]
    slipped = 0
    for seed in args.seed:
        numbers = gang_control(cell, seed, args.ticks, args.scale,
                               args.control)
        caught = numbers[number] > 0 and numbers["ticks_mismatched"] > 0
        slipped += not caught
        print(json.dumps({"control": args.control, "shows_in": number,
                          "workload": args.workload, "seed": seed,
                          "caught": caught, "numbers": numbers}), flush=True)
    return 1 if slipped else 0


if __name__ == "__main__":
    sys.exit(main())
