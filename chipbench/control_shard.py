"""The controls of a `shard` cell: its comparison has to come out as NOT
correct when what the configuration states is broken.

    python3 chipbench/control_shard.py --workload <cell> --seed <n> [<n> ...]
        [--ticks N] [--control all_as_21_cpus|stale_rows]

As in `control.py`, the plain reference, computed the control's way, is put in
the program's place (it places, the harness churns what it placed) and the
run's own comparison is made against the reference as the configuration
states it.  No chip takes part.

- `all_as_21_cpus` breaks the guarantee the configuration adds: a whole-node
  request is read as one for 21 cpus (the class whose place the whole-node
  classes took), so it lands beside other tasks and several fit a worker.
- `stale_rows` is `control.py`'s: the rows a delta upload carries reach the
  solve a tick late.

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

from chipbench import generate_shard, manifest  # noqa: E402
from chipbench.drivers import shard as shard_driver  # noqa: E402
from chipbench.drivers import tick as tick_driver  # noqa: E402

CONTROLS = {
    "all_as_21_cpus": {"whole_node": "as_21_cpus"},
    "stale_rows": {"stale_rows": True},
    None: {},
}


def stand_in_log(cell, seed: int, n_ticks: int, scale=None, control=None):
    """The plain reference, broken as `control` says, in the program's place
    under the cell's churn.  Returns (world, log, rq_ids, worker_ids) as the
    driver records them."""
    reference_cls = manifest.reference(cell["config"]["reference"])
    world = generate_shard.world(cell["config"], cell["traffic"], seed, scale)
    stand_in = reference_cls(world, **CONTROLS[control])
    n_p = world.n_priorities
    rq_ids = list(range(1, world.class_needs.shape[0] + 1))
    worker_ids = list(range(1, world.worker_total.shape[0] + 1))
    level_of = (world.task_class.astype(np.int64) * n_p
                + world.task_prio).tolist()
    rng = np.random.default_rng([int(seed), 9])
    share = float(cell["traffic"]["churn_per_tick"])
    log = []
    for _ in range(n_ticks):
        _cells, taken = stand_in.tick()
        assignments = [
            ((1 << 32) | t, worker_ids[stand_in.running[t][0]],
             rq_ids[level // n_p], stand_in.running[t][2])
            for level, ids in taken.items() for t in ids
        ]
        placed = sorted(t for ids in taken.values() for t in ids)
        new_levels = [level_of[t] for t in placed]
        stand_in.arrive(range(len(level_of), len(level_of) + len(placed)),
                        new_levels)
        level_of.extend(new_levels)
        running = sorted(stand_in.running)
        k = min(len(running), max(1, round(share * len(running))))
        finished = [running[i] for i in
                    rng.choice(len(running), size=k, replace=False).tolist()]
        stand_in.finish(finished)
        log.append([assignments, finished])
    return world, log, rq_ids, worker_ids


def shard_control(cell, seed: int, n_ticks: int, scale=None,
                  control="all_as_21_cpus") -> dict:
    """The comparison's numbers with the control in the program's place."""
    recorded = stand_in_log(cell, seed, n_ticks, scale, control)
    return {
        **tick_driver.compare_with_reference(
            *recorded, manifest.reference(cell["config"]["reference"])),
        **shard_driver.audit_placements(*recorded),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--ticks", type=int, default=60)
    parser.add_argument("--control", default="all_as_21_cpus",
                        choices=("all_as_21_cpus", "stale_rows"))
    parser.add_argument("--scale", type=json.loads, default=None)
    args = parser.parse_args(argv)
    cell = manifest.cell(args.workload)
    if cell["traffic"]["driver"] != "shard":
        raise SystemExit("these are the controls of a `shard` cell")
    slipped = 0
    for seed in args.seed:
        numbers = shard_control(cell, seed, args.ticks, args.scale,
                                args.control)
        caught = numbers["ticks_mismatched"] > 0
        slipped += not caught
        print(json.dumps({"control": args.control, "workload": args.workload,
                          "seed": seed, "caught": caught, "numbers": numbers}),
              flush=True)
    return 1 if slipped else 0


if __name__ == "__main__":
    sys.exit(main())
