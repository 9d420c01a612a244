"""The controls of a `gang_shard` cell: its comparison has to come out as NOT
correct when what the configuration states is broken.

    python3 chipbench/control_gang_shard.py --workload <cell> --seed <n> [<n> ...]
        [--ticks N] [--control <name>]

`control_gang.py`'s controls, called with this cell's world (the plain
reference knows no mesh, so they are the same controls at another width), and
one more, which only a sharded solve can commit:

- `local_groups` (`gang_split`): each shard selects among its own rows alone
  (`reference/gang_local_groups.py`), so a gang whose group has eligible
  workers on two shards gets members from both, more than its n.

At the cell's own size the gangs' fill takes some 93 ticks in which every
gang starts and nothing is held; `no_hold` shows once a gang cannot start
(from tick 114), `local_groups` when the fill's front crosses a shard boundary
(tick 61) and after that whenever a straddling group is chosen: hence 160
ticks by default.

Prints one JSON line per seed.  Exit code 0 means the control FAILED the
comparison on every seed, as it has to; 1 means it slipped through.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import control_gang, manifest  # noqa: E402
from chipbench.drivers import gang_shard as gang_shard_driver  # noqa: E402
from chipbench.drivers import tick as tick_driver  # noqa: E402

LOCAL_GROUPS = "local_groups"
LOCAL_GROUPS_REFERENCE = "gang_local_groups"
# control -> the number it must raise (besides `ticks_mismatched`)
CONTROLS = {**{name: number for name, (_how, number)
               in control_gang.CONTROLS.items()},
            LOCAL_GROUPS: "gang_split"}


def stand_in_log(cell, seed: int, n_ticks: int, scale=None, control=None):
    """`control_gang.stand_in_log`; for `local_groups` the stand-in is the
    reference that selects shard by shard."""
    if control != LOCAL_GROUPS:
        return control_gang.stand_in_log(cell, seed, n_ticks, scale, control)
    broken = copy.deepcopy(cell)
    broken["config"]["reference"] = LOCAL_GROUPS_REFERENCE
    return control_gang.stand_in_log(broken, seed, n_ticks, scale, None)


def gang_shard_control(cell, seed: int, n_ticks: int, scale=None,
                       control=LOCAL_GROUPS) -> dict:
    """The comparison's and the audit's numbers with the control in the
    program's place."""
    world, log, gang_log, rq_ids, worker_ids = stand_in_log(
        cell, seed, n_ticks, scale, control)
    return {
        **gang_shard_driver.compare_with_reference(
            world, log, gang_log, rq_ids, worker_ids,
            manifest.reference(cell["config"]["reference"])),
        **tick_driver.audit_placements(world, log, rq_ids, worker_ids),
        **gang_shard_driver.audit_gangs(
            world, log, gang_log, worker_ids,
            int(cell["traffic"]["gang_rows_per_tick"])),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--ticks", type=int, default=160)
    parser.add_argument("--control", default=LOCAL_GROUPS,
                        choices=[c for c in CONTROLS if c])
    parser.add_argument("--scale", type=json.loads, default=None)
    args = parser.parse_args(argv)
    cell = manifest.cell(args.workload)
    if cell["traffic"]["driver"] != "gang_shard":
        raise SystemExit("these are the controls of a `gang_shard` cell")
    number = CONTROLS[args.control]
    slipped = 0
    for seed in args.seed:
        numbers = gang_shard_control(cell, seed, args.ticks, args.scale,
                                     args.control)
        caught = numbers[number] > 0 and numbers["ticks_mismatched"] > 0
        slipped += not caught
        print(json.dumps({"control": args.control, "shows_in": number,
                          "workload": args.workload, "seed": seed,
                          "caught": caught, "numbers": numbers}), flush=True)
    return 1 if slipped else 0


if __name__ == "__main__":
    sys.exit(main())
