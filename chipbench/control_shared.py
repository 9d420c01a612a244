"""The controls of a `shared` cell: its comparison has to come out as NOT
correct when what the configuration states is broken.

    python3 chipbench/control_shared.py --workload <cell> --seed <n> [<n> ...]
        [--ticks N] [--control <name>]

As in `control_gang.py`, the plain reference, computed the control's way,
is put in the program's place under the cell's churn and set-up (it
places, reserves and starts; the harness churns what it placed and ends
gangs), and the run's own comparison and audit are made against the
reference as the configuration states it.  No chip takes part.

Each control breaks one thing the configuration states, and has to show in
the number named beside it as well as in `ticks_mismatched`:

- `idle_only` (`reservation_unhonoured`): no reservation at all, the
  semantics of `--gang-drain idle`;
- `feed_reserved` (`reserved_fed`): reserved workers take single-node tasks;
- `lift_each_tick` (`reservation_unhonoured`): a reservation lasts one
  tick, so a drained set is not kept for its gang;
- `any_group` (`gang_split`): reservations and gangs take workers from the
  whole cluster.

`--rows` instead runs the reference alone through the cell's whole set-up
and `--ticks` window ticks and prints, per seed, the dense rows (workers in
no gang) of every window tick at their least: the rehearsal that sizes the
traffic.  Prints one JSON line per seed.  Exit code 0 means the control
FAILED the comparison on every seed, as it has to; 1 means it slipped
through.
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

from chipbench import generate_shared, manifest  # noqa: E402
from chipbench.drivers import shared as shared_driver  # noqa: E402
from chipbench.drivers import tick as tick_driver  # noqa: E402

# control -> (how the stand-in reference is broken, the number it must raise)
CONTROLS = {
    "idle_only": ({"reserve": False}, "reservation_unhonoured"),
    "feed_reserved": ({"feed_reserved": True}, "reserved_fed"),
    "lift_each_tick": ({"lift_each_tick": True}, "reservation_unhonoured"),
    "any_group": ({"groups": "any_group"}, "gang_split"),
    None: ({}, None),
}


def schedule(traffic: dict, n_window: int, scale=None) -> list:
    """The driver's ticks as (share of running tasks that finish, share of
    running gangs that end, gangs arrive) after the first tick: the gangs
    arrive with the first churn, then the settle steps, then the window."""
    steps = []
    for n_ticks, share, gang_share in (scale or {}).get(
            "settle", traffic["settle"]):
        steps += [(float(share), float(gang_share))] * int(n_ticks)
    steps += [(float(traffic["churn_per_tick"]),
               float(traffic["gang_finish_per_tick"]))] * n_window
    return steps


def stand_in_log(cell, seed: int, n_window: int, scale=None, control=None):
    """The plain reference, broken as `control` says, in the program's place
    under the cell's churn: one tick of the filler alone, then the gangs
    arrive, then the settle steps and `n_window` ticks of the window's
    churn.  Returns (world, log, gang_log, resv_log, rq_ids, worker_ids)
    as the driver records them."""
    reference_cls = manifest.reference(cell["config"]["reference"])
    traffic = cell["traffic"]
    world = generate_shared.world(cell["config"], traffic, seed, scale)
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
    log, gang_log, resv_log = [], [], []
    steps = [(float(traffic["churn_per_tick"]), 0.0)] + schedule(
        traffic, n_window, scale)
    for i, (share, gang_share) in enumerate(steps):
        _cells, taken = stand_in.tick()
        assignments = [
            ((1 << 32) | t, worker_ids[stand_in.running[t][0]],
             rq_ids[level // n_p], stand_in.running[t][2])
            for level, ids in taken.items() for t in ids
        ]
        started = [(g, [worker_ids[w] for w in members])
                   for g, members in stand_in.last_gangs]
        resv_log.append({g: [worker_ids[w] for w in rows]
                         for g, rows in stand_in.last_reservations.items()})
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
            if gangs and gang_share else 0
        ended = [gangs[j] for j in gang_rng.choice(
            len(gangs), size=k, replace=False).tolist()] if k else []
        stand_in.finish(finished, ended)
        log.append([assignments, finished])
        gang_log.append([started, ended, arrived])
    return world, log, gang_log, resv_log, rq_ids, worker_ids


def shared_control(cell, seed: int, n_window: int, scale=None,
                   control="idle_only") -> dict:
    """The comparison's and the audit's numbers with the control in the
    program's place."""
    world, log, gang_log, resv_log, rq_ids, worker_ids = stand_in_log(
        cell, seed, n_window, scale, control)
    return {
        **shared_driver.compare_with_reference(
            world, log, gang_log, resv_log, rq_ids, worker_ids,
            manifest.reference(cell["config"]["reference"])),
        **tick_driver.audit_placements(world, log, rq_ids, worker_ids),
        **shared_driver.audit_shared(
            world, log, gang_log, resv_log, worker_ids,
            int(cell["traffic"]["gang_rows_per_tick"])),
    }


def dense_rows(cell, seed: int, n_window: int, scale=None) -> dict:
    """The reference alone through set-up and window: the dense rows
    (workers in no gang) and the gangs started, per window tick."""
    world, _log, gang_log, resv_log, _rq, _w = stand_in_log(
        cell, seed, n_window, scale)
    n_w = world.worker_total.shape[0]
    nodes: dict = {}
    in_gang = 0
    rows, started = [], []
    for started_now, ended, _arrived in gang_log:
        rows.append(n_w - in_gang)          # at the tick's start
        started.append(len(started_now))
        for g, members in started_now:
            nodes[g] = len(members)
            in_gang += len(members)
        in_gang -= sum(nodes.pop(g) for g in ended)
    window = rows[-n_window:]
    reserved = [sum(len(v) for v in r.values()) for r in resv_log[-n_window:]]
    return {"rows_min": min(window), "rows_p50": float(np.median(window)),
            "rows_max": max(window),
            "gangs_started_p50": float(np.median(started[-n_window:])),
            "reserved_p50": float(np.median(reserved)),
            "setup_rows_min": min(rows[:-n_window] or [n_w])}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--ticks", type=int, default=200)
    parser.add_argument("--control", default="idle_only",
                        choices=[c for c in CONTROLS if c])
    parser.add_argument("--rows", action="store_true")
    parser.add_argument("--scale", type=json.loads, default=None)
    args = parser.parse_args(argv)
    cell = manifest.cell(args.workload)
    if cell["traffic"]["driver"] != "shared":
        raise SystemExit("these are the controls of a `shared` cell")
    if args.rows:
        for seed in args.seed:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              **dense_rows(cell, seed, args.ticks,
                                           args.scale)}), flush=True)
        return 0
    number = CONTROLS[args.control][1]
    slipped = 0
    for seed in args.seed:
        numbers = shared_control(cell, seed, args.ticks, args.scale,
                                 args.control)
        caught = numbers[number] > 0 and numbers["ticks_mismatched"] > 0
        slipped += not caught
        print(json.dumps({"control": args.control, "shows_in": number,
                          "workload": args.workload, "seed": seed,
                          "caught": caught, "numbers": numbers}), flush=True)
    return 1 if slipped else 0


if __name__ == "__main__":
    sys.exit(main())
