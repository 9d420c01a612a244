"""The controls: the cell's comparison has to come out as NOT correct when
what the configuration states is broken.

    python3 chipbench/control.py --workload <cell> --seed <n> [<n> ...]
        [--ticks N] [--control stale_rows|float32_nofix]

The plain reference, computed the control's way, is put in the program's
place (it places, the harness churns what it placed) and the run's own
comparison is made against the exact reference.

- `stale_rows` breaks a guarantee at the place a `tick` cell is about: the
  rows that a delta upload carries reach the solve a tick late (what finished
  since the last tick is not seen), so placements differ on the first tick
  after a task finishes.
- `float32_nofix` is the lower precision.  The configuration states exact
  integer quotients; the kernel takes them as a float32 multiply by the
  reciprocal and corrects the result with integer multiplies.  This is that
  quotient without the correction, the step that would tempt a later PR: on
  this configuration's 21- and 42-cpu requests it reads 0 where 1 fits and
  1 where 2 fit.  (The same in bfloat16 reads exact here, PERF.md section 4:
  rounding to 8 bits happens to land on the whole number.)

No chip takes part in a `tick` cell's controls; run on the chip's machine they
show at the cell's own size on that machine's numpy.

`sim` cells: the control breaks the configuration's guarantee underneath the
real server: every `--every`-th finished task is recorded as finished a second
time in the journal.  That needs the chip (`--scheduler tpu`); `--rehearse`
runs it on the host solve.

Prints one JSON line per seed with the numbers compared.  Exit code 0 means
the control FAILED the comparison on every seed, as it has to; 1 means it
slipped through.
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

from chipbench import generate, manifest  # noqa: E402
from chipbench import run as run_py  # noqa: E402
from chipbench.drivers import tick as tick_driver  # noqa: E402


def stand_in_log(cell, seed: int, n_ticks: int, scale=None,
                 control="stale_rows"):
    """The plain reference in the program's place, under the cell's churn.
    Returns (world, log, rq_ids, worker_ids) as the tick driver records
    them."""
    reference_cls = manifest.reference(cell["config"]["reference"])
    world = generate.world(cell["config"], cell["traffic"], seed, scale)
    stand_in = reference_cls(
        world, stale_rows=control == "stale_rows",
        capacity="float32_nofix" if control == "float32_nofix" else "exact",
    )
    n_p = world.n_priorities
    n_w = world.worker_total.shape[0]
    rq_ids = list(range(1, world.class_needs.shape[0] + 1))
    worker_ids = list(range(1, n_w + 1))
    level_of = (world.task_class.astype(np.int64) * n_p
                + world.task_prio).tolist()
    rng = np.random.default_rng([int(seed), 9])
    share = float(cell["traffic"]["churn_per_tick"])
    log = []
    for _ in range(n_ticks):
        cells, taken = stand_in.tick()
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


def tick_control(cell, seed: int, n_ticks: int, scale=None,
                 control="stale_rows") -> dict:
    """The comparison's numbers with the control in the program's place."""
    recorded = stand_in_log(cell, seed, n_ticks, scale, control)
    return {
        **tick_driver.compare_with_reference(
            *recorded, manifest.reference(cell["config"]["reference"])),
        **tick_driver.audit_placements(*recorded),
    }


def sim_control(cell, seed: int, seconds: float, every: int, rehearse: bool,
                scale=None) -> dict:
    """The served cell with every `every`-th completion journaled twice."""
    from hyperqueue_tpu.server import reactor

    ctx = run_py.Context(cell, seed, seconds, False, rehearse, scale)
    if not rehearse:
        devices, _cache = run_py.find_chips(cell["chips"])
        ctx.device = devices[0]
        ctx.compiles.listen()
    original = reactor.on_task_finished
    seen = {"n": 0}

    def twice(core, comm, events, task_id, instance_id, wtrace=None):
        task = core.tasks.get(task_id)
        live = (task is not None and task.instance_id == instance_id
                and not task.is_done)
        original(core, comm, events, task_id, instance_id, wtrace)
        if live:
            seen["n"] += 1
            if seen["n"] % every == 0:
                # the durable record says this task finished a second time
                events.on_task_finished(task_id, wtrace=wtrace)

    reactor.on_task_finished = twice
    try:
        outcome = manifest.driver("sim")(ctx)
    finally:
        reactor.on_task_finished = original
    return {name: value for name, value, _limit in outcome["checks"]}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--ticks", type=int, default=300)
    parser.add_argument("--control", default="stale_rows",
                        choices=("stale_rows", "float32_nofix"))
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--every", type=int, default=500)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--scale", type=json.loads, default=None)
    args = parser.parse_args(argv)
    cell = manifest.cell(args.workload)
    driver = cell["traffic"]["driver"]
    if driver not in ("tick", "sim"):
        raise SystemExit(f"no control for driver {driver!r}")
    if driver == "sim" and len(args.seed) > 1:
        raise SystemExit("a sim control holds the chip: one seed a process")
    slipped = 0
    for seed in args.seed:
        if driver == "tick":
            numbers = tick_control(cell, seed, args.ticks, args.scale,
                                   args.control)
            caught = numbers["ticks_mismatched"] > 0
        else:
            numbers = sim_control(cell, seed, args.seconds, args.every,
                                  args.rehearse, args.scale)
            caught = numbers["finished_twice"] > 0
        slipped += not caught
        print(json.dumps({"control": args.control if driver == "tick"
                          else "finished_twice", "workload": args.workload,
                          "seed": seed,
                          "caught": caught, "numbers": numbers}), flush=True)
    return 1 if slipped else 0


if __name__ == "__main__":
    sys.exit(main())
