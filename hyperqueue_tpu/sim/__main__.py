"""``python -m hyperqueue_tpu.sim`` — seed-reproducible cluster scenarios.

Run a synthetic workload under a seeded fault schedule on the virtual
clock, with invariants checked throughout::

    python -m hyperqueue_tpu.sim --seed 7
    python -m hyperqueue_tpu.sim --seed 7 --workload bursty --workers 64 \
        --tasks 20000 --fault-rate 0.05 --server-kills 2

On an invariant violation the harness re-runs the scenario with binary-
searched fault-schedule prefixes to find the minimal failing prefix and
prints the one-line repro.  ``--replay JOURNAL --compare-scheduler S``
drives the journal-replay regression mode instead.

For cross-invocation bit-reproducibility set ``PYTHONHASHSEED`` (a few
str-set iteration orders inside the server depend on it).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import sys
from collections import Counter


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m hyperqueue_tpu.sim",
        description="deterministic cluster simulator (virtual clock, "
                    "seeded faults, invariant checking)",
    )
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--workload", default="uniform",
                        help="uniform | bursty | dag | gang | tail")
    parser.add_argument("--workers", type=int, default=32)
    parser.add_argument("--worker-cpus", type=int, default=4)
    parser.add_argument("--tasks", type=int, default=2000,
                        help="task count for sized workloads")
    parser.add_argument("--dur-ms", type=float, default=1000.0,
                        help="median task duration (uniform workload)")
    parser.add_argument("--fault-rate", type=float, default=0.0,
                        help="faults per worker-second; 0 = fault-free")
    parser.add_argument("--server-kills", type=int, default=1,
                        help="server kill -9 + restore events in the "
                             "schedule (with --fault-rate > 0)")
    parser.add_argument("--horizon", type=float, default=None,
                        help="virtual deadline (default: auto)")
    parser.add_argument("--scheduler", default="greedy-numpy")
    parser.add_argument("--no-bisect", action="store_true",
                        help="skip minimal-prefix bisection on failure")
    parser.add_argument("--replay", metavar="JOURNAL",
                        help="journal-replay mode: rebuild the workload "
                             "from this journal")
    parser.add_argument("--compare-scheduler", default=None,
                        help="with --replay: run twice and compare "
                             "makespan/decisions between --scheduler and "
                             "this one")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="machine-readable result line")
    parser.add_argument("-v", "--verbose", action="store_true")
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO if args.verbose else logging.ERROR,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )

    from hyperqueue_tpu.sim import (
        FaultSchedule,
        InvariantViolation,
        SimDeadlockError,
        Simulation,
        bisect_failure,
        build,
        run_scenario,
    )

    if args.replay:
        from hyperqueue_tpu.sim.replay import (
            replay_compare,
            workload_from_journal,
        )

        if args.compare_scheduler:
            cmp_result = replay_compare(
                args.replay, args.scheduler, args.compare_scheduler,
                seed=args.seed, n_workers=args.workers,
            )
            print(cmp_result.summary())
            return 0
        workload = workload_from_journal(args.replay)
    else:
        sizing = {
            "uniform": {"n_tasks": args.tasks, "dur_ms": args.dur_ms},
            "bursty": {"tasks_per_burst": max(args.tasks // 12, 1)},
            "dag": {"width": max(args.tasks // 12, 4)},
            "gang": {"filler_tasks": args.tasks},
            "tail": {"n_tasks": args.tasks},
        }.get(args.workload, {})
        workload = build(args.workload, seed=args.seed, **sizing)

    worker_names = [f"w{i}" for i in range(args.workers)]
    faults = None
    if args.fault_rate > 0:
        # a rough virtual-makespan guess keeps faults inside the run
        guess = max(
            workload.horizon_hint + args.tasks * args.dur_ms
            / 1e3 / max(args.workers * args.worker_cpus, 1), 30.0,
        )
        faults = FaultSchedule.generate(
            args.seed, horizon=guess, worker_names=worker_names,
            rate=args.fault_rate, server_kills=args.server_kills,
        )

    def make_sim(schedule):
        return Simulation(
            workload, seed=args.seed, n_workers=args.workers,
            worker_cpus=args.worker_cpus, faults=schedule,
            scheduler=args.scheduler, horizon=args.horizon,
        )

    try:
        result = run_scenario(
            workload, seed=args.seed, n_workers=args.workers,
            worker_cpus=args.worker_cpus, faults=faults,
            scheduler=args.scheduler, horizon=args.horizon,
        )
    except (InvariantViolation, SimDeadlockError, TimeoutError,
            asyncio.TimeoutError) as e:  # asyncio alias != builtin on 3.10
        print(f"FAIL: {e}", file=sys.stderr)
        if faults is not None and not args.no_bisect and len(faults):
            k, prefix = bisect_failure(make_sim, faults)
            print(f"minimal failing fault prefix: {k} event(s)",
                  file=sys.stderr)
            for line in prefix:
                print(f"  {line}", file=sys.stderr)
        print(
            "repro: python -m hyperqueue_tpu.sim "
            f"--seed {args.seed} --workload {args.workload} "
            f"--workers {args.workers} --tasks {args.tasks} "
            f"--fault-rate {args.fault_rate} "
            f"--server-kills {args.server_kills}",
            file=sys.stderr,
        )
        return 1

    if args.as_json:
        # which backend solved each tick, and how each solve ended: a run
        # meant for the device shows here every tick the host took instead
        solves = [d["solver"] for d in result.decisions if d.get("solver")]
        print(json.dumps({
            "seed": result.seed,
            "workload": result.workload,
            "n_tasks": result.n_tasks,
            "makespan_virtual_s": round(result.makespan, 3),
            "wall_s": round(result.wall_s, 3),
            "virtual_tasks_per_wall_s": round(
                result.virtual_tasks_per_wall_s, 1
            ),
            "server_boots": result.server_boots,
            "audit": result.audit,
            "solves_by_backend": dict(
                Counter(str(v.get("backend")) for v in solves)
            ),
            "solves_by_status": dict(
                Counter(str(v.get("status")) for v in solves)
            ),
            "decision_digest": result.decision_digest,
            "journal_digest": result.journal_digest,
        }))
    else:
        print(
            f"OK seed={result.seed} workload={result.workload} "
            f"tasks={result.n_tasks} finished={result.audit['finished']} "
            f"makespan={result.makespan:.1f}s(virtual) "
            f"wall={result.wall_s:.2f}s boots={result.server_boots} "
            f"executions={result.audit['executions']}"
        )
        print(f"decision digest {result.decision_digest[:16]}… "
              f"journal digest {result.journal_digest[:16]}…")
    return 0


if __name__ == "__main__":
    sys.exit(main())
