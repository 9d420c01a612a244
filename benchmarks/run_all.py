"""Run the full benchmark matrix into the result database.

Reference: benchmarks/src/benchmark/runner.py — iterates BenchmarkIdentifiers,
skipping those the Database already has a record for under the current
revision (`has_record_for` resume), so an interrupted matrix picks up where
it left off.

Usage:
    python benchmarks/run_all.py            # full matrix, resume-aware
    python benchmarks/run_all.py --fresh    # ignore existing records
    python benchmarks/run_all.py --only per-task-overhead
"""

from __future__ import annotations

import argparse
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from database import Database, current_git_rev  # noqa: E402

# (experiment name in the db, script, argv, params-for-resume-check, timeout_s)
# params must match what the script's emit() will store as config for the
# resume check to hit; scripts that emit several records list each config.
MATRIX = [
    ("per-task-overhead", "experiment_per_task_overhead.py", ["10000"],
     [{"n_tasks": 10000, "n_workers": 1, "reference_claim_ms": 0.1}], 900),
    ("per-task-overhead", "experiment_per_task_overhead.py", ["50000"],
     [{"n_tasks": 50000, "n_workers": 1, "reference_claim_ms": 0.1}], 1800),
    ("per-task-overhead", "experiment_per_task_overhead.py", ["200000"],
     [{"n_tasks": 200000, "n_workers": 1, "reference_claim_ms": 0.1}], 1800),
    ("per-task-overhead", "experiment_per_task_overhead.py", ["1000000"],
     [{"n_tasks": 1000000, "n_workers": 1, "reference_claim_ms": 0.1}], 3600),
    # multi-worker sweep (VERDICT r5 missing #3): same task count, 1-16
    # local workers
    ("per-task-overhead", "experiment_per_task_overhead.py",
     ["50000", "2", "4", "8", "16"],
     [{"n_tasks": 50000, "n_workers": w, "reference_claim_ms": 0.1}
      for w in (2, 4, 8, 16)], 3600),
    ("scalability", "experiment_scalability.py", [],
     [{"n_tasks": 2000, "n_workers": w} for w in (1, 2, 4)], 900),
    ("fractional-resources", "experiment_fractional_resources.py", [],
     [{"n_tasks": 2000, "gpu_share": 0.25}], 600),
    ("alternative-resources", "experiment_alternative_resources.py", [],
     [{"n_tasks": 1000}], 600),
    ("numa-coupling", "experiment_numa.py", [],
     [{"n_tasks": 2000}], 600),
    ("encryption-overhead", "experiment_encryption_overhead.py", [],
     [{"n_tasks": 30000}], 900),
    ("io-streaming", "experiment_io_streaming.py", [],
     [{"n_tasks": 2000}], 600),
    ("server-cpu-util", "experiment_server_cpu_util.py", [],
     [{"n_tasks": 50000}], 1800),
    ("stress-dag", "experiment_stress_dag.py", [],
     [{"n_tasks": 2000, "n_layers": 20, "width": 100,
       "shape": "layered"}], 900),
    # >=10k tasks, two DAG shapes (VERDICT r5 weak #5)
    ("stress-dag", "experiment_stress_dag.py",
     ["100", "100", "layered", "diamond"],
     [{"n_tasks": 10000, "n_layers": 100, "width": 100,
       "shape": "layered"},
      {"n_tasks": 10200, "n_layers": 100, "width": 100,
       "shape": "diamond"}], 1800),
    ("total-overhead", "experiment_total_overhead.py", [],
     [{"n_tasks": 1000, "sleep_ms": 10.0}], 600),
    ("dask-comparison", "experiment_dask_comparison.py", [],
     [{"n_tasks": 200, "cores": 4}, {"n_tasks": 1000, "cores": 4},
      {"n_tasks": 5000, "cores": 8}], 1800),
    ("makespan-oracle", "experiment_makespan_oracle.py", ["0", "1", "2"],
     [{"seed": s} for s in (0, 1, 2)], 900),
]


def covered(db: Database, experiment: str, param_sets: list[dict],
            rev: str) -> bool:
    """True when every config this invocation would produce already has a
    record under `rev`.  Configs are matched loosely (subset of stored
    params) because emit() records more config keys than the matrix lists."""
    hits = db.query(experiment, git_rev=rev)
    for want in param_sets:
        ok = any(
            all(str(r.params.get(k)) == str(v) for k, v in want.items())
            for r in hits
        )
        if not ok:
            return False
    return True


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--fresh", action="store_true",
                        help="re-run even when records exist for this rev")
    parser.add_argument("--only", help="run only this experiment")
    args = parser.parse_args()

    rev = current_git_rev()
    db = Database()
    failures = []
    for experiment, script, argv, param_sets, timeout in MATRIX:
        if args.only and experiment != args.only:
            continue
        if not args.fresh and covered(db, experiment, param_sets, rev):
            print(f"-- {experiment} {argv}: covered at {rev}, skipping")
            continue
        print(f"== {experiment} {argv} (timeout {timeout}s)")
        t0 = time.time()
        try:
            # experiments measure the host product path, so each child
            # runs on the CPU backend unless told otherwise; this parent
            # never touches jax, so a child that is told otherwise finds
            # the chip free
            import os

            env = {**os.environ}
            env.setdefault("JAX_PLATFORMS", "cpu")
            proc = subprocess.run(
                [sys.executable, str(HERE / script), *argv],
                cwd=HERE, timeout=timeout, env=env,
            )
            status = "ok" if proc.returncode == 0 else f"exit {proc.returncode}"
        except subprocess.TimeoutExpired:
            status = "TIMEOUT"
        if status != "ok":
            failures.append((experiment, argv, status))
        print(f"   {status} in {time.time() - t0:.0f}s")
        db._records = None  # new records were appended by the child
    if failures:
        print(f"\n{len(failures)} failures: {failures}")
        return 1
    print("\nmatrix complete; regenerate BASELINE.json with "
          "`python benchmarks/report.py baseline`")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
