"""Makespan oracle: greedy dense scheduler vs the exact MILP model.

Reference: the reference's scheduler quality story rests on its LP-backed
solver (crates/tako/src/internal/scheduler/solver.rs); this experiment
measures how close the TPU greedy cut-scan gets to the scipy-HiGHS exact
MILP on simulated heterogeneous workloads — the published
`stress_dag_makespan_vs_oracle` numbers in BASELINE.json come from these
stored runs (benchmarks/report.py build_published).
"""

import heapq
import os
import sys
from pathlib import Path

import numpy as np

# the simulation solves tiny instances: the host backend is the right one
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tests"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from common import emit  # noqa: E402


def simulate(env, durations):
    """Event-driven execution of the scheduled workload (same harness as
    tests/test_makespan.py simulate)."""
    from hyperqueue_tpu.server import reactor
    from hyperqueue_tpu.server.task import TaskState

    clock = 0.0
    running = []
    n_started = 0

    def start_assigned():
        nonlocal n_started
        for task in env.core.tasks.values():
            if task.state is TaskState.ASSIGNED:
                n_started += 1
                reactor.on_task_running(
                    env.core, env.events, task.task_id, task.instance_id
                )
                heapq.heappush(
                    running, (clock + durations[task.task_id], task.task_id)
                )

    env.schedule()
    start_assigned()
    while running:
        clock, task_id = heapq.heappop(running)
        env.finish(task_id)
        env.schedule()
        start_assigned()
    assert n_started == len(durations), (
        f"only {n_started}/{len(durations)} tasks ever ran"
    )
    return clock


def run_seed(seed: int) -> dict:
    from hyperqueue_tpu.models.milp import MilpModel

    from utils_env import TestEnv

    rng = np.random.default_rng(seed)

    def build(model):
        env = TestEnv(model=model)
        env.worker(cpus=8, gpus=2)
        env.worker(cpus=8)
        env.worker(cpus=4)
        ids = []
        ids += env.submit(n=60, rqv=env.rqv(cpus=1))
        ids += env.submit(n=20, rqv=env.rqv(cpus=4))
        ids += env.submit(n=12, rqv=env.rqv(gpus=1))
        return env, ids

    durations = None
    results = {}
    for name, model in [("greedy", None), ("milp", MilpModel())]:
        env, ids = build(model)
        if durations is None:
            durations = {t: float(rng.uniform(0.2, 2.0)) for t in ids}
        results[name] = simulate(env, durations)
    return {
        "experiment": "makespan-oracle",
        "seed": seed,
        "n_tasks": len(durations),
        "greedy_s": round(results["greedy"], 3),
        "milp_s": round(results["milp"], 3),
        "ratio": round(results["greedy"] / results["milp"], 4),
    }


def main():
    seeds = [int(s) for s in sys.argv[1:]] or [0, 1, 2]
    for seed in seeds:
        emit(run_seed(seed))


if __name__ == "__main__":
    main()
