"""Soak: concurrent clients + worker churn against one server.

A fast race-shaker (reference stresses this shape via
its experiment-scalability-stress.py benchmark and tests killing workers):
many interleaved submits from parallel client processes while workers die
and rejoin mid-flight; every job must still converge, with crash retries
absorbing the churn.
"""

import json
import subprocess
import sys

import pytest

from utils_e2e import HqEnv, _env_base, wait_until

N_JOBS = 12
TASKS_PER_JOB = 20


@pytest.fixture
def env(tmp_path):
    with HqEnv(tmp_path) as e:
        yield e


def test_soak_concurrent_clients_and_worker_churn(env):
    env.start_server()
    for _ in range(3):
        env.start_worker(cpus=4)
    env.wait_workers(3)

    # N_JOBS submits racing from parallel client processes
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "hyperqueue_tpu", "submit",
             "--name", f"soak-{i}", "--array", f"1-{TASKS_PER_JOB}",
             "--", "bash", "-c", "sleep 0.0$((RANDOM % 5)); true"],
            env={**_env_base(), "HQ_SERVER_DIR": str(env.server_dir)},
            cwd=env.work_dir,
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        )
        for i in range(N_JOBS)
    ]
    # churn: kill a worker while submits are in flight, twice, replacing it
    env.kill_process("worker0")
    env.start_worker(cpus=4)
    for p in procs[: N_JOBS // 2]:
        assert p.wait(timeout=60) == 0, p.stderr.read()
    env.kill_process("worker1")
    env.start_worker(cpus=4)
    for p in procs[N_JOBS // 2:]:
        assert p.wait(timeout=60) == 0, p.stderr.read()

    env.command(["job", "wait", "all"], timeout=90)
    jobs = json.loads(
        env.command(["job", "list", "--all", "--output-mode", "json"])
    )
    assert len(jobs) == N_JOBS
    assert all(j["status"] == "finished" for j in jobs), [
        (j["id"], j["status"]) for j in jobs
    ]
    assert sum(j["counters"]["finished"] for j in jobs) == N_JOBS * TASKS_PER_JOB

    # the server survived the churn with a consistent core
    dump = json.loads(env.command(["server", "debug-dump"]))
    assert dump["tasks"]["by_state"].get("finished", 0) == N_JOBS * TASKS_PER_JOB
    assert dump["tasks"]["ready_queued"] == 0


def test_journal_restore_under_churn(env, tmp_path):
    """Kill the server MID-CHURN (workers dying, submits racing) and
    restore from the journal: no finished work re-runs, pending work
    completes, ids continue where they left off."""
    journal = tmp_path / "journal.bin"
    env.start_server("--journal", str(journal))
    for _ in range(2):
        env.start_worker(cpus=4)
    env.wait_workers(2)

    env.command(["submit", "--array", "1-10", "--", "true"])
    env.command(["job", "wait", "1"])
    # a slow job that will straddle the crash
    env.command(["submit", "--array", "1-8",
                 "--", "bash", "-c", "sleep 0.4"])
    env.kill_process("worker0")   # churn while job 2 runs
    env.kill_process("server")    # hard-kill: journal replay must cope

    env.start_server("--journal", str(journal))
    env.start_worker(cpus=4)
    env.command(["job", "wait", "2"], timeout=60)
    jobs = json.loads(
        env.command(["job", "list", "--all", "--output-mode", "json"])
    )
    assert {j["id"] for j in jobs} == {1, 2}
    by_id = {j["id"]: j for j in jobs}
    assert by_id[1]["status"] == "finished"
    assert by_id[1]["counters"]["finished"] == 10
    assert by_id[2]["status"] == "finished"
    assert by_id[2]["counters"]["finished"] == 8
    # id allocation resumes past restored state
    out = env.command(["submit", "--output-mode", "quiet", "--", "true"])
    assert out.strip() == "3"


def test_virtual_scale_1k_workers():
    """1000-worker virtual scale through the production schedule path (no
    subprocesses): 5k tasks spread over the fleet in a handful of ticks,
    every worker's capacity respected."""
    from utils_env import TestEnv

    env = TestEnv()
    workers = [env.worker(cpus=4) for _ in range(1000)]
    env.submit(n=5000)
    for _ in range(10):
        env.schedule()
        assigned = sum(len(w.assigned_tasks) for w in workers)
        if assigned >= 4000:  # fleet saturated: 1000 workers x 4 slots
            break
    assigned_by_worker = [len(w.assigned_tasks) for w in workers]
    assert sum(assigned_by_worker) == 4000
    assert max(assigned_by_worker) <= 4
    assert min(assigned_by_worker) >= 3  # near-even spread, no hot worker
