"""Deterministic cluster-simulator tests (ISSUE 14).

Everything here runs the REAL server on the virtual-clock loop; wall time
per test is milliseconds-to-seconds even though the scenarios cover
minutes of virtual time, kill -9 + restore, and thousand-task workloads.
"""

from __future__ import annotations

import asyncio
import re
from pathlib import Path

import pytest

from hyperqueue_tpu.sim import (
    FaultEvent,
    FaultSchedule,
    InvariantViolation,
    SimDeadlockError,
    SimEventLoop,
    build,
    run_scenario,
)
from hyperqueue_tpu.sim.harness import Simulation
from hyperqueue_tpu.sim.invariants import InvariantMonitor

pytestmark = pytest.mark.sim

REPO_ROOT = Path(__file__).resolve().parent.parent


# --- virtual clock ----------------------------------------------------
def test_virtual_loop_jumps_time_instantly():
    loop = SimEventLoop()
    try:
        t0_wall = __import__("time").perf_counter()

        async def scenario():
            t_start = loop.time()
            await asyncio.sleep(600.0)       # ten virtual minutes
            return loop.time() - t_start

        elapsed_virtual = loop.run_until_complete(scenario())
        elapsed_wall = __import__("time").perf_counter() - t0_wall
        assert elapsed_virtual == pytest.approx(600.0)
        assert elapsed_wall < 1.0            # idle waits are free
    finally:
        loop.close()


def test_virtual_loop_detects_deadlock():
    loop = SimEventLoop()
    try:

        async def hang_forever():
            await loop.create_future()       # nothing will ever set it

        with pytest.raises(SimDeadlockError):
            loop.run_until_complete(hang_forever())
    finally:
        loop.close()


# --- chaos schedule-driven mode (satellite) ---------------------------
def test_chaos_virtual_time_trigger():
    from hyperqueue_tpu.utils import chaos, clock

    class FakeClock:
        def __init__(self):
            self.t = 0.0

        def time(self):
            return self.t

        def monotonic(self):
            return self.t

    fake = FakeClock()
    prev = clock.install(fake)
    try:
        chaos.install_plan({"rules": [
            {"site": "solve", "action": "raise", "at_t": 100.0, "at": 2},
        ]})
        # before the gate: never fires, and occurrences do NOT count
        for _ in range(5):
            chaos.fire("solve")
        fake.t = 150.0
        chaos.fire("solve")                  # 1st post-gate match
        with pytest.raises(chaos.ChaosInjectedError):
            chaos.fire("solve")              # 2nd post-gate match -> fires
        chaos.fire("solve")                  # at=2 consumed; quiet again
    finally:
        chaos.clear_plan()
        clock.install(prev)


# --- basic scenario ---------------------------------------------------
def test_small_scenario_completes_green():
    wl = build("uniform", seed=1, n_tasks=200, dur_ms=400)
    res = run_scenario(wl, seed=1, n_workers=8)
    assert res.audit["finished"] == 200
    assert res.audit["executions"] == 200
    assert not res.violations
    assert res.server_boots == 1
    assert 0 < res.makespan < 120.0


def test_dag_and_gang_workloads_complete():
    res = run_scenario(build("dag", seed=2, layers=5, width=8), seed=2,
                       n_workers=4)
    assert res.audit["finished"] == 40
    res = run_scenario(
        build("gang", seed=2, n_gangs=3, gang_size=3, filler_tasks=60),
        seed=2, n_workers=9,
    )
    assert res.audit["finished"] == 63


@pytest.mark.parametrize(
    "name,kwargs,workers,seed",
    [("gang", dict(n_gangs=8, gang_size=4, filler_tasks=600), 8, 11),
     ("dag", dict(layers=12, width=30), 8, 5)],
    ids=["gang-heavy", "stress-dag"],
)
def test_fused_scheduler_never_loses_to_host_greedy(name, kwargs, workers,
                                                    seed):
    """The same seeded workload under `greedy-numpy` and under the fused
    gang/lookahead scheduler: in virtual time the fused makespan does not
    exceed the host baseline, no task is lost, and every gang starts once,
    atomically (the monitor's gang-atomicity invariant runs throughout)."""
    wl = build(name, seed=seed, **kwargs)
    base = run_scenario(wl, seed=seed, n_workers=workers,
                        scheduler="greedy-numpy")
    fused = run_scenario(wl, seed=seed, n_workers=workers,
                         scheduler="greedy-fused")
    for res in (base, fused):
        assert res.audit["finished"] == wl.n_tasks
        assert not res.violations
        assert res.audit.get("gang_starts", 0) == kwargs.get("n_gangs", 0)
    assert fused.makespan <= base.makespan + 1e-6


# --- determinism regression (satellite) -------------------------------
def test_same_seed_bit_identical_digests():
    faults = FaultSchedule(seed=5, events=[
        FaultEvent(at=4.0, kind="server_kill", delay=1.0),
        FaultEvent(at=9.0, kind="worker_kill", target="w2", delay=1.0),
    ])

    def one_run():
        wl = build("bursty", seed=5, n_tenants=3, bursts_per_tenant=2,
                   tasks_per_burst=50, window=20)
        schedule = FaultSchedule(
            seed=faults.seed, events=list(faults.events)
        )
        return run_scenario(wl, seed=5, n_workers=8, faults=schedule)

    a = one_run()
    b = one_run()
    assert a.decision_digest == b.decision_digest
    assert a.journal_digest == b.journal_digest
    assert a.audit == b.audit
    # a different seed must not produce the same history
    wl = build("bursty", seed=6, n_tenants=3, bursts_per_tenant=2,
               tasks_per_burst=50, window=20)
    c = run_scenario(wl, seed=6, n_workers=8)
    assert c.journal_digest != a.journal_digest


@pytest.mark.profile
def test_profiler_inert_under_sim_digests_unchanged():
    """ISSUE 19 satellite: profiling requested ON in one run of a
    determinism pair must be a no-op under the simulator — the sampler
    is double-gated (memory-transport servers never start it, and
    SamplingProfiler.start() refuses under a simulated clock), so the
    journals stay bit-identical. The no-metrics scan above keeps
    hq_profile_* literals out of sim code for the same reason."""
    from hyperqueue_tpu.utils.profiler import PROFILER

    def one_run(profile_hz: float):
        wl = build("uniform", seed=21, n_tasks=150, dur_ms=300)
        return run_scenario(wl, seed=21, n_workers=6,
                            server_kwargs={"profile_hz": profile_hz})

    a = one_run(0.0)
    b = one_run(19.0)   # requested on; must stay inert
    assert not PROFILER.running
    assert a.journal_digest == b.journal_digest
    assert a.decision_digest == b.decision_digest
    assert a.audit == b.audit


# --- kill -9 re-enactment (satellite: sim/e2e agreement) --------------
def test_kill9_mid_chunked_submit_exactly_once():
    """Sim re-enactment of the real-process chaos scenario
    (tests/test_ingest.py kill -9 mid-chunked-submit with restore): the
    server dies at the 8th applied chunk, the client replays unacked
    chunks against the restored incarnation, and the outcome is the same
    exactly-once contract the e2e test pins — every task exactly once,
    no duplicates from the replay."""
    wl = build("uniform", seed=6, n_tasks=2000, dur_ms=200)
    faults = FaultSchedule(seed=6, events=[
        FaultEvent(at=0.0, kind="chaos_rule",
                   rule={"site": "server.event", "event": "job-submitted",
                         "at": 8, "action": "kill"}),
    ])
    sim = Simulation(wl, seed=6, n_workers=12, faults=faults,
                     chunk_size=100)
    res = sim.run()
    assert res.server_boots == 2, "the chaos kill must have fired"
    assert res.audit["finished"] == 2000
    assert res.audit["executions"] == 2000
    # the ack-implies-durable check ran at restore (chunks acked before
    # the kill were present afterwards) — and the monitor saw acks both
    # before and after the crash
    assert sim.monitor.acked_chunks


# --- seeded fault soak -------------------------------------------------
@pytest.mark.parametrize(
    "name,kwargs,workers,seed,rate,outlives_the_kill",
    [("uniform", dict(n_tasks=400, dur_ms=1000), 12, 13, 0.05, True),
     ("dag", dict(layers=8, width=16), 8, 7, 0.03, False),
     ("gang", dict(n_gangs=6, gang_size=3, filler_tasks=300), 12, 7, 0.03,
      True),
     ("tail", dict(n_tasks=800), 12, 7, 0.03, True)],
    ids=["uniform", "deep-dag", "gang-heavy", "straggler-tail"],
)
def test_fault_soak_invariants_green(name, kwargs, workers, seed, rate,
                                     outlives_the_kill):
    """Each workload shape under a generated fault schedule with one
    server kill -9: nothing lost, every invariant green, and the server
    restored where the run is long enough to meet the kill."""
    wl = build(name, seed=seed, **kwargs)
    names = [f"w{i}" for i in range(workers)]
    faults = FaultSchedule.generate(
        seed, horizon=40.0, worker_names=names, rate=rate, server_kills=1,
    )
    res = run_scenario(wl, seed=seed, n_workers=workers, faults=faults)
    assert res.audit["finished"] == wl.n_tasks
    assert not res.violations
    assert res.server_boots >= (2 if outlives_the_kill else 1)


@pytest.mark.slow
def test_fault_soak_many_seeds():
    """Randomized multi-seed soak: every seed must quiesce with all
    invariants green under kill -9, worker churn, partitions,
    stragglers, clock skew, and message dup/delay."""
    for seed in (101, 202, 303, 404, 505):
        wl = build("uniform", seed=seed, n_tasks=600, dur_ms=1500)
        names = [f"w{i}" for i in range(16)]
        faults = FaultSchedule.generate(
            seed, horizon=60.0, worker_names=names, rate=0.05,
            server_kills=2,
        )
        res = run_scenario(wl, seed=seed, n_workers=16, faults=faults)
        assert res.audit["finished"] == 600, f"seed {seed}"
        assert not res.violations, f"seed {seed}: {res.violations}"


# --- drain invariant ---------------------------------------------------
def test_drain_means_no_new_assignments():
    wl = build("uniform", seed=8, n_tasks=200, dur_ms=800)
    sim = Simulation(wl, seed=8, n_workers=6)
    orig_main = sim._main

    async def main_with_drain():
        async def drain_later():
            await asyncio.sleep(3.0)
            await sim.drain_worker(sim.workers["w2"], timeout=30.0)

        sim.loop.create_task(drain_later())
        return await orig_main()

    sim._main = main_with_drain
    res = sim.run()
    assert res.audit["finished"] == 200
    assert not res.violations
    assert sim.monitor.drain_started  # the drain actually registered


# --- the invariant checkers themselves ---------------------------------
def test_monitor_detects_double_spawn_and_fence_regression():
    mon = InvariantMonitor(sim=None)
    mon.on_exec_started("wa", 1, 42, 3, 1.0)
    with pytest.raises(InvariantViolation):
        mon.on_exec_started("wb", 2, 42, 3, 2.0)  # same (task, instance)
    mon2 = InvariantMonitor(sim=None)
    mon2.on_exec_started("wa", 1, 42, 5, 1.0)
    with pytest.raises(InvariantViolation):
        mon2.on_exec_started("wb", 2, 42, 4, 2.0)  # instance went DOWN
    mon3 = InvariantMonitor(sim=None)
    mon3.on_drain_started(7, 10.0)
    with pytest.raises(InvariantViolation):
        mon3.on_compute_delivered("wc", 7, 42, 0, 11.0)


# --- journal replay regression (tentpole satellite) ---------------------
def test_replay_same_scheduler_reproduces_makespan(tmp_path):
    from hyperqueue_tpu.sim.replay import (
        replay_compare,
        workload_from_journal,
    )

    wl = build("uniform", seed=9, n_tasks=150, dur_ms=500)
    sim = Simulation(wl, seed=9, n_workers=6, server_dir=tmp_path / "rec")
    recorded = sim.run()
    assert recorded.audit["finished"] == 150
    journal = tmp_path / "rec" / "journal.bin"
    assert journal.exists()
    replayed = workload_from_journal(journal)
    assert replayed.n_tasks == 150
    cmp_result = replay_compare(
        journal, "greedy-numpy", "greedy-numpy", seed=9, n_workers=6,
    )
    # same recorded workload + same scheduler + same seed = the same run
    assert cmp_result.makespan_a == pytest.approx(cmp_result.makespan_b)
    assert cmp_result.assigned_a == cmp_result.assigned_b


def test_replay_compares_host_and_fused_on_a_gang_journal(tmp_path):
    """A recorded gang run rebuilt from its journal and replayed under
    `greedy-numpy` and `greedy-fused`: both place every task of the
    recording."""
    from hyperqueue_tpu.sim.replay import replay_compare

    wl = build("gang", seed=3, n_gangs=4, gang_size=3, filler_tasks=150)
    recorded = run_scenario(wl, seed=3, n_workers=9,
                            server_dir=tmp_path / "rec")
    assert recorded.audit["finished"] == wl.n_tasks
    cmp_result = replay_compare(
        tmp_path / "rec" / "journal.bin", "greedy-numpy", "greedy-fused",
        seed=3, n_workers=9,
    )
    assert cmp_result.makespan_a > 0 and cmp_result.makespan_b > 0
    assert cmp_result.assigned_a == cmp_result.assigned_b > 0
    assert "makespan" in cmp_result.summary()


# --- metrics hygiene (satellite) ----------------------------------------
def test_sim_package_registers_no_metrics():
    """The simulator consumes DecisionRecords and the trace store
    unchanged and must register NO hq_* metrics of its own (the
    observability catalog checker in test_metrics.py would also flag
    undocumented names — this pins the stronger property that sim code
    never touches the registry at all)."""
    sim_dir = REPO_ROOT / "hyperqueue_tpu" / "sim"
    offenders = []
    for path in sorted(sim_dir.glob("*.py")):
        text = path.read_text()
        if re.search(r"REGISTRY\.(counter|gauge|histogram)", text):
            offenders.append(path.name)
        if re.search(r"""["']hq_[a-z0-9_]+["']""", text):
            offenders.append(f"{path.name} (hq_* literal)")
    assert not offenders, (
        f"sim code must not register metrics: {offenders}"
    )
