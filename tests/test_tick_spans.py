"""The tick's phases are timed through one primitive (`TRACER.phase`): the
new spans are where the work happens, children lie inside their parents in
the `phases` dict and in a profiler trace, a process without JAX stays
without it, and the benchmark's new readers read them (ISSUE 25).  The
record accounts for the whole cycle (ISSUE 35): `sync` is timed where it
runs, what is timed with no tick's dict in reach joins the tick that runs
next, the ready path between ticks lies under `cycle/`, and what no span
covers is named `unattributed`."""

import glob
import json
import os
import statistics
import subprocess
import sys
from dataclasses import dataclass, field
from pathlib import Path

import pytest

from hyperqueue_tpu.models.greedy import GreedyCutScanModel
from hyperqueue_tpu.scheduler.tick import create_batches, run_tick
from hyperqueue_tpu.scheduler.tick_cache import TickPhaseStats
from hyperqueue_tpu.utils.trace import TRACER

from utils_e2e import HqEnv
from utils_env import TestEnv, displace_workers

ROOT = Path(__file__).resolve().parent.parent
DEVICE_CHILDREN = ("solve_dispatch/upload", "solve_dispatch/launch",
                   "device_sync/counts", "device_sync/state")
MODEL_PHASES = ("assemble", "solve_host_prep", "solve_host_prep/visit",
                "solve_dispatch", "device_sync", "mapping")


@dataclass
class _EveryTick(TickPhaseStats):
    """`Core.tick_stats` that also keeps each tick's own dict (`last_ms`
    keeps a key's reading of an earlier tick, and the flight recorder
    drops idle ticks)."""

    seen: list = field(default_factory=list)

    def record(self, phases: dict) -> None:
        super().record(phases)
        self.seen.append(dict(phases))


def _env(backend: str, workers: int = 3, tasks: int = 40) -> TestEnv:
    env = TestEnv(model=GreedyCutScanModel(backend=backend))
    env.core.tick_stats = _EveryTick()
    for _ in range(workers):
        env.worker(cpus=2)
    env.submit(n=tasks)
    return env


def _run_tick(env) -> dict:
    """One tick as the benchmark's `tick` driver makes it; returns its
    phases and applies the assignments so the next tick differs."""
    core = env.core
    phases: dict = {}
    snap = core.tick_cache.sync(core)
    out = run_tick(
        core.queues, None, core.rq_map, core.resource_map, env.model,
        batches=create_batches(core.queues), dense=snap, phases=phases,
        key_cache=core.tick_cache,
    )
    for task_id, worker_id, rq_id, variant in out:
        worker = core.workers[worker_id]
        worker.assign(task_id, core.variant_amounts(rq_id, variant, worker))
    env.last_assignments = out
    return phases


def _finish_one(env) -> None:
    task_id, worker_id, rq_id, variant = env.last_assignments[0]
    worker = env.core.workers[worker_id]
    worker.unassign(task_id, env.core.variant_amounts(rq_id, variant, worker))


def _close(parent: float, children: float) -> bool:
    return parent - children <= max(0.1 * parent, 0.2)


def test_device_tick_fills_the_four_new_spans_inside_their_parents():
    env = _env("jax")
    _run_tick(env)  # compiles, uploads in full
    gaps = {"solve_dispatch": [], "device_sync": []}
    for _ in range(5):
        _finish_one(env)  # a dirty row: the next upload is a delta
        phases = _run_tick(env)
        assert set(DEVICE_CHILDREN + MODEL_PHASES) <= set(phases)
        for parent in gaps:
            children = sum(v for k, v in phases.items()
                           if k.startswith(parent + "/"))
            assert children <= phases[parent]
            gaps[parent].append((phases[parent], children))
    for parent, readings in gaps.items():
        # every tick is held to the order; the distance to the median tick,
        # so that one collector pause between two children fails nothing
        assert statistics.median(_close(p, c) for p, c in readings), (
            parent, readings)
    stats = env.model.resident_stats()
    assert stats["delta_uploads"] >= 1 and stats["backend"] == "device-jax"


def test_host_tick_fills_every_old_phase_and_no_device_child():
    env = _env("numpy")
    phases = _run_tick(env)
    assert set(MODEL_PHASES) <= set(phases)
    assert not set(DEVICE_CHILDREN) & set(phases)
    assert all(phases[k] >= 0.0 for k in MODEL_PHASES)
    assert (phases["solve_host_prep/visit"] <= phases["solve_host_prep"])


def test_milp_solve_is_the_ticks_solve_dispatch():
    from hyperqueue_tpu.models.milp import MilpModel

    env = TestEnv(model=MilpModel())
    env.worker(cpus=2)
    env.submit(n=4)
    env.schedule(prefill=False)
    last = env.core.tick_stats.last_ms
    assert last["solve_dispatch"] > 0.0 and "device_sync" not in last


def test_schedule_splits_prefill_into_its_three_passes():
    env = _env("numpy", workers=4, tasks=60)
    env.schedule(prefill=True)       # assigns and prefills the backlog
    env.submit(n=30)                 # more ready work over prefilled workers
    assert all(w.prefilled_tasks for w in env.core.workers.values())
    TRACER.reset()
    env.schedule(prefill=True)
    last = env.core.tick_stats.last_ms
    passes = ("prefill/fill", "prefill/displace", "prefill/rebalance")
    assert set(passes) <= set(last)
    children = sum(last[k] for k in passes)
    assert children <= last["prefill"] and _close(last["prefill"], children)
    assert last["prefill"] <= last["total"]
    # the spans that were TRACER records keep their documented names
    snap = TRACER.snapshot()
    for name in ("scheduler/tick", "scheduler/solve", "scheduler/prefill"):
        assert snap[name]["count"] == 1
    # no prefill: the phase and its passes are absent, as before
    totals = dict(env.core.tick_stats.totals_ms)
    env.submit(n=2)
    env.schedule(prefill=False)
    assert all(env.core.tick_stats.totals_ms[k] == totals[k]
               for k in passes + ("prefill",))


@pytest.mark.parametrize("outranked", [False, True],
                         ids=["pass-skips", "pass-scans"])
def test_displace_span_is_recorded_whether_the_pass_skips_or_scans(outranked):
    """ISSUE 26: the displacement pass passes over every worker when nothing
    queued outranks the backlog; its span and its counter say so."""
    env = _env("numpy", workers=2, tasks=30)
    env.schedule(prefill=True)
    # ready work the full workers cannot take: 3-cpu tasks on 2-cpu nodes
    env.submit(n=5, rqv=env.rqv(cpus=3))
    if outranked:
        env.submit(n=1, rqv=env.rqv(cpus=3), priority=(4, 0), job=2)
        env.submit(n=1, priority=(4, 0), job=3)
    ticks = env.core.tick_stats.ticks
    recorded = env.core.tick_stats.totals_ms.get("prefill/displace", 0.0)
    before = displace_workers()
    env.schedule(prefill=True)
    last = env.core.tick_stats.last_ms
    assert env.core.tick_stats.ticks == ticks + 1
    assert 0.0 <= last["prefill/displace"] <= last["prefill"]
    assert env.core.tick_stats.totals_ms["prefill/displace"] >= recorded
    moved = {o: n - before[o] for o, n in displace_workers().items()}
    assert moved == ({"skipped": 0, "scanned": 2} if outranked
                     else {"skipped": 2, "scanned": 0})


def test_pipelined_tick_records_the_wait_and_its_split_when_it_takes():
    from hyperqueue_tpu.scheduler.pipeline import TickPipeline

    env = _env("jax")
    env.core.tick_pipeline = TickPipeline()
    env.schedule(prefill=False)      # dispatches, maps nothing yet
    first = dict(env.core.tick_stats.last_ms)
    assert "solve_dispatch/launch" in first and "pipeline_wait" not in first
    assert not [k for k in first if k.startswith("device_sync")]
    env.schedule(prefill=False)      # takes the result of the first
    second = env.core.tick_stats.last_ms
    split = second["device_sync/counts"] + second["device_sync/state"]
    assert split <= second["pipeline_wait"]
    assert "device_sync" not in second  # pipeline_wait is the parent here


def test_spans_lie_in_the_profilers_trace_nested_with_the_tick_number(
        tmp_path):
    import jax
    from jax.profiler import ProfileData

    env = _env("jax")
    env.schedule(prefill=True)       # compiles outside the trace
    env.submit(n=6)
    jax.profiler.start_trace(str(tmp_path))
    try:
        env.schedule(prefill=True)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("hq/"):
                    assert e.name not in spans, f"{e.name} twice in one tick"
                    spans[e.name] = (e.start_ns, e.start_ns + e.duration_ns,
                                     dict(e.stats))
    wanted = ("hq/tick", "hq/tick/assemble", "hq/tick/solve_dispatch",
              "hq/tick/solve_dispatch/upload", "hq/tick/device_sync",
              "hq/tick/device_sync/counts", "hq/tick/mapping",
              "hq/tick/prefill", "hq/tick/prefill/displace")
    assert set(wanted) <= set(spans), sorted(spans)
    assert spans["hq/tick"][2] == {"tick": env.core.tick_counter}
    for name, (start, end, _stats) in spans.items():
        parent = name.rsplit("/", 1)[0]
        if name == "hq/tick":
            continue
        p_start, p_end, _ = spans[parent]
        assert p_start <= start and end <= p_end, (name, parent)


def test_a_host_tick_through_the_primitive_never_imports_jax():
    script = (
        "import sys\n"
        "from utils_env import TestEnv\n"
        "from hyperqueue_tpu.models.greedy import GreedyCutScanModel\n"
        "from hyperqueue_tpu.utils.trace import TRACER\n"
        "env = TestEnv(model=GreedyCutScanModel(backend='numpy'))\n"
        "env.worker(cpus=2)\n"
        "env.submit(n=6)\n"
        "env.schedule(prefill=True)\n"
        "with TRACER.phase(None, 'rpc', root='hq/plane'):\n"
        "    pass\n"
        "last = env.core.tick_stats.last_ms\n"
        "assert last['total'] > 0 and 'prefill/fill' in last, last\n"
        "assert {'sync', 'sync/build', 'unattributed', 'cycle/ready'} "
        "<= set(last), last\n"
        "assert 'scheduler/tick' in TRACER.snapshot()\n"
        "print('jax' in sys.modules, 'jaxlib' in sys.modules)\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "tests")])
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=120, cwd=str(ROOT),
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["False", "False"]


def test_phase_reports_to_every_sink():
    TRACER.reset()
    phases = {"gangs": 1.0}
    seen = []
    with TRACER.phase(phases, "gangs", done=lambda k, s: seen.append((k, s))
                      ) as held:
        pass
    assert phases["gangs"] == pytest.approx(1.0 + held.seconds * 1e3)
    assert seen == [("gangs", held.seconds)]
    assert TRACER.snapshot()["scheduler/gangs"]["count"] == 1
    # a phase with no scheduler/<name> entry records no TRACER span, and a
    # plane's hold is named under its own root
    with TRACER.phase(phases, "assemble"):
        pass
    with TRACER.phase(None, "solve", root="hq/plane"):
        pass
    assert set(TRACER.snapshot(recent=0)) == {"scheduler/gangs"}


def test_shares_count_top_level_phases_once():
    stats = TickPhaseStats()
    stats.record({"assemble": 1.0, "prefill": 3.0, "prefill/fill": 2.0,
                  "prefill/displace": 1.0, "unattributed": 1.0,
                  "cycle/ready": 9.0, "cycle/ready/mn_sort": 8.0,
                  "total": 5.0})
    shares = stats.shares()
    assert "total" not in shares
    # the whole is `total`: what no span covers is a part of it, what lies
    # between the ticks is not
    assert not [k for k in shares if k.startswith("cycle/")]
    assert (shares["assemble"] + shares["prefill"] + shares["unattributed"]
            == pytest.approx(1.0))
    assert shares["unattributed"] == pytest.approx(0.2)
    assert shares["prefill/fill"] == pytest.approx(0.4)
    # the snapshot lists every key, the cycle's like any other
    listed = stats.snapshot()["phases"]
    assert listed["cycle/ready"]["last_ms"] == 9.0
    assert listed["unattributed"]["mean_ms"] == 1.0


def test_readback_counters_grow_by_what_each_device_solve_reads_back():
    env = _env("jax")
    _run_tick(env)
    before = env.model.resident_stats()
    _finish_one(env)
    n_b = len(create_batches(env.core.queues))
    _run_tick(env)
    after = env.model.resident_stats()
    # one buffer a solve (ops/answer.py), here in its dense-small form (one
    # batch): the live (B, V=1) rows of the counts at the padded width,
    # then the padded free_after and nt_after
    mirror = env.model._res
    assert after["readbacks_total"] - before["readbacks_total"] == 1
    assert after["answers_total"] - before["answers_total"] == 1
    assert (after["answers_dense_small"]
            - before["answers_dense_small"]) == 1
    assert after["readback_bytes_total"] - before["readback_bytes_total"] == (
        n_b * 1 * mirror._m_nt.size * 4
        + mirror._m_free.nbytes + mirror._m_nt.nbytes
    )


# the cells whose drivers pass the same `tick_phases_ms`: the tick cell, the
# sharded cell, the `flat-1k` tick cell, the gang cell, the sharded gang cell
# and the shared cell
TICK_CELLS = ["hetero-1k.backlog-1m", "shard-16k.backlog",
              "flat-1k.backlog-1m", "gang-1k.rigid", "gang-16k.campaign",
              "shared-1k.reserve"]
# the cells that submit between ticks (`reactor.on_new_tasks`)
GANG_CELLS = ["gang-1k.rigid", "gang-16k.campaign", "shared-1k.reserve"]
# metric -> (the key it reads, its cells, the end-to-end metric it moves)
PHASE_READERS = {
    "upload_ms": ("solve_dispatch/upload", TICK_CELLS, "tick_ms_p50"),
    "launch_ms": ("solve_dispatch/launch", TICK_CELLS, "tick_ms_p50"),
    "counts_wait_ms": ("device_sync/counts", TICK_CELLS, "tick_ms_p50"),
    "state_readback_ms": ("device_sync/state", TICK_CELLS, "tick_ms_p50"),
    "snapshot_sync_ms": ("sync", TICK_CELLS, "tick_ms_p50"),
    "ready_path_ms": ("cycle/ready", GANG_CELLS, "ticks_per_s"),
    "ready_sort_ms": ("cycle/ready/mn_sort", GANG_CELLS, "ticks_per_s"),
}


@pytest.mark.parametrize("metric", sorted(PHASE_READERS))
def test_span_reader_gives_the_median_or_nothing(metric):
    from chipbench import manifest

    read = manifest.metric_reader(metric)
    key, cells, moves = PHASE_READERS[metric]
    ticks = [{"assemble": 1.0, key: v} for v in (0.4, 0.2, 9.0)]
    assert read({"tick_phases_ms": ticks}) == 0.4
    # a tick that lacks the key (nothing was submitted before it) reads 0
    assert read({"tick_phases_ms": ticks + [{"assemble": 1.0}] * 2}) == 0.2
    # the parent commit's program: the old keys, not this one
    assert read({"tick_phases_ms": [{"assemble": 1.0}] * 3}) is None
    assert read({}) is None
    entry = next(m for m in manifest.load()["per_layer"]
                 if m["name"] == metric)
    assert entry["workloads"] == cells
    assert entry["moves"] == moves and entry["unit"] == "ms"
    assert entry["source"] == "program_span"


def test_unattributed_reader_subtracts_the_top_level_keys_alone():
    from chipbench import manifest

    read = manifest.metric_reader("tick_unattributed_ms")
    lists = {"host_phases": ("snapshot", "assemble", "gangs"),
             "device_phases": ("device_sync",)}

    def tick(total):
        # children, `sync` (inside the harness's `snapshot`) and the
        # cycle's keys (outside `total`) are subtracted by nobody
        return {"total": total, "snapshot": 1.0, "sync": 0.9,
                "assemble": 2.0, "assemble/gang": 1.5, "device_sync": 3.0,
                "device_sync/counts": 2.5, "cycle/ready": 40.0}

    ticks = [tick(t) for t in (6.5, 6.25, 9.0)]
    assert read({"tick_phases_ms": ticks, **lists}) == pytest.approx(0.5)
    # the parent's program has every key this reads: the line holds both
    parent = [{"total": 7.0, "snapshot": 1.0, "assemble": 2.0,
               "device_sync": 3.0}] * 3
    assert read({"tick_phases_ms": parent, **lists}) == pytest.approx(1.0)
    assert read({}) is None
    entry = next(m for m in manifest.load()["per_layer"]
                 if m["name"] == "tick_unattributed_ms")
    assert entry["workloads"] == TICK_CELLS and entry["layer"] == "tick"
    assert entry["moves"] == "tick_ms_p50" and entry["unit"] == "ms"


def test_readback_reader_gives_bytes_per_tick_or_nothing():
    from chipbench import manifest

    read = manifest.metric_reader("readback_bytes_per_tick")
    observed = {
        "uploads_before": {"readback_bytes_total": 1000, "full_uploads": 1},
        "uploads_after": {"readback_bytes_total": 5000, "full_uploads": 1},
        "ticks": 4,
    }
    assert read(observed) == 1000.0
    del observed["uploads_after"]["readback_bytes_total"]
    assert read(observed) is None
    assert read({}) is None


# ---------------------------------------------------------------- ISSUE 35
def _top_level(phases: dict) -> float:
    return sum(ms for key, ms in phases.items()
               if "/" not in key and key not in ("total", "unattributed"))


def _last_tick(env) -> dict:
    """The phases of the last `schedule()` alone."""
    return env.core.tick_stats.seen[-1]


def test_schedule_times_sync_inside_total_and_the_build_only_when_whole():
    env = _env("numpy")
    env.schedule()
    first = _last_tick(env)
    assert 0.0 < first["sync/build"] <= first["sync"] <= first["total"]
    env.submit(n=4)
    env.schedule()                   # steady: the cache is told what moved
    second = _last_tick(env)
    assert 0.0 < second["sync"] <= second["total"]
    assert "sync/build" not in second
    assert env.core.tick_cache.full_rebuilds == 1
    assert not env.core.tick_cache.parked  # a tick's own dict took it all
    env.worker(cpus=2)               # a structural change: every row built
    env.submit(n=2)
    env.schedule()
    third = _last_tick(env)
    assert 0.0 < third["sync/build"] <= third["sync"]
    assert env.core.tick_cache.full_rebuilds == 2


def test_a_sync_with_no_dict_joins_the_tick_that_runs_next_once():
    env = _env("numpy")
    cache = env.core.tick_cache
    phases = _run_tick(env)          # the drivers' order: sync, run_tick
    assert phases["sync"] > 0.0 and phases["sync/build"] <= phases["sync"]
    assert not cache.parked
    # the tick's own sync, not an earlier one's: a second sync before the
    # tick adds to it, and what run_tick took is gone
    cache.sync(env.core)
    parked = cache.parked["sync"]
    _finish_one(env)
    again = _run_tick(env)
    assert again["sync"] > parked and "sync/build" not in again
    assert not cache.parked


def test_a_second_run_tick_without_a_sync_holds_none():
    env = _env("numpy")
    core = env.core
    snap = core.tick_cache.sync(core)
    phases: dict = {}
    run_tick(core.queues, None, core.rq_map, core.resource_map, env.model,
             dense=snap, phases=phases, key_cache=core.tick_cache)
    assert "sync" in phases
    for kwargs in ({}, {"batches": []}):   # a solve, and an early return
        later: dict = {}
        run_tick(core.queues, None, core.rq_map, core.resource_map,
                 env.model, dense=snap, phases=later,
                 key_cache=core.tick_cache, **kwargs)
        assert "sync" not in later and "sync/build" not in later


def test_a_tick_with_nothing_to_solve_still_takes_what_is_parked():
    env = _env("numpy", workers=0, tasks=3)  # no worker: no run_tick
    env.schedule()
    last = _last_tick(env)
    assert last["cycle/ready"] > 0.0 and "assemble" not in last
    assert not env.core.tick_cache.parked


@pytest.mark.parametrize("calls", [0, 1, 2])
def test_ready_path_lies_in_the_next_ticks_record(calls):
    env = _env("numpy", tasks=8)
    env.schedule()
    for _ in range(calls):
        env.submit(n=2)
    parked = dict(env.core.tick_cache.parked)
    env.schedule()
    last = _last_tick(env)
    if not calls:
        # nothing was submitted since the previous tick
        assert not parked and not [k for k in last if k.startswith("cycle/")]
        return
    # one span a call, summed; single-node tasks sort no multi-node queue
    assert set(parked) == {"cycle/ready"}
    assert last["cycle/ready"] == parked["cycle/ready"]
    assert "cycle/ready/mn_sort" not in last
    env.schedule()                   # and only in that tick's
    assert "cycle/ready" not in _last_tick(env)


def test_ready_sort_appears_with_a_multi_node_task_only():
    env = _env("numpy", workers=4, tasks=4)
    env.schedule()
    env.submit(n=3, rqv=env.rqv(n_nodes=2), priority=(1, 0))
    env.submit(n=1)
    env.schedule()
    last = _last_tick(env)
    assert 0.0 < last["cycle/ready/mn_sort"] <= last["cycle/ready"]
    # the driver-shaped tick takes the same keys through run_tick
    env.submit(n=1, rqv=env.rqv(n_nodes=2), priority=(1, 0))
    phases = _run_tick(env)
    assert 0.0 < phases["cycle/ready/mn_sort"] <= phases["cycle/ready"]


def test_cycle_keys_lie_outside_total_and_unattributed_is_the_rest():
    env = _env("numpy", workers=4, tasks=30)
    for _ in range(4):
        env.submit(n=3, rqv=env.rqv(n_nodes=2), priority=(1, 0))
        env.submit(n=5)
        env.schedule(prefill=True)
        last = _last_tick(env)
        assert last["unattributed"] >= 0.0
        assert last["unattributed"] == pytest.approx(
            last["total"] - _top_level(last), abs=1e-9)
        assert last["cycle/ready"] > 0.0
    totals = env.core.tick_stats.totals_ms
    assert totals["unattributed"] + _top_level(totals) == pytest.approx(
        totals["total"], rel=1e-6)
    shares = env.core.tick_stats.shares()
    assert not [k for k in shares if k.startswith("cycle/")]
    assert sum(v for k, v in shares.items() if "/" not in k) == \
        pytest.approx(1.0, abs=2e-3)
    assert env.core.tick_stats.snapshot()["phases"]["cycle/ready"][
        "total_ms"] > 0.0


def test_unattributed_is_never_negative():
    from hyperqueue_tpu.server import reactor

    env = _env("numpy")
    # top-level keys that outweigh `total` (clock noise, or a key parked
    # by a caller outside the tick) must not make the rest negative
    env.core.tick_cache.parked["assemble"] = 1e6
    reactor.schedule(env.core, env.comm, env.events, env.model)
    assert _last_tick(env)["unattributed"] == 0.0


def test_a_measurement_window_starts_with_nothing_parked():
    env = _env("numpy")
    env.core.tick_cache.sync(env.core)
    assert set(env.core.tick_cache.parked) == {"cycle/ready", "sync",
                                               "sync/build"}
    env.core.tick_cache.reset_counters()
    assert not env.core.tick_cache.parked
    phases: dict = {}
    env.core.tick_cache.take_parked(phases)
    assert phases == {}


def test_two_cores_in_one_process_keep_their_records_apart():
    a, b = _env("numpy", tasks=4), _env("numpy", tasks=4)
    a.schedule()
    b.schedule()
    a.submit(n=2)                    # only a's ready path ran
    b.schedule()
    assert "cycle/ready" not in _last_tick(b)
    a.schedule()
    assert "cycle/ready" in _last_tick(a)


def test_sync_and_the_ready_path_lie_in_the_profilers_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    env = _env("jax", workers=4)
    env.schedule()                   # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        env.submit(n=6)
        env.schedule()
        env.submit(n=2, rqv=env.rqv(n_nodes=2), priority=(1, 0))
        env.schedule()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("hq/"):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    ticks = sorted(events["hq/tick"])
    assert [t[2]["tick"] for t in ticks] == [
        env.core.tick_counter - 1, env.core.tick_counter]
    # `sync` nests in its tick; the steady sync builds nothing whole
    for (start, end, _), (t0, t1, _) in zip(sorted(events["hq/tick/sync"]),
                                            ticks):
        assert t0 <= start and end <= t1
    assert "hq/tick/sync/build" not in events
    # the ready path lies between the ticks, with the number of the tick
    # whose record takes it, and the sort inside the second call alone
    ready = sorted(events["hq/cycle/ready"])
    assert [r[2] for r in ready] == [
        {"tasks": 6, "tick": ticks[0][2]["tick"]},
        {"tasks": 2, "tick": ticks[1][2]["tick"]}]
    assert ready[0][1] <= ticks[0][0]
    assert ticks[0][1] <= ready[1][0] and ready[1][1] <= ticks[1][0]
    sorts = sorted(events["hq/cycle/ready/mn_sort"])
    assert [s[2] for s in sorts] == [{"queued": 0}, {"queued": 1}]
    assert all(ready[1][0] <= s0 and s1 <= ready[1][1]
               for s0, s1, _ in sorts)


def test_gang_rows_span_says_what_it_examined_and_swept(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from hyperqueue_tpu.server import reactor
    from hyperqueue_tpu.server.task import TaskState

    env = _env("numpy", workers=2, tasks=4)
    env.schedule()
    env.start_all_assigned()
    gangs = env.submit(n=40, rqv=env.rqv(n_nodes=2))
    env.schedule()                   # host phase: both workers drain
    assert env.core.mn_reservations == {gangs[0]: set(env.core.workers)}
    jax.profiler.start_trace(str(tmp_path))
    try:
        reactor.fused_gang_rows(env.core, {})
        env.core.tasks[gangs[1]].state = TaskState.CANCELED
        reactor.fused_gang_rows(env.core, {})
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    stats = sorted(
        (e.start_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name == "hq/tick/gangs/rows")
    assert [s for _, s in stats] == [
        {"examined": 16, "swept": 2}, {"examined": 17, "swept": 0}]
    assert (env.core.mn_examined_total, env.core.mn_swept_total) == (33, 2)


def test_gang_inputs_span_says_whether_it_walked(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from hyperqueue_tpu.server import reactor

    env = _env("numpy", workers=3, tasks=4)
    cache = env.core.tick_cache
    snap = cache.sync(env.core)
    jax.profiler.start_trace(str(tmp_path))
    try:
        reactor.fused_gang_inputs(env.core, snap.worker_ids, {})
        reactor.fused_gang_inputs(env.core, snap.worker_ids, {})
        reactor.fused_gang_inputs(env.core, list(snap.worker_ids), {})
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    stats = sorted(
        (e.start_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name == "hq/tick/gangs/inputs")
    assert [s for _, s in stats] == [
        {"walked": 1}, {"walked": 0}, {"walked": 1}]
    assert (cache.gang_input_walks, cache.gang_input_reads) == (2, 1)


def test_server_stats_show_sync_unattributed_and_shares_of_total(tmp_path):
    from hyperqueue_tpu.utils.metrics import (
        histogram_summary,
        parse_exposition,
        scrape,
    )

    with HqEnv(tmp_path) as env:
        env.start_server("--metrics-port", "0")
        env.start_worker("--zero-worker", cpus=4)
        env.wait_workers(1)
        env.command(["submit", "--array", "0-19", "--wait", "--", "true"])
        stats = json.loads(env.command(
            ["server", "stats", "--output-mode", "json"]))
        phases = stats["tick"]["phases"]
        assert {"sync", "sync/build", "unattributed", "cycle/ready",
                "total"} <= set(phases)
        shares = stats["tick_shares"]
        assert not [k for k in shares if k.startswith("cycle/")]
        assert sum(v for k, v in shares.items() if "/" not in k) == \
            pytest.approx(1.0, abs=5e-3)
        assert stats["mn_queue"] == {
            "queued": 0, "reserved_for": 0, "examined_total": 0,
            "swept_total": 0}
        text = env.command(["server", "stats"])
        rows = {ln.split()[0]: ln.split() for ln in text.splitlines()
                if ln.strip()}
        assert rows["phase"][-1] == "share"
        assert "gang queue: 0 queued, 0 holding reservations, " \
            "0 entries examined, 0 workers swept by fused ticks" in text
        # no gang row met: the snapshot built no gang columns
        assert (stats["tick_cache"]["gang_input_reads"],
                stats["tick_cache"]["gang_input_walks"]) == (0, 0)
        assert "gang inputs 0 read, 0 walked" in text
        # name, mean, last, max and, inside `total` only, the share
        assert len(rows["sync"]) == len(rows["unattributed"]) == 5
        assert len(rows["cycle/ready"]) == len(rows["total"]) == 4
        port = json.loads(env.command(
            ["server", "info", "--output-mode", "json"]))["metrics_port"]
        series = histogram_summary(
            parse_exposition(scrape("127.0.0.1", port)),
            "hq_tick_phase_seconds")
        for phase in ("sync", "unattributed", "cycle/ready"):
            assert any(f"phase={phase}" in key for key in series), phase
