"""The tick's phases are timed through one primitive (`TRACER.phase`): the
new spans are where the work happens, children lie inside their parents in
the `phases` dict and in a profiler trace, a process without JAX stays
without it, and the benchmark's new readers read them (ISSUE 25)."""

import glob
import os
import statistics
import subprocess
import sys
from pathlib import Path

import pytest

from hyperqueue_tpu.models.greedy import GreedyCutScanModel
from hyperqueue_tpu.scheduler.tick import create_batches, run_tick
from hyperqueue_tpu.scheduler.tick_cache import TickPhaseStats
from hyperqueue_tpu.utils.trace import TRACER

from utils_env import TestEnv, displace_workers

ROOT = Path(__file__).resolve().parent.parent
DEVICE_CHILDREN = ("solve_dispatch/upload", "solve_dispatch/launch",
                   "device_sync/counts", "device_sync/state")
MODEL_PHASES = ("assemble", "solve_host_prep", "solve_host_prep/visit",
                "solve_dispatch", "device_sync", "mapping")


def _env(backend: str, workers: int = 3, tasks: int = 40) -> TestEnv:
    env = TestEnv(model=GreedyCutScanModel(backend=backend))
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
                  "prefill/displace": 1.0, "total": 4.5})
    shares = stats.shares()
    assert "total" not in shares
    assert shares["assemble"] + shares["prefill"] == pytest.approx(1.0)
    assert shares["prefill/fill"] == pytest.approx(0.5)


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


PHASE_READERS = {
    "upload_ms": "solve_dispatch/upload",
    "launch_ms": "solve_dispatch/launch",
    "counts_wait_ms": "device_sync/counts",
    "state_readback_ms": "device_sync/state",
}


@pytest.mark.parametrize("metric", sorted(PHASE_READERS))
def test_span_reader_gives_the_median_or_nothing(metric):
    from chipbench import manifest

    read = manifest.metric_reader(metric)
    key = PHASE_READERS[metric]
    ticks = [{"assemble": 1.0, key: v} for v in (0.4, 0.2, 9.0)]
    assert read({"tick_phases_ms": ticks}) == 0.4
    # the parent commit's program: the old keys, not this one
    assert read({"tick_phases_ms": [{"assemble": 1.0}] * 3}) is None
    assert read({}) is None
    entry = next(m for m in manifest.load()["per_layer"]
                 if m["name"] == metric)
    # the tick cell, since PR 27 the sharded cell, since PR 31 the
    # `flat-1k` tick cell and the gang cell, and since PR 33 the sharded
    # gang cell, whose drivers pass the same `tick_phases_ms`
    assert entry["workloads"] == [
        "hetero-1k.backlog-1m", "shard-16k.backlog", "flat-1k.backlog-1m",
        "gang-1k.rigid", "gang-16k.campaign"]
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
