"""Continuous profiling plane tests (ISSUE 19).

Unit coverage for the dependency-free sampling profiler — bounded
folded trie, plane-label registry, idle classification, golden folded
output, stall burst and Perfetto counter-track views, overhead — plus
e2e coverage for `hq server profile`, the per-plane CPU block in stats,
reset-metrics, profile-on-stall dumps, and the worker overview
piggyback that feeds `hq top` fleet CPU attribution.
"""

from __future__ import annotations

import json
import threading
import time
from pathlib import Path

import pytest

from hyperqueue_tpu.utils import clock
from hyperqueue_tpu.utils.profiler import (
    TRUNCATED,
    FoldedTrie,
    SamplingProfiler,
    diff_counts,
    is_wait_leaf,
    plane_of,
    register_plane,
    register_plane_prefix,
    registered_planes,
    render_folded,
    unregister_plane,
)
from utils_e2e import HqEnv, wait_until

pytestmark = pytest.mark.profile


# ----------------------------------------------------------- folded trie
def test_folded_trie_counts_and_golden_render():
    trie = FoldedTrie()
    trie.fold("reactor", ("main.run", "loop.tick"), 3)
    trie.fold("reactor", ("main.run", "loop.tick", "solve.call"))
    trie.fold("solve", ("worker.loop",), 2)
    counts = trie.counts()
    assert counts == {
        "reactor;main.run;loop.tick": 3,
        "reactor;main.run;loop.tick;solve.call": 1,
        "solve;worker.loop": 2,
    }
    # golden: flamegraph folded text, one `stack count` line, sorted
    assert render_folded(counts) == (
        "reactor;main.run;loop.tick 3\n"
        "reactor;main.run;loop.tick;solve.call 1\n"
        "solve;worker.loop 2\n"
    )


def test_folded_trie_bounded_memory_truncated_sink():
    trie = FoldedTrie(max_nodes=64)
    n_folds = 500
    for i in range(n_folds):
        # every stack unique: must blow the node budget quickly
        trie.fold("plane", (f"mod.f{i}", f"mod.g{i}", f"mod.h{i}"))
    # the bound holds no matter how many unique stacks arrive (+1 slack
    # for the pre-budgeted per-level (truncated) sink node)
    assert trie.nodes <= trie.max_nodes + 1
    assert trie.dropped > 0
    counts = trie.counts()
    # no sample is lost — long-tail stacks degrade into the sink
    assert sum(counts.values()) == n_folds
    assert any(TRUNCATED in stack for stack in counts)
    # clear() releases everything
    trie.clear()
    assert trie.nodes == 0 and trie.dropped == 0 and trie.counts() == {}


def test_folded_trie_minimum_budget_clamped():
    trie = FoldedTrie(max_nodes=1)
    assert trie.max_nodes == 64
    trie.fold("p", ("a.b",))
    assert trie.counts() == {"p;a.b": 1}


def test_diff_counts_window_view():
    before = {"p;a": 5, "p;b": 2, "p;gone": 9}
    after = {"p;a": 8, "p;b": 2, "p;new": 4, "p;gone": 9}
    # only positive growth survives: unchanged and disappeared drop out
    assert diff_counts(after, before) == {"p;a": 3, "p;new": 4}


# --------------------------------------------------------- plane registry
def test_plane_registration_unregistration_and_restart():
    ident = 999_000_001  # fake thread ident — never collides with a real one
    register_plane("journal", ident=ident)
    assert registered_planes()[ident] == "journal"
    assert plane_of(ident, "whatever") == "journal"
    # a restarted thread re-registers and simply overwrites
    register_plane("journal-v2", ident=ident)
    assert plane_of(ident, "whatever") == "journal-v2"
    unregister_plane(ident=ident)
    assert ident not in registered_planes()
    # double-unregister is a no-op
    unregister_plane(ident=ident)


def test_plane_prefix_fallback_for_pool_threads():
    # ThreadPoolExecutor names lazily-spawned workers `<prefix>_N` long
    # after the pool existed to register anything — name-prefix fallback
    assert plane_of(999_000_002, "hq-fanout_3") == "fanout"
    assert plane_of(999_000_002, "hq-journal") == "journal"
    assert plane_of(999_000_002, "hq-solve-watchdog") == "solve"
    assert plane_of(999_000_002, "hq-device-solver_0") == "solve"
    assert plane_of(999_000_002, "ThreadPoolExecutor-0_1") == "other"
    # explicit registration wins over the prefix table
    register_plane("special", ident=999_000_003)
    try:
        assert plane_of(999_000_003, "hq-fanout_0") == "special"
    finally:
        unregister_plane(ident=999_000_003)
    # a new prefix can be added (and re-pointed) at runtime
    register_plane_prefix("hq-proftest", "proftest")
    assert plane_of(999_000_004, "hq-proftest_7") == "proftest"
    register_plane_prefix("hq-proftest", "proftest2")
    assert plane_of(999_000_004, "hq-proftest_7") == "proftest2"


def test_wait_leaf_classification():
    assert is_wait_leaf("/usr/lib/python3.10/threading.py", "wait")
    assert is_wait_leaf("/usr/lib/python3.10/selectors.py", "select")
    assert is_wait_leaf("queue.py", "get")
    assert not is_wait_leaf("/usr/lib/python3.10/threading.py", "run")
    assert not is_wait_leaf("myapp.py", "wait")


# ------------------------------------------------- deterministic sampling
class _Threads:
    """One busy thread + one parked thread, each plane-registered."""

    def __init__(self):
        self.stop = threading.Event()
        self.parked = threading.Event()
        self.busy = threading.Thread(
            target=self._spin, name="proftest-busy", daemon=True
        )
        self.waiter = threading.Thread(
            target=self._park, name="proftest-park", daemon=True
        )

    def _spin(self):
        register_plane("busyplane")
        try:
            while not self.stop.is_set():
                sum(i * i for i in range(500))
        finally:
            unregister_plane()

    def _park(self):
        register_plane("parkplane")
        try:
            self.parked.wait()  # leaf = threading.py:wait -> idle
        finally:
            unregister_plane()

    def __enter__(self):
        self.busy.start()
        self.waiter.start()
        time.sleep(0.05)  # let both reach their steady state
        return self

    def __exit__(self, *exc):
        self.stop.set()
        self.parked.set()
        self.busy.join(timeout=2)
        self.waiter.join(timeout=2)


def test_sample_once_attributes_active_vs_idle():
    prof = SamplingProfiler(hz=50.0)
    with _Threads():
        for _ in range(8):
            prof.sample_once(skip={threading.get_ident()})
            time.sleep(0.01)
        shares = prof.plane_shares()
    # the spinning thread is ACTIVE CPU on its plane
    assert shares["busyplane"]["samples"] == 8
    assert shares["busyplane"]["active"] >= 6
    assert shares["busyplane"]["cpu"] > 0.5
    # the parked thread is sampled but idle: blocked in threading.wait
    assert shares["parkplane"]["samples"] == 8
    assert shares["parkplane"]["active"] == 0
    assert shares["parkplane"]["cpu"] == 0.0
    # folded stacks carry the plane prefix and the registered function
    folded = prof.folded_counts()
    busy_stacks = [s for s in folded if s.startswith("busyplane;")]
    assert busy_stacks and any("_spin" in s for s in busy_stacks)
    assert prof.passes == 8
    assert prof.samples >= 16
    snap = prof.snapshot()
    assert snap["window_passes"] == 8
    assert snap["trie"]["nodes"] > 0


def test_stall_burst_and_counter_track_views():
    prof = SamplingProfiler(hz=50.0)
    with _Threads():
        for _ in range(6):
            prof.sample_once(skip={threading.get_ident()})
            time.sleep(0.01)
    burst = prof.stall_burst(window_s=30.0, limit=40)
    assert burst, "ring should hold the recent samples"
    by_plane = {row["plane"] for row in burst}
    assert "busyplane" in by_plane and "parkplane" in by_plane
    # rows aggregate identical stacks and sort by count desc
    counts = [row["count"] for row in burst]
    assert counts == sorted(counts, reverse=True)
    assert all(
        set(row) == {"plane", "stack", "active", "count"} for row in burst
    )
    # limit is honoured
    assert len(prof.stall_burst(window_s=30.0, limit=1)) == 1
    # an empty window (cutoff in the future) yields nothing
    assert prof.stall_burst(window_s=0.0) == []
    # the Perfetto counter track only counts ACTIVE samples
    track = prof.counter_track(bucket_s=0.5)
    assert "busyplane" in track
    assert "parkplane" not in track
    for series in track.values():
        assert all(cores > 0 for _t, cores in series)


def test_profiler_start_stop_reset_lifecycle():
    prof = SamplingProfiler(hz=97.0)
    assert not prof.running
    try:
        assert prof.start()
        assert prof.start()  # idempotent
        assert prof.running
        wait_until(lambda: prof.passes >= 3 or None, timeout=5,
                   message="sampling passes")
    finally:
        prof.stop()
    assert not prof.running
    assert prof.passes >= 3 and prof.samples > 0
    prof.reset()
    assert prof.passes == 0 and prof.samples == 0
    assert prof.folded_counts() == {} and len(prof.ring) == 0


def test_profiler_refuses_simulated_clock():
    class FakeClock:
        def time(self):
            return 0.0

        def monotonic(self):
            return 0.0

    prof = SamplingProfiler(hz=50.0)
    prev = clock.install(FakeClock())
    try:
        assert clock.is_simulated()
        assert prof.start() is False
        assert not prof.running
    finally:
        clock.install(prev)
    # hz <= 0 refuses too
    assert SamplingProfiler(hz=0.0).start() is False


def test_sampling_overhead_is_small():
    """Lenient unit-level overhead gate: a fixed CPU workload with the
    sampler running at 19 Hz must not take wildly longer than without."""

    def work():
        t0 = time.perf_counter()
        acc = 0
        for i in range(400_000):
            acc += i * i
        return time.perf_counter() - t0

    off_times, on_times = [], []
    prof = SamplingProfiler(hz=19.0)
    for _ in range(3):  # interleaved trials absorb machine noise
        off_times.append(work())
        assert prof.start()
        try:
            on_times.append(work())
        finally:
            prof.stop()
    assert min(on_times) < min(off_times) * 2.0, (
        f"sampling overhead too high: on={on_times} off={off_times}"
    )


# ------------------------------------------------------------------- e2e
def test_server_profile_cli_stats_block_and_reset(tmp_path):
    """`hq server profile` emits folded stacks, stats carry the per-plane
    CPU block, and reset-metrics clears the profiler aggregates."""
    with HqEnv(tmp_path) as env:
        env.start_server("--profile-hz", "47")
        env.command(["submit", "--array", "0-9", "--", "true"])

        def sampled():
            stats = json.loads(env.command(
                ["server", "stats", "--output-mode", "json"]
            ))
            prof = stats.get("profile") or {}
            return prof if prof.get("passes", 0) >= 10 else None

        prof = wait_until(sampled, timeout=15, message="profiler passes")
        assert prof["enabled"] and prof["hz"] == 47.0
        assert prof["planes"], "per-plane shares should be populated"
        assert prof["samples"] > 0 and prof["trie"]["nodes"] > 0
        for agg in prof["planes"].values():
            assert set(agg) == {"samples", "active", "cpu"}

        # human stats output renders the CPU block
        text = env.command(["server", "stats"])
        assert "cpu plane" in text and "Hz sampler" in text

        # folded output: non-comment `stack count` lines, reactor present
        out = env.command(["server", "profile"])
        lines = [ln for ln in out.splitlines()
                 if ln.strip() and not ln.startswith("#")]
        assert lines
        planes_seen = {ln.split(";", 1)[0] for ln in lines}
        assert "reactor" in planes_seen
        for ln in lines:
            stack, _, count = ln.rpartition(" ")
            assert stack and int(count) > 0

        # windowed + json mode
        result = json.loads(env.command(
            ["server", "profile", "--seconds", "0.3", "--format", "json"]
        ))
        assert result["mode"] == "continuous"
        assert result["seconds"] == 0.3
        assert result["passes"] >= 5  # ~14 expected at 47 Hz
        assert "folded" in result

        # the trace export carries the sampler's per-plane cpu counter
        # tracks (only active samples count, so at least the reactor's)
        trace_path = tmp_path / "trace.json"
        env.command(["server", "trace", "export", str(trace_path)])
        counters = [
            e for e in json.loads(trace_path.read_text())["traceEvents"]
            if e.get("ph") == "C" and str(e.get("name")).startswith("cpu ")
        ]
        assert counters and all(e["pid"] == 2 for e in counters)

        # reset-metrics clears the profiler aggregates (steady-state
        # measurement contract) but sampling continues
        pre = json.loads(env.command(
            ["server", "stats", "--output-mode", "json"]
        ))["profile"]["passes"]
        env.command(["server", "reset-metrics"])
        post = json.loads(env.command(
            ["server", "stats", "--output-mode", "json"]
        ))["profile"]
        assert post["passes"] < pre
        assert post["enabled"], "reset must not stop the sampler"


def test_profile_burst_on_unprofiled_server(tmp_path):
    """A `--profile-hz 0` server still answers `hq server profile` with a
    throwaway burst sampler covering the requested window."""
    with HqEnv(tmp_path) as env:
        env.start_server("--profile-hz", "0")
        stats = json.loads(env.command(
            ["server", "stats", "--output-mode", "json"]
        ))
        assert not (stats.get("profile") or {}).get("enabled")
        result = json.loads(env.command(
            ["server", "profile", "--seconds", "0.5", "--format", "json"]
        ))
        assert result["mode"] == "burst"
        assert result["passes"] > 0
        assert result["folded"]
        # the burst sampler is throwaway: the server stays unprofiled
        stats = json.loads(env.command(
            ["server", "stats", "--output-mode", "json"]
        ))
        assert not (stats.get("profile") or {}).get("enabled")


def test_profile_on_stall_dump_names_solve_plane(tmp_path):
    """PR 8 stall detector + ISSUE 19: the auto-captured stall dump
    attaches the stack burst from the stall window, and the chaos-delayed
    solve shows up as solve-plane samples."""
    plan = json.dumps({
        "rules": [
            {"site": "solve", "action": "delay", "delay_ms": 600, "at": 1}
        ]
    })
    with HqEnv(tmp_path) as env:
        env.start_server("--stall-budget", "0.15", "--profile-hz", "47",
                         env_extra={"HQ_FAULT_PLAN": plan})
        env.start_worker("--zero-worker", cpus=4)
        env.wait_workers(1)
        env.command(["submit", "--array", "0-3", "--wait", "--", "true"],
                    timeout=60)

        def stalled():
            stats = json.loads(env.command(
                ["server", "stats", "--output-mode", "json"]
            ))
            return stats["stalls"]["captured"] >= 1 and stats["stalls"]

        stalls = wait_until(stalled, timeout=20, message="stall capture")
        dump = json.loads(Path(stalls["last"]["dump"]).read_text())
        assert dump["plane"] == "solve"
        burst = dump.get("profile")
        assert burst, "stall dump must attach the profile burst"
        assert all(
            set(row) >= {"plane", "stack", "active", "count"}
            for row in burst
        )
        # the delayed solve was sampled ON the solve plane, active
        solve_rows = [r for r in burst if r["plane"] == "solve"]
        assert solve_rows, f"no solve-plane rows in {burst}"
        assert any(r["active"] for r in solve_rows)


def test_worker_plane_shares_piggyback_to_top(tmp_path):
    """Bugfix satellite: workers piggyback hq_worker_profile_plane_cpu_share
    on overviews, so the `hq top` fleet view attributes worker CPU without
    any per-worker scrape."""
    with HqEnv(tmp_path) as env:
        env.start_server("--profile-hz", "29")
        env.start_worker("--zero-worker", "--overview-interval", "0.2",
                         "--profile-hz", "29", cpus=4)
        env.wait_workers(1)
        env.command(["submit", "--array", "0-19", "--wait", "--", "true"])

        def worker_planes():
            top = json.loads(env.command(
                ["top", "--once", "--output-mode", "json"]
            ))
            rows = top.get("workers") or []
            if rows and rows[0].get("planes"):
                return top
            return None

        top = wait_until(worker_planes, timeout=20,
                         message="piggybacked worker plane shares")
        planes = top["workers"][0]["planes"]
        # the worker runtime thread registered itself
        assert "runtime" in planes
        assert all(isinstance(v, (int, float)) for v in planes.values())
        # the server-side sample carries its own plane shares too
        assert top.get("profile"), "server plane shares missing from sample"
        assert "reactor" in top["profile"]
