"""Fleet observability (ISSUE 15): cross-shard metrics federation,
stitched lend/failover traces, and the fleet feed.

Unit tier: exposition relabel/merge, trace-store annotations (dedupe,
snapshot round trip), FleetFeed fan-in semantics (shard tagging,
DOWN→UP transitions) against fake subscribe generators, and the
down-fleet exposition (every shard visible as shard_up 0). E2e tier:
2 shards + standby + a lent worker — kill -9 the task's owning shard
mid-run; the fleet feed must show the DOWN→UP transition across the
promotion, the metrics proxy must serve both shards under distinct
shard labels, and the stitched `hq task trace` must stay ONE closed
trace carrying both the lend and the failover annotation.
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path

import pytest

from hyperqueue_tpu.client.connection import ClientSession
from utils_e2e import HqEnv, start_fleet_proxy, wait_until

pytestmark = pytest.mark.federation


# ---------------------------------------------------------------------------
# exposition relabel + merge (the metrics proxy's building blocks)
# ---------------------------------------------------------------------------
def test_merge_expositions_groups_metrics_under_one_header():
    from hyperqueue_tpu.utils.metrics import (
        MetricsRegistry,
        merge_expositions,
        parse_exposition,
    )

    r0 = MetricsRegistry()
    r0.counter("hq_x_total", "x").inc(3)
    r0.gauge("hq_g", "g", labels=("k",)).labels("a").set(1.5)
    r0.histogram("hq_h_seconds", "h").observe(0.002)
    r1 = MetricsRegistry()
    r1.counter("hq_x_total", "x").inc(7)
    r1.gauge("hq_only_one", "solo").set(9)

    merged = merge_expositions({"0": r0.render(), "1": r1.render()})
    # the text format forbids a metric appearing under two headers
    assert merged.count("# TYPE hq_x_total counter") == 1
    parsed = parse_exposition(merged)
    samples = parsed["hq_x_total"]["samples"]
    assert samples[("hq_x_total", frozenset({("shard", "0")}))] == 3.0
    assert samples[("hq_x_total", frozenset({("shard", "1")}))] == 7.0
    # existing labels keep their values next to the injected shard label
    assert parsed["hq_g"]["samples"][
        ("hq_g", frozenset({("shard", "0"), ("k", "a")}))
    ] == 1.5
    # histogram child samples (_bucket/_sum/_count) travel with their base
    assert parsed["hq_h_seconds"]["type"] == "histogram"
    assert ("hq_h_seconds_count", frozenset({("shard", "0")})) in (
        parsed["hq_h_seconds"]["samples"]
    )
    # a metric present on one shard only still renders
    assert parsed["hq_only_one"]["samples"][
        ("hq_only_one", frozenset({("shard", "1")}))
    ] == 9.0


# ---------------------------------------------------------------------------
# trace annotations: dedupe + snapshot/seed round trip
# ---------------------------------------------------------------------------
def test_trace_annotations_dedupe_and_roundtrip():
    from hyperqueue_tpu.utils.trace import TaskTraceStore

    store = TaskTraceStore(capacity=8)
    store.begin(1, "t-1")
    store.begin(2, "t-2")
    lend = {"kind": "lend", "worker": 5, "home_shard": 0,
            "host_shard": 1, "instance": 0, "time": 10.0}
    store.annotate(1, lend)
    # replay re-reports the same fact (different wall stamp): ONE note
    store.annotate(1, {**lend, "time": 11.0})
    assert len(store.get(1)["notes"]) == 1
    # a different identity (new instance) is a new note
    store.annotate(1, {**lend, "instance": 1})
    assert len(store.get(1)["notes"]) == 2

    # failover stamps every OPEN trace; closed ones keep their history
    store.close(2)
    stamped = store.annotate_open(
        {"kind": "failover", "shard": 1, "lease_epoch": 2, "time": 12.0}
    )
    assert stamped == 1
    assert "notes" not in store.get(2)
    kinds = [n["kind"] for n in store.get(1)["notes"]]
    assert kinds == ["lend", "lend", "failover"]

    # snapshot_live copies notes; seed adopts them; annotate still dedups
    snap = store.snapshot_live([1])
    fresh = TaskTraceStore(capacity=8)
    fresh.seed(1, snap[1])
    fresh.annotate(1, dict(lend))  # replayed journal fact
    assert len(fresh.get(1)["notes"]) == 3
    # the copies are independent of the source store
    snap[1]["notes"][0]["worker"] = 99
    assert store.get(1)["notes"][0]["worker"] == 5

    # disabled store: annotate is a no-op, not a crash
    off = TaskTraceStore(capacity=0)
    off.annotate(1, dict(lend))


def test_restore_keeps_lend_note_across_home_shard_restart():
    """A borrowed-worker start followed by a home-shard restart must not
    lose the lend annotation on restore: lends accumulate across
    task-started events instead of riding only the LAST wtrace (which
    each start overwrites)."""
    from types import SimpleNamespace

    from hyperqueue_tpu.events.restore import (
        _rebuild_traces,
        _replay_record,
        _RestoreAcc,
    )
    from hyperqueue_tpu.ids import make_task_id
    from hyperqueue_tpu.utils.trace import TaskTraceStore

    acc = _RestoreAcc()
    server = SimpleNamespace(
        core=SimpleNamespace(traces=TaskTraceStore(capacity=8)),
        shard_id=1,
    )
    for rec in (
        {"event": "task-started", "job": 2, "task": 0, "instance": 0,
         "workers": [7], "trace": {"id": "t-1", "lends": [[7, 0]]}},
        {"event": "task-restarted", "job": 2, "task": 0, "instance": 1,
         "crash_count": 1},
        # the restart runs on a HOME worker: no lends key, and this
        # event's wtrace is the one that sticks
        {"event": "task-started", "job": 2, "task": 0, "instance": 1,
         "workers": [3], "trace": {"id": "t-1"}},
    ):
        _replay_record(server, acc, rec)
    _rebuild_traces(server, acc)
    notes = server.core.traces.get(make_task_id(2, 0))["notes"]
    assert [
        (n["kind"], n["worker"], n["home_shard"], n["instance"])
        for n in notes
    ] == [("lend", 7, 0, 0)]


# ---------------------------------------------------------------------------
# FleetFeed fan-in against fake subscribe generators
# ---------------------------------------------------------------------------
def test_fleet_feed_tags_merges_and_rides_shard_death(tmp_path, monkeypatch):
    from hyperqueue_tpu.client import connection
    from hyperqueue_tpu.client.fleet import FleetFeed
    from hyperqueue_tpu.utils import serverdir

    serverdir.write_federation(tmp_path, 2)
    attempts: dict[int, int] = {0: 0, 1: 0}

    def fake_subscribe(server_dir, filters=(), sample_interval=0.0,
                       buffer=4096, overviews=False, on_subscribed=None,
                       shard=0, on_connected=None):
        if on_connected is not None:
            on_connected(lambda: None)
        shard_id = serverdir.shard_id_of(Path(server_dir))
        attempts[shard_id] = attempts.get(shard_id, 0) + 1
        yield {"op": "sub_live", "seq": 0}
        yield {"op": "events", "records": [
            {"event": "task-finished", "job": 1, "task": 0, "time": 1.0},
        ]}
        yield {"op": "sample", "time": 1.0, "ready": shard_id}
        if shard_id == 1 and attempts[1] == 1:
            # shard 1 "dies" once, then its successor answers
            raise ConnectionError("kill -9")
        # stay "live" until the feed stops
        while True:
            time.sleep(0.05)
            yield {"op": "sample", "time": 2.0, "ready": shard_id}

    monkeypatch.setattr(connection, "subscribe", fake_subscribe)
    feed = FleetFeed(tmp_path, sample_interval=0.1, retry_delay=0.1)
    seen: list[dict] = []
    with feed:
        deadline = time.monotonic() + 10.0
        for frame in feed.frames(timeout=1.0):
            seen.append(frame)
            ups = [f for f in seen
                   if f["op"] == "shard-up" and f["shard"] == 1]
            downs = [f for f in seen if f["op"] == "shard-down"]
            if len(ups) >= 2 and downs:
                break
            assert time.monotonic() < deadline, seen

    # every frame carries the shard dimension
    assert all("shard" in f for f in seen)
    # events records are tagged individually too
    ev = next(f for f in seen if f["op"] == "events" and f["shard"] == 0)
    assert ev["records"][0]["shard"] == 0
    assert ev["records"][0]["event"] == "task-finished"
    # samples tagged with their shard
    assert {f["shard"] for f in seen if f["op"] == "sample"} == {0, 1}
    # the death was a DOWN marker + a resumed UP, never an exception
    downs = [f for f in seen if f["op"] == "shard-down"]
    assert downs and downs[0]["shard"] == 1
    assert attempts[1] >= 2  # it re-resolved and resubscribed
    assert feed.states[1] == "up"


def test_fleet_exposition_all_shards_down_still_visible(tmp_path):
    """No shard running at all: the fleet exposition still renders, one
    hq_federation_shard_up 0 row per shard — dead shards are data, not
    errors."""
    from hyperqueue_tpu.client.fleet import build_fleet_exposition
    from hyperqueue_tpu.utils import serverdir
    from hyperqueue_tpu.utils.metrics import parse_exposition

    serverdir.write_federation(tmp_path, 3)
    text = build_fleet_exposition(tmp_path, retry_window=0.0)
    parsed = parse_exposition(text)
    samples = parsed["hq_federation_shard_up"]["samples"]
    for k in range(3):
        assert samples[(
            "hq_federation_shard_up", frozenset({("shard", str(k))})
        )] == 0.0


def test_fleet_surfaces_reject_classic_server_dir(tmp_path):
    from hyperqueue_tpu.client.fleet import FleetFeed, shard_count_of

    with pytest.raises(ValueError):
        shard_count_of(tmp_path)
    with pytest.raises(ValueError):
        FleetFeed(tmp_path)


# ---------------------------------------------------------------------------
# e2e: the acceptance scenario — 2 shards + standby + lent worker,
# kill -9 the task's owning shard mid-run
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_fleet_feed_proxy_and_stitched_trace_across_failover(tmp_path):
    from hyperqueue_tpu.client.fleet import FleetFeed
    from hyperqueue_tpu.utils.metrics import parse_exposition, scrape

    with HqEnv(tmp_path) as env:
        env.start_shard(0, 2, "--lease-timeout", "1")
        env.start_shard(1, 2, "--lease-timeout", "1")
        env.start_standby("--lease-timeout", "1", "--no-coordinator")
        env.start_worker("--shard", "0", "--on-server-lost",
                         "reconnect", cpus=2)
        env.wait_workers(1)

        # the feed attaches BEFORE the lend so the structured lend event
        # lands in a live subscription (subscribe has no history replay)
        feed = FleetFeed(env.server_dir, sample_interval=0.3,
                         retry_delay=0.3)
        feed.start()
        frames: list[dict] = []
        collector_stop = threading.Event()

        def collect() -> None:
            for frame in feed.frames(timeout=2.0):
                frames.append(frame)
                if collector_stop.is_set():
                    return

        collector = threading.Thread(target=collect, daemon=True)
        collector.start()
        wait_until(
            lambda: all(s == "up" for s in feed.states.values()),
            message="fleet feed live on both shards",
        )

        # lend the idle worker 0 -> 1 (driven directly for determinism)
        with ClientSession(env.shard_dir(0)) as s0:
            assert s0.request(
                {"op": "worker_lend", "worker_id": 1, "to_shard": 1}
            )["lent"] is True

        def borrowed() -> bool:
            stats = json.loads(env.command(
                ["server", "stats", "--shard", "1",
                 "--output-mode", "json"]
            ))
            return stats["federation"]["workers_borrowed"] == 1

        wait_until(borrowed, message="worker lent to shard 1")

        # a blocked task owned by shard 1, running on the BORROWED worker
        # (shard 1's strided id counter allocates (job_id-1) % 2 == 1)
        marker = env.work_dir / "starts.txt"
        flag = env.work_dir / "flag"
        os.environ["HQ_SHARD"] = "1"
        try:
            submit_out = env.command([
                "submit", "--", "bash", "-c",
                f'echo "start:$HQ_TASK_ID:$HQ_INSTANCE_ID" >> {marker}; '
                f"while [ ! -f {flag} ]; do sleep 0.2; done",
            ])
        finally:
            os.environ.pop("HQ_SHARD", None)
        job_id = int(submit_out.split("job ID: ")[1].split()[0])
        assert job_id % 2 == 0  # (job_id - 1) % 2 == 1 -> shard 1
        wait_until(lambda: marker.exists(), message="task started")

        # --- feed completeness: an array on shard 1 (its only worker is
        # the borrowed one): every task-finished event reaches the feed
        # exactly once, under the label of the shard that owns the job
        n_array = 10
        os.environ["HQ_SHARD"] = "1"
        try:
            array_out = env.command([
                "submit", "--array", f"0-{n_array - 1}", "--", "true",
            ])
        finally:
            os.environ.pop("HQ_SHARD", None)
        array_job = int(array_out.split("job ID: ")[1].split()[0])
        env.command(["job", "wait", str(array_job)], timeout=60)

        def finished_seen() -> dict:
            seen: dict = {}
            for frame in list(frames):
                if frame.get("op") != "events":
                    continue
                for rec in frame["records"]:
                    if rec.get("event") == "task-finished" \
                            and rec["job"] == array_job:
                        key = (rec["shard"], rec["task"])
                        seen[key] = seen.get(key, 0) + 1
            return seen

        wait_until(lambda: len(finished_seen()) >= n_array,
                   message="fleet feed saw every task-finished event")
        assert finished_seen() == {(1, t): 1 for t in range(n_array)}

        # --- metrics proxy: one scrape covers both shards -------------
        port = start_fleet_proxy(env.server_dir)
        text = scrape("127.0.0.1", port)
        parsed = parse_exposition(text)
        up = parsed["hq_federation_shard_up"]["samples"]
        for k in ("0", "1"):
            assert up[(
                "hq_federation_shard_up", frozenset({("shard", k)})
            )] == 1.0
        workers = parsed["hq_workers_connected"]["samples"]
        # the lent worker is registered with shard 1 now
        assert workers[(
            "hq_workers_connected", frozenset({("shard", "1")})
        )] == 1.0
        assert workers[(
            "hq_workers_connected", frozenset({("shard", "0")})
        )] == 0.0

        # fleet view --once over the federation root: every shard a row
        top = json.loads(env.command(
            ["top", "--once", "--output-mode", "json"]
        ))
        assert set(top["shards"]) == {"0", "1"}
        assert top["shards"]["1"]["federation"]["workers_borrowed"] == 1

        # --- kill -9 the task's owning shard mid-run ------------------
        env.kill_process("shard1-0")

        def saw(op: str, shard: int) -> bool:
            return any(
                f["op"] == op and f["shard"] == shard for f in frames
            )

        # the feed flips shard 1 DOWN, then back UP once the standby
        # promotes — the client-side contract: markers, not crashes
        wait_until(lambda: saw("shard-down", 1), timeout=30,
                   message="fleet feed DOWN marker for shard 1")

        def up_after_down() -> bool:
            snapshot = list(frames)
            down_i = next(
                (i for i, f in enumerate(snapshot)
                 if f["op"] == "shard-down" and f["shard"] == 1), None,
            )
            return down_i is not None and any(
                f["op"] == "shard-up" and f["shard"] == 1
                for f in snapshot[down_i + 1:]
            )

        wait_until(up_after_down, timeout=30,
                   message="fleet feed UP after promotion")

        # promoted successor visible in the feed's sample
        def promoted_sample() -> bool:
            s = feed.last_sample.get(1)
            return bool(s and (s.get("federation") or {}).get("promoted"))

        wait_until(promoted_sample, timeout=30,
                   message="promoted flag in fleet sample")

        # scrape again: both shards up (successor serves shard 1)
        parsed2 = parse_exposition(scrape("127.0.0.1", port))
        assert parsed2["hq_federation_shard_up"]["samples"][(
            "hq_federation_shard_up", frozenset({("shard", "1")})
        )] == 1.0

        # --- task finishes after reattach; trace is stitched ----------
        def reattached() -> bool:
            jobs = json.loads(env.command(
                ["job", "list", "--all", "--output-mode", "json"]
            ))
            return bool(jobs) and jobs[0]["counters"]["running"] == 1

        wait_until(reattached, timeout=30, message="task reattached")
        flag.touch()
        env.command(["job", "wait", "all"], timeout=60)
        assert marker.read_text().splitlines() == ["start:0:0"]

        # `hq task trace` routes through the federation root to the
        # owning shard; ONE closed trace with BOTH fleet annotations
        trace = json.loads(env.command(
            ["task", "trace", f"{job_id}.0", "--output-mode", "json"]
        ))
        assert trace["closed"], trace
        names = {s["name"] for s in trace["spans"]}
        assert "worker/run" in names and "server/commit" in names
        notes = {n["kind"]: n for n in trace.get("annotations") or ()}
        assert notes["lend"]["home_shard"] == 0
        assert notes["lend"]["host_shard"] == 1
        assert notes["failover"]["shard"] == 1
        assert notes["failover"]["lease_epoch"] == 2

        # structured lending flow reached the feed (no string parsing)
        lends = [
            rec
            for f in frames if f["op"] == "events"
            for rec in f["records"]
            if rec.get("event") == "worker-lost"
            and rec.get("lent_to") is not None
        ]
        assert lends and lends[0]["shard"] == 0
        assert lends[0]["lent_to"] == 1

        # --- satellite: reset-metrics --shard all fans out ------------
        out = env.command(["server", "reset-metrics", "--shard", "all"])
        assert "shard 0: metrics reset" in out
        assert "shard 1: metrics reset" in out

        # --- fleet trace export: a row group per shard + lend marker --
        out_path = env.work_dir / "fleet-trace.json"
        env.command(["fleet", "trace-export", str(out_path)])
        fleet_trace = json.loads(out_path.read_text())
        events = fleet_trace["traceEvents"]
        proc_names = {
            e["args"]["name"] for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        assert any(n.startswith("shard 0:") for n in proc_names)
        assert any(n.startswith("shard 1:") for n in proc_names)
        lend_marks = [e for e in events if e.get("cat") == "lend"]
        assert any("lend worker" in e["name"] for e in lend_marks)
        # shard 1 journals two boots: the original + the promotion
        boots1 = [
            e for e in events
            if e.get("cat") == "fleet" and "boot" in e.get("name", "")
            and 100 <= e.get("pid", 0) < 200
        ]
        assert len(boots1) >= 2, boots1

        collector_stop.set()
        feed.stop()
