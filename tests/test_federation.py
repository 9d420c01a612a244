"""Federated control plane (ISSUE 11): sharded servers, fenced failover,
cross-shard worker lending.

Unit tier: torn-access-record retry, atomic lease claim races + fencing,
the strided job-id partition, plan_lending, and the server-uid lineage
fence across a failover. E2e tier: job-id routing + fan-out over two live
shards, and the chaos gate — kill -9 a shard mid-chunked-submit while a
LENT worker runs one of its tasks; the standby's promotion must restore
the journal, absorb the stream replay exactly-once, and reattach the
worker's running task without re-execution (one unbroken trace).
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path

import pytest

from hyperqueue_tpu.client.connection import ClientSession, SubmitStream
from utils_e2e import HqEnv, wait_until

pytestmark = pytest.mark.federation


# ---------------------------------------------------------------------------
# satellite: load_access tolerates a torn/mid-rewrite record
# ---------------------------------------------------------------------------
def _publish_instance(server_dir: Path, record_json: str) -> Path:
    instance = server_dir / "001"
    instance.mkdir(parents=True)
    (server_dir / "hq-current").symlink_to("001")
    (instance / "access.json").write_text(record_json)
    return instance


def _valid_record() -> str:
    return json.dumps({
        "version": 1, "server_uid": "u1",
        "client": {"host": "h", "port": 1, "key": None},
        "worker": {"host": "h", "port": 2, "key": None},
    })


def test_load_access_rides_out_torn_record(tmp_path):
    """Failover rewrites the access record while workers/clients re-read
    it: a reader catching a torn state retries briefly and succeeds once
    the atomic publish lands."""
    from hyperqueue_tpu.utils import serverdir

    instance = _publish_instance(tmp_path, '{"version": 1, "server_')

    def heal():
        time.sleep(0.15)
        tmp = instance / ".access.json.tmp"
        tmp.write_text(_valid_record())
        tmp.replace(instance / "access.json")

    t = threading.Thread(target=heal)
    t.start()
    try:
        access = serverdir.load_access(tmp_path, retry_secs=2.0)
    finally:
        t.join()
    assert access.server_uid == "u1"
    assert access.client_port == 1


def test_load_access_torn_forever_still_raises(tmp_path):
    from hyperqueue_tpu.utils import serverdir

    _publish_instance(tmp_path, "not json at all")
    t0 = time.monotonic()
    with pytest.raises(ValueError):
        serverdir.load_access(tmp_path, retry_secs=0.2)
    assert time.monotonic() - t0 >= 0.2  # it did retry for the window


def test_load_access_missing_record_in_live_instance_retries(tmp_path):
    """The window between the hq-current flip and the access-file rename:
    retry; but with NO symlink at all fail fast (no server)."""
    from hyperqueue_tpu.utils import serverdir

    with pytest.raises(FileNotFoundError):
        serverdir.load_access(tmp_path, retry_secs=0.1)  # no symlink

    instance = tmp_path / "001"
    instance.mkdir()
    (tmp_path / "hq-current").symlink_to("001")

    def publish():
        time.sleep(0.15)
        (instance / "access.json").write_text(_valid_record())

    t = threading.Thread(target=publish)
    t.start()
    try:
        access = serverdir.load_access(tmp_path, retry_secs=2.0)
    finally:
        t.join()
    assert access.server_uid == "u1"


# ---------------------------------------------------------------------------
# job-id partition
# ---------------------------------------------------------------------------
def test_strided_job_id_partition():
    from hyperqueue_tpu.ids import IdCounter
    from hyperqueue_tpu.utils.serverdir import shard_for_job

    n = 3
    counters = [IdCounter(start=k + 1, stride=n) for k in range(n)]
    seen = set()
    for k, c in enumerate(counters):
        for _ in range(5):
            job_id = c.next()
            assert shard_for_job(job_id, n) == k
            seen.add(job_id)
    assert len(seen) == 15  # no collisions across shards

    # ensure_above keeps the congruence class (restore watermarks land
    # mid-class all the time)
    c = IdCounter(start=2, stride=3)  # shard 1 of 3: 2, 5, 8, ...
    c.ensure_above(9)
    assert c.peek() == 11 and shard_for_job(c.next(), 3) == 1

    # stride-1 behaves exactly like the classic counter
    c = IdCounter()
    c.ensure_above(7)
    assert c.next() == 8


def test_federation_descriptor_roundtrip_and_conflict(tmp_path):
    from hyperqueue_tpu.utils import serverdir

    assert serverdir.load_federation(tmp_path) is None
    serverdir.write_federation(tmp_path, 4)
    fed = serverdir.load_federation(tmp_path)
    assert fed["shard_count"] == 4
    assert serverdir.shard_path(tmp_path, 2).is_dir()
    # idempotent re-publish; conflicting shard count is a hard error
    serverdir.write_federation(tmp_path, 4)
    with pytest.raises(ValueError):
        serverdir.write_federation(tmp_path, 8)
    assert serverdir.shard_id_of(serverdir.shard_path(tmp_path, 2)) == 2
    assert serverdir.shard_id_of(tmp_path) is None


# ---------------------------------------------------------------------------
# lease: claim atomicity, staleness, fencing
# ---------------------------------------------------------------------------
def test_lease_lifecycle_and_fence(tmp_path):
    from hyperqueue_tpu.utils.lease import LeaseHeldError, ShardLease

    a = ShardLease(tmp_path, timeout=0.3)
    rec = a.acquire("holder-a")
    assert rec["epoch"] == 1 and a.state() == "held"
    assert a.renew() is True

    # a live holder blocks claimers
    b = ShardLease(tmp_path, timeout=0.3)
    with pytest.raises(LeaseHeldError):
        b.acquire("holder-b")

    # holder dies (stops renewing) -> stale -> takeover bumps the epoch
    time.sleep(0.35)
    assert b.state() == "stale"
    rec_b = b.acquire("holder-b")
    assert rec_b["epoch"] == 2

    # the old incarnation wakes up post-fence: renew refuses, and its
    # release must NOT delete the successor's lease
    assert a.renew() is False
    a.release()
    assert b.read()["owner"] == "holder-b"
    assert b.renew() is True

    # clean shutdown retires the lease: nothing left to fail over
    b.release()
    assert b.state() == "absent"


def test_lease_claim_race_exactly_one_winner(tmp_path):
    """Two would-be successors race for a dead shard: the O_EXCL claim
    lock admits exactly one; losers back off with LeaseRaceLost /
    LeaseHeldError (the lease-safety regression from the issue)."""
    from hyperqueue_tpu.utils.lease import (
        LeaseError,
        ShardLease,
    )

    dead = ShardLease(tmp_path, timeout=0.1)
    dead.acquire("dead-shard")
    time.sleep(0.15)  # let it go stale

    n = 8
    barrier = threading.Barrier(n)
    results: list[tuple[str, bool]] = []
    lock = threading.Lock()

    def claim(uid: str) -> None:
        lease = ShardLease(tmp_path, timeout=0.1)
        barrier.wait()
        try:
            lease.acquire(uid)
            won = True
        except LeaseError:
            won = False
        with lock:
            results.append((uid, won))

    threads = [
        threading.Thread(target=claim, args=(f"claimer-{i}",))
        for i in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    winners = [uid for uid, won in results if won]
    assert len(winners) == 1, results
    final = ShardLease(tmp_path, timeout=0.1).read()
    assert final["owner"] == winners[0]
    assert final["epoch"] == 2


def test_claim_lock_held_then_released(tmp_path):
    """A mutation in flight holds the flock: concurrent claimers back
    off with LeaseRaceLost; once the lock drops (including a claimer
    DYING mid-claim — the kernel releases flocks on process death, so a
    crash leaves no debris to break) the retry wins."""
    import fcntl

    from hyperqueue_tpu.utils.lease import LeaseRaceLost, ShardLease

    dead = ShardLease(tmp_path, timeout=0.1)
    dead.acquire("dead-shard")
    time.sleep(0.15)

    # simulate an in-flight claim: hold the flock from another fd
    fd = os.open(tmp_path / "lease.lock", os.O_CREAT | os.O_RDWR)
    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
    lease = ShardLease(tmp_path, timeout=0.1)
    with pytest.raises(LeaseRaceLost):
        lease.acquire("successor")
    os.close(fd)  # the in-flight claimer "dies": flock auto-released
    assert lease.acquire("successor")["epoch"] == 2


def test_renew_under_claim_lock_cannot_overwrite_successor(tmp_path):
    """The fencing-inversion regression: an owner paused mid-renew must
    not overwrite a successor's claim when it resumes — renew's
    read-check-write shares the flock with claims, so the resumed owner
    either blocks the claim (lock held) or sees the new epoch (lock
    released) and fences itself. Never both alive."""
    from hyperqueue_tpu.utils.lease import ShardLease

    owner = ShardLease(tmp_path, timeout=0.1)
    owner.acquire("owner")
    time.sleep(0.15)  # owner "paused": lease goes stale

    successor = ShardLease(tmp_path, timeout=0.1)
    successor.acquire("successor")  # epoch 2

    # the owner resumes and runs its renew: same lock, fresh read —
    # it must observe the successor's claim and fence, NOT overwrite
    assert owner.renew() is False
    assert successor.read()["owner"] == "successor"
    assert successor.renew() is True  # successor is unaffected


# ---------------------------------------------------------------------------
# lending plan (pure function)
# ---------------------------------------------------------------------------
def _sample(ready=0, workers=(), reasons=None, age=0.0):
    return {
        "time": time.time() - age,
        "ready": ready,
        "mn_queued": 0,
        "n_workers": len(workers),
        "workers": [
            {"id": wid, "running": running, "prefilled": 0}
            for wid, running in workers
        ],
        "pending_reasons": reasons or {},
    }


def test_plan_lending_moves_idle_capacity_to_backlog():
    from hyperqueue_tpu.server.federation import plan_lending

    moves = plan_lending({
        0: _sample(ready=0, workers=[(1, 0), (2, 0)]),
        1: _sample(ready=5, workers=[]),
    })
    assert moves == [{"from": 0, "worker_id": 2, "to": 1}]

    # a shard whose workers are all busy needs the insufficient-capacity
    # reason code before it borrows (backlog alone may just be one tick
    # of latency)
    moves = plan_lending({
        0: _sample(ready=0, workers=[(1, 0)]),
        1: _sample(ready=5, workers=[(9, 3)]),
    })
    assert moves == []
    moves = plan_lending({
        0: _sample(ready=0, workers=[(1, 0)]),
        1: _sample(ready=5, workers=[(9, 3)],
                   reasons={"insufficient-capacity": 5}),
    })
    assert moves == [{"from": 0, "worker_id": 1, "to": 1}]


def test_plan_lending_never_lends_from_backlogged_or_stale_shards():
    from hyperqueue_tpu.server.federation import plan_lending

    # the only idle worker sits on a shard with its own backlog
    assert plan_lending({
        0: _sample(ready=2, workers=[(1, 0)]),
        1: _sample(ready=5, workers=[]),
    }) == []
    # a stale sample neither lends nor borrows (dead data)
    assert plan_lending({
        0: _sample(ready=0, workers=[(1, 0)], age=60.0),
        1: _sample(ready=5, workers=[]),
    }) == []
    assert plan_lending({
        0: _sample(ready=0, workers=[(1, 0)]),
        1: None,
    }) == []
    # one worker per borrower per round, neediest first
    moves = plan_lending({
        0: _sample(ready=0, workers=[(1, 0), (2, 0), (3, 0)]),
        1: _sample(ready=5, workers=[]),
        2: _sample(ready=9, workers=[]),
    })
    assert [m["to"] for m in moves] == [2, 1]
    assert len({m["worker_id"] for m in moves}) == 2

    # a refused worker (wrong policy, raced busy) is excluded so the
    # planner moves on to a lendable sibling instead of starving the
    # borrower on the same doomed pick every round
    samples = {
        0: _sample(ready=0, workers=[(1, 0), (2, 0)]),
        1: _sample(ready=5, workers=[]),
    }
    first = plan_lending(samples)[0]["worker_id"]
    retry = plan_lending(samples, exclude={(0, first)})
    assert retry and retry[0]["worker_id"] != first
    assert plan_lending(samples, exclude={(0, 1), (0, 2)}) == []


# ---------------------------------------------------------------------------
# lineage fence across failover (extends the server-uid reattach fence)
# ---------------------------------------------------------------------------
def test_reattach_lineage_fence_across_failover(tmp_path):
    """After a failover, the successor restored the dead shard's journal:
    a worker reattaching with the DEAD incarnation's server uid is the
    same lineage (accepted); a worker claiming a uid that never wrote
    this journal is a different server's numbering (rejected)."""
    from hyperqueue_tpu.events.journal import Journal
    from hyperqueue_tpu.events.restore import restore_from_journal
    from hyperqueue_tpu.ids import make_task_id
    from hyperqueue_tpu.resources.descriptor import (
        ResourceDescriptor,
        ResourceDescriptorItem,
    )
    from hyperqueue_tpu.server.bootstrap import Server
    from hyperqueue_tpu.server.worker import Worker, WorkerConfiguration

    journal = tmp_path / "j.bin"
    j = Journal(journal)
    j.open_for_append()
    for rec in [
        {"event": "server-uid", "server_uid": "uid-dead-shard", "seq": 0,
         "time": 1.0},
        {"event": "job-submitted", "job": 1, "seq": 1, "time": 2.0,
         "desc": {"name": "j", "tasks": [{"id": 0, "body": {}},
                                         {"id": 1, "body": {}}]},
         "n_tasks": 2},
        {"event": "task-started", "job": 1, "task": 0, "instance": 0,
         "variant": 0, "workers": [1], "seq": 2, "time": 3.0},
        {"event": "task-started", "job": 1, "task": 1, "instance": 0,
         "variant": 0, "workers": [1], "seq": 3, "time": 3.5},
    ]:
        j.write(rec)
    j.close()

    # the successor (promoted standby) restores the dead shard's journal
    successor = Server(
        server_dir=tmp_path / "shard-0000", journal_path=journal,
        reattach_timeout=60.0, promoted=True,
    )
    restore_from_journal(successor)
    successor.journal_uids.add("uid-successor")  # its own boot record
    held = make_task_id(1, 0)
    held2 = make_task_id(1, 1)
    assert held in successor.reattach_pending

    def make_worker():
        config = WorkerConfiguration(
            descriptor=ResourceDescriptor(
                items=(ResourceDescriptorItem.range("cpus", 0, 3),)
            )
        )
        return Worker.create(
            successor.core.worker_id_counter.next(), config,
            successor.core.resource_map,
        )

    # same lineage: the dead incarnation's uid wrote this journal
    reattached, discard = successor._process_reattach(
        {"worker_id": 1, "server_uid": "uid-dead-shard",
         "running": [{"id": held, "instance": 0, "variant": 0}]},
        make_worker(),
    )
    assert reattached == [held] and discard == []

    # foreign lineage: a uid that never wrote this journal — every claim
    # is discarded (task ids could collide at instance 0)
    reattached, discard = successor._process_reattach(
        {"worker_id": 7, "server_uid": "uid-other-federation",
         "running": [{"id": held2, "instance": 0, "variant": 0}]},
        make_worker(),
    )
    assert reattached == [] and discard == [held2]
    assert held2 in successor.reattach_pending  # still claimable by its
    # true owner within the window


# ---------------------------------------------------------------------------
# e2e: routing, fan-out, lending
# ---------------------------------------------------------------------------
def _shard_stats(env, shard: int) -> dict:
    return json.loads(env.command(
        ["server", "stats", "--shard", str(shard), "--output-mode", "json"]
    ))


def test_federated_routing_fanout_and_lending(tmp_path):
    """Two live shards: job ids land in each shard's partition, job list
    fans out, the federation block reports shard identity, and the
    standby's coordinator lends the idle worker to the starved shard."""
    with HqEnv(tmp_path) as env:
        env.start_shard(0, 2, "--lease-timeout", "2")
        env.start_shard(1, 2, "--lease-timeout", "2")
        env.start_standby(
            "--lease-timeout", "2", "--coordinator-interval", "0.25"
        )
        env.start_worker("--shard", "0", "--on-server-lost",
                         "reconnect", cpus=2)
        env.wait_workers(1)

        os.environ["HQ_SHARD"] = "0"
        try:
            out = env.command(["submit", "--array", "0-3", "--", "true"])
            assert "job ID: 1" in out  # (1-1) % 2 == 0 -> shard 0
            os.environ["HQ_SHARD"] = "1"
            out = env.command(["submit", "--array", "0-3", "--", "true"])
            assert "job ID: 2" in out  # (2-1) % 2 == 1 -> shard 1
        finally:
            os.environ.pop("HQ_SHARD", None)

        # fan-out job list sees both shards' jobs
        jobs = json.loads(
            env.command(["job", "list", "--all", "--output-mode", "json"])
        )
        assert sorted(j["id"] for j in jobs) == [1, 2]

        # shard-0 job completes with its local worker; shard-1 job has no
        # worker of its own — the coordinator must lend the idle one over
        env.command(["job", "wait", "1"], timeout=60)
        env.command(["job", "wait", "2"], timeout=60)

        stats0 = _shard_stats(env, 0)
        stats1 = _shard_stats(env, 1)
        assert stats0["federation"]["shard_id"] == 0
        assert stats0["federation"]["shard_count"] == 2
        assert stats0["federation"]["workers_lent"] >= 1
        assert stats1["federation"]["workers_borrowed"] >= 1
        assert stats1["federation"]["lease_owner"]
        info = json.loads(env.command(
            ["server", "info", "--shard", "1", "--output-mode", "json"]
        ))
        assert info["federation"]["partition"] == "(job_id - 1) % 2 == 1"

        # --shard all fans out: one record per shard
        all_info = json.loads(env.command(
            ["server", "info", "--shard", "all", "--output-mode", "json"]
        ))
        assert [
            r["federation"]["shard_id"] for r in all_info["shards"]
        ] == [0, 1]


@pytest.mark.chaos
def test_sigstop_fence_hands_workers_to_successor(tmp_path):
    """A shard paused past its lease timeout (SIGSTOP — the VM-pause
    case) is claimed by the standby; when the old incarnation resumes it
    must fence itself WITHOUT stopping its workers: they belong to the
    successor now, and a `stop` op would kill the fleet the promotion
    just inherited. The worker must reconnect, reattach its running
    task (one instance), and finish the job on the successor."""
    import signal

    with HqEnv(tmp_path) as env:
        env.start_shard(0, 2, "--lease-timeout", "1")
        env.start_shard(1, 2, "--lease-timeout", "1")
        env.start_standby("--lease-timeout", "1", "--no-coordinator")
        worker = env.start_worker("--shard", "1", "--on-server-lost",
                                  "reconnect", cpus=2)
        env.wait_workers(1)

        marker = env.work_dir / "starts.txt"
        flag = env.work_dir / "flag"
        os.environ["HQ_SHARD"] = "1"
        try:
            env.command([
                "submit", "--", "bash", "-c",
                f'echo "start:$HQ_TASK_ID:$HQ_INSTANCE_ID" >> {marker}; '
                f"while [ ! -f {flag} ]; do sleep 0.2; done",
            ])
        finally:
            os.environ.pop("HQ_SHARD", None)
        wait_until(lambda: marker.exists(), message="task started")

        shard1 = next(p for n, p in env.processes if n == "shard1-0")
        shard1.send_signal(signal.SIGSTOP)
        try:
            # promotion is visible on disk (epoch bump) without talking
            # to anyone — the paused incarnation still holds its client
            # socket open and must not be allowed to wedge the test
            lease_path = env.shard_dir(1) / "lease.json"
            wait_until(
                lambda: json.loads(lease_path.read_text())["epoch"] == 2,
                timeout=30, message="standby promotion (lease epoch 2)",
            )
        finally:
            shard1.send_signal(signal.SIGCONT)

        # the resumed incarnation fences itself and EXITS — without
        # taking the worker with it
        wait_until(lambda: shard1.poll() is not None, timeout=30,
                   message="fenced incarnation stopped")
        assert worker.poll() is None, env.read_log("worker0")

        def reattached():
            jobs = json.loads(env.command(
                ["job", "list", "--all", "--output-mode", "json"]
            ))
            return jobs and jobs[0]["counters"]["running"] == 1

        wait_until(reattached, timeout=30, message="task reattached")
        flag.touch()
        env.command(["job", "wait", "all"], timeout=60)
        assert marker.read_text().splitlines() == ["start:0:0"]
        assert worker.poll() is None


# ---------------------------------------------------------------------------
# chaos gate: kill -9 a shard mid-chunked-submit with a lent worker
# running one of its tasks
# ---------------------------------------------------------------------------
@pytest.mark.chaos
def test_kill9_shard_failover_exactly_once(tmp_path):
    """The ISSUE 11 chaos gate, all in one death: shard 1 borrows a
    worker (manual worker_lend — determinism over coordinator timing),
    runs a blocked task on it, and is kill -9'd mid-chunked-submit. The
    standby claims the lease, restores the journal, and the choreography
    completes: the SubmitStream replays its unacked chunks exactly-once,
    the lent worker reattaches its running task to the successor (one
    instance, no re-execution, one closed trace), and the job finishes."""
    n_chunks, chunk = 8, 25
    with HqEnv(tmp_path) as env:
        env.start_shard(0, 2, "--lease-timeout", "1")
        env.start_shard(1, 2, "--lease-timeout", "1",
                        "--lazy-array-threshold", "10")
        env.start_standby("--lease-timeout", "1", "--no-coordinator")
        env.start_worker("--shard", "0", "--on-server-lost",
                         "reconnect", cpus=2)
        env.wait_workers(1)

        # lend the idle worker 0 -> 1 (the coordinator's RPC, driven
        # directly so the test is deterministic)
        with ClientSession(env.shard_dir(0)) as s0:
            resp = s0.request(
                {"op": "worker_lend", "worker_id": 1, "to_shard": 1}
            )
        assert resp["lent"] is True

        def borrowed():
            return _shard_stats(env, 1)["federation"]["workers_borrowed"]

        wait_until(lambda: borrowed() == 1, message="worker lent to shard 1")

        # a long-running task on the BORROWED worker, owned by shard 1
        marker = env.work_dir / "starts.txt"
        flag = env.work_dir / "flag"
        os.environ["HQ_SHARD"] = "1"
        try:
            env.command([
                "submit", "--", "bash", "-c",
                f'echo "start:$HQ_TASK_ID:$HQ_INSTANCE_ID" >> {marker}; '
                f"while [ ! -f {flag} ]; do sleep 0.2; done",
            ])
        finally:
            os.environ.pop("HQ_SHARD", None)
        wait_until(lambda: marker.exists(), message="task started")

        # chunked stream into shard 1: half acked, then kill -9 mid-stream
        body = {"cmd": ["true"], "env": {},
                "submit_dir": str(env.work_dir)}
        with ClientSession(env.shard_dir(1)) as s1:
            stream = SubmitStream(
                s1, {"name": "survivor", "submit_dir": str(env.work_dir)}
            )
            for i in range(n_chunks // 2):
                stream.send_chunk(array={
                    "id_range": [i * chunk, (i + 1) * chunk],
                    "body": dict(body), "request": {},
                    "priority": 0, "crash_limit": 5,
                })
            while stream._unacked:
                stream._recv_ack()
            assert stream.job_id is not None

            killed_at = time.monotonic()
            env.kill_process("shard1-0")

            # the stream's own retry machinery rides out the failover:
            # remaining chunks replay against the promoted successor
            for i in range(n_chunks // 2, n_chunks):
                stream.send_chunk(array={
                    "id_range": [i * chunk, (i + 1) * chunk],
                    "body": dict(body), "request": {},
                    "priority": 0, "crash_limit": 5,
                })
            job_id, n_tasks = stream.finish()
        failover_s = time.monotonic() - killed_at
        assert n_tasks == n_chunks * chunk

        # the successor is a promoted instance over the SAME shard dir
        stats1 = _shard_stats(env, 1)
        assert stats1["federation"]["promoted"] is True
        assert stats1["federation"]["lease_epoch"] == 2

        # exactly-once across the failover: every task id exactly once
        info = json.loads(env.command(
            ["job", "info", str(job_id), "--output-mode", "json"]
        ))[0]
        assert info["n_tasks"] == n_chunks * chunk
        ids = [t["id"] for t in info["tasks"]]
        assert sorted(ids) == list(range(n_chunks * chunk))

        # the lent worker reattached its running task to the successor:
        # release it and require ONE start, instance 0, job finished
        def reattached():
            jobs = json.loads(env.command(
                ["job", "list", "--all", "--output-mode", "json"]
            ))
            row = next(j for j in jobs if j["name"] == "bash")
            return row["counters"]["running"] == 1

        wait_until(reattached, timeout=30, message="task reattached")
        flag.touch()
        env.command(["job", "wait", "all"], timeout=120)
        starts = marker.read_text().splitlines()
        assert starts == ["start:0:0"], starts  # no re-execution

        # one unbroken trace for the reattached task (submit -> run ->
        # commit spans survive the shard death)
        jobs = json.loads(env.command(
            ["job", "list", "--all", "--output-mode", "json"]
        ))
        bash_job = next(j for j in jobs if j["name"] == "bash")["id"]
        trace = json.loads(env.command(
            ["task", "trace", f"{bash_job}.0", "--output-mode", "json"]
        ))
        names = {s["name"] for s in trace["spans"]}
        assert trace["closed"], trace
        assert "worker/run" in names and "server/commit" in names
        # the failover is bounded: a generous cap for the slow CI box (how
        # long it takes is measured nowhere)
        assert failover_s < 60.0
