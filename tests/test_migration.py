"""Elastic resharding (ISSUE 17): journaled job ownership, exactly-once
live migration, and online shard add.

Three tiers:

- ownership-log unit tests (claim/commit/finish/abort state machine,
  double-claim fencing, added-shard id-block routing, resolver);
- federated-simulator scenarios: the migration kill matrix (source,
  destination, and driver each killed at every protocol phase), the
  SIGSTOP'd-source fence, O(chunks) lazy-job moves, and online N -> N+1
  — all on one virtual clock under the always-on invariant monitor;
- real-process end-to-ends: the rebalancer moving a job off a hot shard,
  and live migration under a pinned HQ_SHARD
  session, including a chunked submit stream that follows the job to
  its new shard mid-stream.
"""

from __future__ import annotations

import asyncio
import json
import os

import pytest

from hyperqueue_tpu.utils import serverdir
from hyperqueue_tpu.utils.ownership import (
    ADDED_ID_BASE,
    MigrationClaimed,
    OwnershipError,
    OwnershipStore,
    added_shard_block,
)
from utils_e2e import HqEnv, wait_until

pytestmark = pytest.mark.federation


# ---------------------------------------------------------------------------
# ownership log: the journaled source of truth
# ---------------------------------------------------------------------------
def _store(root, shards: int = 2) -> OwnershipStore:
    serverdir.write_federation(root, shards)
    return OwnershipStore(root)


def test_ownership_modulo_baseline(tmp_path):
    m = _store(tmp_path, 4).load()
    assert m.epoch == 0
    assert [m.shard_for_job(j) for j in (1, 2, 3, 4, 5)] == [0, 1, 2, 3, 0]


def test_migration_protocol_phases(tmp_path):
    store = _store(tmp_path)
    rec = store.begin_migration(1, 0, 1, mig="m-1")
    assert rec["kind"] == "migration-intent"
    m = store.load()
    # an intent does NOT move ownership; the job is merely in flight
    assert m.shard_for_job(1) == 0
    assert [r["phase"] for r in m.in_flight()] == ["exporting"]
    store.commit_migration("m-1")
    m = store.load()
    # commit is the linearization point of the transfer
    assert m.shard_for_job(1) == 1
    assert [r["phase"] for r in m.in_flight()] == ["finalizing"]
    assert m.epoch > 0
    store.finish_migration("m-1")
    m = store.load()
    assert not m.in_flight()
    assert m.shard_for_job(1) == 1      # assignment survives retirement
    assert m.owned_counts().get(1) == 1


def test_double_claim_of_same_job_is_fenced(tmp_path):
    store = _store(tmp_path)
    store.begin_migration(1, 0, 1, mig="m-a")
    # a DIFFERENT migration of the same job must not get a second claim
    with pytest.raises(MigrationClaimed):
        store.begin_migration(1, 0, 1, mig="m-b")
    # ... but the SAME mig uid re-claims its own record (crashed driver)
    again = store.begin_migration(1, 0, 1, mig="m-a")
    assert again["mig"] == "m-a"
    store.abort_migration("m-a")
    # retired uids can never be claimed again
    with pytest.raises(OwnershipError):
        store.begin_migration(1, 0, 1, mig="m-a")


def test_claim_by_non_owner_rejected(tmp_path):
    store = _store(tmp_path)
    with pytest.raises(OwnershipError):
        store.begin_migration(1, 1, 0, mig="m-x")  # job 1 lives on shard 0


def test_abort_refused_after_commit(tmp_path):
    store = _store(tmp_path)
    store.begin_migration(1, 0, 1, mig="m-c")
    store.commit_migration("m-c")
    with pytest.raises(OwnershipError):
        store.abort_migration("m-c")    # ownership moved; only finish
    store.finish_migration("m-c")
    # retirement makes both idempotent no-ops
    assert store.abort_migration("m-c") is None
    assert store.finish_migration("m-c") is None


def test_added_shard_id_block_routing(tmp_path):
    store = _store(tmp_path)
    serverdir.grow_federation(tmp_path, 3)
    m = store.load()
    assert (m.base_shard_count, m.shard_count) == (2, 3)
    lo, hi = added_shard_block(2, 2)
    assert lo == ADDED_ID_BASE
    # the new shard's reserved id block routes to it without any journal
    assert m.shard_for_job(lo + 1) == 2
    assert m.shard_for_job(hi) == 2
    # pre-existing ids keep the FROZEN boot-time modulo partition
    assert m.shard_for_job(1) == 0 and m.shard_for_job(2) == 1
    # shrinking is a hard error; re-growing to the same count is a no-op
    with pytest.raises(ValueError):
        serverdir.grow_federation(tmp_path, 2)
    serverdir.grow_federation(tmp_path, 3)
    # an explicit assignment (completed migration) overrides every level
    store.begin_migration(1, 0, 2, mig="m-g")
    store.commit_migration("m-g")
    store.finish_migration("m-g")
    assert store.load().shard_for_job(1) == 2


def test_resolver_consults_ownership_log(tmp_path):
    from hyperqueue_tpu.client.routing import Resolver

    serverdir.write_federation(tmp_path, 2)
    r = Resolver(tmp_path, 2)
    assert r.shard_for_job(1) == 0      # modulo until something moves
    store = OwnershipStore(tmp_path)
    store.begin_migration(1, 0, 1, mig="m-r")
    store.commit_migration("m-r")
    store.finish_migration("m-r")
    r.refresh()
    assert r.shard_for_job(1) == 1
    assert r.shard_for_job(2) == 1      # untouched ids stay on modulo


def test_plan_rebalance_hysteresis():
    from hyperqueue_tpu.server.federation import plan_rebalance
    from hyperqueue_tpu.utils import clock

    now = clock.now()

    def sample(ready):
        return {"ready": ready, "time": now}

    # hot shard over 1.5x the mean with real slack: move hot -> cold
    plan = plan_rebalance({0: sample(30), 1: sample(2), 2: sample(1)})
    assert plan is not None and (plan["from"], plan["to"]) == (0, 2)
    assert plan["ratio"] > 1.5
    # near-balanced fleet sits still (hysteresis band)
    assert plan_rebalance({0: sample(5), 1: sample(4)}) is None
    # an all-idle fleet never rebalances
    assert plan_rebalance({0: sample(0), 1: sample(0)}) is None
    # one live sample is not a fleet
    assert plan_rebalance({0: sample(30), 1: None}) is None


# ---------------------------------------------------------------------------
# federated simulator: the chaos-gated migration matrix
# ---------------------------------------------------------------------------
def _array(n: int, dur_ms: int = 100, lo: int = 0) -> dict:
    return {
        "id_range": [lo, lo + n],
        "body": {"cmd": ["sim"], "sim": {"dur_ms": dur_ms}},
        "request": {}, "priority": 0, "crash_limit": 5,
    }


def test_sim_live_migration_green():
    """Baseline: a running job moves shard 0 -> 1 mid-execution; every
    task still finishes exactly once and ownership lands on 1."""
    from hyperqueue_tpu.sim.federation import FederatedSimulation

    async def scenario(fed):
        reply = await fed.submit(0, {"name": "live",
                                     "array": _array(20, 500)})
        job = reply["job_id"]
        await asyncio.sleep(1.0)
        out = await fed.migrate(job, 1)
        assert out is not None and out["job"] == job
        omap = fed.store().load()
        assert omap.shard_for_job(job) == 1
        assert not omap.in_flight()

    fed = FederatedSimulation(shard_count=2, seed=11)
    res = fed.run(scenario)
    assert res["audit"]["tasks_terminal"] == 20
    assert not res["violations"]
    assert res["shard_boots"] == [1, 1]


def test_sim_migration_round_trip_clears_tombstone():
    """A job that migrates 0 -> 1 -> 0 is SERVED by shard 0 again: the
    wrong-shard tombstone from the first export dies with the re-import
    (a returning job must not redirect forever) — and the same holds
    across a kill -9 of the home shard, whose journal replays the
    migration-out-done tombstone BEFORE the migration-in that voids it."""
    from hyperqueue_tpu.sim.federation import FederatedSimulation

    async def scenario(fed):
        reply = await fed.submit(0, {"name": "boomerang",
                                     "array": _array(12, 2000)})
        job = reply["job_id"]
        await asyncio.sleep(0.5)
        assert (await fed.migrate(job, 1)) is not None
        await asyncio.sleep(0.5)
        assert (await fed.migrate(job, 0)) is not None
        omap = fed.store().load()
        assert omap.shard_for_job(job) == 0
        src = fed.shards[0].server
        assert job not in src.migrated_out
        assert job not in src.migrating_out
        info = await fed.rpc(0, {"op": "job_info", "job_ids": [job]})
        assert info["jobs"][0]["id"] == job
        # restore path: the replayed journal must reach the same state
        await fed.kill_shard(0)
        await asyncio.sleep(10.0)
        restored = fed.shards[0].server
        assert job not in restored.migrated_out
        info = await fed.rpc(0, {"op": "job_info", "job_ids": [job]})
        assert info["jobs"][0]["id"] == job

    fed = FederatedSimulation(shard_count=2, seed=31)
    res = fed.run(scenario)
    assert res["audit"]["tasks_terminal"] == 12
    assert not res["violations"]
    assert res["shard_boots"][0] == 2


def test_rebalancer_pick_respects_peak_improvement(tmp_path, monkeypatch):
    """_pick_job never proposes a move that cannot lower the fleet peak:
    a job whose pending count >= the hot-cold gap would leave the
    receiver at least as hot as the donor was, so the next pass would
    move it straight back (the observed ping-pong). Under a cap the
    largest STRICTLY-improving job wins; an indivisible job that is the
    whole backlog stays put."""
    from hyperqueue_tpu.server import federation as fedmod

    jobs = [
        {"id": 1, "n_tasks": 10, "is_open": False,
         "counters": {"finished": 0, "failed": 0, "canceled": 0}},
        {"id": 2, "n_tasks": 4, "is_open": False,
         "counters": {"finished": 1, "failed": 0, "canceled": 0}},
    ]
    monkeypatch.setattr(fedmod, "_shard_rpc",
                        lambda root, shard, msg: {"jobs": jobs})
    coord = fedmod.FederationCoordinator(tmp_path)
    assert coord._pick_job(0) == 1            # unbounded: largest first
    assert coord._pick_job(0, cap=10) == 2    # job 1 mirrors the gap
    assert coord._pick_job(0, cap=3) is None  # nothing improves the peak


# one kill -9 per protocol phase, on each of the three parties. The
# server.event rules fire AFTER the named journal record is durable (the
# worst instant: state committed locally, nobody else told yet); the
# federation.migration rules kill the DRIVER between phases, leaving a
# dangling intent for recovery to re-drive.
KILL_MATRIX = [
    ("source-dies-mid-export",
     {"site": "server.event", "event": "migration-out", "shard": 0,
      "action": "kill", "times": 1}, False),
    ("dest-dies-mid-import",
     {"site": "server.event", "event": "migration-in", "shard": 1,
      "action": "kill", "times": 1}, False),
    ("source-dies-at-finalize",
     {"site": "server.event", "event": "migration-out-done", "shard": 0,
      "action": "kill", "times": 1}, False),
    ("driver-dies-after-claim",
     {"site": "federation.migration", "op": "claim",
      "action": "kill", "times": 1}, True),
    ("driver-dies-after-export",
     {"site": "federation.migration", "op": "export",
      "action": "kill", "times": 1}, True),
    ("driver-dies-after-import",
     {"site": "federation.migration", "op": "import",
      "action": "kill", "times": 1}, True),
    ("driver-dies-after-commit",
     {"site": "federation.migration", "op": "commit",
      "action": "kill", "times": 1}, True),
    ("driver-dies-after-finalize",
     {"site": "federation.migration", "op": "finalize",
      "action": "kill", "times": 1}, True),
]


@pytest.mark.parametrize("name,rule,driver_dies", KILL_MATRIX,
                         ids=[m[0] for m in KILL_MATRIX])
def test_sim_migration_kill_matrix(name, rule, driver_dies):
    """kill -9 at every phase of the protocol: either the migration
    completes transparently (shard kills ride the rpc retry + re-entrant
    handlers) or the driver's dangling intent is re-driven by recovery —
    always ending with exactly one owner and exactly-once execution."""
    from hyperqueue_tpu.sim.federation import FederatedSimulation

    async def scenario(fed):
        reply = await fed.submit(0, {"name": f"mig-{name}",
                                     "array": _array(12, 600)})
        job = reply["job_id"]
        await asyncio.sleep(1.0)
        out = await fed.migrate(job, 1)
        if driver_dies:
            assert out is None          # the driver coroutine was killed
            redone = await fed.recover()
            assert [r["job"] for r in redone if r] == [job]
        else:
            assert out is not None and out["job"] == job
        omap = fed.store().load()
        assert omap.shard_for_job(job) == 1
        assert not omap.in_flight()

    fed = FederatedSimulation(shard_count=2, seed=7, rules=[rule])
    res = fed.run(scenario)
    assert res["audit"]["tasks_terminal"] == 12
    assert not res["violations"]
    if driver_dies:
        assert res["driver_kills"] == 1
    else:
        assert sum(res["shard_boots"]) >= 3  # one shard was kill -9'd


def test_sim_stale_source_worker_is_fenced():
    """SIGSTOP analog: a shard-0 worker partitioned through the whole
    migration never sees the recall, keeps 'running' its task, and
    replays a stale completion when the partition heals — after shard 1
    already took ownership and re-ran the task under a higher instance.
    The fence must discard the stale incarnation (exactly-once holds,
    no double finish anywhere in the fleet)."""
    from hyperqueue_tpu.sim.federation import FederatedSimulation

    async def scenario(fed):
        reply = await fed.submit(0, {"name": "stale",
                                     "array": _array(8, 20_000)})
        job = reply["job_id"]
        await asyncio.sleep(2.0)        # all 8 running on shard 0
        victim = next(w for w in fed.shards[0].workers.values()
                      if w.running)
        stale = {(e.task_id, e.instance) for e in victim.running.values()}
        victim.partition(True)
        out = await fed.migrate(job, 1)
        assert out is not None
        # the destination owns the job BEFORE the stale worker resurfaces
        assert fed.store().load().shard_for_job(job) == 1
        await asyncio.sleep(30.0)       # stale execs "finish" while cut off
        assert victim._done_log         # it really does replay something
        victim.partition(False)
        await asyncio.sleep(10.0)       # reconnect + done-log replay
        # the stale incarnations were never double-counted: each of those
        # tasks finished under a HIGHER instance on the destination
        for task_id, instance in stale:
            newer = [i for (t, i) in fed.monitor.exec_started
                     if t == task_id and i > instance]
            assert newer, (task_id, instance)

    fed = FederatedSimulation(shard_count=2, seed=23)
    res = fed.run(scenario)
    assert res["audit"]["tasks_terminal"] == 8
    assert not res["violations"]


def test_sim_lazy_million_task_migration_moves_chunks():
    """A 2^20-task lazy array migrates in CHUNK form: no materialization
    on the source at export, none on the destination at import — the
    moved state is O(chunks), never O(tasks)."""
    from hyperqueue_tpu.sim.federation import FederatedSimulation

    CHUNK = 1 << 14
    N_CHUNKS = 64                       # 2^20 tasks total

    async def scenario(fed):
        stream = fed.stream(0, uid="lazy-mig", header={"name": "mega"})
        for i in range(N_CHUNKS):
            await stream.send_chunk(
                array={"id_range": [i * CHUNK, (i + 1) * CHUNK],
                       "body": {"cmd": ["sim"]}, "request": {},
                       "priority": 0, "crash_limit": 5},
                last=(i == N_CHUNKS - 1),
            )
        job = stream.job_id
        assert stream.n_tasks == N_CHUNKS * CHUNK
        src = fed.shards[0].server
        stats = src.core.lazy.stats()
        assert stats["unmaterialized"] == N_CHUNKS * CHUNK
        assert stats["materialized_total"] == 0
        out = await fed.migrate(job, 1)
        assert out is not None
        s_src = fed.shards[0].server.core.lazy.stats()
        s_dst = fed.shards[1].server.core.lazy.stats()
        assert s_src["materialized_total"] == 0
        assert s_dst["materialized_total"] == 0
        assert s_dst["unmaterialized"] == N_CHUNKS * CHUNK
        assert s_src["unmaterialized"] == 0     # source forgot in chunk form
        info = await fed.rpc(1, {"op": "job_info", "job_ids": [job]})
        assert info["jobs"][0]["n_tasks"] == N_CHUNKS * CHUNK

    # no workers: nothing may run (running would materialize legitimately)
    fed = FederatedSimulation(shard_count=2, n_workers_per_shard=0, seed=5)
    res = fed.run(scenario)
    assert not res["violations"]


def test_sim_online_shard_add():
    """--shards N -> N+1 with the fleet live: the new shard registers
    (descriptor grows, ownership log records the add), existing shards
    never restart, fresh submits on the new shard draw from its reserved
    id block, and an existing job migrates onto it. Zero task loss."""
    from hyperqueue_tpu.sim.federation import FederatedSimulation

    async def scenario(fed):
        r1 = await fed.submit(0, {"name": "pre", "array": _array(10, 300)})
        new_id = await fed.add_shard()
        assert new_id == 2
        desc = serverdir.load_federation(fed.root)
        assert desc["shard_count"] == 3
        assert desc["base_shard_count"] == 2    # modulo stays frozen
        assert [s.server_boots for s in fed.shards[:2]] == [1, 1]
        omap = fed.store().load()
        assert omap.shard_count == 3
        assert any(int(rec["shard"]) == 2 for rec in omap.shard_adds)
        # a submit on the new shard allocates from its reserved id block
        r2 = await fed.submit(2, {"name": "new", "array": _array(6, 300)})
        assert r2["job_id"] > ADDED_ID_BASE
        assert omap.shard_for_job(r2["job_id"]) == 2
        # an existing job moves onto the new shard
        out = await fed.migrate(r1["job_id"], 2)
        assert out is not None
        assert fed.store().load().shard_for_job(r1["job_id"]) == 2

    fed = FederatedSimulation(shard_count=2, seed=3)
    res = fed.run(scenario)
    assert res["audit"]["tasks_terminal"] == 16
    assert not res["violations"]


def test_sim_shard_add_under_chaos():
    """The chaos gate for elasticity: the new shard is kill -9'd right
    after its first migration import lands; the re-driven protocol must
    still converge to single ownership on the restored incarnation."""
    from hyperqueue_tpu.sim.federation import FederatedSimulation

    async def scenario(fed):
        r1 = await fed.submit(0, {"name": "pre", "array": _array(10, 500)})
        await fed.add_shard()
        await asyncio.sleep(0.5)
        out = await fed.migrate(r1["job_id"], 2)
        assert out is not None and out["to"] == 2
        omap = fed.store().load()
        assert omap.shard_for_job(r1["job_id"]) == 2
        assert not omap.in_flight()

    fed = FederatedSimulation(shard_count=2, seed=31, rules=[
        {"site": "server.event", "event": "migration-in", "shard": 2,
         "action": "kill", "times": 1},
    ])
    res = fed.run(scenario)
    assert res["audit"]["tasks_terminal"] == 10
    assert not res["violations"]
    assert res["shard_boots"][2] >= 2


# ---------------------------------------------------------------------------
# real processes: pinned sessions across a live migration
# ---------------------------------------------------------------------------
def _job_info(env: HqEnv, job_id: int) -> dict:
    return json.loads(env.command(
        ["job", "info", str(job_id), "--output-mode", "json"]
    ))[0]


def test_e2e_migration_with_pinned_session(tmp_path):
    """Live migration between real server processes while a session
    pinned to the OLD shard (stale HQ_SHARD) keeps using the job: the
    pinned client must follow the wrong-shard redirect — one retry, not
    an error — and a chunked submit stream opened through the pinned
    session follows the job to its new shard mid-stream."""
    from hyperqueue_tpu.client.connection import (
        FederatedSession,
        SubmitStream,
    )

    with HqEnv(tmp_path) as env:
        env.start_shard(0, 2, "--lease-timeout", "2")
        env.start_shard(1, 2, "--lease-timeout", "2")
        env.start_worker("--shard", "0", cpus=2)
        env.start_worker("--shard", "1", cpus=2)
        env.wait_workers(2)

        body = {"cmd": ["true"], "env": {},
                "submit_dir": str(env.work_dir)}
        chunk = 50
        os.environ["HQ_SHARD"] = "0"
        try:
            fed = FederatedSession(env.server_dir)
            stream = SubmitStream(
                fed, {"name": "follow", "submit_dir": str(env.work_dir)},
                window=1,
            )
            for i in range(2):          # window 1: second send acks first
                stream.send_chunk(array={
                    "id_range": [i * chunk, (i + 1) * chunk],
                    "body": dict(body), "request": {},
                    "priority": 0, "crash_limit": 5,
                })
            job_id = stream.job_id
            assert job_id == 1          # (1-1) % 2 == 0 -> pinned shard 0

            # migrate the job out from under the open stream
            out = env.command(["fleet", "migrate", str(job_id), "1"])
            assert f"migrated job {job_id}: shard 0 -> 1" in out

            # the remaining chunks redirect to shard 1 and dedup there
            for i in range(2, 4):
                stream.send_chunk(array={
                    "id_range": [i * chunk, (i + 1) * chunk],
                    "body": dict(body), "request": {},
                    "priority": 0, "crash_limit": 5,
                })
            jid, n_tasks = stream.finish()
            assert (jid, n_tasks) == (job_id, 4 * chunk)
            assert stream._redirects >= 1

            # a plain job op through the same stale pin redirects too
            info = _job_info(env, job_id)
            assert info["n_tasks"] == 4 * chunk
        finally:
            os.environ.pop("HQ_SHARD", None)

        env.command(["job", "wait", str(job_id)], timeout=60)
        info = _job_info(env, job_id)
        assert info["counters"]["finished"] == 4 * chunk
        ids = sorted(t["id"] for t in info["tasks"])
        assert ids == list(range(4 * chunk))    # exactly once, no gaps

        # ownership is visible to the operator surface
        status = env.command(["fleet", "status"])
        assert "ownership epoch" in status
        assert "in-flight migrations" in status

        # the ownership log agrees: job 1 is an explicit assignment now
        omap = OwnershipStore(env.server_dir).load()
        assert omap.shard_for_job(job_id) == 1
        assert not omap.in_flight()


def test_e2e_rebalancer_moves_a_job_off_the_hot_shard(tmp_path):
    """Real processes, the standby's `--rebalance` loop: every job lands
    pinned on shard 0 while shard 1's worker is held busy by a small
    pinned warm-up (so lending has no idle donor and the backlog can only
    even out by migrating jobs).  The rebalancer must commit at least one
    live migration to shard 1, and every submitted task must still finish
    exactly once."""
    with HqEnv(tmp_path) as env:
        env.start_shard(0, 2, "--lease-timeout", "2")
        env.start_shard(1, 2, "--lease-timeout", "2")
        env.start_worker("--shard", "0", cpus=2)
        env.start_worker("--shard", "1", cpus=2)
        env.wait_workers(2)

        def submit(shard, *args):
            os.environ["HQ_SHARD"] = str(shard)
            try:
                out = env.command(["submit", *args])
            finally:
                os.environ.pop("HQ_SHARD", None)
            return int(out.split("job ID: ")[1].split()[0])

        warm = submit(1, "--array", "0-5", "--", "sleep", "1")
        hot = [submit(0, "--array", "0-11", "--", "sleep", "1")
               for _ in range(2)]
        # the control loop on a fast fixed cadence, not the sampler's
        env.start_standby("--lease-timeout", "2",
                          "--coordinator-interval", "0.25", "--rebalance",
                          env_extra={"HQ_REBALANCE_INTERVAL": "0.25"})
        store = OwnershipStore(env.server_dir)
        wait_until(lambda: store.load().assignments, timeout=60,
                   message="the rebalancer committed a migration")

        env.command(["job", "wait", "all"], timeout=120)
        jobs = json.loads(env.command(
            ["job", "list", "--all", "--output-mode", "json"]
        ))
        finished = {j["id"]: j["counters"]["finished"] for j in jobs}
        assert finished == {warm: 6, hot[0]: 12, hot[1]: 12}
        for job_id in hot:
            ids = sorted(t["id"] for t in _job_info(env, job_id)["tasks"])
            assert ids == list(range(12))       # exactly once, no gaps
        omap = store.load()
        moved = [j for j in hot if omap.shard_for_job(j) == 1]
        assert moved and not omap.in_flight()
        assert any(v for v in omap.verdicts)    # the verdicts are logged
        assert "federation:" in env.command(["fleet", "status"])


@pytest.mark.slow
def test_e2e_online_shard_add(tmp_path):
    """Real-process N -> N+1: a third shard joins a live 2-shard fleet
    (no restarts), receives a migrated job, and finishes it."""
    with HqEnv(tmp_path) as env:
        env.start_shard(0, 2, "--lease-timeout", "2")
        env.start_shard(1, 2, "--lease-timeout", "2")
        env.start_worker("--shard", "0", cpus=2)
        env.wait_workers(1)

        flag = env.work_dir / "flag"
        os.environ["HQ_SHARD"] = "0"
        try:
            env.command([
                "submit", "--array", "0-3", "--", "bash", "-c",
                f"while [ ! -f {flag} ]; do sleep 0.2; done",
            ])
        finally:
            os.environ.pop("HQ_SHARD", None)

        env.start_shard(2, 3, "--lease-timeout", "2")

        def fed_desc():
            return serverdir.load_federation(env.server_dir)

        wait_until(lambda: fed_desc()["shard_count"] == 3,
                   message="descriptor grew to 3 shards")
        assert fed_desc()["base_shard_count"] == 2
        env.start_worker("--shard", "2", cpus=2)

        out = env.command(["fleet", "migrate", "1", "2"])
        assert "shard 0 -> 2" in out
        flag.touch()
        env.command(["job", "wait", "1"], timeout=60)
        info = _job_info(env, 1)
        assert info["counters"]["finished"] == 4
        omap = OwnershipStore(env.server_dir).load()
        assert omap.shard_for_job(1) == 2
        assert any(int(rec["shard"]) == 2 for rec in omap.shard_adds)
