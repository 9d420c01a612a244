"""Incremental tick-state cache: golden parity, dirty tracking, phase
stats, and the satellite regression tests that ride with the PR
(stream-writer eviction, stream placeholders, --array subsetting,
selector parsing, the pure-Python ChaCha20-Poly1305 fallback)."""

from __future__ import annotations

import random

import numpy as np
import pytest

from utils_env import TestEnv

from hyperqueue_tpu.scheduler.tick import assemble_solve_inputs, create_batches
from hyperqueue_tpu.scheduler.tick_cache import paranoid_check


def _scratch_kwargs(core):
    rows = [r for r in core.worker_rows() if r.cpu_floor <= 0]
    batches = create_batches(core.queues)
    return assemble_solve_inputs(
        rows, batches, core.rq_map, core.resource_map
    )


def _incremental_kwargs(core):
    snap = core.tick_cache.sync(core)
    assert snap is not None
    batches = create_batches(core.queues)
    return assemble_solve_inputs(
        None, batches, core.rq_map, core.resource_map, dense=snap,
        key_cache=core.tick_cache,
    )


def _assert_kwargs_equal(a, b):
    assert set(a) == set(b), (set(a), set(b))
    for key in a:
        if key == "priorities":
            assert a[key] == b[key]
            continue
        assert np.array_equal(np.asarray(a[key]), np.asarray(b[key])), key


# ---------------------------------------------------------------- golden
def test_randomized_incremental_vs_scratch_golden():
    """>= 200 random mutation steps (submits, schedules, finishes, worker
    joins/leaves, resource-map widening, gang reservations); after every
    schedulable state change the incremental assembly must be
    bit-identical to a from-scratch one.  paranoid_tick=1 additionally
    runs the production paranoid check inside every schedule()."""
    env = TestEnv()
    env.core.paranoid_tick = 1
    rng = random.Random(7)
    assigned_pool: list[int] = []
    worker_ids: list[int] = []
    extra_resources = 0

    for _ in range(3):
        worker_ids.append(env.worker(cpus=rng.choice([2, 4, 8])).worker_id)

    steps = 0
    mutations = 0
    while mutations < 220:
        op = rng.random()
        steps += 1
        if op < 0.30:
            rqv = env.rqv(
                cpus=rng.choice([1, 1, 2]),
                gpus=rng.choice([0, 0, 0, 1]),
            )
            env.submit(
                n=rng.randrange(1, 6), rqv=rqv,
                priority=(rng.randrange(0, 3), 0),
            )
            mutations += 1
        elif op < 0.45 and assigned_pool:
            env.finish(assigned_pool.pop(rng.randrange(len(assigned_pool))))
            mutations += 1
        elif op < 0.55:
            gpus = rng.choice([0, 0, 2])
            worker_ids.append(
                env.worker(cpus=rng.choice([2, 4, 8]), gpus=gpus).worker_id
            )
            mutations += 1
        elif op < 0.62 and len(worker_ids) > 1:
            wid = worker_ids.pop(rng.randrange(len(worker_ids)))
            assigned = set(env.core.workers[wid].assigned_tasks)
            env.lose_worker(wid)
            assigned_pool[:] = [t for t in assigned_pool if t not in assigned]
            mutations += 1
        elif op < 0.66:
            # widen the resource map without touching any worker (a task
            # naming a fresh resource interns it)
            extra_resources += 1
            env.core.resource_map.get_or_create(f"res{extra_resources}")
            mutations += 1
        elif op < 0.70:
            # a pending gang reserves (and later releases) workers —
            # membership changes without connect/disconnect
            env.submit(rqv=env.rqv(n_nodes=2), priority=(5, 0))
            mutations += 1
        if rng.random() < 0.5 and env.core.queues.total_ready():
            # schedule() runs the paranoid bit-identity check itself
            before = {
                t for t, task in env.core.tasks.items()
                if task.state.value == "assigned"
            }
            env.schedule()
            env.start_all_assigned()
            after = {
                t for t, task in env.core.tasks.items()
                if task.state.value == "running"
            }
            assigned_pool.extend(after - before)
        # independent explicit comparison of both assembly paths
        if env.core.queues.total_ready() and any(
            w.mn_task == 0 and w.mn_reserved == 0
            for w in env.core.workers.values()
        ):
            _assert_kwargs_equal(
                _scratch_kwargs(env.core), _incremental_kwargs(env.core)
            )
    assert mutations >= 220
    assert env.core.tick_cache.incremental_syncs > 0


# ---------------------------------------------------------- dirty tracking
def test_steady_state_zero_full_rebuilds():
    env = TestEnv()
    for _ in range(3):
        env.worker(cpus=4)
    ids = env.submit(n=30)
    env.schedule()
    rebuilds = env.core.tick_cache.full_rebuilds
    env.start_all_assigned()
    for t in ids[:8]:
        env.finish(t)
    env.schedule()
    env.schedule()
    assert env.core.tick_cache.full_rebuilds == rebuilds
    assert env.core.tick_cache.incremental_syncs >= 2


def test_connect_disconnect_trigger_rebuild():
    env = TestEnv()
    w1 = env.worker(cpus=4)
    env.submit(n=4)
    env.schedule()
    r0 = env.core.tick_cache.full_rebuilds
    w2 = env.worker(cpus=2)
    env.submit(n=1)
    env.schedule()
    assert env.core.tick_cache.full_rebuilds == r0 + 1
    assert w2.worker_id in env.core.tick_cache.worker_ids
    env.lose_worker(w1.worker_id)
    env.submit(n=1)
    env.schedule()
    assert env.core.tick_cache.full_rebuilds == r0 + 2
    assert w1.worker_id not in env.core.tick_cache.worker_ids


def test_resource_map_widening_pads_columns():
    env = TestEnv()
    env.worker(cpus=4)
    env.submit(n=2)
    env.schedule()
    old_width = env.core.tick_cache.n_r
    env.core.resource_map.get_or_create("fpga")
    env.submit(n=1)
    _assert_kwargs_equal(
        _scratch_kwargs(env.core), _incremental_kwargs(env.core)
    )
    assert env.core.tick_cache.n_r == old_width + 1
    assert np.all(env.core.tick_cache.free[:, old_width:] == 0)


def test_overcommit_negative_free_stays_bit_identical():
    """Prefill races can drive a worker's free negative; the cache must
    mirror the raw (negative) value exactly like the scratch snapshot."""
    env = TestEnv()
    w = env.worker(cpus=2)
    env.submit(n=2)
    env.schedule()
    # force over-commit directly (what a prefill race does)
    w.assign(999_001, [(0, 50_000)])
    assert w.free[0] < 0
    env.submit(n=1)
    a = _scratch_kwargs(env.core)
    b = _incremental_kwargs(env.core)
    _assert_kwargs_equal(a, b)
    row = env.core.tick_cache.worker_ids.index(w.worker_id)
    assert env.core.tick_cache.free[row, 0] < 0
    assert env.core.tick_cache.nt_free[row] >= 0  # clamped like scratch


def test_min_utilization_worker_disables_cache():
    env = TestEnv()
    w = env.worker(cpus=4)
    w.configuration.min_utilization = 0.5
    env.core.bump_membership()
    env.submit(n=3)
    assert env.core.tick_cache.sync(env.core) is None
    # the reactor must still schedule through the legacy path
    n = env.schedule()
    assert n > 0


def test_paranoid_check_detects_corruption():
    env = TestEnv()
    env.worker(cpus=4)
    env.submit(n=4)
    snap = env.core.tick_cache.sync(env.core)
    batches = create_batches(env.core.queues)
    paranoid_check(
        env.core, snap, batches, env.core.rq_map, env.core.resource_map
    )  # clean state passes
    env.core.tick_cache.free[0, 0] += 7  # corrupt without an epoch bump
    with pytest.raises(AssertionError):
        paranoid_check(
            env.core, snap, batches, env.core.rq_map, env.core.resource_map
        )


def test_phase_stats_recorded():
    env = TestEnv()
    env.worker(cpus=4)
    env.submit(n=8)
    env.schedule()
    stats = env.core.tick_stats
    assert stats.ticks >= 1
    snap = stats.snapshot()
    for phase in ("batches", "assemble", "mapping", "total"):
        assert phase in snap["phases"], snap
    counters = env.core.tick_cache.counters()
    assert counters["full_rebuilds"] >= 1
    assert counters["workers"] == 1


def test_dense_solve_assignments_match_legacy():
    """Same queue/worker state scheduled through the cache and through
    from-scratch WorkerRows must produce identical assignments."""
    import copy

    def build():
        env = TestEnv()
        for cpus in (2, 4, 8):
            env.worker(cpus=cpus)
        env.submit(n=12, rqv=env.rqv(cpus=1), priority=(1, 0))
        env.submit(n=7, rqv=env.rqv(cpus=2), priority=(3, 0))
        return env

    env_a = build()  # cache path (default)
    env_b = build()  # legacy path: force by pretending a mu worker exists
    env_a.schedule()
    orig_sync = env_b.core.tick_cache.sync
    env_b.core.tick_cache.sync = lambda core: None
    env_b.schedule()
    env_b.core.tick_cache.sync = orig_sync

    def placements(env):
        return sorted(
            (t.task_id, t.assigned_worker)
            for t in env.core.tasks.values()
            if t.assigned_worker
        )

    assert placements(env_a) == placements(env_b)


# ------------------------------------------------------ satellite: streams
class _DummyWriter:
    def __init__(self, *a, **k):
        self.closed = False

    def close(self):
        self.closed = True


def _make_runtime():
    from hyperqueue_tpu.resources.descriptor import (
        ResourceDescriptor,
        ResourceDescriptorItem,
    )
    from hyperqueue_tpu.server.worker import WorkerConfiguration
    from hyperqueue_tpu.worker.runtime import WorkerRuntime

    config = WorkerConfiguration(
        descriptor=ResourceDescriptor(
            items=(ResourceDescriptorItem.range("cpus", 0, 1),)
        )
    )
    return WorkerRuntime("localhost", 0, None, config)


def test_stream_writer_eviction_skips_in_use(monkeypatch):
    import hyperqueue_tpu.events.outputlog as outputlog

    monkeypatch.setattr(outputlog, "StreamWriter", _DummyWriter)
    rt = _make_runtime()
    rt.MAX_STREAM_WRITERS = 4
    held = [rt._acquire_streamer(f"/busy/{i}") for i in range(4)]
    # a 5th dir must NOT close any in-use writer: the bound is exceeded
    rt._acquire_streamer("/new/0")
    assert all(not w.closed for w in held)
    assert len(rt._streamers) == 5
    # release one: the next acquisition may evict exactly that writer
    rt._release_streamer("/busy/2")
    rt._release_streamer("/new/0")
    rt._acquire_streamer("/new/1")
    assert rt._streamers.get("/busy/2") is None or held[2].closed is False
    closed = [d for d, w in zip(["/busy/0"], held) if w.closed]
    assert "/busy/0" not in closed  # still held -> never closed


def test_stream_writer_lru_reuse_moves_to_end(monkeypatch):
    import hyperqueue_tpu.events.outputlog as outputlog

    monkeypatch.setattr(outputlog, "StreamWriter", _DummyWriter)
    rt = _make_runtime()
    a = rt._acquire_streamer("/a")
    rt._acquire_streamer("/b")
    rt._release_streamer("/a")
    rt._release_streamer("/b")
    # reuse /a: it must move to the END of the LRU order
    assert rt._acquire_streamer("/a") is a
    rt._release_streamer("/a")
    assert list(rt._streamers) == ["/b", "/a"]
    # eviction now hits /b (least recently used), not /a
    rt.MAX_STREAM_WRITERS = 2
    rt._acquire_streamer("/c")
    assert "/b" not in rt._streamers
    assert "/a" in rt._streamers


def test_stream_writer_refcount_shared_dir(monkeypatch):
    import hyperqueue_tpu.events.outputlog as outputlog

    monkeypatch.setattr(outputlog, "StreamWriter", _DummyWriter)
    rt = _make_runtime()
    w1 = rt._acquire_streamer("/shared")
    w2 = rt._acquire_streamer("/shared")
    assert w1 is w2
    assert rt._streamer_users["/shared"] == 2
    rt._release_streamer("/shared")
    assert rt._streamer_users["/shared"] == 1
    rt._release_streamer("/shared")
    assert "/shared" not in rt._streamer_users


# ----------------------------------------------- satellite: cli validation
def test_stream_task_scope_placeholder_is_submit_error(capsys):
    import argparse

    from hyperqueue_tpu.client.cli import _check_submit_placeholders

    def make_args(stream):
        return argparse.Namespace(
            cwd=None, stdout=None, stderr=None, stream=stream
        )

    with pytest.raises(SystemExit):
        _check_submit_placeholders(
            make_args("/logs/%{TASK_ID}"), is_array=True
        )
    err = capsys.readouterr().err
    assert "task-scope" in err
    # job-scope placeholders stay fine
    _check_submit_placeholders(
        make_args("/logs/%{JOB_ID}-%{SERVER_UID}"), is_array=True
    )
    # truly unknown names still only warn
    _check_submit_placeholders(make_args("/logs/%{NOPE}"), is_array=True)
    assert "WARNING: unknown placeholder" in capsys.readouterr().err


def test_array_entries_intersection_warns_and_fails(capsys):
    from hyperqueue_tpu.client.cli import _subset_array_entries

    entries = ["l0", "l1", "l2"]
    ids, values = _subset_array_entries([1, 2, 7, 9], entries)
    assert ids == [1, 2]
    assert values == ["l1", "l2"]
    assert "2 --array id(s) outside" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        _subset_array_entries([5, 6], entries)
    assert "selects no tasks" in capsys.readouterr().err
    # no --array: every entry
    ids, values = _subset_array_entries(None, entries)
    assert ids == [0, 1, 2] and values == entries


def test_parse_selector_underscores():
    from hyperqueue_tpu.client.cli import parse_selector

    assert parse_selector("1_000") == [1000]
    assert parse_selector("1-1_0") == list(range(1, 11))
    assert parse_selector("1,2_5,3-4") == [1, 25, 3, 4]
    for bad in ("_5", "5_", "1-_5", "x_y", "nope"):
        with pytest.raises(SystemExit):
            parse_selector(bad)


# ------------------------------------------- satellite: chacha fallback
def test_pure_python_chacha_rfc8439_vectors():
    from hyperqueue_tpu.transport._chacha import ChaCha20Poly1305

    key = bytes(range(0x80, 0xA0))
    nonce = bytes([7, 0, 0, 0, 0x40, 0x41, 0x42, 0x43, 0x44, 0x45, 0x46,
                   0x47])
    aad = bytes([0x50, 0x51, 0x52, 0x53, 0xC0, 0xC1, 0xC2, 0xC3, 0xC4,
                 0xC5, 0xC6, 0xC7])
    pt = (b"Ladies and Gentlemen of the class of '99: If I could offer "
          b"you only one tip for the future, sunscreen would be it.")
    sealed = ChaCha20Poly1305(key).encrypt(nonce, pt, aad)
    assert sealed[-16:] == bytes.fromhex("1ae10b594f09e26a7e902ecbd0600691")
    assert ChaCha20Poly1305(key).decrypt(nonce, sealed, aad) == pt
    tampered = sealed[:-1] + bytes([sealed[-1] ^ 1])
    with pytest.raises(ValueError):
        ChaCha20Poly1305(key).decrypt(nonce, tampered, aad)


def test_stream_seal_roundtrip_with_fallback():
    from hyperqueue_tpu.transport import _chacha
    from hyperqueue_tpu.transport.auth import StreamSeal

    key = bytes(32)
    a = StreamSeal.__new__(StreamSeal)
    a._aead = _chacha.ChaCha20Poly1305(key)
    a._counter = 0
    a._prefix = b"dirA"
    b = StreamSeal.__new__(StreamSeal)
    b._aead = _chacha.ChaCha20Poly1305(key)
    b._counter = 0
    b._prefix = b"dirA"
    for msg in (b"x", b"hello" * 100, b""):
        assert b.open(a.seal(msg)) == msg


# ------------------------------------------- the tick loop, run directly
def _steady_ticks(backend, reps=8):
    """`sync -> create_batches -> run_tick -> apply` over a small mixed
    cluster (gpu classes, a cpu-only fallback variant, four priority
    levels), the loop the benchmark's tick driver and `reactor.schedule`
    both run.  One warm tick, then `reps` ticks of the same load: what
    each placed is taken back between ticks."""
    import time

    from hyperqueue_tpu.ids import make_task_id
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel
    from hyperqueue_tpu.scheduler.tick import run_tick

    env = TestEnv()
    core = env.core
    for i in range(16):
        env.worker(cpus=(4, 8, 16)[i % 3], gpus=(0, 0, 2)[i % 3])
    classes = [
        core.intern_rqv(rqv) for rqv in (
            env.rqv(cpus=1), env.rqv(cpus=2), env.rqv(cpus=1, gpus=0.5),
            env.rqv(variants=[env.rq(cpus=2, gpus=1), env.rq(cpus=4)]),
        )
    ]
    prio = {}
    for t in range(2000):
        task_id = make_task_id(1, t)
        prio[task_id] = (t % 4, 0)
        core.queues.add(classes[t % len(classes)], prio[task_id], task_id)
    model = GreedyCutScanModel(backend=backend)

    def tick():
        phases: dict = {}
        t0 = time.perf_counter()
        snap = core.tick_cache.sync(core)
        t1 = time.perf_counter()
        batches = create_batches(core.queues)
        t2 = time.perf_counter()
        out = run_tick(
            core.queues, None, core.rq_map, core.resource_map, model,
            batches=batches, dense=snap, phases=phases,
            key_cache=core.tick_cache,
        )
        t3 = time.perf_counter()
        for task_id, worker_id, rq_id, variant in out:
            worker = core.workers[worker_id]
            worker.assign(
                task_id, core.variant_amounts(rq_id, variant, worker)
            )
        t4 = time.perf_counter()
        phases.update(snapshot=(t1 - t0) * 1e3, batches=(t2 - t1) * 1e3,
                      apply=(t4 - t3) * 1e3, total=(t4 - t0) * 1e3)
        return out, phases

    def take_back(out):
        for task_id, worker_id, rq_id, variant in out:
            worker = core.workers[worker_id]
            worker.unassign(
                task_id, core.variant_amounts(rq_id, variant, worker)
            )
            core.queues.add(rq_id, prio[task_id], task_id)

    warm, _ = tick()
    take_back(warm)
    record = {
        "core": core,
        "assigned": len(warm),
        "rebuilds_after_warm": core.tick_cache.full_rebuilds,
        "shapes_after_warm": model.shape_allocations,
        "phases": [],
    }
    for _ in range(reps):
        out, phases = tick()
        assert len(out) == len(warm)
        record["phases"].append(phases)
        take_back(out)
    record["rebuilds"] = core.tick_cache.full_rebuilds
    record["shapes"] = model.shape_allocations
    return record


@pytest.fixture(scope="module", params=["numpy", "jax"])
def steady(request):
    return _steady_ticks(request.param)


def test_tick_phases_account_for_the_tick(steady):
    """The phases a tick records are disjoint spans inside its `total`
    (nested `a/b` keys lie inside `a`): in every tick their sum does not
    exceed it, and no stretch of the tick is left without a span.  The
    second half is read from the tick with the smallest remainder: a
    stretch nothing spans shows in every tick, a busy host does not."""
    assert steady["assigned"] > 0
    remainders = []
    for phases in steady["phases"]:
        for phase in ("assemble", "mapping", "snapshot", "batches", "apply"):
            assert phase in phases, phases
        total = phases["total"]
        parts = sum(
            v for k, v in phases.items() if k != "total" and "/" not in k
        )
        assert parts <= total + 1e-6, phases
        remainders.append((total - parts) / total)
    assert min(remainders) <= 0.35, steady["phases"]


def test_steady_ticks_rebuild_no_snapshot(steady):
    """The first tick builds the (W, R) snapshot; ticks that only assign
    and release update rows in place."""
    assert steady["rebuilds_after_warm"] == 1
    assert steady["rebuilds"] == 1
    counters = steady["core"].tick_cache.counters()
    assert counters["full_rebuilds"] == 1
    assert counters["incremental_syncs"] >= len(steady["phases"])


def test_steady_ticks_allocate_no_solver_shape(steady):
    """One bucket shape serves every steady tick: a new one would be a
    new padded buffer set and, on the jitted path, a recompilation."""
    assert steady["shapes_after_warm"] == 1
    assert steady["shapes"] == 1


def test_steady_state_assembly_equals_scratch(steady):
    """After the steady ticks the incremental snapshot of this state
    (variants, fractional gpus, priorities) still assembles what a
    from-scratch one does."""
    core = steady["core"]
    snap = core.tick_cache.sync(core)
    paranoid_check(
        core, snap, create_batches(core.queues), core.rq_map,
        core.resource_map,
    )
    _assert_kwargs_equal(_scratch_kwargs(core), _incremental_kwargs(core))
