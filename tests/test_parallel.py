"""Multi-chip sharded solver tests on the virtual 8-device CPU mesh.

The sharded kernel is semantically IDENTICAL to the single-chip kernel by
construction (parallel/solve.py module docstring) — so these tests assert
BITWISE count equality, not just totals, across random and adversarial
instances (priorities, variants, min_time, heterogeneous workers), plus the
production model wrapper (models/multichip.py) against GreedyCutScanModel.

The device-resident path (parallel/resident.py) adds a multi-tick contract:
delta uploads + donated buffers must stay bitwise identical to a fresh
full-upload solve EVERY tick, across completions, worker churn (mesh-padded
W resizes) and ALL-policy solves — the randomized soaks below drive it with
the paranoid cross-check armed (the same check `--paranoid-tick` runs in
production).

Everything here carries the `multichip` marker: the suite runs inside
tier-1 on CPU-only hosts because conftest.py forces the virtual 8-device
mesh (XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""

import numpy as np

import jax
import pytest

from hyperqueue_tpu.models.greedy import GreedyCutScanModel
from hyperqueue_tpu.models.multichip import MultichipModel

pytestmark = pytest.mark.multichip
from hyperqueue_tpu.ops.assign import (
    greedy_cut_scan,
    host_visit_classes,
    scarcity_weights,
)
from hyperqueue_tpu.parallel.solve import (
    make_worker_mesh,
    pack_batch_table,
    sharded_cut_scan_donate,
)
from hyperqueue_tpu.utils.constants import INF_TIME

U = 10_000


def _random_instance(rng, n_w, n_r, n_b, n_v, with_lifetimes=False):
    free = (rng.integers(0, 8, size=(n_w, n_r)) * U).astype(np.int32)
    nt_free = rng.integers(0, 10, size=n_w).astype(np.int32)
    if with_lifetimes:
        lifetime = rng.choice(
            [60, 600, int(INF_TIME)], size=n_w
        ).astype(np.int32)
    else:
        lifetime = np.full(n_w, INF_TIME, dtype=np.int32)
    needs = (rng.integers(0, 3, size=(n_b, n_v, n_r)) * (U // 2)).astype(
        np.int32
    )
    sizes = rng.integers(0, 30, size=n_b).astype(np.int32)
    min_time = (
        rng.choice([0, 120, 3600], size=(n_b, n_v)).astype(np.int32)
        if with_lifetimes
        else np.zeros((n_b, n_v), dtype=np.int32)
    )
    return free, nt_free, lifetime, needs, sizes, min_time


def _sharded_solve(mesh, free, nt_free, lifetime, needs, sizes, min_time,
                   class_m, order_ids):
    """The resident tick's program (the one jitted sharded entry), as
    `MultichipModel` calls it: per-batch inputs packed into one table,
    free/nt_free as copies because the program consumes them."""
    return sharded_cut_scan_donate(
        mesh, free.copy(), nt_free.copy(), lifetime,
        pack_batch_table(needs, sizes, min_time, order_ids), class_m,
        extents=needs.shape,
    )


def _both_solves(free, nt_free, lifetime, needs, sizes, min_time):
    scarcity = np.asarray(
        scarcity_weights(free.astype(np.int64).sum(axis=0))
    ).astype(np.float32)
    class_m, order_ids = host_visit_classes(free, needs, scarcity)
    single, free_s, nt_s = greedy_cut_scan(
        free, nt_free, lifetime, needs, sizes, min_time, class_m, order_ids
    )
    sharded, free_d, nt_d = _sharded_solve(
        make_worker_mesh(8), free, nt_free, lifetime, needs, sizes, min_time,
        class_m, order_ids,
    )
    return (
        np.asarray(single), np.asarray(sharded),
        np.asarray(free_s), np.asarray(free_d),
        np.asarray(nt_s), np.asarray(nt_d),
    )


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


@pytest.mark.parametrize("seed", [7, 11, 13])
def test_sharded_exact_parity_random(seed):
    rng = np.random.default_rng(seed)
    args = _random_instance(rng, n_w=16, n_r=4, n_b=8, n_v=2)
    single, sharded, free_s, free_d, nt_s, nt_d = _both_solves(*args)
    np.testing.assert_array_equal(single, sharded)
    np.testing.assert_array_equal(free_s, free_d)
    np.testing.assert_array_equal(nt_s, nt_d)


def test_sharded_exact_parity_lifetimes_min_time():
    rng = np.random.default_rng(3)
    args = _random_instance(
        rng, n_w=32, n_r=4, n_b=8, n_v=2, with_lifetimes=True
    )
    single, sharded, *_ = _both_solves(*args)
    np.testing.assert_array_equal(single, sharded)


def test_sharded_exact_parity_heterogeneous_workers():
    # distinct per-worker resource patterns => many visit classes; parity
    # must hold per (batch, variant, worker) cell, not just per totals
    rng = np.random.default_rng(42)
    n_w, n_r = 24, 6
    free = (rng.integers(0, 5, size=(n_w, n_r)) * U).astype(np.int32)
    free[::3, 1] = 0   # a third of workers lack r1
    free[1::3, 2] = 0  # another third lack r2
    nt_free = rng.integers(1, 12, size=n_w).astype(np.int32)
    lifetime = np.full(n_w, INF_TIME, dtype=np.int32)
    needs = np.zeros((6, 2, n_r), dtype=np.int32)
    needs[:, 0, 0] = U
    needs[0, 0, 1] = U       # class 0 prefers r0+r1
    needs[1, 1, 2] = 2 * U   # class 1 falls back to r2
    needs[2, 0, 3] = U // 2  # fractional r3
    needs[3, 0, 0] = 3 * U
    needs[4, 1, 0] = U
    needs[5, 0, 5] = U
    sizes = np.array([9, 7, 5, 11, 4, 6], dtype=np.int32)
    min_time = np.zeros((6, 2), dtype=np.int32)
    single, sharded, *_ = _both_solves(
        free, nt_free, lifetime, needs, sizes, min_time
    )
    np.testing.assert_array_equal(single, sharded)


def test_sharded_feasible():
    rng = np.random.default_rng(5)
    free, nt_free, lifetime, needs, sizes, min_time = _random_instance(
        rng, n_w=16, n_r=4, n_b=8, n_v=2
    )
    _, sharded, _, free_d, *_ = _both_solves(
        free, nt_free, lifetime, needs, sizes, min_time
    )
    used = np.einsum("bvw,bvr->wr", sharded, needs)
    assert (used <= free).all()
    assert (sharded.sum(axis=(0, 1)) <= nt_free).all()
    assert (sharded.sum(axis=(1, 2)) <= sizes).all()
    assert (free_d == free - used).all()


def test_sharded_priority_dominance():
    # high-priority batch first even when capacity spans devices
    n_w = 8
    free = np.full((n_w, 1), 2 * U, dtype=np.int32)
    nt_free = np.full(n_w, 4, dtype=np.int32)
    lifetime = np.full(n_w, INF_TIME, dtype=np.int32)
    needs = np.array([[[U]], [[U]]], dtype=np.int32)
    sizes = np.array([16, 16], dtype=np.int32)
    min_time = np.zeros((2, 1), dtype=np.int32)
    _, sharded, *_ = _both_solves(
        free, nt_free, lifetime, needs, sizes, min_time
    )
    assert sharded[0].sum() == 16  # high priority fully placed
    assert sharded[1].sum() == 0   # low priority starved (capacity exhausted)


# ---------------------------------------------------------------------------
# the production model wrapper (what `--scheduler=multichip` instantiates)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [2, 9])
def test_multichip_model_matches_greedy_model(seed):
    rng = np.random.default_rng(seed)
    # deliberately awkward unpadded shapes: the model buckets W to a
    # multiple of the device count itself
    n_w, n_r, n_b, n_v = 13, 3, 5, 2
    free, nt_free, lifetime, needs, sizes, min_time = _random_instance(
        rng, n_w, n_r, n_b, n_v, with_lifetimes=True
    )
    greedy = GreedyCutScanModel(backend="jax")
    multi = MultichipModel()
    kwargs = dict(
        free=free, nt_free=nt_free, lifetime=lifetime,
        needs=needs, sizes=sizes, min_time=min_time,
    )
    np.testing.assert_array_equal(greedy.solve(**kwargs), multi.solve(**kwargs))


def test_resident_sharded_ticks_equal_the_host_solve():
    """Evolving ticks through ONE resident MultichipModel on the 8-device
    mesh against the single-chip host solve of the same inputs: counts
    bitwise equal every tick, with the delta-upload path engaged (one
    worker completes everything between ticks) and the fresh-solve guard
    armed on every solve."""
    rng = np.random.default_rng(42)
    n_w, n_r, n_b, n_v = 64, 4, 16, 2
    free0 = (rng.integers(1, 9, size=(n_w, n_r)) * 4 * U).astype(np.int32)
    nt0 = rng.integers(4, 17, size=n_w).astype(np.int32)
    lifetime = np.full(n_w, INF_TIME, dtype=np.int32)
    needs = (rng.integers(0, 3, size=(n_b, n_v, n_r)) * (U // 2)).astype(
        np.int32
    )
    needs[:, 0, 0] = np.maximum(needs[:, 0, 0], U)
    sizes = rng.integers(5, 12, size=n_b).astype(np.int32)
    min_time = np.zeros((n_b, n_v), dtype=np.int32)
    multi = MultichipModel()
    multi.paranoid_resident = 1
    host = GreedyCutScanModel(backend="numpy")
    free, nt_free = free0.copy(), nt0.copy()
    placed = 0
    for tick in range(5):
        kwargs = dict(lifetime=lifetime, needs=needs, sizes=sizes,
                      min_time=min_time)
        sharded = multi.solve(free=free.copy(), nt_free=nt_free.copy(),
                              **kwargs)
        single = host.solve(free=free.copy(), nt_free=nt_free.copy(),
                            **kwargs)
        np.testing.assert_array_equal(
            sharded, single, err_msg=f"sharded diverged at tick {tick}"
        )
        placed += int(sharded.sum())
        used = np.einsum(
            "bvw,bvr->wr", sharded.astype(np.int64), needs.astype(np.int64)
        )
        free = (free - used).astype(np.int32)
        nt_free = (nt_free - sharded.sum(axis=(0, 1))).astype(np.int32)
        free[tick] = free0[tick]
        nt_free[tick] = nt0[tick]
    assert placed > 0
    assert multi.last_backend == "device-sharded"
    assert multi.get_mesh().devices.size == 8
    stats = multi.resident_stats()
    assert stats["full_uploads"] == 1 and stats["delta_uploads"] >= 1
    assert multi.paranoid_checks == 5


def test_multichip_model_single_device_fallback():
    model = MultichipModel(n_devices=1)
    free = np.array([[4 * U]], dtype=np.int32)
    counts = model.solve(
        free=free,
        nt_free=np.array([8], dtype=np.int32),
        lifetime=np.array([INF_TIME], dtype=np.int32),
        needs=np.array([[[U]]], dtype=np.int32),
        sizes=np.array([3], dtype=np.int32),
        min_time=np.zeros((1, 1), dtype=np.int32),
    )
    assert counts.sum() == 3
    assert model._mesh is False  # degraded to the single-chip kernel


# ---------------------------------------------------------------------------
# device-resident multi-tick soak: delta uploads + donated buffers must be
# bitwise identical to a fresh full-upload solve EVERY tick
# ---------------------------------------------------------------------------

def _random_tick_batches(rng, n_r, with_all=False, with_gangs=False):
    n_b = int(rng.integers(1, 9))
    n_v = int(rng.integers(1, 3))
    needs = (rng.integers(0, 3, size=(n_b, n_v, n_r)) * (U // 2)).astype(
        np.int32
    )
    # every batch requests something in its first variant so no batch is
    # accidentally absent (U//2 amounts double as fractional requests)
    needs[:, 0, 0] = np.maximum(needs[:, 0, 0], U)
    sizes = rng.integers(0, 25, size=n_b).astype(np.int32)
    min_time = rng.choice([0, 0, 120, 3600], size=(n_b, n_v)).astype(np.int32)
    kwargs = dict(needs=needs, sizes=sizes, min_time=min_time)
    if with_gangs and rng.random() < 0.5:
        # one fused gang row: all-or-nothing over a worker group; the
        # resident path caches gang_ok/group_onehot placements too
        gang_nodes = np.zeros(n_b, dtype=np.int32)
        g = int(rng.integers(0, n_b))
        gang_nodes[g] = int(rng.integers(2, 4))
        sizes[g] = 1
        kwargs["gang_nodes"] = gang_nodes
    if with_all and rng.random() < 0.3:
        # ALL-policy on resource 1 for one batch: the kernel drains the
        # whole pool; the resident mirror must track the zeroing exactly
        # (it does — the mirror is the donated free_after read back)
        all_mask = np.zeros((n_b, n_v, n_r), dtype=np.int32)
        all_mask[0, 0, :] = 0
        all_mask[0, 0, 1] = 1
        needs[0, 0, 1] = 0
        kwargs["all_mask"] = all_mask
    return kwargs


def _random_workers(rng, n_w, n_r):
    free = (rng.integers(0, 8, size=(n_w, n_r)) * U).astype(np.int32)
    total = free.copy()
    nt_free = rng.integers(0, 10, size=n_w).astype(np.int32)
    lifetime = rng.choice(
        [600, 3600, int(INF_TIME)], size=n_w
    ).astype(np.int32)
    return free, total, nt_free, lifetime


@pytest.mark.parametrize(
    "seed",
    [0, pytest.param(3, marks=pytest.mark.slow)],
)
def test_resident_multi_tick_soak_bitwise(seed):
    """Randomized multi-tick history through ONE resident model vs a fresh
    full-upload model per tick: counts must match bitwise every tick, with
    completions dirtying rows, worker join/leave resizing the mesh-padded
    W, ALL-policy ticks, and the paranoid fresh-solve cross-check armed
    (the `--paranoid-tick` wiring)."""
    rng = np.random.default_rng(seed)
    n_r = 4
    n_w = int(rng.integers(9, 20))
    free, total, nt_free, lifetime = _random_workers(rng, n_w, n_r)

    resident = MultichipModel()
    # fresh-solve cross-check every 2nd solve (every solve is a second
    # full sharded solve — the half cadence keeps the soak inside the
    # tier-1 budget while still covering every shape the soak produces)
    resident.paranoid_resident = 2
    gang_ticks = 0
    for tick in range(12):
        batch_kwargs = _random_tick_batches(
            rng, n_r, with_all=True, with_gangs=True
        )
        kwargs = dict(
            free=free.copy(), nt_free=nt_free.copy(),
            lifetime=lifetime.copy(),
            **batch_kwargs,
        )
        if "all_mask" in batch_kwargs:
            kwargs["total"] = total.copy()
        if "gang_nodes" in batch_kwargs:
            # worker-side gang inputs track the current (churned) W
            gang_ticks += 1
            w_now = free.shape[0]
            kwargs["gang_ok"] = rng.integers(
                0, 2, size=w_now
            ).astype(np.int32)
            gids = rng.integers(0, 2, size=w_now).astype(np.int32)
            kwargs["group_onehot"] = (
                gids[:, None] == np.arange(2, dtype=np.int32)[None, :]
            ).astype(np.int32)
        out_res = resident.solve(**{k: v.copy() for k, v in kwargs.items()})
        fresh = MultichipModel()  # no residency: full upload by definition
        out_fresh = fresh.solve(**kwargs)
        np.testing.assert_array_equal(
            out_res, out_fresh,
            err_msg=f"resident diverged from fresh at tick {tick}",
        )
        assert out_res.flags.c_contiguous  # device-sliced before readback

        # --- evolve the host state like the reactor would ---------------
        needs = batch_kwargs["needs"]
        used = np.einsum(
            "bvw,bvr->wr", out_res.astype(np.int64), needs.astype(np.int64)
        )
        free = (free - used).astype(np.int32)
        if "all_mask" in batch_kwargs:
            drained = np.einsum(
                "bvw,bvr->wr", out_res.astype(np.int64),
                batch_kwargs["all_mask"].astype(np.int64),
            ) > 0
            free[drained] = 0
        nt_free = (nt_free - out_res.sum(axis=(0, 1))).astype(np.int32)
        # random completions release some of what is in use
        release_rows = rng.integers(0, 2, size=free.shape[0]).astype(bool)
        free[release_rows] = np.minimum(
            free[release_rows] + U * rng.integers(
                0, 3, size=(int(release_rows.sum()), n_r)
            ).astype(np.int64),
            total[release_rows],
        ).astype(np.int32)
        nt_free[release_rows] = np.minimum(nt_free[release_rows] + 1, 10)
        # lifetimes decay for limited workers
        finite = lifetime < int(INF_TIME)
        lifetime[finite] = np.maximum(lifetime[finite] - 1, 0)

        # --- occasional worker churn: join/leave resizes the padded W ---
        if rng.random() < 0.25:
            if rng.random() < 0.5 and free.shape[0] > 6:
                gone = int(rng.integers(0, free.shape[0]))
                free = np.delete(free, gone, axis=0)
                total = np.delete(total, gone, axis=0)
                nt_free = np.delete(nt_free, gone)
                lifetime = np.delete(lifetime, gone)
            else:
                nf, nt2, nn, nl = _random_workers(rng, 1, n_r)
                free = np.concatenate([free, nf])
                total = np.concatenate([total, nt2])
                nt_free = np.concatenate([nt_free, nn])
                lifetime = np.concatenate([lifetime, nl])

    stats = resident.resident_stats()
    assert stats["delta_uploads"] > 0, (
        "the soak never exercised the dirty-row delta path"
    )
    assert resident.paranoid_checks > 0
    assert gang_ticks > 0, "the soak never exercised a fused gang row"


def test_resident_steady_state_uploads_only_dirty_rows():
    """A tick whose inputs equal the donated outputs of the previous solve
    uploads NOTHING; touching one worker row uploads a one-row delta."""
    rng = np.random.default_rng(7)
    n_w, n_r = 16, 4
    free, total, nt_free, lifetime = _random_workers(rng, n_w, n_r)
    lifetime[:] = int(INF_TIME)
    model = MultichipModel()
    batch = _random_tick_batches(np.random.default_rng(1), n_r)
    kwargs = dict(
        free=free, nt_free=nt_free, lifetime=lifetime, **batch
    )
    out = model.solve(**{k: v.copy() for k, v in kwargs.items()})
    res = model._res
    assert res.stats()["full_uploads"] == 1

    # reactor-applied state == donated free_after: nothing is dirty
    needs = batch["needs"]

    def apply(free_in, nt_in, counts):
        used = np.einsum(
            "bvw,bvr->wr", counts.astype(np.int64), needs.astype(np.int64)
        )
        return (
            (free_in - used).astype(np.int32),
            (nt_in - counts.sum(axis=(0, 1))).astype(np.int32),
        )

    free2, nt2 = apply(free, nt_free, out)
    out2 = model.solve(free=free2, nt_free=nt2, lifetime=lifetime, **batch)
    assert res.dirty_rows_last == 0

    # one completion dirties exactly one row
    free3, nt3 = apply(free2, nt2, out2)
    free3[3] = total[3]
    nt3[3] = nt3[3] + 1
    model.solve(free=free3, nt_free=nt3, lifetime=lifetime, **batch)
    assert res.dirty_rows_last == 1
    assert res.stats()["full_uploads"] == 1  # never re-uploaded in full


def test_resident_paranoid_check_fires_on_corruption():
    """If the resident device state ever diverged from the host's view,
    the paranoid fresh-solve cross-check must catch it."""
    rng = np.random.default_rng(11)
    n_r = 4
    free, total, nt_free, lifetime = _random_workers(rng, 12, n_r)
    model = MultichipModel()
    batch = _random_tick_batches(np.random.default_rng(2), n_r)
    model.solve(free=free, nt_free=nt_free, lifetime=lifetime, **batch)
    # corrupt the mirror so it claims the device ALREADY holds the next
    # tick's inputs: the delta diff then uploads nothing, the solve runs on
    # stale device state, and only the paranoid cross-check can catch it
    res = model._res
    nt_next = np.full_like(nt_free, 10)
    res._m_free[: total.shape[0]] = total
    res._m_nt[: total.shape[0]] = nt_next
    model.paranoid_resident = 1
    with pytest.raises(AssertionError, match="paranoid-resident"):
        model.solve(
            free=total.copy(), nt_free=nt_next, lifetime=lifetime, **batch,
        )


@pytest.mark.parametrize(
    "dirty,expect", [(2048, "delta"), (3000, "delta"), (4096, "delta"),
                     (5000, "full")],
    ids=["2048-rows", "3000-rows", "4096-rows", "over-half"],
)
def test_sharded_residency_large_delta_equals_fresh_upload(dirty, expect):
    """The residency's large buckets, which a 1k-worker cluster never
    leaves 512 to meet: a sharded `DeviceResidency` on 4 devices takes a
    delta of thousands of dirty rows (buckets 2 048 and 4 096, with and
    without padding) and one above FULL_UPLOAD_FRACTION, and then holds
    what a fresh upload holds, row for row."""
    from hyperqueue_tpu.parallel.resident import DeviceResidency
    from hyperqueue_tpu.parallel.solve import _mesh_shardings

    n_w, n_r = 8192, 4
    rng = np.random.default_rng(dirty)
    res = DeviceResidency(shardings=_mesh_shardings(make_worker_mesh(4)))
    free = (rng.integers(0, 8, size=(n_w, n_r)) * U).astype(np.int32)
    total = free + U
    nt_free = rng.integers(0, 10, size=n_w).astype(np.int32)
    lifetime = np.full(n_w, INF_TIME, dtype=np.int32)
    res.sync(free, nt_free, lifetime, total)
    stats = res.stats()
    assert (stats["mesh_devices"], stats["rows_per_device"]) == (4, 2048)
    uploaded = stats["upload_bytes_total"]
    assert uploaded == free.nbytes * 2 + nt_free.nbytes * 2  # sharded: once

    rows = rng.choice(n_w, size=dirty, replace=False)
    free2, nt2, life2 = free.copy(), nt_free.copy(), lifetime.copy()
    free2[rows] += U
    nt2[rows[::2]] += 1
    life2[rows[::3]] = 600
    got = res.sync(free2, nt2, life2, total)
    stats = res.stats()
    assert stats["dirty_rows_last"] == (dirty if expect == "delta" else n_w)
    assert stats["delta_uploads"] == (expect == "delta")
    assert stats["full_uploads"] == 1 + (expect == "full")
    if expect == "delta":
        # indices and rows are put replicated: every device receives them
        bucket = 2048 if dirty <= 2048 else 4096
        row_bytes = 4 * (2 * n_r + 3)
        assert stats["upload_bytes_total"] - uploaded == 4 * bucket * row_bytes
    for dev, want in zip(got, (free2, nt2, life2, total)):
        assert len(dev.sharding.device_set) == 4
        np.testing.assert_array_equal(np.asarray(dev), want)
    fresh = DeviceResidency(shardings=_mesh_shardings(make_worker_mesh(4)))
    for dev, other in zip(got[:4], fresh.sync(free2, nt2, life2, total)[:4]):
        np.testing.assert_array_equal(np.asarray(dev), np.asarray(other))
        assert dev.sharding == other.sharding


@pytest.mark.parametrize("with_all", [False, True], ids=["plain", "all-mask"])
def test_batch_table_round_trip(with_all):
    from hyperqueue_tpu.parallel.solve import (
        _unpack_batch_table,
        pack_batch_table,
    )

    rng = np.random.default_rng(5)
    n_b, n_v, n_r = 8, 2, 4
    needs = rng.integers(0, 9, size=(n_b, n_v, n_r)).astype(np.int32)
    sizes = rng.integers(0, 99, size=n_b).astype(np.int32)
    min_time = rng.integers(0, 9, size=(n_b, n_v)).astype(np.int32)
    order_ids = rng.integers(0, 4, size=(n_b, n_v)).astype(np.int32)
    all_mask = (needs == 0).astype(np.int32) if with_all else None
    table = pack_batch_table(needs, sizes, min_time, order_ids, all_mask)
    assert table.dtype == np.int32 and table.ndim == 1
    got = _unpack_batch_table(table, (n_b, n_v, n_r), with_all)
    for have, want in zip(got, (needs, sizes, min_time, order_ids, all_mask)):
        if want is None:
            assert have is None
        else:
            np.testing.assert_array_equal(have, want)


def test_changed_batch_order_costs_the_sharded_tick_one_put():
    """Everything a sharded solve brings to the devices rides ONE put, a
    row a device: a tick that repeats the batch table and one that
    reorders the batches cost the same one put of the same bytes (each
    device its shard of the state and of `class_m`, and the table whole),
    and nothing is placed beside it."""
    rng = np.random.default_rng(3)
    n_w, n_r = 16, 4
    free, total, nt_free, lifetime = _random_workers(rng, n_w, n_r)
    lifetime[:] = int(INF_TIME)
    batch = _random_tick_batches(np.random.default_rng(1), n_r)
    model = MultichipModel(n_devices=4)

    def solve(b):
        # the same worker state every time, in the full form
        model.invalidate_resident()
        return model.solve(free=free.copy(), nt_free=nt_free.copy(),
                           lifetime=lifetime, **b)

    first = solve(batch)
    res = model._res
    before = res.stats()
    np.testing.assert_array_equal(solve(batch), first)
    again = res.stats()
    # the padded extents; the batches have eight distinct request masks
    pb, pv, pr, pm = 8, batch["needs"].shape[1], 4, 8
    state_bytes = free.nbytes + nt_free.nbytes + lifetime.nbytes
    table_bytes = 4 * (pb * pv * pr + pb + 2 * pb * pv)
    class_bytes = 4 * pm * n_w
    assert again["upload_bytes_total"] - before["upload_bytes_total"] \
        == state_bytes + 4 * table_bytes + class_bytes
    assert again["puts_total"] - before["puts_total"] == 1
    assert again["input_programs_total"] - before["input_programs_total"] == 1
    # needs, sizes, min_time and order_ids cross in the table: nothing of
    # a sharded solve is left on the placement cache
    assert again["rep_cache_hits"] == before["rep_cache_hits"] == 0

    order = np.arange(len(batch["sizes"]))[::-1]
    flipped = {k: (v[order] if k != "priorities" else v)
               for k, v in batch.items()}
    solve(flipped)
    after = res.stats()
    assert after["upload_bytes_total"] - again["upload_bytes_total"] \
        == state_bytes + 4 * table_bytes + class_bytes
    assert after["puts_total"] - again["puts_total"] == 1


def _reserved_mesh_case(rng, n_w=32):
    """32 workers in four groups of 8 shifted by 4, so that every group
    straddles a shard boundary on a 4-device mesh (8 rows a shard); the
    first gang row holds five workers of group 1 (rows 4-11: shards 0 and
    1), the second two of group 2, and a few rows are reserved for a gang
    no row carries."""
    from hyperqueue_tpu.ops.assign import RESV_ELSEWHERE

    n_r, n_b, n_v = 2, 6, 1
    free = (rng.integers(2, 8, size=(n_w, n_r)) * U).astype(np.int32)
    nt_free = rng.integers(1, 6, size=n_w).astype(np.int32)
    lifetime = np.full(n_w, INF_TIME, dtype=np.int32)
    needs = np.zeros((n_b, n_v, n_r), dtype=np.int32)
    needs[:, 0, 0] = U
    sizes = np.asarray([1, 1, 9, 9, 9, 9], dtype=np.int32)
    min_time = np.zeros((n_b, n_v), dtype=np.int32)
    gang_nodes = np.asarray([5, 3, 0, 0, 0, 0], dtype=np.int32)
    groups = ((np.arange(n_w) + 4) // 8) % 4
    group_onehot = np.eye(4, dtype=np.int32)[groups]
    resv = np.zeros(n_w, dtype=np.int32)
    resv[[5, 6, 8, 9, 11]] = 1     # group 1, over shards 0 and 1
    resv[[13, 14]] = 2             # group 2
    resv[[0, 30]] = RESV_ELSEWHERE
    gang_ok = (rng.random(n_w) < 0.6).astype(np.int32)
    gang_ok[[5, 6, 8, 9, 11]] = 1  # the first gang's drain is done
    return (free, nt_free, lifetime, needs, sizes, min_time, gang_nodes,
            gang_ok, group_onehot, resv)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sharded_reservations_equal_one_chip_across_a_shard_boundary(seed):
    """Under `--gang-drain busy` the sharded scan on 4 virtual devices gives
    the one-chip kernel's counts bit for bit when a reserved group
    straddles a shard boundary: the first gang row takes its own five
    workers, on two shards; with the codes all none both paths equal the
    path without them."""
    rng = np.random.default_rng(seed + 40)
    (free, nt_free, lifetime, needs, sizes, min_time, gang_nodes, gang_ok,
     group_onehot, resv) = _reserved_mesh_case(rng)
    scarcity = np.asarray(
        scarcity_weights(free.astype(np.int64).sum(axis=0))
    ).astype(np.float32)
    class_m, order_ids = host_visit_classes(free, needs, scarcity)
    mesh = make_worker_mesh(4)

    def both(codes):
        gang = dict(gang_nodes=gang_nodes, gang_ok=gang_ok,
                    group_onehot=group_onehot)
        if codes is not None:
            gang["gang_resv"] = codes
        single, _f, _n = greedy_cut_scan(
            free, nt_free, lifetime, needs, sizes, min_time, class_m,
            order_ids, **gang)
        sharded, _f, _n = sharded_cut_scan_donate(
            mesh, free.copy(), nt_free.copy(), lifetime,
            pack_batch_table(needs, sizes, min_time, order_ids), class_m,
            extents=needs.shape, **gang)
        return np.asarray(single), np.asarray(sharded)

    single, sharded = both(resv)
    assert np.array_equal(single, sharded)
    assert np.flatnonzero(single[0, 0]).tolist() == [5, 6, 8, 9, 11]
    assert single[2:][:, :, resv != 0].sum() == 0
    none_single, none_sharded = both(np.zeros_like(resv))
    plain_single, plain_sharded = both(None)
    assert np.array_equal(none_single, plain_single)
    assert np.array_equal(none_sharded, plain_sharded)
    assert np.array_equal(plain_single, plain_sharded)


# -- a solve with gang rows: each row does only its own kind's work ---------

def _straight_scan(free, nt_free, lifetime, needs, sizes, min_time, class_m,
                   order_ids, total=None, all_mask=None, gang_nodes=None,
                   gang_ok=None, group_onehot=None, policy_mask=None,
                   gang_resv=None):
    """The kernel as one `lax.scan` that runs every part of its work on
    every row, padded rows too, and masks what the row's kind discards:
    the gang selection taken only on a gang row, the water-fills spending
    a gang row's size as 0.  Built from the kernel's own parts, it is what
    the row-kind loops of `scan_batches` must equal bit for bit."""
    import jax.numpy as jnp

    from hyperqueue_tpu.ops.assign import (
        _gang_select_local, _variant_capacity, _water_fill_classed,
        expand_onehots,
    )

    n_b, n_v, _n_r = needs.shape
    onehots = expand_onehots(class_m, order_ids)
    unreserved = (
        None if gang_resv is None else (gang_resv == 0).astype(jnp.int32))

    def body(carry, i):
        free, nt_free, avail = carry
        is_gang = (gang_nodes[i] > 0).astype(jnp.int32)
        elig = (avail * (min_time[i, 0] <= lifetime)
                * (nt_free >= 1)).astype(jnp.int32)
        if policy_mask is not None:
            elig = elig * policy_mask[i]
        mine = None
        if gang_resv is not None:
            mine = (gang_resv == i + 1).astype(jnp.int32)
            elig = elig * jnp.maximum(unreserved, mine)
        take, feasible = _gang_select_local(
            elig, group_onehot, gang_nodes[i], mine=mine)
        take = take * is_gang
        emit = take * feasible.astype(jnp.int32)
        free = free * (1 - take)[:, None]
        nt_free = nt_free * (1 - take)
        avail = avail * (1 - take)
        remaining = sizes[i] * (1 - is_gang)
        rows = []
        for v in range(n_v):
            all_r = None if all_mask is None else all_mask[i, v]
            cap = _variant_capacity(
                free, nt_free, needs[i, v], min_time[i, v] <= lifetime,
                total=total, all_r=all_r)
            cap = jnp.minimum(cap, remaining)
            if policy_mask is not None:
                cap = cap * policy_mask[i]
            if unreserved is not None:
                cap = cap * unreserved
            assign, assigned = _water_fill_classed(
                cap, remaining, onehots[i, v])
            remaining = remaining - assigned
            free = free - assign[:, None] * needs[i, v][None, :]
            if all_r is not None:
                free = free * (1 - assign[:, None] * all_r[None, :])
            nt_free = nt_free - assign
            avail = avail * (assign == 0).astype(jnp.int32)
            rows.append(assign)
        rows[0] = rows[0] + emit
        return (free, nt_free, avail), jnp.stack(rows)

    (free, nt_free, _), counts = jax.lax.scan(
        body, (free, nt_free, gang_ok), jnp.arange(n_b))
    return counts, free, nt_free


# name: (live rows, gang rows, what else the solve carries); the bucket is
# 8 rows, so up to the last live one the rest is padding
GANG_KERNEL_CASES = {
    "gang-at-head": (6, [0], ()),
    "gang-in-middle": (6, [3], ()),
    "gang-at-last-live-row": (6, [5], ()),
    "live-row-of-size-0": (6, [1, 4], ("size-0",)),
    "full-bucket": (8, [2, 7], ()),
    "gangs-only": (4, [0, 1, 2, 3], ()),
    "reservations": (6, [1, 3], ("resv",)),
    "policy-mask": (6, [2, 4], ("pmask",)),
    "all-policy": (6, [1], ("all",)),
}


def _gang_kernel_case(name):
    """Padded inputs of one solve with gang rows: 32 workers in four
    groups of 8 shifted by 4 (every group straddles a shard boundary on a
    4-device mesh), a bucket of 8 rows, 2 variants."""
    from hyperqueue_tpu.ops.assign import RESV_ELSEWHERE

    live, gangs, extras = GANG_KERNEL_CASES[name]
    rng = np.random.default_rng(sorted(GANG_KERNEL_CASES).index(name) + 410)
    n_w, n_r, n_b, n_v = 32, 2, 8, 2
    free = (rng.integers(2, 8, size=(n_w, n_r)) * U).astype(np.int32)
    nt_free = rng.integers(1, 6, size=n_w).astype(np.int32)
    lifetime = np.where(rng.random(n_w) < 0.2, 100, INF_TIME).astype(
        np.int32)
    needs = np.zeros((n_b, n_v, n_r), dtype=np.int32)
    needs[:live, :, 0] = rng.integers(1, 3, size=(live, n_v)) * U
    needs[:live, :, 1] = rng.integers(0, 3, size=(live, n_v)) * (U // 2)
    sizes = np.zeros(n_b, dtype=np.int32)
    sizes[:live] = rng.integers(1, 12, size=live)
    min_time = np.zeros((n_b, n_v), dtype=np.int32)
    min_time[:live] = np.where(rng.random((live, n_v)) < 0.2, 3600, 0)
    gang_nodes = np.zeros(n_b, dtype=np.int32)
    gang_nodes[gangs] = rng.integers(2, 5, size=len(gangs))
    sizes[gangs] = 1
    if "size-0" in extras:
        sizes[2] = 0
    groups = ((np.arange(n_w) + 4) // 8) % 4
    kw = dict(
        gang_nodes=gang_nodes,
        gang_ok=(rng.random(n_w) < 0.7).astype(np.int32),
        group_onehot=np.eye(4, dtype=np.int32)[groups],
    )
    all_mask = None
    if "all" in extras:
        total = free.copy()
        total[::2] += U
        all_mask = np.zeros((n_b, n_v, n_r), dtype=np.int32)
        all_mask[[0, 2, 3], 0, 1] = 1
        kw.update(total=total, all_mask=all_mask)
    if "pmask" in extras:
        kw["policy_mask"] = (rng.random((n_b, n_w)) < 0.8).astype(np.int32)
    if "resv" in extras:
        resv = np.zeros(n_w, dtype=np.int32)
        resv[[5, 6, 8, 9]] = gangs[0] + 1   # group 1, over shards 0 and 1
        resv[[13, 14]] = gangs[1] + 1       # group 2
        resv[[0, 30]] = RESV_ELSEWHERE
        kw["gang_ok"][[5, 6, 8, 9]] = 1
        kw["gang_resv"] = resv
    scarcity = np.asarray(
        scarcity_weights(free.astype(np.int64).sum(axis=0))
    ).astype(np.float32)
    class_m, order_ids = host_visit_classes(
        free, needs, scarcity, all_mask=all_mask)
    args = (free, nt_free, lifetime, needs, sizes, min_time, class_m,
            order_ids)
    return args, kw


@pytest.mark.parametrize("path", ["one-chip", "mesh-4"])
@pytest.mark.parametrize("name", sorted(GANG_KERNEL_CASES))
def test_gang_row_kernel_equals_the_straight_scan(name, path):
    """A solve with gang rows runs each row's own work alone; its counts,
    free and slots after equal bit for bit those of a scan that runs
    every part on every row and masks the rest, and the numpy twin's, on
    one device and through `_sharded_body` on a 4-device mesh."""
    from hyperqueue_tpu.ops.assign import greedy_cut_scan_numpy

    args, kw = _gang_kernel_case(name)
    free, nt_free, lifetime, needs, sizes, min_time, class_m, order_ids = (
        args)
    straight = jax.jit(_straight_scan)(*args, **kw)
    host = greedy_cut_scan_numpy(*args, **kw)
    if path == "one-chip":
        got = greedy_cut_scan(free.copy(), nt_free.copy(), *args[2:], **kw)
    else:
        kw = dict(kw)
        all_mask = kw.pop("all_mask", None)
        got = sharded_cut_scan_donate(
            make_worker_mesh(4), free.copy(), nt_free.copy(), lifetime,
            pack_batch_table(needs, sizes, min_time, order_ids, all_mask),
            class_m, extents=needs.shape, has_all=all_mask is not None,
            **kw)
    for what, g, s, h in zip(("counts", "free", "nt_free"), got, straight,
                             host):
        assert np.array_equal(np.asarray(g), np.asarray(s)), what
        assert np.array_equal(np.asarray(g), np.asarray(h)), what
    counts = np.asarray(got[0])
    assert counts[np.asarray(kw["gang_nodes"]) > 0, 1:].sum() == 0
    assert counts[int(np.flatnonzero(sizes).max()) + 1:].sum() == 0


def _primitive_names(jaxpr):
    """Every primitive of a jaxpr, those of its sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names += _primitive_names(inner)
    return names


@pytest.mark.parametrize("gang", [False, True], ids=["gangless", "gang-rows"])
@pytest.mark.parametrize("path", ["one-chip", "mesh-4"])
def test_only_a_solve_with_gang_rows_leaves_the_one_scan(path, gang):
    """A solve without gang rows lowers to the one `lax.scan` of
    water-fills over every row, as it always has: no loop and no
    conditional besides.  A solve with gang rows has no scan and no
    conditional either: three loops, over the gang rows, over the
    single-node rows before each, and over those after the last."""
    import functools

    from hyperqueue_tpu.ops.assign import greedy_cut_scan_impl
    from hyperqueue_tpu.parallel.solve import _sharded_cut_scan_impl

    args, kw = _gang_kernel_case("reservations")
    if not gang:
        kw = {}
    impl = (greedy_cut_scan_impl if path == "one-chip" else
            functools.partial(_sharded_cut_scan_impl, make_worker_mesh(4)))
    names = _primitive_names(jax.make_jaxpr(impl)(*args, **kw).jaxpr)
    assert names.count("cond") == 0
    assert names.count("scan") == (0 if gang else 1)
    assert names.count("while") == (3 if gang else 0)
