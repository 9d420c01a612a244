"""Golden tests for the dense tick solver.

These encode the scheduler semantics the reference tier-1 Rust tests pin down
(crates/tako/src/internal/tests/test_scheduler_sn.rs): strict priority
dominance, resource variants, fractional amounts, min_time masking, task-slot
caps — plus randomized cross-checks of the JAX kernel against the pure-Python
oracle.
"""

import numpy as np
import pytest

from hyperqueue_tpu.models.greedy import GreedyCutScanModel
from hyperqueue_tpu.ops.assign import INF_TIME
from hyperqueue_tpu.scheduler.oracle import solve_oracle

U = 10_000  # one resource unit in fractions
INF = int(INF_TIME)

MODEL = GreedyCutScanModel()


def run(free, nt_free, lifetime, needs, sizes, min_time):
    free = np.asarray(free, dtype=np.int32)
    counts = MODEL.solve(
        free=free,
        nt_free=np.asarray(nt_free, dtype=np.int32),
        lifetime=np.asarray(lifetime, dtype=np.int32),
        needs=np.asarray(needs, dtype=np.int32),
        sizes=np.asarray(sizes, dtype=np.int32),
        min_time=np.asarray(min_time, dtype=np.int32),
    )
    return counts


def test_single_batch_spreads_over_workers():
    # 3 workers x 4 cpus; 10 one-cpu tasks -> 4+4+2 in index order
    counts = run(
        free=[[4 * U]] * 3,
        nt_free=[8] * 3,
        lifetime=[INF] * 3,
        needs=[[[U]]],
        sizes=[10],
        min_time=[[0]],
    )
    assert counts[0, 0].tolist() == [4, 4, 2]


def test_priority_dominance():
    # one worker, 4 cpus. High-prio batch (first row) takes all; low gets none.
    counts = run(
        free=[[4 * U]],
        nt_free=[8],
        lifetime=[INF],
        needs=[[[U]], [[U]]],
        sizes=[4, 4],
        min_time=[[0], [0]],
    )
    assert counts[0, 0, 0] == 4
    assert counts[1, 0, 0] == 0


def test_gap_relaxation():
    # High-prio needs 3 cpus: one fits (free 4), leaving gap 1; low-prio
    # 1-cpu tasks fill the gap even though high-prio tasks remain unplaced.
    counts = run(
        free=[[4 * U]],
        nt_free=[8],
        lifetime=[INF],
        needs=[[[3 * U]], [[U]]],
        sizes=[5, 5],
        min_time=[[0], [0]],
    )
    assert counts[0, 0, 0] == 1
    assert counts[1, 0, 0] == 1


def test_variants_preference_and_fallback():
    # Batch may use 1 gpu (preferred) or 2 cpus. Worker0 has only cpus,
    # worker1 has 1 gpu + cpus. 3 tasks: 1 runs on the gpu variant (w1),
    # the rest fall back to cpu variant.
    counts = run(
        free=[[4 * U, 0], [4 * U, 1 * U]],
        nt_free=[8, 8],
        lifetime=[INF, INF],
        needs=[[[0, U], [2 * U, 0]]],
        sizes=[3],
        min_time=[[0, 0]],
    )
    gpu_variant = counts[0, 0]
    cpu_variant = counts[0, 1]
    assert gpu_variant.tolist() == [0, 1]
    assert cpu_variant.sum() == 2


def test_fractional_resources():
    # 1 gpu, tasks need 0.5 gpu each -> exactly 2 fit
    counts = run(
        free=[[4 * U, 1 * U]],
        nt_free=[8],
        lifetime=[INF],
        needs=[[[U, U // 2]]],
        sizes=[5],
        min_time=[[0]],
    )
    assert counts[0, 0, 0] == 2


def test_min_time_masks_short_lived_worker():
    # Two workers; w0 has 100s left, w1 unlimited. Task min_time 3600s.
    counts = run(
        free=[[4 * U], [4 * U]],
        nt_free=[8, 8],
        lifetime=[100, INF],
        needs=[[[U]]],
        sizes=[8],
        min_time=[[3600]],
    )
    assert counts[0, 0].tolist() == [0, 4]


def test_task_slot_cap():
    counts = run(
        free=[[100 * U]],
        nt_free=[3],
        lifetime=[INF],
        needs=[[[U]]],
        sizes=[50],
        min_time=[[0]],
    )
    assert counts[0, 0, 0] == 3


def test_scarcity_avoids_gpu_worker_for_cpu_tasks():
    # w0 is a GPU box (scarce resource), w1 is cpu-only. CPU-only tasks that
    # fit entirely on w1 must prefer w1 despite its higher index.
    counts = run(
        free=[[8 * U, 2 * U], [8 * U, 0]],
        nt_free=[16, 16],
        lifetime=[INF, INF],
        needs=[[[U, 0]]],
        sizes=[8],
        min_time=[[0]],
    )
    assert counts[0, 0].tolist() == [0, 8]


def test_empty_and_padding_batches():
    counts = run(
        free=[[4 * U]],
        nt_free=[8],
        lifetime=[INF],
        needs=[[[U]], [[0]]],  # second batch is an all-zero padding row
        sizes=[0, 7],
        min_time=[[0], [0]],
    )
    assert counts.sum() == 0


@pytest.mark.parametrize("seed", range(8))
def test_random_cross_check_vs_oracle(seed):
    rng = np.random.default_rng(seed)
    n_w = int(rng.integers(1, 9))
    n_r = int(rng.integers(1, 4))
    n_b = int(rng.integers(1, 6))
    n_v = int(rng.integers(1, 3))
    free = rng.integers(0, 8, size=(n_w, n_r)) * U
    nt_free = rng.integers(0, 10, size=n_w)
    lifetime = np.where(rng.random(n_w) < 0.2, 100, INF)
    needs = rng.integers(0, 3, size=(n_b, n_v, n_r)) * (U // 2)
    sizes = rng.integers(0, 12, size=n_b)
    min_time = np.where(rng.random((n_b, n_v)) < 0.2, 3600, 0)

    counts = run(free, nt_free, lifetime, needs, sizes, min_time)

    from hyperqueue_tpu.ops.assign import scarcity_weights

    pad_free = np.zeros((8 if n_w <= 8 else 16, 4), dtype=np.int64)
    pad_free[:n_w, :n_r] = free
    scarcity = np.asarray(scarcity_weights(pad_free.sum(axis=0)))[:n_r]
    expected = solve_oracle(
        free.tolist(),
        nt_free.tolist(),
        lifetime.tolist(),
        needs.tolist(),
        sizes.tolist(),
        min_time.tolist(),
        scarcity.tolist(),
    )
    assert counts.tolist() == expected


def test_feasibility_invariants_random():
    # whatever the assignment, resources and slots must never go negative
    rng = np.random.default_rng(123)
    for _ in range(5):
        n_w, n_r, n_b = 6, 3, 8
        free = rng.integers(0, 16, size=(n_w, n_r)) * U
        nt_free = rng.integers(1, 6, size=n_w)
        needs = rng.integers(0, 4, size=(n_b, 1, n_r)) * (U // 4)
        sizes = rng.integers(0, 40, size=n_b)
        counts = run(
            free,
            nt_free,
            [INF] * n_w,
            needs,
            sizes,
            np.zeros((n_b, 1), dtype=np.int32),
        )
        used = np.einsum("bvw,bvr->wr", counts, needs)
        assert (used <= free).all()
        assert (counts.sum(axis=(0, 1)) <= nt_free).all()
        assert (counts.sum(axis=(1, 2)) <= sizes).all()


@pytest.mark.parametrize("seed", range(4))
def test_numpy_backend_matches_jax(seed):
    """The numpy CPU path and the jitted kernel are the same semantics."""
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel

    rng = np.random.default_rng(seed + 100)
    n_w, n_r, n_b, n_v = 6, 3, 5, 2
    free = rng.integers(0, 8, size=(n_w, n_r)) * U
    nt_free = rng.integers(0, 10, size=n_w)
    lifetime = np.where(rng.random(n_w) < 0.2, 100, INF)
    needs = rng.integers(0, 3, size=(n_b, n_v, n_r)) * (U // 2)
    sizes = rng.integers(0, 12, size=n_b)
    min_time = np.where(rng.random((n_b, n_v)) < 0.2, 3600, 0)
    args = dict(
        free=free.astype(np.int32),
        nt_free=nt_free.astype(np.int32),
        lifetime=lifetime.astype(np.int32),
        needs=needs.astype(np.int32),
        sizes=sizes.astype(np.int32),
        min_time=min_time.astype(np.int32),
    )
    jax_counts = GreedyCutScanModel(backend="jax").solve(**args)
    np_counts = GreedyCutScanModel(backend="numpy").solve(**args)
    assert (jax_counts == np_counts).all()


def test_backend_init_failure_falls_back_to_host(monkeypatch):
    """Under "auto", a jax backend that fails to initialize must not raise
    out of the solve — the scheduler loop dies silently otherwise. The
    model falls back to the host numpy path and sticks with it."""
    import jax

    model = GreedyCutScanModel(backend="auto")
    monkeypatch.setattr(
        jax, "default_backend",
        lambda: (_ for _ in ()).throw(
            RuntimeError("Unable to initialize backend 'tpu'")
        ),
    )
    assert model._numpy_path() is True
    assert model._use_numpy is True  # sticky: jax caches the failed init
    counts = model.solve(
        free=np.full((1, 1), 10_000, dtype=np.int32),
        nt_free=np.array([4], dtype=np.int32),
        lifetime=np.array([INF], dtype=np.int32),
        needs=np.full((1, 1, 1), 10_000, dtype=np.int32),
        sizes=np.array([1], dtype=np.int32),
        min_time=np.zeros((1, 1), dtype=np.int32),
    )
    assert counts.sum() == 1

@pytest.mark.parametrize("seed", range(6))
def test_gang_rows_numpy_matches_jax_and_hold_invariants(seed):
    """Fused gang rows: the numpy and jitted kernels agree bitwise, and
    every gang row is all-or-nothing — it emits exactly n_nodes counts on
    idle (gang_ok) members of ONE group in variant 0, or nothing; gang
    members never overlap across gangs or with in-scan assignments."""
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel

    rng = np.random.default_rng(seed + 500)
    n_w = int(rng.integers(4, 12))
    n_r, n_b, n_v = 2, int(rng.integers(2, 7)), 2
    n_g = int(rng.integers(1, 3))
    free = rng.integers(0, 8, size=(n_w, n_r)) * U
    nt_free = rng.integers(0, 10, size=n_w)
    lifetime = np.where(rng.random(n_w) < 0.2, 100, INF)
    needs = rng.integers(0, 3, size=(n_b, n_v, n_r)) * (U // 2)
    needs[:, 0, 0] = np.maximum(needs[:, 0, 0], U)
    sizes = rng.integers(0, 12, size=n_b)
    min_time = np.where(rng.random((n_b, n_v)) < 0.2, 3600, 0)
    gang_nodes = np.zeros(n_b, dtype=np.int64)
    for b in rng.choice(n_b, size=min(2, n_b), replace=False):
        gang_nodes[b] = int(rng.integers(2, 4))
        sizes[b] = 1
    gang_ok = rng.integers(0, 2, size=n_w)
    gids = rng.integers(0, n_g, size=n_w)
    group_onehot = (
        gids[:, None] == np.arange(n_g, dtype=np.int64)[None, :]
    ).astype(np.int32)
    args = dict(
        free=free.astype(np.int32),
        nt_free=nt_free.astype(np.int32),
        lifetime=lifetime.astype(np.int32),
        needs=needs.astype(np.int32),
        sizes=sizes.astype(np.int32),
        min_time=min_time.astype(np.int32),
        gang_nodes=gang_nodes.astype(np.int32),
        gang_ok=gang_ok.astype(np.int32),
        group_onehot=group_onehot,
    )
    jax_counts = GreedyCutScanModel(backend="jax").solve(**args)
    np_counts = GreedyCutScanModel(backend="numpy").solve(**args)
    assert (jax_counts == np_counts).all()

    counts = np.asarray(np_counts)
    # amount accounting covers ordinary rows only: a gang emit occupies
    # the whole node (free zeroed on take), not the row's needs vector
    ordinary = (gang_nodes == 0)[:, None, None]
    used = np.einsum("bvw,bvr->wr", (counts * ordinary).astype(np.int64),
                     needs.astype(np.int64))
    assert (used <= free).all()
    taken_by_gangs: set[int] = set()
    for b in range(n_b):
        n = int(gang_nodes[b])
        if not n:
            continue
        assert counts[b, 1:].sum() == 0  # gangs emit in variant 0 only
        members = np.flatnonzero(counts[b, 0])
        assert counts[b, 0, members].tolist() == [1] * len(members)
        assert len(members) in (0, n), (
            f"gang row {b} partially emitted: {members}"
        )
        for w in members:
            assert gang_ok[w] == 1
            assert w not in taken_by_gangs
            taken_by_gangs.add(int(w))
        if len(members):
            assert len({int(gids[w]) for w in members}) == 1


def test_scan_steps_by_kind_count_a_solve_with_gang_rows():
    """A device solve of 16 gang rows among 256 single-node rows, in a
    bucket of 512, adds 16 / 256 / 240 to the steps by kind: in the
    registry and in `resident_stats()`."""
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel
    from hyperqueue_tpu.utils.metrics import REGISTRY

    rng = np.random.default_rng(41)
    n_w, n_r, n_b = 64, 2, 272
    gang_nodes = np.zeros(n_b, dtype=np.int32)
    gang_nodes[np.arange(8, n_b, 17)[:16]] = 2
    sizes = rng.integers(1, 5, size=n_b).astype(np.int32)
    args = dict(
        free=np.full((n_w, n_r), 8 * U, dtype=np.int32),
        nt_free=np.full(n_w, 8, dtype=np.int32),
        lifetime=np.full(n_w, INF, dtype=np.int32),
        needs=np.full((n_b, 1, n_r), U, dtype=np.int32),
        sizes=sizes, min_time=np.zeros((n_b, 1), dtype=np.int32),
        gang_nodes=gang_nodes,
        gang_ok=np.ones(n_w, dtype=np.int32),
        group_onehot=np.eye(4, dtype=np.int32)[np.arange(n_w) // 16],
    )
    counter = REGISTRY.get("hq_solve_scan_steps_by_kind_total")
    kinds = ("gang", "fill", "idle")
    before = [counter.labels(kind).value for kind in kinds]
    model = GreedyCutScanModel(backend="jax")
    counts = model.solve(**args)
    assert model.last_backend == "device-jax"
    assert counts[gang_nodes > 0].sum() > 0
    after = [counter.labels(kind).value for kind in kinds]
    assert [a - b for a, b in zip(after, before)] == [16, 256, 240]
    stats = model.resident_stats()
    assert [stats[f"scan_steps_{kind}"] for kind in kinds] == [16, 256, 240]
    model.solve(**dict(args, gang_nodes=np.zeros(n_b, dtype=np.int32)))
    assert model.resident_stats()["scan_steps_fill"] == 256


def _random_reserved_case(rng):
    """Gang rows over workers in groups, and reservations: each gang row
    holds some workers of one group (row b's code is b + 1), a few workers
    are reserved for a gang no row carries."""
    from hyperqueue_tpu.ops.assign import RESV_ELSEWHERE

    n_w = int(rng.integers(6, 16))
    n_r, n_b, n_v = 2, int(rng.integers(3, 8)), 2
    n_g = int(rng.integers(1, 4))
    free = rng.integers(0, 8, size=(n_w, n_r)) * U
    nt_free = rng.integers(1, 10, size=n_w)
    lifetime = np.full(n_w, INF)
    needs = rng.integers(0, 3, size=(n_b, n_v, n_r)) * (U // 2)
    needs[:, 0, 0] = np.maximum(needs[:, 0, 0], U)
    sizes = rng.integers(0, 12, size=n_b)
    min_time = np.zeros((n_b, n_v), dtype=np.int64)
    gang_nodes = np.zeros(n_b, dtype=np.int64)
    gids = rng.integers(0, n_g, size=n_w)
    resv = np.zeros(n_w, dtype=np.int64)
    for b in rng.choice(n_b, size=min(3, n_b), replace=False):
        gang_nodes[b] = int(rng.integers(2, 4))
        sizes[b] = 1
        members = np.flatnonzero((gids == rng.integers(0, n_g)) & (resv == 0))
        take = members[: int(rng.integers(0, gang_nodes[b] + 1))]
        resv[take] = b + 1
    resv[(resv == 0) & (rng.random(n_w) < 0.1)] = RESV_ELSEWHERE
    gang_ok = (rng.random(n_w) < 0.7).astype(np.int64)
    group_onehot = (
        gids[:, None] == np.arange(n_g, dtype=np.int64)[None, :]
    ).astype(np.int32)
    args = dict(
        free=free.astype(np.int32), nt_free=nt_free.astype(np.int32),
        lifetime=lifetime.astype(np.int32), needs=needs.astype(np.int32),
        sizes=sizes.astype(np.int32), min_time=min_time.astype(np.int32),
        gang_nodes=gang_nodes.astype(np.int32),
        gang_ok=gang_ok.astype(np.int32), group_onehot=group_onehot,
    )
    return args, resv.astype(np.int32), gids


@pytest.mark.parametrize("seed", range(8))
def test_gang_rows_with_reservations_numpy_matches_jax(seed):
    """Reservations (`--gang-drain busy`): the numpy and jitted kernels
    agree bitwise; a reserved worker takes nothing from any single-node
    row; a gang row takes no worker reserved for another, and one whose
    own idle reserved workers number n takes n of them; with the codes all
    none the counts are bit for bit those of the path without them."""
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel

    rng = np.random.default_rng(seed + 700)
    args, resv, gids = _random_reserved_case(rng)
    jax_counts = np.asarray(
        GreedyCutScanModel(backend="jax").solve(**args, gang_resv=resv))
    np_counts = np.asarray(
        GreedyCutScanModel(backend="numpy").solve(**args, gang_resv=resv))
    assert (jax_counts == np_counts).all()
    gang_nodes, gang_ok = args["gang_nodes"], args["gang_ok"]
    single = gang_nodes == 0
    assert np_counts[single][:, :, resv != 0].sum() == 0
    for b in np.flatnonzero(gang_nodes):
        members = np.flatnonzero(np_counts[b, 0])
        assert set(resv[members].tolist()) <= {0, b + 1}
        own = np.flatnonzero((resv == b + 1) & (gang_ok == 1))
        if len(own) >= gang_nodes[b] and not np.isin(
                own, np.flatnonzero(np_counts[:b].sum(axis=(0, 1)))).any():
            assert members.tolist() == own[: gang_nodes[b]].tolist()
    none = np.zeros_like(resv)
    for backend in ("numpy", "jax"):
        model = GreedyCutScanModel(backend=backend)
        assert (np.asarray(model.solve(**args, gang_resv=none))
                == np.asarray(model.solve(**args))).all()


# -- weighted objective (policy affinity rows; scheduler/policy.py) --------

def _random_weighted_case(rng):
    n_w = int(rng.integers(1, 9))
    n_r = int(rng.integers(1, 4))
    n_b = int(rng.integers(1, 6))
    n_v = int(rng.integers(1, 3))
    free = rng.integers(0, 8, size=(n_w, n_r)) * U
    nt_free = rng.integers(0, 10, size=n_w)
    lifetime = np.where(rng.random(n_w) < 0.2, 100, INF)
    needs = rng.integers(0, 3, size=(n_b, n_v, n_r)) * (U // 2)
    sizes = rng.integers(0, 12, size=n_b)
    min_time = np.where(rng.random((n_b, n_v)) < 0.2, 3600, 0)
    # mixed rows: zeros (hard exclusion), fractional and >1 weights
    affinity = rng.choice(
        np.array([0.0, 0.5, 1.0, 2.0, 4.0]), size=(n_b, n_w))
    return free, nt_free, lifetime, needs, sizes, min_time, affinity


@pytest.mark.policy
@pytest.mark.parametrize("seed", range(6))
def test_weighted_affinity_numpy_matches_jax(seed):
    """The weighted objective is backend-invariant: the numpy twin and the
    jitted kernel agree bitwise with an affinity matrix in play."""
    rng = np.random.default_rng(seed + 900)
    free, nt_free, lifetime, needs, sizes, min_time, affinity = (
        _random_weighted_case(rng))
    args = dict(
        free=free.astype(np.int32),
        nt_free=nt_free.astype(np.int32),
        lifetime=lifetime.astype(np.int32),
        needs=needs.astype(np.int32),
        sizes=sizes.astype(np.int32),
        min_time=min_time.astype(np.int32),
        affinity=affinity.astype(np.float32),
    )
    jax_counts = GreedyCutScanModel(backend="jax").solve(**args)
    np_counts = GreedyCutScanModel(backend="numpy").solve(**args)
    assert (jax_counts == np_counts).all()


@pytest.mark.policy
@pytest.mark.parametrize("seed", range(6))
def test_weighted_affinity_matches_oracle(seed):
    """Kernel-vs-oracle parity for the weighted objective: the fused solve
    under an affinity matrix must equal the pure-Python reference, which
    visits workers in (-affinity, waste, index) order and treats weight 0
    as a hard exclusion."""
    rng = np.random.default_rng(seed + 1300)
    free, nt_free, lifetime, needs, sizes, min_time, affinity = (
        _random_weighted_case(rng))
    n_w, n_r = free.shape

    counts = MODEL.solve(
        free=free.astype(np.int32),
        nt_free=nt_free.astype(np.int32),
        lifetime=lifetime.astype(np.int32),
        needs=needs.astype(np.int32),
        sizes=sizes.astype(np.int32),
        min_time=min_time.astype(np.int32),
        affinity=affinity.astype(np.float32),
    )

    from hyperqueue_tpu.ops.assign import scarcity_weights

    pad_free = np.zeros((8 if n_w <= 8 else 16, 4), dtype=np.int64)
    pad_free[:n_w, :n_r] = free
    scarcity = np.asarray(scarcity_weights(pad_free.sum(axis=0)))[:n_r]
    expected = solve_oracle(
        free.tolist(),
        nt_free.tolist(),
        lifetime.tolist(),
        needs.tolist(),
        sizes.tolist(),
        min_time.tolist(),
        scarcity.tolist(),
        affinity=affinity.tolist(),
    )
    assert counts.tolist() == expected


@pytest.mark.policy
def test_zero_weight_is_hard_exclusion():
    # 2 workers x 4 cpus; batch excluded from worker 0 places only the 4
    # tasks worker 1 can hold, even with capacity idle on worker 0
    counts = MODEL.solve(
        free=np.asarray([[4 * U], [4 * U]], dtype=np.int32),
        nt_free=np.asarray([8, 8], dtype=np.int32),
        lifetime=np.asarray([INF, INF], dtype=np.int32),
        needs=np.asarray([[[U]]], dtype=np.int32),
        sizes=np.asarray([8], dtype=np.int32),
        min_time=np.asarray([[0]], dtype=np.int32),
        affinity=np.asarray([[0.0, 1.0]], dtype=np.float32),
    )
    assert counts[0, 0].tolist() == [0, 4]


@pytest.mark.policy
def test_affinity_reorders_water_fill():
    # equal workers, weights [1, 3, 2]: the fill visits workers in
    # descending-affinity order instead of index order
    counts = MODEL.solve(
        free=np.asarray([[4 * U]] * 3, dtype=np.int32),
        nt_free=np.asarray([8] * 3, dtype=np.int32),
        lifetime=np.asarray([INF] * 3, dtype=np.int32),
        needs=np.asarray([[[U]]], dtype=np.int32),
        sizes=np.asarray([6], dtype=np.int32),
        min_time=np.asarray([[0]], dtype=np.int32),
        affinity=np.asarray([[1.0, 3.0, 2.0]], dtype=np.float32),
    )
    assert counts[0, 0].tolist() == [0, 4, 2]


@pytest.mark.parametrize("kernel", ["jitted", "numpy"])
@pytest.mark.parametrize("units", [21, 39, 42, 63])
def test_capacity_quotient_is_exact_where_float32_is_not(units, kernel):
    """The kernel takes free // need as a float32 multiply by the
    reciprocal plus an integer correction.  For needs of 21, 39, 42 and 63
    units (10 000 to the unit, as `hetero-1k`'s 21-cpu class on 42-core
    nodes) the float32 product alone falls short of k at exactly k * need
    free: a worker with k * need free takes k tasks, one with a fraction
    less takes k - 1."""
    from hyperqueue_tpu.ops.assign import (
        greedy_cut_scan,
        greedy_cut_scan_numpy,
        host_visit_classes,
        scarcity_weights,
    )

    need = units * U
    ks = [1, 2, 3, 7, 13]
    assert max(ks) * need < 2**23  # the kernel's float32-exact range
    free = np.asarray(
        [[k * need - short] for k in ks for short in (0, 1)], dtype=np.int32
    )
    n_w = len(free)
    needs = np.asarray([[[need]]], dtype=np.int32)
    scarcity = np.asarray(
        scarcity_weights(free.astype(np.int64).sum(axis=0))
    ).astype(np.float32)
    class_m, order_ids = host_visit_classes(free, needs, scarcity)
    solve = greedy_cut_scan if kernel == "jitted" else greedy_cut_scan_numpy
    counts, free_after, _nt = solve(
        free.copy(), np.full(n_w, 64, dtype=np.int32),
        np.full(n_w, INF, dtype=np.int32), needs,
        np.asarray([1000], dtype=np.int32), np.zeros((1, 1), dtype=np.int32),
        class_m, order_ids,
    )
    want = [k - short for k in ks for short in (0, 1)]
    assert np.asarray(counts)[0, 0].tolist() == want
    assert np.asarray(free_after)[:, 0].tolist() == [
        int(f) - n * need for f, n in zip(free[:, 0], want)
    ]
