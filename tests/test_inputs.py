"""The form a solve's inputs reach the device in (ops/inputs.py).

One packed put a solve: the dirty worker rows (or the whole state) and the
inputs that change every tick in one int32 buffer, a row a device, turned
into the solve's inputs by one program.  These tests hold the packed path
to what it replaces, bit for bit: the resident state and the placed inputs
equal a fresh full upload of the same padded inputs, with the shardings
they had, in both forms and every row bucket; and they count the puts.  On
one device and on the 4-device virtual mesh (conftest.py).
"""

import numpy as np
import pytest

from hyperqueue_tpu.models.greedy import GreedyCutScanModel
from hyperqueue_tpu.models.multichip import MultichipModel
from hyperqueue_tpu.ops import answer, inputs
from hyperqueue_tpu.parallel.resident import DeviceResidency
from hyperqueue_tpu.parallel.solve import _mesh_shardings, make_worker_mesh
from hyperqueue_tpu.utils.constants import INF_TIME

pytestmark = pytest.mark.multichip

U = 10_000
PW, PR, PM, PB, PV, PG = 1024, 4, 4, 16, 2, 4
DEVICES = [1, 4]


def _residency(devices):
    if devices == 1:
        return DeviceResidency()
    return DeviceResidency(
        shardings=_mesh_shardings(make_worker_mesh(devices)))


def _state(rng, pw=PW, with_total=False):
    free = (rng.integers(0, 8, size=(pw, PR)) * U).astype(np.int32)
    nt_free = rng.integers(0, 10, size=pw).astype(np.int32)
    lifetime = rng.choice([600, int(INF_TIME)], size=pw).astype(np.int32)
    return free, nt_free, lifetime, (free + U if with_total else None)


def _tick_inputs(rng, pw=PW, gang=False):
    """Per-solve inputs as `sync` takes them, one of every sharding kind."""
    out = [
        ("class_m", rng.integers(0, 16, size=(PM, pw)).astype(np.int32), 3),
        ("order_ids", rng.integers(0, PM, size=(PB, PV)).astype(np.int32), 2),
    ]
    if gang:
        out += [
            ("gang_nodes", rng.integers(0, 4, size=PB).astype(np.int32), 2),
            ("gang_ok", rng.integers(0, 2, size=pw).astype(np.int32), 1),
            ("group_onehot",
             rng.integers(0, 2, size=(pw, PG)).astype(np.int32), 0),
        ]
    return out


def _dirty(rng, state, n):
    """The state with `n` rows changed, in one, two or three arrays."""
    free, nt_free, lifetime, total = (
        None if a is None else a.copy() for a in state
    )
    rows = rng.choice(free.shape[0], size=n, replace=False)
    free[rows[::2]] += U
    nt_free[rows[1::2]] += 1
    lifetime[rows[::3]] = 77
    if total is not None:
        total[rows[::5]] += U
    return free, nt_free, lifetime, total


def _assert_equals_fresh(devices, got, state, tick_inputs):
    """What `sync` returned holds the padded inputs, and equals what a
    fresh residency's full upload of them holds, shardings included."""
    fresh = _residency(devices).sync(*state, inputs=tick_inputs)
    for dev, other, want in zip(got[:4], fresh[:4], state):
        if want is None:
            assert dev is None and other is None
            continue
        np.testing.assert_array_equal(np.asarray(dev), want)
        np.testing.assert_array_equal(np.asarray(other), want)
        assert dev.sharding == other.sharding
        assert len(dev.sharding.device_set) == devices
    assert list(got[4]) == [name for name, _arr, _kind in tick_inputs]
    for name, want, kind in tick_inputs:
        dev, other = got[4][name], fresh[4][name]
        np.testing.assert_array_equal(np.asarray(dev), want)
        assert dev.sharding == other.sharding
        if devices > 1:
            want_sharding = _mesh_shardings(make_worker_mesh(devices))[kind]
            assert dev.sharding.is_equivalent_to(want_sharding, want.ndim)


@pytest.mark.parametrize("devices", DEVICES)
@pytest.mark.parametrize("bucket", [16, 32, 64, 128, 256, 512])
def test_delta_of_every_row_bucket_equals_a_fresh_full_upload(devices, bucket):
    rng = np.random.default_rng(bucket + devices)
    res = _residency(devices)
    state = _state(rng)
    res.sync(*state, inputs=_tick_inputs(rng))
    before = res.stats()
    n = bucket - 3 if bucket > 16 else 5  # padded up to the bucket
    state2 = _dirty(rng, state, n)
    tick_inputs = _tick_inputs(rng)
    got = res.sync(*state2, inputs=tick_inputs)
    stats = res.stats()
    assert stats["dirty_rows_last"] == n
    assert stats["delta_uploads"] - before["delta_uploads"] == 1
    assert stats["full_uploads"] == before["full_uploads"] == 1
    assert stats["puts_total"] - before["puts_total"] == 1
    assert stats["input_programs_total"] - before["input_programs_total"] == 1
    # every device receives the indices and the rows, its own columns of
    # class_m, and order_ids whole
    row_words = bucket * (1 + PR + 2) + PB * PV
    assert stats["upload_bytes_total"] - before["upload_bytes_total"] \
        == 4 * (devices * row_words + PM * PW)
    _assert_equals_fresh(devices, got, state2, tick_inputs)


@pytest.mark.parametrize("devices", DEVICES)
@pytest.mark.parametrize(
    "case", ["totals", "gang", "over-half", "worker-bucket"]
)
def test_packed_path_equals_a_fresh_full_upload(devices, case):
    rng = np.random.default_rng(len(case) + devices)
    res = _residency(devices)
    gang = case == "gang"
    state = _state(rng, with_total=case == "totals")
    res.sync(*state, inputs=_tick_inputs(rng, gang=gang))
    before = res.stats()
    pw, n = PW, 40
    if case == "over-half":
        n = PW // 2 + 1
    if case == "worker-bucket":
        pw = 2 * PW
        state2 = _state(rng, pw=pw)
    else:
        state2 = _dirty(rng, state, n)
    tick_inputs = _tick_inputs(rng, pw=pw, gang=gang)
    got = res.sync(*state2, inputs=tick_inputs)
    stats = res.stats()
    full = case in ("over-half", "worker-bucket")
    assert stats["full_uploads"] - before["full_uploads"] == full
    assert stats["delta_uploads"] - before["delta_uploads"] == (not full)
    assert stats["dirty_rows_last"] == (pw if full else n)
    assert stats["puts_total"] - before["puts_total"] == 1
    assert stats["input_programs_total"] - before["input_programs_total"] == 1
    _assert_equals_fresh(devices, got, state2, tick_inputs)


@pytest.mark.parametrize("devices", DEVICES)
def test_tick_with_no_dirty_row_puts_only_what_changed(devices):
    """The state has not changed (a served cluster with every slot busy).
    Inputs that equal what the last crossing left on the device keep
    their device arrays: nothing crosses where none has changed, one or
    two changed inputs are put one by one (cheaper than a put and a
    program), and more ride the packed path in the smallest row bucket,
    row 0 re-set to what it holds."""
    rng = np.random.default_rng(70 + devices)
    res = _residency(devices)
    state = _state(rng)
    tick_inputs = _tick_inputs(rng, gang=True)
    first = res.sync(*state, inputs=tick_inputs)

    def tick(changed):
        nonlocal tick_inputs
        fresh = dict((n, (a, k)) for n, a, k in _tick_inputs(rng, gang=True))
        tick_inputs = [
            (n, fresh[n][0] if n in changed else a.copy(), k)
            for n, a, k in tick_inputs
        ]
        before = res.stats()
        got = res.sync(*state, inputs=tick_inputs)
        after = res.stats()
        assert after["dirty_rows_last"] == 0
        assert after["delta_uploads"] == before["delta_uploads"]
        assert after["full_uploads"] == before["full_uploads"] == 1
        _assert_equals_fresh(devices, got, state, tick_inputs)
        return got, tuple(
            after[key] - before[key]
            for key in ("puts_total", "input_programs_total",
                        "upload_bytes_total"))

    got, cost = tick(())
    assert cost == (0, 0, 0)
    assert all(got[4][name] is first[4][name] for name in got[4])
    got, cost = tick({"order_ids"})
    assert cost == (1, 0, 4 * PB * PV * devices)  # replicated: to each
    assert got[4]["class_m"] is first[4]["class_m"]
    _got, cost = tick({"gang_ok", "class_m"})
    assert cost == (2, 0, 4 * (PW + PM * PW))     # sharded: once
    # three inputs changed: the packed path, the smallest row bucket
    got, cost = tick({"class_m", "order_ids", "gang_nodes"})
    assert cost[:2] == (1, 1)
    assert cost[2] == 4 * (devices * (16 * (1 + PR + 2) + PB * PV + PB)
                           + PM * PW + PW + PW * PG)
    assert got[4]["gang_ok"] is not first[4]["gang_ok"]
    # and the tick after it compares with that crossing
    assert tick(())[1] == (0, 0, 0)


@pytest.mark.parametrize("devices", DEVICES)
def test_invalidate_mid_sequence_falls_back_to_one_full_form_put(devices):
    rng = np.random.default_rng(devices)
    res = _residency(devices)
    state = _state(rng)
    res.sync(*state, inputs=_tick_inputs(rng))
    state = _dirty(rng, state, 20)
    res.sync(*state, inputs=_tick_inputs(rng))
    res.invalidate()
    before = res.stats()
    assert not before["resident"]
    state = _dirty(rng, state, 20)
    tick_inputs = _tick_inputs(rng)
    got = res.sync(*state, inputs=tick_inputs)
    stats = res.stats()
    assert stats["resident"]
    assert stats["full_uploads"] - before["full_uploads"] == 1
    assert stats["delta_uploads"] == before["delta_uploads"]
    assert stats["puts_total"] - before["puts_total"] == 1
    assert stats["upload_bytes_total"] - before["upload_bytes_total"] \
        == 4 * (PW * (PR + 2) + PM * PW + devices * PB * PV)
    _assert_equals_fresh(devices, got, state, tick_inputs)
    # a solve that was dispatched and never applied is as unknowable
    res.adopt_outputs(got[0], got[1])
    res.sync(*state, inputs=tick_inputs)
    assert res.stats()["full_uploads"] - stats["full_uploads"] == 1


@pytest.mark.parametrize("devices", DEVICES)
def test_row_buckets_stay_compiled_when_the_inputs_change_shape(devices):
    """A state key that has crossed in some forms (the full one, row
    buckets) meets inputs of new shapes (a gang row appears): the forms it
    has met are compiled for the new shapes on that tick, so a later tick
    in any of them compiles nothing.  A bucket it has not met stays
    uncompiled until it is."""
    rng = np.random.default_rng(9 + devices)
    res = _residency(devices)
    state = _state(rng, pw=256)
    unpacker = inputs._unpacker()

    def tick(n, gang):
        nonlocal state
        state = _dirty(rng, state, n)
        tick_inputs = _tick_inputs(rng, pw=256, gang=gang)
        before = res.stats()["puts_total"], unpacker._cache_size()
        got = res.sync(*state, inputs=tick_inputs)
        cost = (res.stats()["puts_total"] - before[0],
                unpacker._cache_size() - before[1])
        _assert_equals_fresh(devices, got, state, tick_inputs)
        return cost

    assert tick(0, False)[0] == 1          # the full form
    assert tick(10, False) == (1, 1)       # bucket 16: compiled as met
    assert tick(60, False) == (1, 1)       # bucket 64
    # the gang inputs appear, in bucket 16: bucket 64 and the full form
    # are compiled for them on the same tick
    assert tick(10, True) == (3, 3)
    assert tick(60, True) == (1, 0)
    assert tick(10, True) == (1, 0)
    assert tick(200, True) == (1, 0)       # over half the rows: full form
    assert tick(30, True) == (2, 2)        # bucket 32 is new: both shapes
    assert tick(30, False) == (1, 0)


def test_layout_places_every_part_on_its_device():
    """The buffer, read on the host: row d holds the replicated parts
    whole and device d's share of the sharded ones, in the caller's
    order, at the offsets the layout gives."""
    rng = np.random.default_rng(2)
    devices, pw, k = 4, 32, 16
    wl = pw // devices
    state = _state(rng, pw=pw, with_total=True)
    parts = [(arr, kind)
             for _n, arr, kind in _tick_inputs(rng, pw=pw, gang=True)]
    idx = np.arange(k, dtype=np.int32)
    head = [(idx, 2)] + [(a[idx], 2) for a in state]
    layout = inputs.layout_for((pw, PR, True), k, parts, devices)
    assert layout.head == k * (1 + PR + 1 + 1 + PR)
    buf = inputs.pack_inputs(layout, head, parts)
    assert buf.shape == (devices, layout.length) and buf.dtype == np.int32
    class_m, order_ids, gang_nodes, gang_ok, group_onehot = (
        arr for arr, _kind in parts
    )
    for d in range(devices):
        row, at = buf[d], 0
        own = slice(d * wl, (d + 1) * wl)
        for want in (idx, state[0][idx], state[1][idx], state[2][idx],
                     state[3][idx], class_m[:, own], order_ids, gang_nodes,
                     gang_ok[own], group_onehot[own]):
            np.testing.assert_array_equal(
                row[at:at + want.size], want.reshape(-1))
            at += want.size
        assert at == layout.length
    full = inputs.layout_for((pw, PR, True), None, parts, devices)
    assert full.head == wl * (2 * PR + 2)
    buf = inputs.pack_inputs(
        full, list(zip(state, (0, 1, 1, 0))), parts)
    np.testing.assert_array_equal(
        buf[1, : wl * PR], state[0][wl:2 * wl].reshape(-1))


def _model(devices):
    if devices == 1:
        return GreedyCutScanModel(backend="jax")
    return MultichipModel(n_devices=devices)


def _soak_world(rng, n_w, n_b=7, n_r=4, n_v=2):
    needs = (rng.integers(0, 3, size=(n_b, n_v, n_r)) * (U // 2)).astype(
        np.int32)
    needs[:, 0, 0] = np.maximum(needs[:, 0, 0], U // 2)
    return dict(
        free=(rng.integers(1, 9, size=(n_w, n_r)) * U).astype(np.int32),
        nt_free=rng.integers(1, 10, size=n_w).astype(np.int32),
        lifetime=rng.choice([600, int(INF_TIME)], size=n_w).astype(np.int32),
        needs=needs,
        sizes=rng.integers(1, 5, size=n_b).astype(np.int32),
        min_time=rng.choice([0, 0, 120], size=(n_b, n_v)).astype(np.int32),
    )


@pytest.mark.parametrize("devices", DEVICES)
def test_resident_soak_with_worker_churn_and_the_guard_armed(devices):
    """A multi-tick resident history through `solve_cells` with
    `paranoid_resident = 1`: workers join and leave (the worker bucket
    changes twice), gang rows come and go, the state is dropped once in
    the middle.  Every tick equals the host solve and passes the
    resident-vs-fresh guard, and every steady solve cost one put and one
    input program."""
    rng = np.random.default_rng(40 + devices)
    model = _model(devices)
    model.paranoid_resident = 1
    host = GreedyCutScanModel(backend="numpy")
    workers = [33, 33, 33, 70, 70, 70, 33, 33, 33, 33, 33, 33]
    gangs = {4, 5, 9, 10}
    world = _soak_world(rng, 33)
    steady = []
    for tick, n_w in enumerate(workers):
        if n_w != world["free"].shape[0]:
            world.update({k: v for k, v in _soak_world(rng, n_w).items()
                          if k in ("free", "nt_free", "lifetime")})
        kwargs = dict(world)
        kwargs["sizes"] = rng.integers(0, 6, size=7).astype(np.int32)
        if tick in gangs:
            gang_nodes = np.zeros(7, dtype=np.int32)
            gang_nodes[2] = 3
            kwargs["sizes"][2] = 1
            gids = rng.integers(0, 2, size=n_w).astype(np.int32)
            kwargs.update(
                gang_nodes=gang_nodes,
                gang_ok=rng.integers(0, 2, size=n_w).astype(np.int32),
                group_onehot=(gids[:, None] == np.arange(2)[None, :]).astype(
                    np.int32),
            )
        if tick == 8:
            model.invalidate_resident()
        before = model.resident_stats()
        got = model.solve_cells(**{k: v.copy() for k, v in kwargs.items()})
        want = host.solve_cells(**kwargs)
        np.testing.assert_array_equal(got.flat, want.flat, err_msg=str(tick))
        np.testing.assert_array_equal(got.vals, want.vals)
        after = model.resident_stats()
        if tick in (1, 2, 7, 11):
            # shapes and row bucket met before: one put, one program
            steady.append((after["puts_total"] - before.get("puts_total", 0),
                           after["input_programs_total"]
                           - before.get("input_programs_total", 0)))
        # apply the placements, then release a few rows for the next tick
        dense = answer.dense_of_cells(got).astype(np.int64)
        used = np.einsum(
            "bvw,bvr->wr", dense, kwargs["needs"].astype(np.int64))
        world["free"] = world["free"] - used.astype(np.int32)
        world["nt_free"] = world["nt_free"] - dense.sum(axis=(0, 1)).astype(
            np.int32)
        rows = rng.choice(n_w, 5, replace=False)
        world["free"][rows] += U
        world["nt_free"][rows] += 1
    stats = model.resident_stats()
    assert model.paranoid_checks == len(workers)
    assert stats["backend"] == ("device-jax" if devices == 1
                                else "device-sharded")
    assert stats["delta_uploads"] >= 6 and stats["full_uploads"] >= 4
    assert stats["invalidations"] >= 1
    assert steady == [(1, 1)] * 4
