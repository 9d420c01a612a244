"""The form a device solve's answer reaches the host in (ops/answer.py).

One packed readback a solve: the nonzero cells of the counts, `free_after`
and `nt_after` in one int32 buffer, compacted on the device (each device of
a mesh its own W-shard).  These tests hold the packed answer to what it
replaces, bit for bit: the cells equal the nonzero of the dense counts of
the same solve, the state part equals separate readbacks of the kernel's
other two outputs, and an answer with more cells than the buffer holds
takes the dense fallback to the same placements.  On the forced `jax`
backend with one device and on the 4-device virtual mesh (conftest.py).
"""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from hyperqueue_tpu.models.greedy import GreedyCutScanModel
from hyperqueue_tpu.models.multichip import MultichipModel
from hyperqueue_tpu.ops import answer
from hyperqueue_tpu.parallel.solve import make_worker_mesh
from hyperqueue_tpu.utils.constants import INF_TIME

pytestmark = pytest.mark.multichip

U = 10_000
KINDS = ("flat", "variants", "whole-node", "gang", "policy-mask")


def _model(devices):
    if devices == 1:
        return GreedyCutScanModel(backend="jax")
    return MultichipModel(n_devices=devices)


def _world(kind, seed, n_w=100, n_b=7, n_r=4):
    """Seeded solve inputs of one kind; compact form at these extents."""
    rng = np.random.default_rng(seed)
    n_v = 1 if kind == "flat" else 2
    free = (rng.integers(1, 9, size=(n_w, n_r)) * U).astype(np.int32)
    needs = (rng.integers(0, 3, size=(n_b, n_v, n_r)) * (U // 2)).astype(
        np.int32
    )
    needs[:, 0, 0] = np.maximum(needs[:, 0, 0], U // 2)
    kwargs = dict(
        free=free,
        nt_free=rng.integers(0, 10, size=n_w).astype(np.int32),
        lifetime=rng.choice([600, int(INF_TIME)], size=n_w).astype(np.int32),
        needs=needs,
        sizes=rng.integers(1, 5, size=n_b).astype(np.int32),
        min_time=rng.choice([0, 0, 120], size=(n_b, n_v)).astype(np.int32),
    )
    if kind == "whole-node":
        all_mask = np.zeros((n_b, n_v, n_r), dtype=np.int32)
        all_mask[1, 0, 1] = 1
        needs[1, 0, 1] = 0
        kwargs.update(all_mask=all_mask, total=free.copy())
    if kind == "gang":
        gang_nodes = np.zeros(n_b, dtype=np.int32)
        gang_nodes[2] = 3
        kwargs["sizes"][2] = 1
        gids = rng.integers(0, 2, size=n_w).astype(np.int32)
        kwargs.update(
            gang_nodes=gang_nodes,
            gang_ok=rng.integers(0, 2, size=n_w).astype(np.int32),
            group_onehot=(gids[:, None] == np.arange(2)[None, :]).astype(
                np.int32
            ),
        )
    if kind == "policy-mask":
        affinity = rng.choice([0.0, 0.5, 1.0, 2.0], size=(n_b, n_w)).astype(
            np.float32
        )
        kwargs["affinity"] = affinity
    return kwargs


def _dispatch_recording_outputs(model, kwargs):
    """(handle, outputs): the kernel's three device outputs of the solve,
    kept aside for separate readbacks."""
    outputs = {}
    dispatch = model._kernel_dispatch

    def recording(*args):
        out = dispatch(*args)
        outputs["counts"], outputs["free"], outputs["nt"] = out
        return out

    model._kernel_dispatch = recording
    try:
        handle = model.solve_async(**{k: v.copy() for k, v in kwargs.items()})
    finally:
        del model._kernel_dispatch
    return handle, outputs


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("kind", KINDS)
def test_packed_cells_equal_the_nonzero_of_the_dense_counts(kind, devices):
    model = _model(devices)
    for seed in (3, 4):
        kwargs = _world(kind, seed)
        handle, out = _dispatch_recording_outputs(model, kwargs)
        # the separate readbacks the packed buffer replaces
        n_b, n_v, _ = kwargs["needs"].shape
        n_w = kwargs["free"].shape[0]
        dense = np.ascontiguousarray(
            np.asarray(out["counts"])[:n_b, :n_v, :n_w]
        )
        free_after, nt_after = np.asarray(out["free"]), np.asarray(out["nt"])
        before = model.resident_stats()
        cells = handle.cells()
        after = model.resident_stats()
        assert cells.form in ("compact", "overflow")
        assert cells.shape == dense.shape
        want_b, want_v, want_w = np.nonzero(dense)
        np.testing.assert_array_equal(
            np.stack(np.unravel_index(cells.flat, cells.shape)),
            np.stack([want_b, want_v, want_w]),
        )
        np.testing.assert_array_equal(cells.vals, dense[dense != 0])
        native = answer.cells_of_dense(dense)
        np.testing.assert_array_equal(cells.flat, native.flat)
        np.testing.assert_array_equal(handle.result(), dense)
        assert handle.result().flags.c_contiguous
        # the mirror is literally the device's output, from the same buffer
        np.testing.assert_array_equal(model._res._m_free, free_after)
        np.testing.assert_array_equal(model._res._m_nt, nt_after)
        overflow = after["answers_overflow"] - before["answers_overflow"]
        assert after["answers_total"] - before["answers_total"] == 1
        assert (after["readbacks_total"] - before["readbacks_total"]
                == 1 + overflow)
        # and the placements are the host solve's
        np.testing.assert_array_equal(
            GreedyCutScanModel(backend="numpy").solve(**kwargs), dense
        )
    assert model.resident_stats()["answers_compact"] > 0


def _put(arr, mesh, spec):
    if mesh is None:
        return jax.device_put(arr)
    return jax.device_put(arr, NamedSharding(mesh, spec))


def _pack_unpack(counts, extents, devices, pr=4):
    """Pack synthetic padded counts on `devices` devices and unpack."""
    pb, pv, pw = counts.shape
    mesh = None if devices == 1 else make_worker_mesh(devices)
    rng = np.random.default_rng(pw)
    free = rng.integers(0, 99, size=(pw, pr)).astype(np.int32)
    nt = rng.integers(0, 9, size=pw).astype(np.int32)
    layout = answer.layout_for(extents, (pb, pv, pw, pr), devices)
    buf = np.asarray(answer.pack_answer(
        _put(counts, mesh, P(None, None, "w")),
        _put(free, mesh, P("w", None)), _put(nt, mesh, P("w")),
        layout, mesh,
    ))
    assert buf.shape == (devices, layout.length) and buf.dtype == np.int32
    cells, free_after, nt_after = answer.unpack_answer(buf, layout)
    np.testing.assert_array_equal(free_after, free)
    np.testing.assert_array_equal(nt_after, nt)
    return layout, cells


def _synthetic(case, devices):
    """(padded counts, live extents, expect) for one named case; K is the
    device's capacity, pw // devices."""
    pb, pv, pw = 8, 2, 32 * devices
    k = pw // devices
    extents = (6, 2, pw - 3)
    rng = np.random.default_rng(len(case) + devices)
    counts = np.zeros((pb, pv, pw), dtype=np.int32)

    def fill(n_cells, first_col, n_cols):
        """n_cells distinct live cells within n_cols columns from
        first_col"""
        at = rng.choice(extents[0] * pv * n_cols, n_cells, replace=False)
        counts[at // (pv * n_cols), at // n_cols % pv,
               first_col + at % n_cols] = rng.integers(1, 2**30, n_cells)

    if case == "zero":
        return counts, extents, "compact"
    if case == "exactly-K":  # every device exactly full
        for d in range(devices):
            fill(k, d * k, min(k, extents[2] - d * k))
        return counts, extents, "compact"
    if case == "K-plus-1":  # one device a cell over
        fill(k + 1, 0, min(k, extents[2]))
        return counts, extents, None
    if case == "one-shard-over":  # total under D*K, the last shard over
        fill(k + 2, (devices - 1) * k, extents[2] - (devices - 1) * k)
        return counts, extents, None
    if case == "row-major-merge":  # one row's cells on every device
        counts[3, 1, rng.choice(extents[2], k // 2, replace=False)] = 7
        counts[0, 0, rng.choice(extents[2], k // 4, replace=False)] = 9
        return counts, extents, "compact"
    assert case == "dense-small"
    extents = (1, 2, pw - 3)
    fill_at = rng.choice(extents[2], 11, replace=False)
    counts[0, 1, fill_at] = rng.integers(1, 2**30, 11)
    return counts, extents, "dense-small"


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize(
    "case",
    ["zero", "exactly-K", "K-plus-1", "one-shard-over", "row-major-merge",
     "dense-small"],
)
def test_packed_buffer_forms(case, devices):
    counts, extents, expect = _synthetic(case, devices)
    layout, cells = _pack_unpack(counts, extents, devices)
    live = np.ascontiguousarray(
        counts[:extents[0], :extents[1], :extents[2]]
    )
    assert live.sum() == counts.sum()  # the case kept to the live extents
    if expect is None:
        assert layout.rows is None and cells is None  # the dense fallback
        return
    assert cells.form == expect
    assert (layout.rows is None) == (expect == "compact")
    want = np.flatnonzero(live)
    np.testing.assert_array_equal(cells.flat, want)  # ascending: row-major
    np.testing.assert_array_equal(cells.vals, live.reshape(-1)[want])
    np.testing.assert_array_equal(answer.dense_of_cells(cells), live)


@pytest.mark.parametrize("devices", [1, 4])
def test_overflowing_solve_takes_the_dense_fallback_to_the_same_placements(
    devices,
):
    """8 workers, 7 classes that all fit everywhere: more cells than K = 8
    (2 a device on the mesh), so the compact buffer overflows; the counter
    moves, a second (dense) readback is made, the placements are the host
    solve's."""
    kwargs = _world("variants", seed=9, n_w=8)
    kwargs["nt_free"][:] = 50
    kwargs["sizes"][:] = 5
    model = _model(devices)
    cells = model.solve_cells(**{k: v.copy() for k, v in kwargs.items()})
    stats = model.resident_stats()
    assert cells.form == "overflow" and cells.flat.size > 8
    assert stats["answers_overflow"] == stats["answers_total"] == 1
    assert stats["readbacks_total"] == 2
    host = GreedyCutScanModel(backend="numpy")
    want = host.solve_cells(**kwargs)
    np.testing.assert_array_equal(cells.flat, want.flat)
    np.testing.assert_array_equal(cells.vals, want.vals)
    assert want.form == "host" and cells.shape == want.shape


@pytest.mark.parametrize("devices", [1, 4])
def test_resident_soak_through_the_cells_handle_with_the_guard_armed(devices):
    """A multi-tick resident history through `solve_cells` with
    `paranoid_resident = 1` (every solve's cells against a fresh solve's,
    read back dense): every tick equals the host solve, and every answer
    crossed in one readback, or two where it overflowed."""
    rng = np.random.default_rng(devices)
    model = _model(devices)
    model.paranoid_resident = 1
    host = GreedyCutScanModel(backend="numpy")
    kwargs = _world("variants", seed=5, n_w=33)
    free, nt_free = kwargs["free"], kwargs["nt_free"]
    forms = set()
    ticks = 8
    for tick in range(ticks):
        kwargs["sizes"] = rng.integers(0, 6, size=7).astype(np.int32)
        got = model.solve_cells(**{k: v.copy() for k, v in kwargs.items()})
        want = host.solve_cells(**kwargs)
        np.testing.assert_array_equal(got.flat, want.flat, err_msg=str(tick))
        np.testing.assert_array_equal(got.vals, want.vals)
        forms.add(got.form)
        # apply the placements, then release a few rows for the next tick
        dense = answer.dense_of_cells(got).astype(np.int64)
        used = np.einsum("bvw,bvr->wr", dense, kwargs["needs"].astype(np.int64))
        free -= used.astype(np.int32)
        nt_free -= dense.sum(axis=(0, 1)).astype(np.int32)
        rows = rng.choice(33, 5, replace=False)
        free[rows] += U
        nt_free[rows] += 1
    stats = model.resident_stats()
    assert model.paranoid_checks == ticks
    assert stats["answers_total"] == ticks and stats["delta_uploads"] > 0
    assert stats["readbacks_total"] == ticks + stats["answers_overflow"]
    assert "compact" in forms


def test_guard_compares_cells_and_fires_on_a_wrong_answer(monkeypatch):
    model = _model(1)
    model.paranoid_resident = 1
    kwargs = _world("flat", seed=1)
    real = answer.unpack_answer

    def wrong(buf, layout):
        cells, free_after, nt_after = real(buf, layout)
        return cells._replace(vals=cells.vals + 1), free_after, nt_after

    import hyperqueue_tpu.models.greedy as greedy

    monkeypatch.setattr(greedy, "unpack_answer", wrong)
    with pytest.raises(greedy.ResidentParanoidError):
        model.solve_cells(**kwargs)


def test_host_handles_find_their_cells_with_the_nonzero_they_ran_before():
    from hyperqueue_tpu.scheduler.watchdog import SolverWatchdog

    kwargs = _world("variants", seed=2)
    host = GreedyCutScanModel(backend="numpy")
    dense = host.solve(**kwargs)
    want = np.flatnonzero(dense)
    for handle in (
        host.solve_async(**kwargs),
        SolverWatchdog(host, timeout_s=0.0).solve_async(**kwargs),
    ):
        cells = answer.handle_cells(handle)
        assert cells.form == "host" and cells.shape == dense.shape
        np.testing.assert_array_equal(cells.flat, want)
        np.testing.assert_array_equal(handle.result(), dense)

    class DenseOnly:  # a handle from elsewhere
        def result(self):
            return dense

    np.testing.assert_array_equal(
        answer.handle_cells(DenseOnly()).flat, want
    )
    # non-contiguous and int64 counts take numpy's nonzero
    np.testing.assert_array_equal(
        answer.cells_of_dense(dense.astype(np.int64)).flat, want
    )
    view = np.asfortranarray(dense)
    np.testing.assert_array_equal(answer.cells_of_dense(view).flat, want)


@pytest.mark.parametrize("cells", [False, True], ids=["dense", "cells"])
def test_watchdog_answers_in_the_form_asked_also_when_it_degrades(cells):
    from hyperqueue_tpu.scheduler.watchdog import SolverWatchdog

    kwargs = _world("flat", seed=6)
    want = GreedyCutScanModel(backend="numpy").solve(**kwargs)

    class Broken:
        def solve(self, **kw):
            raise RuntimeError("primary broken")

    for primary in (GreedyCutScanModel(backend="numpy"), Broken()):
        watchdog = SolverWatchdog(primary, timeout_s=0.0, rearm_ticks=2)
        out = (watchdog.solve_cells if cells else watchdog.solve)(**kwargs)
        if cells:
            assert isinstance(out, answer.SolveCells)
            out = answer.dense_of_cells(out)
        np.testing.assert_array_equal(out, want)
        assert watchdog.last_solve_degraded == isinstance(primary, Broken)


@pytest.mark.parametrize("devices", [1, 4])
def test_chip_smokes_forced_overflow_check_passes_here_too(devices):
    """What `chip_smoke.py` asserts on the chip about a solve forced over
    K, on the CPU's devices: exactly K crosses compact, K + 1 overflows
    and the dense fallback gives the same cells."""
    import chip_smoke  # conftest puts the repo root on sys.path

    mesh = None if devices == 1 else make_worker_mesh(devices)
    seen = chip_smoke.check_overflow_is_exact("cpu", mesh)
    assert seen == {"K": 512, "devices": devices,
                    "cells": {512: 512, 513: 513}}


def test_form_follows_the_extents():
    padded = (256, 2, 1024, 4)
    assert answer.answer_form((200, 2, 1000), padded) == "compact"
    # one batch: 1 000 live cells against a compact form of 2 048
    assert answer.answer_form((1, 1, 1000), padded) == "dense-small"
    assert answer.answer_form((2, 1, 1024), padded) == "dense-small"
    assert answer.answer_form((3, 1, 1000), padded) == "compact"
    # a padded volume whose flat index would not fit int32
    assert answer.answer_form((9, 2, 2**20), (1024, 2, 2**20, 4)) == \
        "dense-small"
    layout = answer.layout_for((224, 2, 16384), (256, 2, 16384, 4), 4)
    assert layout.capacity == 4096
    assert 4 * 4 * layout.length == 458_768  # bytes a tick, four devices


def test_compact_answer_pct_reads_the_counter_or_nothing():
    from chipbench import manifest

    read = manifest.metric_reader("compact_answer_pct")
    before = {"answers_total": 10, "answers_compact": 4}
    after = {"answers_total": 30, "answers_compact": 24}
    assert read({"uploads_before": before, "uploads_after": after}) == 100.0
    after["answers_compact"] = 19
    assert read({"uploads_before": before, "uploads_after": after}) == 75.0
    # the parent commit's program has no such counter
    old = {"readback_bytes_total": 5}
    assert read({"uploads_before": old, "uploads_after": old}) is None
    assert read({}) is None
    assert read({"uploads_before": before, "uploads_after": before}) is None
