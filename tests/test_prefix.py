"""The jitted kernel's one way to take a prefix sum (ops/assign.py,
`_exclusive_prefix_rows`): bit for bit `np.cumsum(x, 0) - x` in int32 over
the whole domain the kernel accepts (amounts up to 2**30, sums that wrap),
at every width from one row to a sharded chip's 4 096, and the classed
water-fill built on it against the `jnp.cumsum` line it replaced.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hyperqueue_tpu.ops.assign import (
    N_VISIT_CLASSES,
    PREFIX_FORMULATION,
    _exclusive_prefix_rows,
    _water_fill_classed,
)

WIDTHS = [1, 8, 64, 100, 128, 1000, 1024, 4096]
# exclusive upper ends of the value ranges: 0/1, small counts, the
# float32-exact range, and the kernel's whole domain (a column's sum wraps)
RANGES = {"0-1": 2, "to-512": 513, "to-2**23": 2**23 + 1,
          "to-2**30-wraps": 2**30 + 1}

prefix = jax.jit(_exclusive_prefix_rows)


def numpy_prefix(x):
    """`np.cumsum(x, 0) - x` as int32 arithmetic does it: mod 2**32."""
    wide = np.asarray(x, dtype=np.int64)
    return (np.cumsum(wide, axis=0) - wide).astype(np.uint32).astype(np.int32)


@pytest.mark.parametrize("hi", RANGES.values(), ids=RANGES.keys())
@pytest.mark.parametrize("c", [1, 16])
@pytest.mark.parametrize("w", WIDTHS)
def test_prefix_equals_numpy_cumsum_bit_for_bit(w, c, hi):
    rng = np.random.default_rng(w * 131 + c * 7 + hi % 1009)
    x = rng.integers(0, hi, size=(w, c)).astype(np.int32)
    if hi > 2**30 and w >= 8:
        x[: w // 2] = 2**30  # the largest amount in every row: wraps at once
    got = np.asarray(prefix(x))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, numpy_prefix(x))


@pytest.mark.parametrize("w", WIDTHS)
def test_prefix_of_zeros_and_of_one_hot_rows(w):
    zeros = np.zeros((w, N_VISIT_CLASSES), np.int32)
    np.testing.assert_array_equal(np.asarray(prefix(zeros)), zeros)
    rng = np.random.default_rng(w)
    onehot = (
        rng.integers(0, N_VISIT_CLASSES, size=w)[:, None]
        == np.arange(N_VISIT_CLASSES)
    ).astype(np.int32)  # one 1 a row: what `class_onehot` looks like
    np.testing.assert_array_equal(
        np.asarray(prefix(onehot)), numpy_prefix(onehot)
    )


@pytest.mark.parametrize("w", [1, 16, 100, 1024])
def test_prefix_of_a_vector(w):
    """The 16 per-class sums and the gang's eligible mask are (n,)."""
    x = np.random.default_rng(w).integers(0, 2**30 + 1, size=w).astype(
        np.int32)
    np.testing.assert_array_equal(np.asarray(prefix(x)), numpy_prefix(x))


def _water_fill_with_cumsum(
    cap, remaining, class_onehot, per_class_total=None, same_class_before=0
):
    """`_water_fill_classed` as it stood before PR 28."""
    cap_c = cap[:, None] * class_onehot
    per_class = jnp.sum(cap_c, axis=0)
    if per_class_total is None:
        per_class_total = per_class
    class_before = jnp.cumsum(per_class_total) - per_class_total
    within_excl = jnp.cumsum(cap_c, axis=0) - cap_c
    prefix = jnp.sum(
        (within_excl + (class_before + same_class_before)[None, :])
        * class_onehot,
        axis=1,
    )
    assign = jnp.clip(remaining - prefix, 0, cap)
    return assign, jnp.minimum(remaining, jnp.sum(per_class_total))


new_fill = jax.jit(_water_fill_classed)
old_fill = jax.jit(_water_fill_with_cumsum)


@pytest.mark.parametrize("seed", range(20))
def test_water_fill_equals_the_cumsum_line_it_replaced(seed):
    """W = 1 024, whole and as the sharded path passes it: four shards of
    256 rows, each with the cluster-wide per-class totals and the same-class
    capacity of the shards below it."""
    rng = np.random.default_rng(seed)
    w, d = 1024, 4
    remaining = np.int32(rng.integers(1, [50, 5_000, 2**30][seed % 3]))
    cap = np.minimum(
        rng.integers(0, [4, 64, 2**30][seed % 3] + 1, size=w), remaining
    ).astype(np.int32) * (rng.random(w) < 0.7)
    cap = cap.astype(np.int32)
    onehot = (
        rng.integers(0, [2, 5, N_VISIT_CLASSES][seed % 3], size=w)[:, None]
        == np.arange(N_VISIT_CLASSES)
    ).astype(np.int32)

    want, want_total = old_fill(cap, remaining, onehot)
    got, got_total = new_fill(cap, remaining, onehot)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(got_total) == int(want_total)

    per_shard = (cap[:, None] * onehot).reshape(d, w // d, -1).sum(axis=1)
    per_class_total = per_shard.sum(axis=0, dtype=np.int32)
    shards = []
    for dev in range(d):
        rows = slice(dev * w // d, (dev + 1) * w // d)
        kwargs = {
            "per_class_total": per_class_total,
            "same_class_before": per_shard[:dev].sum(axis=0, dtype=np.int32),
        }
        a_new, t_new = new_fill(cap[rows], remaining, onehot[rows], **kwargs)
        a_old, t_old = old_fill(cap[rows], remaining, onehot[rows], **kwargs)
        np.testing.assert_array_equal(np.asarray(a_new), np.asarray(a_old))
        assert int(t_new) == int(t_old) == int(want_total)
        shards.append(np.asarray(a_new))
    # and the shards together are the one chip's answer
    np.testing.assert_array_equal(np.concatenate(shards), np.asarray(want))


def test_solves_are_counted_by_their_prefix_formulation():
    """`hq_solve_prefix_total{impl}`: a solve of the jitted kernel counts
    under its formulation, a host solve under `cumsum`."""
    from types import SimpleNamespace

    from hyperqueue_tpu.scheduler.tick import _count_solve
    from hyperqueue_tpu.utils.metrics import REGISTRY

    counter = REGISTRY.get("hq_solve_prefix_total")
    needs = np.zeros((3, 2, 4), np.int32)
    for backend, impl in (("device-jax", PREFIX_FORMULATION),
                          ("device-sharded", PREFIX_FORMULATION),
                          ("host-numpy", "cumsum"),
                          ("host-native", "cumsum")):
        before = counter.labels(impl).value
        _count_solve(SimpleNamespace(last_backend=backend), needs)
        assert counter.labels(impl).value - before == 1
    assert PREFIX_FORMULATION != "cumsum"
