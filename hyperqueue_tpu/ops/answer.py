"""The form in which a solve's answer reaches the host.

A dense solve answers with counts (B, V, W): how many tasks of batch b, in
variant v, go to worker w.  Of those cells a tick sets a few thousandths
(one or two a worker that freed something), so the mapping
(scheduler/tick.py `_map_counts`) wants the CELLS: `SolveCells`, the flat
row-major indices of the nonzero counts, their values and the live shape.
Row-major order is part of the result: within a batch the mapping takes
task ids FIFO in (variant, worker) order, and replay, the decision digests
and the resident-vs-fresh guard compare placements bit for bit.

Host solves hold dense counts and find the cells with one nonzero pass
(`cells_of_dense`).  A device solve never ships the dense counts: the
packing program (`pack_answer`, a jitted program of its own, run right
behind the kernel on the kernel's outputs) writes ONE int32 buffer a
device, which crosses to the host in one readback:

    compact      [n, flat[K], vals[K], free_after, nt_after]
    dense-small  [counts[:n_b, :n_v, :], free_after, nt_after]

`free_after` / `nt_after` are the kernel's other two outputs, which the
residency mirror needs every solve (parallel/resident.py `apply_outputs`);
they stay the resident device buffers the next solve donates, the packer
only reads them.  K is the device's share of the padded worker count: one
cell a worker.  The form follows the extents (`answer_form`): a solve whose
live dense volume is no larger than the compact form (2K cells: one batch,
as in the served path's steady state) puts the dense rows into the buffer
as they are, because compacting them would gain nothing but the fused
round trip.

Exactness: no cell is ever dropped.  `n` is the true count of the device's
nonzero cells; where it exceeds K the buffer's pairs are incomplete and
`unpack_answer` says so (`cells is None`): the caller then reads the dense
live slice (`live_slicer`) and goes on as before the packer existed, the
same placements, slower, counted (`answers_overflow`).

On a mesh every device compacts its own W-shard (`shard_map` over the
kernel's mesh) with GLOBAL flat indices and the host merges the short
lists by flat index; the packer adds no collective (left to GSPMD, a
compaction would all-gather the dense counts).

The compaction (chosen on the chip, PERF.md section 6, PR 30): the shard's
(B*V, Wl) matrix is cut into blocks of T = 128 consecutive cells; block
counts, a prefix over each row's blocks and over the rows say in which
block slot j's cell lies (compares, no search); ONE gather of K blocks
brings those 128 cells, and a compare against the within-block rank picks
the cell.  Every step is int32; nothing is approximate.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import numpy as np

# cells of one compaction block: the lane width of a TPU vector register
_BLOCK = 128


class SolveCells(NamedTuple):
    """The nonzero cells of a solve's (B, V, W) counts, row-major."""

    flat: np.ndarray   # (n,) int64, ascending flat indices into `shape`
    vals: np.ndarray   # (n,) int64, the counts at those cells (all > 0)
    shape: tuple       # live (n_b, n_v, n_w)
    # how the answer reached the host: "host" (a host solve's nonzero),
    # "compact", "dense-small" (the packed buffer's two forms) or
    # "overflow" (more cells than the buffer holds: dense fallback)
    form: str = "host"


def cells_of_dense(counts, form: str = "host") -> SolveCells:
    """The cells of dense counts: one native pass where the array allows
    it (C-contiguous int32, what every backend hands over), else numpy."""
    from hyperqueue_tpu.utils.native import native_nonzero

    counts = np.asarray(counts)
    found = native_nonzero(counts)
    if found is None:
        flat = np.flatnonzero(counts)
        found = flat, counts.reshape(-1)[flat].astype(np.int64)
    return SolveCells(found[0], found[1], tuple(counts.shape), form)


def dense_of_cells(cells: SolveCells) -> np.ndarray:
    """Dense (B, V, W) int32 counts, C-contiguous, from the cells."""
    counts = np.zeros(cells.shape, dtype=np.int32)
    counts.reshape(-1)[cells.flat] = cells.vals
    return counts


def handle_cells(handle) -> SolveCells:
    """The cells of a solve handle: its own `cells()` where it has one
    (every handle of this package does), else the nonzero of its dense
    `result()` (a handle from elsewhere that only knows dense counts)."""
    cells = getattr(handle, "cells", None)
    if cells is not None:
        return cells()
    return cells_of_dense(handle.result())


def model_cells(model, kwargs: dict) -> SolveCells:
    """One solve of `model`, answered in cells: its `solve_cells` where it
    has one, else the nonzero of its dense `solve` (the MILP, a model from
    elsewhere)."""
    solve_cells = getattr(model, "solve_cells", None)
    if solve_cells is not None:
        return solve_cells(**kwargs)
    return cells_of_dense(model.solve(**kwargs))


def answer_form(extents: tuple, padded: tuple) -> str:
    """"compact" or "dense-small", from the live extents and the padded
    shape alone: dense where the live volume is no larger than the compact
    form's 2K cells (K = the padded worker count), or where a flat index
    into the padded volume would not fit int32."""
    n_b, n_v, n_w = extents
    pb, pv, pw = padded[:3]
    if n_b * n_v * n_w <= 2 * pw or pb * pv * pw >= 2**31:
        return "dense-small"
    return "compact"


def _capacity(wl: int) -> int:
    """K of one device from its share of the padded worker count: one cell
    a worker.  The packer and the host's unpacking both ask here."""
    return wl


class AnswerLayout(NamedTuple):
    """Where the parts of one device's packed buffer lie (int32 words)."""

    devices: int        # D: rows of the (D, L) buffer
    padded: tuple       # (pb, pv, pw, pr)
    extents: tuple      # live (n_b, n_v, n_w)
    rows: tuple | None  # (n_b, n_v) of the dense-small form, else None

    @property
    def wl(self) -> int:
        return self.padded[2] // self.devices

    @property
    def capacity(self) -> int:
        """K of one device: the cells its compact form holds."""
        return _capacity(self.wl)

    @property
    def body(self) -> int:
        if self.rows is None:
            return 1 + 2 * self.capacity
        return self.rows[0] * self.rows[1] * self.wl

    @property
    def length(self) -> int:
        return self.body + self.wl * self.padded[3] + self.wl


def layout_for(extents, padded, devices: int = 1) -> AnswerLayout:
    rows = (
        None if answer_form(extents, padded) == "compact"
        else (extents[0], extents[1])
    )
    return AnswerLayout(devices, tuple(padded), tuple(extents), rows)


# -- the device side --------------------------------------------------------

def _prefix_lanes(x):
    """Inclusive prefix sum along the last axis of an int32 array, as
    log-step shifted adds (ops/assign.py `_exclusive_prefix_rows` along
    the other axis): no `cumsum`, so no `reduce-window` on a TPU."""
    import jax
    import jax.numpy as jnp

    n = x.shape[-1]
    y, d = x, 1
    while d < n:
        shift = [(0, 0, 0)] * (x.ndim - 1) + [(d, -d, 0)]
        y = y + jax.lax.pad(y, jnp.int32(0), shift)
        d *= 2
    return y


def _compact_cells(c2, capacity: int, col0, row_stride: int):
    """(n, flat[capacity], vals[capacity]) of the nonzero cells of the
    int32 matrix c2 (R, Wl), row-major; flat = r * row_stride + col0 + w.
    n is the true count; slots at and beyond min(n, capacity) hold 0."""
    import jax.numpy as jnp

    from hyperqueue_tpu.ops.assign import _exclusive_prefix_rows

    n_rows, wl = c2.shape
    t = math.gcd(wl, _BLOCK)  # a block never straddles a row
    nb = wl // t
    blocks = c2.reshape(n_rows * nb, t)
    in_block = jnp.sum((blocks != 0).astype(jnp.int32), axis=1)
    # cells up to and including each block, within its row
    row_blocks = _prefix_lanes(in_block.reshape(n_rows, nb))
    in_row = row_blocks[:, -1]
    before_row = _exclusive_prefix_rows(in_row)
    upto_row = before_row + in_row
    n = upto_row[-1]

    slot = jnp.arange(capacity, dtype=jnp.int32)
    # the row of slot j: how many rows end at or before it
    row = jnp.minimum(
        jnp.sum((upto_row[None, :] <= slot[:, None]).astype(jnp.int32),
                axis=1),
        n_rows - 1,
    )
    in_row_rank = slot - before_row[row]
    upto = row_blocks[row]  # (capacity, nb): a gather of short rows
    passed = upto <= in_row_rank[:, None]
    blk = jnp.minimum(jnp.sum(passed.astype(jnp.int32), axis=1), nb - 1)
    # cells of the row in the blocks passed: the prefix is nondecreasing
    in_block_rank = in_row_rank - jnp.max(jnp.where(passed, upto, 0), axis=1)

    cells = blocks[row * nb + blk]  # (capacity, t): THE gather
    held = cells != 0
    rank = _prefix_lanes(held.astype(jnp.int32))
    hit = held & (rank == (in_block_rank + 1)[:, None])
    lane = jnp.sum(
        jnp.where(hit, jnp.arange(t, dtype=jnp.int32)[None, :], 0), axis=1
    )
    vals = jnp.sum(jnp.where(hit, cells, 0), axis=1)
    flat = row * row_stride + col0 + blk * t + lane
    live = slot < n
    return n, jnp.where(live, flat, 0), jnp.where(live, vals, 0)


def _pack_device(counts, free_after, nt_after, shard, n_shards, rows):
    """One device's buffer, (1, L): its W-shard of the padded counts in
    the form `rows` says, then its shard of the state."""
    import jax.numpy as jnp

    pb, pv, wl = counts.shape
    if rows is None:
        n, flat, vals = _compact_cells(
            counts.reshape(pb * pv, wl), _capacity(wl), shard * wl,
            wl * n_shards,
        )
        body = [n[None], flat, vals]
    else:
        body = [counts[: rows[0], : rows[1], :].reshape(-1)]
    return jnp.concatenate(
        body + [free_after.reshape(-1), nt_after]
    )[None, :]


@functools.lru_cache(maxsize=None)
def _packer():
    """The jitted packing program, built on first use (jax stays out of
    host-only processes).  One compiled program a padded shape for the
    compact form; the dense-small form is also keyed by its live rows."""
    import jax
    from jax.sharding import PartitionSpec as P

    @functools.partial(jax.jit, static_argnames=("mesh", "rows"))
    def pack_answer(counts, free_after, nt_after, mesh=None, rows=None):
        if mesh is None:
            return _pack_device(counts, free_after, nt_after, 0, 1, rows)
        n_shards = mesh.devices.size

        def body(counts, free_after, nt_after):
            return _pack_device(
                counts, free_after, nt_after, jax.lax.axis_index("w"),
                n_shards, rows,
            )

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None, "w"), P("w", None), P("w")),
            out_specs=P("w", None),
            check_vma=False,
        )(counts, free_after, nt_after)

    return pack_answer


def pack_answer(counts, free_after, nt_after, layout: AnswerLayout,
                mesh=None):
    """Enqueue the packing program on a kernel's three outputs; returns
    the (D, L) int32 device buffer `unpack_answer` reads."""
    return _packer()(
        counts, free_after, nt_after, mesh=mesh, rows=layout.rows
    )


@functools.lru_cache(maxsize=64)
def live_slicer(n_b: int, n_v: int, n_w: int):
    """Jitted padded->live slicer for the overflow fallback: trims the
    padded (PB, PV, PW) counts to the live extents ON the device, so the
    dense readback never carries the padded volume and arrives
    C-contiguous.  Compiled once per distinct extent triple."""
    import jax

    @jax.jit
    def slice_live(c):
        return c[:n_b, :n_v, :n_w]

    return slice_live


# -- the host side ----------------------------------------------------------

def unpack_answer(buf: np.ndarray, layout: AnswerLayout):
    """(cells, free_after, nt_after) of a packed buffer (D, L) on the host.
    `cells` is None where a device found more cells than its compact form
    holds: the pairs are then incomplete and the caller reads the dense
    counts.  The state arrays are views into `buf`."""
    devices, wl = layout.devices, layout.wl
    pb, pv, pw, pr = layout.padded
    n_b, n_v, n_w = layout.extents
    at = layout.body
    free_after = buf[:, at:at + wl * pr].reshape(pw, pr)
    nt_after = buf[:, at + wl * pr:].reshape(pw)
    if layout.rows is not None:
        # (D, n_b, n_v, Wl) -> (n_b, n_v, D * Wl): the devices' columns in a row
        dense = buf[:, :at].reshape(devices, n_b, n_v, wl)
        dense = dense.transpose(1, 2, 0, 3).reshape(n_b, n_v, pw)
        cells = cells_of_dense(
            np.ascontiguousarray(dense[:, :, :n_w]), form="dense-small"
        )
        return cells, free_after, nt_after
    k = layout.capacity
    found = buf[:, 0]
    if (found > k).any():
        return None, free_after, nt_after
    flat = np.concatenate(
        [buf[d, 1:1 + found[d]] for d in range(devices)]
    ).astype(np.int64)
    vals = np.concatenate(
        [buf[d, 1 + k:1 + k + found[d]] for d in range(devices)]
    ).astype(np.int64)
    if devices > 1:
        # each device's list is row-major over its own columns: merge
        order = np.argsort(flat, kind="stable")
        flat, vals = flat[order], vals[order]
    # padded flat index -> live flat index (padding holds no cell)
    b, rest = np.divmod(flat, pv * pw)
    v, w = np.divmod(rest, pw)
    flat = (b * n_v + v) * n_w + w
    return SolveCells(flat, vals, (n_b, n_v, n_w), "compact"), \
        free_after, nt_after
