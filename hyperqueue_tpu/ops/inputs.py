"""The form in which a solve's inputs reach the device.

The twin of ops/answer.py for the way in.  What a solve brings to the
device that differs from the last solve — the worker rows that changed, and
the inputs whose content changes every tick (`class_m`, `order_ids` or the
mesh's batch table, the gang inputs) — crosses in ONE int32 buffer, one
`device_put`, and ONE jitted program (`unpack_inputs`, a module of its own:
`jit_unpack_inputs` in a trace) turns it into the solve's inputs.  A put
and a dispatch cost this kind of host a fixed price each, whatever they
carry (PERF.md section 6, PR 32), so their count is what a tick pays for.

The buffer is (D, L), a row a device (D = 1 off the mesh), put sharded on
its rows, so every device receives exactly its own row:

    delta  [idx[k], free[k, pr], nt[k], life[k], (total[k, pr]), parts...]
    full   [free[Wl, pr], nt[Wl], life[Wl], (total[Wl, pr]), parts...]

Delta form: `k` dirty worker rows (bucketed by the residency, padded with
a repeat of the first: a duplicate set of an identical payload is
order-independent) with their global row indices; every device's row holds
them all, and the program scatters into the device's own shard of the
resident arrays — DONATED, so the scatter is in place — the rows that fall
into it (the others are dropped), which needs no collective.  Full form:
the device's own shard of the whole state and no scatter.  `parts` are the
per-solve inputs in the caller's order, each by its sharding `kind` (the
index into parallel/solve._mesh_shardings): replicated ones whole in every
row, worker-sharded ones the device's own columns or rows.

`InputLayout` says where each part lies; it is computed from what the
caller hands over alone (shapes, kinds, the dirty-row bucket, the devices)
and is the program's compile key.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import numpy as np

# the worker axis of an array by its sharding kind (None: replicated)
_WORKER_AXIS = {0: 0, 1: 0, 2: None, 3: 1}


class InputLayout(NamedTuple):
    """Where the parts of one device's row of the buffer lie."""

    devices: int       # D: rows of the (D, L) buffer
    state: tuple       # (pw, pr, has_total) of the resident state
    rows: int | None   # k of the delta form; None: the full form
    parts: tuple       # ((shape, kind), ...) of the per-solve inputs

    @property
    def wl(self) -> int:
        return self.state[0] // self.devices

    @property
    def widths(self) -> tuple:
        """Columns of each state array (0: a vector): free, nt_free,
        lifetime and, where the key has totals, total."""
        _pw, pr, has_total = self.state
        return (pr, 0, 0) + ((pr,) if has_total else ())

    @property
    def head(self) -> int:
        """Words of the state part: indices and rows, or the shard."""
        cells = sum(max(width, 1) for width in self.widths)
        if self.rows is None:
            return self.wl * cells
        return self.rows * (1 + cells)

    @property
    def length(self) -> int:
        return self.head + sum(
            math.prod(_local_shape(shape, kind, self.devices))
            for shape, kind in self.parts
        )


def layout_for(state, rows, parts, devices: int = 1) -> InputLayout:
    """`parts`: the per-solve inputs as (array, kind) pairs."""
    return InputLayout(
        devices, tuple(state), rows,
        tuple((tuple(arr.shape), kind) for arr, kind in parts),
    )


def _local_shape(shape, kind, devices):
    """The shape of one device's share of an array of `shape`."""
    axis = _WORKER_AXIS[kind]
    if axis is None:
        return tuple(shape)
    local = list(shape)
    local[axis] //= devices
    return tuple(local)


# -- the host side ----------------------------------------------------------

def device_rows(arr, kind, devices):
    """(D or 1, n): row d is what device d holds of `arr`, flattened (one
    row that broadcasts, where every device holds the whole)."""
    axis = _WORKER_AXIS[kind]
    if axis is None or devices == 1:
        return arr.reshape(1, -1)
    if axis == 0:
        return arr.reshape(devices, -1)
    # (M, W): each device its own columns of every row
    m, w = arr.shape
    return arr.reshape(m, devices, w // devices).transpose(1, 0, 2).reshape(
        devices, -1
    )


def pack_inputs(layout: InputLayout, head, parts) -> np.ndarray:
    """The (D, L) int32 buffer on the host.  `head`: the state part as
    (array, kind) pairs in the layout's order — the indices and the dirty
    rows, replicated, or the whole state arrays by their shardings;
    `parts`: the per-solve inputs, likewise."""
    buf = np.empty((layout.devices, layout.length), dtype=np.int32)
    at = 0
    for arr, kind in (*head, *parts):
        rows = device_rows(arr, kind, layout.devices)
        buf[:, at:at + rows.shape[1]] = rows
        at += rows.shape[1]
    assert at == layout.length, (at, layout)
    return buf


# -- the device side --------------------------------------------------------

def _unpack_device(state, row, layout: InputLayout, shard):
    """One device's share: `row` (L,) of the buffer onto its shard of the
    resident `state` (the delta form; () in the full form).  Returns the
    state arrays, then the parts."""
    import jax.numpy as jnp

    wl = layout.wl
    out = []
    if layout.rows is None:
        at = 0
        for width in layout.widths:
            n = wl * max(width, 1)
            seg = row[at:at + n]
            out.append(seg.reshape(wl, width) if width else seg)
            at += n
    else:
        k = layout.rows
        idx = row[:k]
        if layout.devices > 1:
            # global row -> this shard's row; another shard's rows go
            # out of bounds and are dropped
            idx = idx - shard * wl
            idx = jnp.where((idx >= 0) & (idx < wl), idx, wl)
        at = k
        for dst, width in zip(state, layout.widths):
            n = k * max(width, 1)
            vals = row[at:at + n]
            if width:
                vals = vals.reshape(k, width)
            out.append(dst.at[idx].set(vals, mode="drop"))
            at += n
    for shape, kind in layout.parts:
        local = _local_shape(shape, kind, layout.devices)
        n = math.prod(local)
        out.append(row[at:at + n].reshape(local))
        at += n
    return tuple(out)


@functools.lru_cache(maxsize=None)
def _unpacker():
    """The jitted unpack program, built on first use (jax stays out of
    host-only processes).  One compiled program a layout."""
    import jax
    from jax.sharding import PartitionSpec as P

    specs = {0: P("w", None), 1: P("w"), 2: P(), 3: P(None, "w")}

    @functools.partial(
        jax.jit, static_argnames=("layout", "mesh"), donate_argnums=(0,)
    )
    def unpack_inputs(state, buf, layout, mesh=None):
        if mesh is None:
            return _unpack_device(state, buf[0], layout, 0)

        def body(state, buf):
            return _unpack_device(
                state, buf[0], layout, jax.lax.axis_index("w")
            )

        state_specs = tuple(
            specs[0] if width else specs[1] for width in layout.widths
        )
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(state_specs if state else (), specs[0]),
            out_specs=state_specs + tuple(
                specs[kind] for _shape, kind in layout.parts
            ),
            check_vma=False,
        )(state, buf)

    return unpack_inputs


def unpack_inputs(state, buf, layout: InputLayout, mesh=None):
    """Enqueue the unpack program on the put buffer and the resident
    `state` arrays (consumed: the delta form scatters in place; () in the
    full form).  Returns the state arrays, then the parts, as device
    arrays with the shardings their kinds name."""
    return _unpacker()(tuple(state), buf, layout=layout, mesh=mesh)
