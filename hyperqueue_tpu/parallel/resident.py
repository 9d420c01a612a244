"""Device-resident tick state: delta uploads + donated solve buffers.

The end-to-end device solve used to pay a full `device_put` of the padded
(W, R)/(W,) state every tick, even though the tick-over-tick delta is tiny:
the solve itself already computes `free_after`/`nt_after` ON the device, and
only the rows touched by task completions (and other host-side bookkeeping)
between two ticks actually differ from what the device would predict.

`DeviceResidency` keeps the padded solver state alive on the accelerator
across ticks and makes each solve pay only for what changed:

- the device arrays (`free`, `nt_free`, `lifetime`, `total`) stay resident,
  sharded over the worker mesh for the multichip model or on the single
  device for the greedy model;
- a HOST MIRROR (plain numpy, one per array) tracks the device contents
  exactly; each tick the new padded inputs are row-diffed against the
  mirror and only the dirty rows cross (bucketed row counts keep the
  compiled programs few);
- what a solve brings to the device crosses ONCE: the dirty rows (or, in
  the full form, the whole state) and the solve's other int32 inputs
  (`class_m` and the batch-shaped arrays — on the mesh as its batch
  table — and the gang inputs: at a deep backlog all of them change from
  tick to tick) ride ONE packed buffer, one `device_put`, and one jitted
  program (ops/inputs.py `unpack_inputs`) scatters the rows into the
  resident arrays and hands the rest out as static slices;
- the solve runs with `free`/`nt_free` DONATED (ops/assign.greedy_cut_scan
  and parallel/solve.sharded_cut_scan_donate), so `free_after`/`nt_after`
  of solve N become the resident inputs of solve N+1 with zero host
  traffic; the mirror is re-synchronized from the (small) `free_after`/
  `nt_after` arrays, which ride the same device round trip as the answer:
  the packing program (ops/answer.py) puts them behind the solve's cells in
  the ONE buffer a solve reads back, and `apply_outputs` takes them from
  there;
- the policy mask of a policy solve, (B, W) and stable while the policy
  is, is placement-cached by content: a tick that repeats it re-uses the
  device buffer outright.

Correctness contract: the resident path must be BIT-IDENTICAL to a fresh
full-upload solve of the same padded inputs.  `models/greedy.py` exposes it
as a paranoid mode (`--paranoid-tick` re-solves from scratch and asserts
count equality) and tests/test_parallel.py drives a randomized multi-tick
soak with worker churn through it.  Anything this module cannot track
exactly — a dropped pipeline dispatch whose outputs were never read back, a
bucket-shape change, a watchdog fallback that bypassed the device — calls
`invalidate()` and the next tick falls back to one full upload.
"""

from __future__ import annotations

import numpy as np

from hyperqueue_tpu.models.greedy import _bucket
from hyperqueue_tpu.ops.inputs import (
    InputLayout,
    device_rows,
    layout_for,
    pack_inputs,
    unpack_inputs,
)

# dirty-row fraction above which the full form beats the delta (an index
# gather on the host and a scatter on the device; at >=half the rows the
# whole state is strictly simpler)
FULL_UPLOAD_FRACTION = 0.5

# dirty-row counts are bucketed to powers of two (floor 16, the shared
# models/greedy._bucket rule) so the compiled unpack programs stay few;
# padding repeats the first dirty row (a duplicate .set() with an
# identical payload is order-independent)
_ROW_BUCKET_FLOOR = 16

# the sharding kinds (parallel/solve._mesh_shardings) of the state arrays
_STATE_KINDS = (0, 1, 1, 0)   # free, nt_free, lifetime, total

# the inputs a solve with gang rows brings (models/greedy._gang_inputs)
GANG_INPUT_NAMES = ("gang_nodes", "gang_ok", "group_onehot", "gang_resv")

# a tick with no dirty row puts the inputs that changed one by one where
# they are no more than this, and packs them otherwise: a put costs the
# host 0.25 ms and the unpack program's dispatch 0.4 (PERF.md section 6,
# PR 32), so two puts tie with one put and one program and three lose
_FEW_CHANGED_INPUTS = 2


class DeviceResidency:
    """Resident device buffers + host mirror for one solver's padded state.

    Shardings: `shardings` is the (w2, w1, rep) NamedSharding triple for a
    mesh (parallel/solve._mesh_shardings), or None for single-device
    placement (optionally pinned with `device`).
    """

    def __init__(self, shardings=None, device=None):
        self._shardings = shardings
        self._device = device
        # the mesh the state is sharded over (None: one device) and the
        # devices that hold it: a replicated put crosses to each
        self.mesh = None if shardings is None else shardings[2].mesh
        self.mesh_devices = (
            1 if self.mesh is None else int(self.mesh.devices.size)
        )
        self.key = None            # (pw, pr, has_total) of the resident state
        self.free = None           # device (pw, pr) int32
        self.nt_free = None        # device (pw,) int32
        self.lifetime = None       # device (pw,) int32
        self.total = None          # device (pw, pr) int32 (ALL-policy only)
        self._m_free = None        # host mirrors of the device contents
        self._m_nt = None
        self._m_life = None
        self._m_total = None
        self._valid = False
        # set between a donated solve and apply_counts()/invalidate():
        # while True the mirror does NOT reflect the device (the device
        # holds free_after) and sync() must not run
        self._await_apply = False
        # placement cache of the inputs that repeat: name -> (host copy,
        # device arr)
        self._rep_cache: dict = {}
        # (pw, pr, has_total) -> (the per-solve inputs' shapes, the forms:
        # None or a row bucket) this state key has crossed in, and the
        # unpack layouts already run: sync() keeps their cross product
        # compiled
        self._met: dict = {}
        self._ran: set = set()
        # the last solve's crossing: (layout, its buffer on the host,
        # the per-solve inputs on the device); a tick with no dirty row
        # compares its inputs with it and re-uses what has not changed
        self._last = None
        # telemetry (scraped via the model's resident_stats())
        self.full_uploads = 0
        self.delta_uploads = 0
        self.dirty_rows_last = 0
        self.upload_bytes_total = 0
        self.puts_total = 0
        self.input_programs_total = 0
        self.rep_cache_hits = 0
        # what the gang rows cost on the way in: host bytes of the three
        # gang inputs handed to `sync`, and the (padded) groups of the last
        # one-hot, (W, G) int32 every solve
        self.gang_input_bytes_total = 0
        self.gang_groups_last = 0
        self.invalidations = 0
        self.readbacks_total = 0
        self.readback_bytes_total = 0
        # the form each solve's answer crossed in (ops/answer.py): chosen
        # by the extents (compact / dense-small), or the dense fallback a
        # compact buffer that overflowed forces (`overflow`)
        self.answers = {"compact": 0, "dense-small": 0, "overflow": 0}

    # -- placement helpers ------------------------------------------------
    def _put_bytes(self, nbytes: int, kind: int) -> int:
        """Bytes that cross to the devices when `nbytes` are put with
        sharding `kind`: a worker-sharded array (0, 1, 3) reaches each
        device in part, a replicated one (2) reaches every device whole."""
        return int(nbytes) * (self.mesh_devices if kind == 2 else 1)

    def _put(self, arr, kind):
        """THE `device_put` of the residency: every put is counted."""
        import jax

        self.puts_total += 1
        if self._shardings is not None:
            return jax.device_put(arr, self._shardings[kind])
        if self._device is not None:
            return jax.device_put(arr, self._device)
        return jax.device_put(arr)

    # -- the per-tick sync ------------------------------------------------
    def sync(self, free_p, nt_p, life_p, total_p=None, inputs=()):
        """Bring the device up to date with this tick's padded host inputs
        in ONE put and ONE program (ops/inputs.py): the resident state,
        and `inputs`, the solve's per-tick inputs as (name, array, kind)
        (`kind` as `place_cached`'s).  Returns (free, nt_free, lifetime,
        total, placed): the resident device arrays and `inputs` on the
        device by name.  The full form (the whole state) when nothing is
        resident or too much changed, the dirty-row delta otherwise.  A
        tick with no dirty row re-uses the inputs that have not changed
        (`_reuse_inputs`: no put at all where none has); where many have,
        it re-sets row 0 to what it holds, so that they ride a layout
        that is already compiled."""
        if self._await_apply:
            # the previous solve's counts were never applied to the mirror
            # (e.g. a dropped pipeline dispatch): residency is unknowable
            self.invalidate()
        for name, arr, _kind in inputs:
            if name in GANG_INPUT_NAMES:
                self.gang_input_bytes_total += int(arr.nbytes)
                if name == "group_onehot":
                    self.gang_groups_last = int(arr.shape[1])
        pw, pr = free_p.shape
        key = (pw, pr, total_p is not None)
        state_p = (free_p, nt_p, life_p) + (
            () if total_p is None else (total_p,)
        )
        parts = [(arr, kind) for _name, arr, kind in inputs]
        rows = None
        if self._valid and key == self.key:
            rows = self._dirty_rows(state_p)
            if rows.size > pw * FULL_UPLOAD_FRACTION:
                rows = None
            elif rows.size == 0:
                placed = self._reuse_inputs(parts)
                if placed is not None:
                    self.dirty_rows_last = 0
                    return self._state_and(inputs, placed)
        if rows is None:
            layout = layout_for(key, None, parts, self.mesh_devices)
            head = list(zip(state_p, _STATE_KINDS))
            self.key = key
            self._m_free, self._m_nt, self._m_life = (
                a.copy() for a in state_p[:3]
            )
            self._m_total = None if total_p is None else total_p.copy()
            self._valid = True
            self.dirty_rows_last = pw
            self.full_uploads += 1
        else:
            layout = layout_for(
                key, _bucket(int(rows.size), _ROW_BUCKET_FLOOR), parts,
                self.mesh_devices,
            )
            head = self._delta_head(layout.rows, rows, state_p)
            for mirror, arr in zip(self._mirrors(), state_p):
                mirror[rows] = arr[rows]
            self.dirty_rows_last = int(rows.size)
            if rows.size:
                self.delta_uploads += 1
        buf, placed = self._cross(layout, head, parts)
        self._last = (layout, buf, placed)
        self._keep_compiled(layout)
        return self._state_and(inputs, placed)

    def _state_and(self, inputs, placed) -> tuple:
        return self.free, self.nt_free, self.lifetime, self.total, {
            name: dev for (name, _arr, _kind), dev in zip(inputs, placed)
        }

    def _reuse_inputs(self, parts):
        """A tick with no dirty row (a served cluster whose slots are all
        busy): the inputs are compared with what the last crossing left
        on the device, and those that have not changed keep their device
        arrays.  The few that have (the batch sizes, as a rule) are put
        one by one, which is cheaper than a put and a program; None where
        more have changed, or their shapes: the packed path then."""
        if self._last is None:
            return None
        layout, buf, placed = self._last
        if tuple((arr.shape, kind) for arr, kind in parts) != layout.parts:
            return None
        changed, at = [], layout.head
        for i, (arr, kind) in enumerate(parts):
            rows = device_rows(arr, kind, layout.devices)
            seg = buf[:, at:at + rows.shape[1]]
            at += rows.shape[1]
            if not (seg == rows).all():
                changed.append((i, seg, rows))
        if len(changed) > _FEW_CHANGED_INPUTS:
            return None
        placed = list(placed)
        for i, seg, rows in changed:
            arr, kind = parts[i]
            # a copy: the caller's padded buffers are rewritten in place
            placed[i] = self._put(arr.copy(), kind)
            self.upload_bytes_total += self._put_bytes(arr.nbytes, kind)
            seg[...] = rows
        self._last = (layout, buf, tuple(placed))
        return placed

    def _mirrors(self) -> tuple:
        return (self._m_free, self._m_nt, self._m_life) + (
            () if self._m_total is None else (self._m_total,)
        )

    def _dirty_rows(self, state_p):
        """Indices of the rows in which the padded inputs differ from the
        mirror, ascending."""
        dirty = None
        for mirror, arr in zip(self._mirrors(), state_p):
            diff = mirror != arr
            if diff.ndim == 2:
                diff = diff.any(axis=1)
            dirty = diff if dirty is None else np.logical_or(
                dirty, diff, out=dirty
            )
        return np.nonzero(dirty)[0]

    @staticmethod
    def _delta_head(k: int, rows, state_p) -> list:
        """The delta form's state part: `k` row indices (the dirty rows,
        padded with a repeat of the first: a duplicate set of an identical
        payload is order-independent; row 0 where none is dirty) and
        those rows of each array, all replicated."""
        idx = np.zeros(k, dtype=np.int32)
        idx[: rows.size] = rows
        idx[rows.size:] = rows[0] if rows.size else 0
        return [(idx, 2)] + [(arr[idx], 2) for arr in state_p]

    def _cross(self, layout: InputLayout, head, parts) -> tuple:
        """One put, one program: the packed buffer onto the resident
        arrays (donated in the delta form).  Returns the buffer and the
        placed parts."""
        buf = pack_inputs(layout, head, parts)
        state = () if layout.rows is None else tuple(
            a for a in (self.free, self.nt_free, self.lifetime, self.total)
            if a is not None
        )
        # a row a device: each receives its own and nothing else
        out = unpack_inputs(state, self._put(buf, 0), layout, mesh=self.mesh)
        self.upload_bytes_total += buf.nbytes
        self.input_programs_total += 1
        self._ran.add(layout)
        n = len(layout.widths)
        self.free, self.nt_free, self.lifetime = out[:3]
        self.total = out[3] if n == 4 else None
        return buf, out[n:]

    def _keep_compiled(self, layout: InputLayout) -> None:
        """A form that a state key has met (the full one, a row bucket)
        stays compiled whatever the per-solve inputs' shapes, as the
        scatter programs did, which knew nothing of them: the first time
        this key crosses with inputs of new shapes, or in a new form, the
        layouts of (shapes it has met) x (forms it has met) that have not
        run yet run once and change nothing — the mirror put again in
        full, or a delta of no rows (row 0 re-set to what it holds), zeros
        for the inputs, the results dropped.  So a gang row that first
        appears beside a warm state compiles its row buckets on the tick
        that compiles the kernel it brings, not on some later one."""
        met = self._met.get(layout.state)
        if met is None:
            met = self._met[layout.state] = (set(), set())
        shapes, forms = met
        if layout.parts in shapes and layout.rows in forms:
            return
        shapes.add(layout.parts)
        forms.add(layout.rows)
        none = np.zeros(0, dtype=np.int64)
        for parts in shapes:
            for k in forms:
                other = layout._replace(rows=k, parts=parts)
                if other in self._ran:
                    continue
                mirrors = self._mirrors()
                self._cross(
                    other,
                    list(zip(mirrors, _STATE_KINDS)) if k is None
                    else self._delta_head(k, none, mirrors),
                    [(np.zeros(shape, dtype=np.int32), kind)
                     for shape, kind in parts],
                )

    # -- donated-solve bookkeeping ---------------------------------------
    def adopt_outputs(self, free_after, nt_after) -> None:
        """The donated solve consumed `free`/`nt_free`; the returned
        `free_after`/`nt_after` device arrays ARE the next tick's resident
        inputs.  The mirror is stale until apply_counts() replays the
        solve's assignment deltas."""
        self.free = free_after
        self.nt_free = nt_after
        self._await_apply = True

    def read_back(self, dev) -> np.ndarray:
        """One device array on the host (waits for what computes it),
        counted: the twin of the upload counters."""
        host = np.asarray(dev)
        self.readbacks_total += 1
        self.readback_bytes_total += int(host.nbytes)
        return host

    def count_answer(self, form: str) -> None:
        self.answers[form] += 1

    def apply_outputs(self, free_after_host, nt_after_host) -> None:
        """Re-synchronize the mirror with the donated outputs: the caller
        reads `free_after`/`nt_after` back inside the solve's packed answer
        (one round trip) and hands the host arrays here.  Copied because
        they are views into that readback, which can be non-writable, and
        the mirror must accept row scatters.

        This is exact for EVERY kernel feature (including ALL-policy pool
        zeroing) because the mirror is literally the device's output."""
        if not self._await_apply:
            return
        self._m_free = np.array(free_after_host, dtype=np.int32, copy=True)
        self._m_nt = np.array(nt_after_host, dtype=np.int32, copy=True)
        self._await_apply = False

    def invalidate(self) -> None:
        """Drop residency: the next sync() performs a full upload.  Called
        whenever the device state can no longer be tracked exactly (ALL-
        policy solve, watchdog fallback mid-pipeline, abandoned dispatch)."""
        if self._valid or self._await_apply:
            self.invalidations += 1
        self._valid = False
        self._await_apply = False
        self.free = self.nt_free = self.lifetime = self.total = None
        self._m_free = self._m_nt = self._m_life = self._m_total = None
        self._last = None

    # -- replicated-input placement cache --------------------------------
    def place_cached(self, name: str, arr, kind: int = 2):
        """Device-put `arr` with placement caching by CONTENT: if the same
        array bytes were placed under `name` last tick, the existing device
        buffer is reused (steady-state ticks repeat the batch layout and
        class tables exactly).  The host copy is defensive — callers reuse
        and mutate their padded buffers in place across ticks."""
        if arr is None:
            return None
        cached = self._rep_cache.get(name)
        if (
            cached is not None
            and cached[0].shape == arr.shape
            and cached[0].dtype == arr.dtype
            and np.array_equal(cached[0], arr)
        ):
            self.rep_cache_hits += 1
            return cached[1]
        dev = self._put(arr, kind)
        self._rep_cache[name] = (arr.copy(), dev)
        self.upload_bytes_total += self._put_bytes(arr.nbytes, kind)
        return dev

    # -- telemetry --------------------------------------------------------
    def stats(self) -> dict:
        return {
            "resident": bool(self._valid),
            "mesh_devices": self.mesh_devices,
            "rows_per_device": (
                self.key[0] // self.mesh_devices if self.key else 0
            ),
            "full_uploads": self.full_uploads,
            "delta_uploads": self.delta_uploads,
            "dirty_rows_last": self.dirty_rows_last,
            "upload_bytes_total": self.upload_bytes_total,
            "puts_total": self.puts_total,
            "input_programs_total": self.input_programs_total,
            "rep_cache_hits": self.rep_cache_hits,
            "gang_input_bytes_total": self.gang_input_bytes_total,
            "gang_groups_last": self.gang_groups_last,
            "invalidations": self.invalidations,
            "readbacks_total": self.readbacks_total,
            "readback_bytes_total": self.readback_bytes_total,
            "answers_total": sum(self.answers.values()),
            "answers_compact": self.answers["compact"],
            "answers_dense_small": self.answers["dense-small"],
            "answers_overflow": self.answers["overflow"],
        }
