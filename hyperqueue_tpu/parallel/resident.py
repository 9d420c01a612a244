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
  mirror and only the dirty rows are scatter-updated on device (bucketed
  row counts keep the compiled scatter programs few);
- the solve runs with `free`/`nt_free` DONATED (ops/assign.greedy_cut_scan
  and parallel/solve.sharded_cut_scan_donate), so `free_after`/`nt_after`
  of solve N become the resident inputs of solve N+1 with zero host
  traffic; the mirror is re-synchronized from the (small) `free_after`/
  `nt_after` arrays, which ride the same device round trip as the answer:
  the packing program (ops/answer.py) puts them behind the solve's cells in
  the ONE buffer a solve reads back, and `apply_outputs` takes them from
  there;
- small replicated inputs (needs / sizes / min_time / class_m / order_ids)
  are placement-cached by content: a steady-state tick that repeats the
  same batch layout re-uses the device buffers outright.

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

# dirty-row fraction above which one full upload beats the gather+scatter
# round (the scatter path costs an index gather on host + a scatter program
# on device; at >=half the rows the dense put is strictly simpler)
FULL_UPLOAD_FRACTION = 0.5

# dirty-row counts are bucketed to powers of two (floor 16, the shared
# models/greedy._bucket rule) so the jitted scatter programs stay few;
# padding repeats the first dirty row (a duplicate .set() with an
# identical payload is order-independent)
_ROW_BUCKET_FLOOR = 16


def _scatter_rows(dst, idx, vals):
    return dst.at[idx].set(vals)


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
        # replicated-input placement cache: name -> (host copy, device arr)
        self._rep_cache: dict = {}
        self._scatter_jit = None
        # telemetry (scraped via the model's resident_stats())
        self.full_uploads = 0
        self.delta_uploads = 0
        self.dirty_rows_last = 0
        self.upload_bytes_total = 0
        self.rep_cache_hits = 0
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
        import jax

        if self._shardings is not None:
            return jax.device_put(arr, self._shardings[kind])
        if self._device is not None:
            return jax.device_put(arr, self._device)
        return jax.device_put(arr)

    def _scatter(self, dst, idx, vals, kind):
        import jax

        if self._scatter_jit is None:
            if self._shardings is not None:
                w2, w1, _rep = self._shardings[:3]
                self._scatter_jit = (
                    jax.jit(_scatter_rows, donate_argnums=(0,),
                            out_shardings=w2),
                    jax.jit(_scatter_rows, donate_argnums=(0,),
                            out_shardings=w1),
                )
            else:
                fn = jax.jit(_scatter_rows, donate_argnums=(0,))
                self._scatter_jit = (fn, fn)
        return self._scatter_jit[kind](dst, idx, vals)

    # -- the per-tick sync ------------------------------------------------
    def sync(self, free_p, nt_p, life_p, total_p=None):
        """Bring the resident device state up to date with this tick's
        padded host inputs; returns (free, nt_free, lifetime, total) device
        arrays.  Full upload when nothing is resident (or too much changed),
        dirty-row scatter otherwise."""
        if self._await_apply:
            # the previous solve's counts were never applied to the mirror
            # (e.g. a dropped pipeline dispatch): residency is unknowable
            self.invalidate()
        pw, pr = free_p.shape
        key = (pw, pr, total_p is not None)
        if not self._valid or key != self.key:
            return self._full_upload(key, free_p, nt_p, life_p, total_p)

        dirty = (self._m_free != free_p).any(axis=1)
        np.logical_or(dirty, self._m_nt != nt_p, out=dirty)
        np.logical_or(dirty, self._m_life != life_p, out=dirty)
        if total_p is not None:
            np.logical_or(
                dirty, (self._m_total != total_p).any(axis=1), out=dirty
            )
        rows = np.nonzero(dirty)[0]
        self.dirty_rows_last = int(rows.size)
        if rows.size == 0:
            return self.free, self.nt_free, self.lifetime, self.total
        if rows.size > pw * FULL_UPLOAD_FRACTION:
            return self._full_upload(key, free_p, nt_p, life_p, total_p)

        k = _bucket(int(rows.size), _ROW_BUCKET_FLOOR)
        idx = np.empty(k, dtype=np.int32)
        idx[: rows.size] = rows
        idx[rows.size:] = rows[0]  # idempotent duplicate scatter padding
        idx_d = self._put(idx, 2)
        self.free = self._scatter(self.free, idx_d, self._put(free_p[idx], 2),
                                  0)
        self.nt_free = self._scatter(
            self.nt_free, idx_d, self._put(nt_p[idx], 2), 1
        )
        self.lifetime = self._scatter(
            self.lifetime, idx_d, self._put(life_p[idx], 2), 1
        )
        if total_p is not None:
            self.total = self._scatter(
                self.total, idx_d, self._put(total_p[idx], 2), 0
            )
        self._m_free[rows] = free_p[rows]
        self._m_nt[rows] = nt_p[rows]
        self._m_life[rows] = life_p[rows]
        if total_p is not None:
            self._m_total[rows] = total_p[rows]
        self.delta_uploads += 1
        # the row indices and the rows are put replicated (the scatter
        # runs under GSPMD): every device receives them whole
        self.upload_bytes_total += self._put_bytes(
            k * (free_p.itemsize * pr * (2 if total_p is not None else 1)
                 + nt_p.itemsize + life_p.itemsize + idx.itemsize),
            2,
        )
        return self.free, self.nt_free, self.lifetime, self.total

    def _full_upload(self, key, free_p, nt_p, life_p, total_p):
        self.key = key
        self.free = self._put(free_p, 0)
        self.nt_free = self._put(nt_p, 1)
        self.lifetime = self._put(life_p, 1)
        self.total = None if total_p is None else self._put(total_p, 0)
        self._m_free = free_p.copy()
        self._m_nt = nt_p.copy()
        self._m_life = life_p.copy()
        self._m_total = None if total_p is None else total_p.copy()
        self._valid = True
        self.dirty_rows_last = free_p.shape[0]
        self.full_uploads += 1
        self.upload_bytes_total += int(
            free_p.nbytes + nt_p.nbytes + life_p.nbytes
            + (0 if total_p is None else total_p.nbytes)
        )
        return self.free, self.nt_free, self.lifetime, self.total

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
            "rep_cache_hits": self.rep_cache_hits,
            "invalidations": self.invalidations,
            "readbacks_total": self.readbacks_total,
            "readback_bytes_total": self.readback_bytes_total,
            "answers_total": sum(self.answers.values()),
            "answers_compact": self.answers["compact"],
            "answers_dense_small": self.answers["dense-small"],
            "answers_overflow": self.answers["overflow"],
        }
