"""Greedy cut-scan scheduling model: bucketing + compile-cache around the kernel.

The kernel (ops/assign.py) needs static shapes; real ticks have varying worker
counts, batch counts, resource counts and variant counts. This wrapper pads
every dimension up to a bucket (powers of two with a small floor) so that in
steady state every tick hits one already-compiled program — the same trick the
reference uses to keep its MILP warm is unnecessary there but essential under
XLA (see SURVEY.md §7 "Fixed shapes on TPU").

Padding is semantically inert: padded workers have zero free resources and
zero task slots; padded batches have size 0; padded variants are all-zero
need rows which `_variant_capacity` masks off.

Device path (new in the device-resident tick): the padded state stays
RESIDENT on the accelerator (parallel/resident.py) — what a solve brings
to the device (the dirty-row delta and the inputs that change every tick)
crosses in one packed put (ops/inputs.py), the solve donates its buffers
so free_after/nt_after of solve N feed solve N+1 on-device, and the answer
crosses to the host ONCE and compact: a packing program behind the kernel
(ops/answer.py) writes the nonzero cells of the counts, `free_after` and
`nt_after` into one buffer, so a solve costs one device-to-host round trip.
Backend choice is a per-solve cost model over measured host and device
times with a periodically re-probed sync latency, so one slow probe does not
disable the device path for the life of the process.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from hyperqueue_tpu.ops.answer import (
    SolveCells,
    cells_of_dense,
    dense_of_cells,
    layout_for,
    live_slicer,
    pack_answer,
    unpack_answer,
)
from hyperqueue_tpu.ops.assign import (
    greedy_cut_scan,
    greedy_cut_scan_numpy,
    host_visit_classes,
    scan_step_kinds,
    scarcity_weights,
)
from hyperqueue_tpu.utils.constants import INF_TIME
from hyperqueue_tpu.utils import clock
from hyperqueue_tpu.utils.jaxdev import device_block
from hyperqueue_tpu.utils.metrics import REGISTRY
from hyperqueue_tpu.utils.trace import TRACER


def _bucket(n: int, floor: int) -> int:
    size = floor
    while size < n:
        size *= 2
    return size


# Device sync-latency probe, shared by all models in the process.
# None = not yet resolved; float = measured round-trip ms (inf = probe
# failed). Probed in a BACKGROUND daemon thread: in-process (a chip belongs
# to one process, so a subprocess could not reach it), and without ever
# blocking the caller (a probe that hangs simply never resolves and the
# host solve stays selected).  A resolved measurement AGES OUT
# (REPROBE_INTERVAL_S): callers that pass max_age_s re-launch the probe in
# the background when the value is stale, so a device that was slow at
# startup gets re-evaluated instead of being benched forever.
_DEVICE_SYNC_MS: float | None = None
_PROBE_RUNNING = False
_PROBE_DONE = None  # threading.Event of the probe currently in flight
_PROBE_TS = 0.0     # monotonic stamp of the last RESOLVED probe
_PROBE_LOCK = threading.Lock()

# A tick must complete in single-digit milliseconds; a device whose
# dispatch+readback round trip alone exceeds this is not worth using for
# the solve: the scheduler runs on the host and cannot see the counts
# sooner than that round trip allows, however fast the kernel is.
DISPATCH_LATENCY_BUDGET_MS = 5.0

# re-probe the sync latency when the last measurement is older than this
# and the host path is currently winning (the device path self-measures)
REPROBE_INTERVAL_S = 30.0

# while the cost model picks the host, retry the device path after this
# many solves even if the last device measurement lost — measurements go
# stale as shapes and host load drift
DEVICE_RETRY_SOLVES = 512

# cost-model EWMA smoothing for per-shape host/device solve times
_EWMA_ALPHA = 0.25


def _start_probe_locked() -> None:
    global _PROBE_RUNNING, _PROBE_DONE
    _PROBE_RUNNING = True
    _PROBE_DONE = threading.Event()
    done = _PROBE_DONE

    def _probe():
        global _DEVICE_SYNC_MS, _PROBE_RUNNING, _PROBE_TS
        try:
            import jax

            @jax.jit
            def sync_probe(v):
                return (v * 2).sum()

            x = jax.device_put(np.arange(256, dtype=np.int32))
            np.asarray(sync_probe(x))  # compile + first transfer
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(sync_probe(x))
                ts.append((time.perf_counter() - t0) * 1000)
            measured = min(ts)
        except Exception:
            measured = float("inf")
        with _PROBE_LOCK:
            _DEVICE_SYNC_MS = measured
            _PROBE_TS = clock.monotonic()
            _PROBE_RUNNING = False
        done.set()

    threading.Thread(
        target=_probe, name="hq-device-probe", daemon=True
    ).start()


def device_sync_ms(wait_s: float = 0.0,
                   max_age_s: float | None = None) -> float | None:
    """Current known device sync round trip in ms.

    Starts the background probe on first call; returns None while the
    FIRST probe is unresolved (callers treat that as "use the host solve
    for now").  `max_age_s` triggers a background RE-probe when the last
    resolved measurement is older — the stale value keeps being returned
    until the new one lands, so callers never block on freshness.
    `wait_s` > 0 blocks up to that long for a result — benchmarks use it
    for a stable backend choice; the server never passes it."""
    with _PROBE_LOCK:
        if _DEVICE_SYNC_MS is None and not _PROBE_RUNNING:
            _start_probe_locked()
        elif (
            max_age_s is not None
            and not _PROBE_RUNNING
            and _DEVICE_SYNC_MS is not None
            and clock.monotonic() - _PROBE_TS > max_age_s
        ):
            _start_probe_locked()
        done = _PROBE_DONE
    if wait_s > 0 and done is not None:
        done.wait(wait_s)
    return _DEVICE_SYNC_MS


def _reset_probe_for_tests() -> None:
    global _DEVICE_SYNC_MS, _PROBE_RUNNING, _PROBE_DONE, _PROBE_TS
    with _PROBE_LOCK:
        _DEVICE_SYNC_MS = None
        _PROBE_RUNNING = False
        _PROBE_DONE = None
        _PROBE_TS = 0.0


class ResidentParanoidError(AssertionError):
    """The device-resident solve diverged from a fresh full-upload solve.

    Deliberately loud: the solver watchdog re-raises it instead of
    degrading (like tick_cache.paranoid_check, the paranoid contract is a
    debug tool — masking the divergence behind the fallback would both
    hide the bug and destroy the evidence via resident invalidation)."""


# the steps of every device solve that carries gang rows, by what each did
# (ops/assign.scan_step_kinds): only the gang rows run the selection, only
# the other live rows the water-fill, and the rows past the last live one
# nothing
_SCAN_STEPS_BY_KIND = REGISTRY.counter(
    "hq_solve_scan_steps_by_kind_total",
    "scan steps of the device solves with gang rows by kind (gang: the "
    "selection; fill: the water-fill; idle: a padded row never visited)",
    labels=("kind",), max_series=4,
)


class _ReadyCounts:
    """Solve handle whose result is already materialized (host paths):
    dense counts, and their cells by one nonzero pass when asked."""

    __slots__ = ("_counts",)

    def __init__(self, counts: np.ndarray):
        self._counts = counts

    def result(self) -> np.ndarray:
        return self._counts

    def cells(self) -> SolveCells:
        return cells_of_dense(self._counts)


class _DeviceCounts:
    """In-flight device solve.  `cells()` waits for kernel and packer,
    makes the solve's ONE readback (the packed answer, ops/answer.py),
    unpacks the cells for the mapping, re-synchronizes the residency
    mirror from the state part of the same buffer, and feeds the cost
    model; `result()` is the dense (B, V, W) counts, built from the cells
    for callers that want the array.  Where the buffer's compact form
    overflowed, the dense live slice of the counts (kept on the device
    until here) is read instead: the same cells, counted as `overflow`.
    The dispatch is asynchronous — between construction and `cells()` the
    device executes while the host does other tick work (the pipelined
    tick exploits exactly this window)."""

    __slots__ = ("_model", "_res", "_packed", "_counts_dev", "_layout",
                 "_prep", "_cells")

    def __init__(self, model, res, packed, counts_dev, layout, prep):
        self._model = model
        self._res = res
        self._packed = packed          # (D, L) device buffer
        self._counts_dev = counts_dev  # padded counts, for the fallback
        self._layout = layout
        self._prep = prep
        self._cells = None

    def result(self) -> np.ndarray:
        return dense_of_cells(self.cells())

    def cells(self) -> SolveCells:
        if self._cells is not None:
            return self._cells
        model = self._model
        prep = self._prep
        res = self._res
        phases = prep["phases"]
        with TRACER.phase(phases, "device_sync"):
            # the wait for kernel and packer, then the one readback (and
            # the dense one behind it where the compact form overflowed)
            with TRACER.phase(phases, "device_sync/counts"):
                buf = res.read_back(self._packed)
                cells, free_after, nt_after = unpack_answer(
                    buf, self._layout
                )
                if cells is None:
                    dense = res.read_back(
                        live_slicer(*self._layout.extents)(self._counts_dev)
                    )
                    cells = cells_of_dense(dense, form="overflow")
                res.count_answer(cells.form)
            # the state part of the same buffer, copied into the mirror
            with TRACER.phase(phases, "device_sync/state"):
                res.apply_outputs(free_after, nt_after)
        self._cells = cells
        self._packed = self._counts_dev = None
        # the cost the TICK pays: dispatch + readback wait.  Synchronous
        # solves ask at once, so device_sync contains the whole device
        # execution; pipelined solves ask a tick later, when the execution
        # already overlapped host work — charging the idle gap would
        # wrongly bench the device in the cost model.
        model._observe(
            "device", prep["shape_key"],
            phases["solve_dispatch"] + phases["device_sync"],
        )
        model._maybe_paranoid_check(prep, cells)
        return cells


class GreedyCutScanModel:
    """Stateless apart from jit's compile cache and the device residency.

    backend: "auto" uses the jitted kernel on an accelerator and the numpy
    implementation on CPU hosts (identical semantics; the XLA while-loop is
    slower than numpy on CPU); "jax"/"numpy" force a path.  With an
    accelerator visible, "auto" runs a per-solve cost model (measured host
    vs device times per padded shape, periodically re-probed sync latency)
    instead of a one-shot permanent decision.
    """

    def __init__(
        self,
        worker_floor: int = 8,
        batch_floor: int = 8,
        resource_floor: int = 4,
        variant_floor: int = 1,
        backend: str = "auto",
    ):
        self.worker_floor = worker_floor
        self.batch_floor = batch_floor
        self.resource_floor = resource_floor
        self.variant_floor = variant_floor
        self.backend = backend
        # which path the last solve actually ran (host-native / host-numpy
        # / device-jax / device-sharded); the DecisionRecords and `hq server
        # stats` report it, with last_backend_reason naming WHY it was chosen
        self.last_backend: str | None = None
        self.last_backend_reason: str = ""
        # {platform, kind, count} of the devices holding the counts the
        # last solve returned (None after a host solve): `hq server stats`
        # shows it, so a tick that quietly ran on the host cannot pass for
        # a device tick
        self.last_device: dict | None = None
        self._use_numpy: bool | None = (
            None if backend == "auto" else (backend == "numpy")
        )
        # persistent padded buffers, keyed by bucket shape: steady-state
        # ticks reuse the same host arrays (and therefore the same
        # compiled program and device buffer donation) instead of
        # re-allocating and re-zeroing every call
        self._buffers: dict[tuple, dict] = {}
        # counts NEW bucket-shape allocations — each implies a fresh XLA
        # compilation on the jit path, so a steady-state tick must not
        # increment it (asserted by tests/test_tick_cache.py)
        self.shape_allocations = 0
        # the last solve's spans in ms, under the tick's phase keys
        # (solve_host_prep, solve_dispatch, device_sync and their
        # children), written through TRACER.phase where the work happens;
        # the tick folds them into its own breakdown (tick.fold_model_phases)
        self.last_phases: dict = {}
        # device residency (parallel/resident.py), built on first device
        # solve; None until then
        self._res = None
        # per-shape EWMA of measured end-to-end solve ms, host vs device —
        # the adaptive backend decision reads these
        self._cost: dict[str, dict[tuple, float]] = {"host": {}, "device": {}}
        self._solves_since_device = 0
        # paranoid mode: every Nth RESIDENT device solve re-runs the same
        # padded inputs through a fresh full-upload solve and asserts
        # bitwise count equality (0 = off); wired to `--paranoid-tick`
        self.paranoid_resident = 0
        self._resident_solves = 0
        self.paranoid_checks = 0
        # steps of the device solves with gang rows, by what each did
        # (ops/assign.scan_step_kinds)
        self.scan_steps = {"gang": 0, "fill": 0, "idle": 0}

    # -- backend selection -------------------------------------------------
    def _sticky_host(self) -> bool | None:
        """Process-sticky part of the backend decision: True = host
        forever (forced numpy, CPU-pinned env, CPU jax backend, failed
        init), False = device forced, None = accelerator visible — decide
        per solve (_backend_decision)."""
        if self._use_numpy is not None:
            return self._use_numpy
        import os

        if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
            # the environment pins the cpu backend: decide without
            # importing jax at all (a multi-second cost per server
            # process that the host solve never pays back)
            self._use_numpy = True
            return True
        import jax

        try:
            backend = jax.default_backend()
        except RuntimeError:
            # the configured accelerator backend failed to initialize:
            # under "auto" the solve must keep working on the host — and
            # the choice is sticky, because jax caches the failed init
            # for the process anyway
            self._use_numpy = True
            import logging

            logging.getLogger(__name__).warning(
                "jax backend unavailable; solving on the host (numpy)",
                exc_info=True,
            )
            return True
        if backend == "cpu":
            # the XLA while-loop overhead loses to numpy on CPU hosts
            self._use_numpy = True
            return True
        return None

    def _numpy_path(self) -> bool:
        """Compatibility probe: True when the solve is host-pinned for the
        process.  With an accelerator visible the answer is per-solve
        (_backend_decision); this returns False then."""
        return self._sticky_host() is True

    def _backend_decision(self, shape_key: tuple) -> tuple[str, str]:
        """("host"|"device", reason) for THIS solve.

        The cost model compares per-shape EWMAs of measured end-to-end
        solve times.  Until a host measurement exists the original budget
        rule applies (device only when its sync round trip fits the tick
        budget); a benched device is retried after DEVICE_RETRY_SOLVES and
        the sync probe re-runs every REPROBE_INTERVAL_S, so a slow first
        probe is not permanent."""
        sticky = self._sticky_host()
        if sticky is True:
            return "host", (
                "forced-numpy" if self.backend == "numpy" else "cpu-host"
            )
        if sticky is False:
            return "device", "forced-jax"
        sync_ms = device_sync_ms(max_age_s=REPROBE_INTERVAL_S)
        if sync_ms is None:
            return "host", "sync-probe-pending"
        if sync_ms == float("inf"):
            return "host", "sync-probe-failed"
        host_est = self._cost["host"].get(shape_key)
        dev_est = self._cost["device"].get(shape_key)
        if dev_est is not None and host_est is not None:
            if dev_est <= host_est:
                return "device", "cost-model"
            if (
                self._solves_since_device >= DEVICE_RETRY_SOLVES
                and sync_ms < host_est
            ):
                return "device", "periodic-retry"
            return "host", (
                f"cost-model (device {dev_est:.1f}ms > host {host_est:.1f}ms)"
            )
        if host_est is None and dev_est is not None:
            return "device", "cost-model"
        if host_est is not None:
            # no device measurement for this shape yet: its end-to-end time
            # is at least the sync round trip — try it when that alone
            # could beat the measured host time
            if sync_ms < host_est:
                return "device", "first-measurement"
            return "host", (
                f"sync {sync_ms:.1f}ms exceeds host {host_est:.1f}ms"
            )
        # no measurements at all: the original conservative budget rule
        if sync_ms <= DISPATCH_LATENCY_BUDGET_MS:
            return "device", "sync-within-budget"
        return "host", (
            f"sync {sync_ms:.1f}ms exceeds the "
            f"{DISPATCH_LATENCY_BUDGET_MS:.0f}ms budget"
        )

    def _observe(self, kind: str, shape_key: tuple, ms: float) -> None:
        table = self._cost[kind]
        prev = table.get(shape_key)
        table[shape_key] = (
            ms if prev is None else prev + _EWMA_ALPHA * (ms - prev)
        )
        if kind == "device":
            self._solves_since_device = 0

    # -- solve -------------------------------------------------------------
    def solve(
        self,
        free: np.ndarray,       # (W, R) int32
        nt_free: np.ndarray,    # (W,) int32
        lifetime: np.ndarray,   # (W,) int32 seconds, INF_TIME when unlimited
        needs: np.ndarray,      # (B, V, R) int32
        sizes: np.ndarray,      # (B,) int32/int64
        min_time: np.ndarray,   # (B, V) int32 seconds
        priorities: list | None = None,  # accepted for model-interface
                                         # parity; rows are already in
                                         # descending priority order
        total: np.ndarray | None = None,     # (W, R) int32 pool totals
        all_mask: np.ndarray | None = None,  # (B, V, R) int32 0/1 ALL-policy
        weights: np.ndarray | None = None,   # (B, V) request weights —
                                             # consumed on the host by
                                             # run_tick's batch ordering;
                                             # accepted for interface parity
        gang_nodes: np.ndarray | None = None,    # (B,) int32 gang sizes
        gang_ok: np.ndarray | None = None,       # (W,) int32 host idleness
        group_onehot: np.ndarray | None = None,  # (W, G) int32 group map
        affinity: np.ndarray | None = None,      # (B, W) float policy
                                                 # weights (heterogeneity
                                                 # matrix rows per batch)
        gang_resv: np.ndarray | None = None,     # (W,) int32 reservation
                                                 # codes (--gang-drain busy)
    ) -> np.ndarray:
        """Returns counts (B, V, W) int32 (unpadded, C-contiguous)."""
        return self.solve_async(
            free, nt_free, lifetime, needs, sizes, min_time,
            priorities=priorities, total=total, all_mask=all_mask,
            weights=weights, gang_nodes=gang_nodes, gang_ok=gang_ok,
            group_onehot=group_onehot, affinity=affinity,
            gang_resv=gang_resv,
        ).result()

    def solve_cells(self, *args, **kwargs) -> SolveCells:
        """`solve`, answered with the nonzero cells of the counts
        (ops/answer.SolveCells) and not the dense array: what the tick's
        mapping reads.  A device solve's cells come straight from its
        packed readback; the dense counts are never built."""
        return self._dispatch(*args, **kwargs).cells()

    def solve_async(self, *args, **kwargs):
        """Dispatch one solve (`solve`'s arguments); returns a handle whose
        `.cells()` yields the nonzero cells of the unpadded counts and
        whose `.result()` the dense counts.  Host backends compute eagerly
        (the handle is just a box); the device backend returns with the
        program ENQUEUED, so the caller can overlap host work with the
        device execution — the pipelined tick (scheduler/pipeline.py) maps
        the previous solve during exactly this window."""
        return self._dispatch(*args, **kwargs)

    def _dispatch(
        self, free, nt_free, lifetime, needs, sizes, min_time,
        priorities=None, total=None, all_mask=None, weights=None,
        gang_nodes=None, gang_ok=None, group_onehot=None, affinity=None,
        gang_resv=None,
    ):
        self.last_phases = phases = {}
        with TRACER.phase(phases, "solve_host_prep"):
            prep = self._prepare(
                free, nt_free, lifetime, needs, sizes, min_time, total,
                all_mask, phases, gang_nodes=gang_nodes, gang_ok=gang_ok,
                group_onehot=group_onehot, affinity=affinity,
                gang_resv=gang_resv,
            )
            backend, reason = self._backend_decision(prep["shape_key"])
        self.last_backend_reason = reason
        self._solves_since_device += 1
        if backend == "host":
            return self._host_solve(prep)
        try:
            return self._device_solve(prep)
        except Exception as e:  # noqa: BLE001 - degrade, don't kill the tick
            import logging

            logging.getLogger(__name__).warning(
                "device solve dispatch failed (%s); falling back to the "
                "host solve for this tick", e, exc_info=True,
            )
            self.invalidate_resident()
            self.last_backend_reason = f"device-dispatch-failed: {e}"
            return self._host_solve(prep)

    # -- preparation (shared by every backend) ----------------------------
    def _prepare(self, free, nt_free, lifetime, needs, sizes, min_time,
                 total, all_mask, phases, gang_nodes=None, gang_ok=None,
                 group_onehot=None, affinity=None, gang_resv=None) -> dict:
        n_w, n_r = free.shape
        n_b, n_v, _ = needs.shape

        pw = self._worker_bucket(n_w)
        pb = _bucket(max(n_b, 1), self.batch_floor)
        pr = _bucket(max(n_r, 1), self.resource_floor)
        pv = _bucket(max(n_v, 1), self.variant_floor)

        if all_mask is not None and not np.any(all_mask):
            all_mask = None  # keep the common no-ALL compiled program
        has_all = all_mask is not None
        if gang_nodes is not None and not np.any(np.asarray(gang_nodes) > 0):
            gang_nodes = None  # keep the common no-gang compiled program
        has_gang = gang_nodes is not None
        if affinity is not None:
            affinity = np.asarray(affinity, dtype=np.float32)
            if (
                affinity.size == 0
                or (affinity.min() == affinity.max() and affinity.min() > 0)
            ):
                # a uniform positive matrix cannot change the visit order or
                # exclude a worker: keep the flat-objective program
                affinity = None
        has_pmask = affinity is not None and bool(np.any(affinity <= 0))

        buf = self._get_buffers(pw, pb, pr, pv, has_all)
        free_p = buf["free"]
        nt_p = buf["nt"]
        life_p = buf["life"]
        needs_p = buf["needs"]
        sizes_p = buf["sizes"]
        mt_p = buf["mt"]
        # zero whatever the PREVIOUS call wrote beyond this call's extents
        # (same bucket, smaller active region), then fill the active slices
        lw, lb, lr, lv = buf["extents"]
        if lw > n_w:
            free_p[n_w:lw] = 0
            nt_p[n_w:lw] = 0
            life_p[n_w:lw] = 0
        if lr > n_r:
            free_p[:n_w, n_r:lr] = 0
            needs_p[:n_b, :n_v, n_r:lr] = 0
        if lb > n_b:
            needs_p[n_b:lb] = 0
            sizes_p[n_b:lb] = 0
        if lv > n_v:
            needs_p[:n_b, n_v:lv] = 0
        buf["extents"] = (n_w, n_b, n_r, n_v)

        free_p[:n_w, :n_r] = free
        nt_p[:n_w] = nt_free
        life_p[:n_w] = lifetime
        needs_p[:n_b, :n_v, :n_r] = needs
        sizes_p[:n_b] = np.minimum(sizes, np.int32(2**30))
        mt_p[:n_b, :n_v] = min_time
        # absent variants must never be eligible: give them infinite
        # min_time; padded batch rows get plain zeros in the live-variant
        # columns (size 0 keeps them inert either way, but the buffer must
        # match a fresh allocation exactly across variant-count changes)
        mt_p[:, n_v:] = int(INF_TIME)
        mt_p[n_b:, :n_v] = 0
        total_p = amask_p = None
        if has_all:
            total_p = buf["total"]
            amask_p = buf["amask"]
            if lw > n_w:
                total_p[n_w:lw] = 0
            if lr > n_r:
                total_p[:n_w, n_r:lr] = 0
                amask_p[:n_b, :n_v, n_r:lr] = 0
            if lb > n_b:
                amask_p[n_b:lb] = 0
            if lv > n_v:
                amask_p[:n_b, n_v:lv] = 0
            total_p[:n_w, :n_r] = total if total is not None else free
            amask_p[:n_b, :n_v, :n_r] = all_mask
        gang_p = gok_p = goh_p = resv_p = None
        pg = 0
        if has_gang:
            # gang inputs are FRESH per-solve allocations, not persistent
            # buffers: whether gang rows ride every tick (a server with a
            # multi-node backlog) or a few, keying the donated-buffer cache
            # on their presence would churn the shape of the ticks without
            # them.  (B,) and (W,) are tiny; the (W, G) one-hot is not at
            # every width: 64 kB at 1 024 workers in 16 groups, 8 MB at
            # 8 192 x 256 and 16 MB at 16 384 x 256, allocated, zeroed and
            # copied here every solve (`solve_host_prep/gang` times it,
            # PERF.md section 7 has what it costs on the chip's host)
            with TRACER.phase(phases, "solve_host_prep/gang"):
                n_g = group_onehot.shape[1] if group_onehot is not None else 1
                pg = _bucket(max(n_g, 1), 4)
                gang_p = np.zeros(pb, dtype=np.int32)
                gang_p[:n_b] = gang_nodes
                gok_p = np.zeros(pw, dtype=np.int32)
                if gang_ok is not None:
                    gok_p[:n_w] = gang_ok
                goh_p = np.zeros((pw, pg), dtype=np.int32)
                if group_onehot is not None:
                    goh_p[:n_w, :n_g] = group_onehot
                if gang_resv is not None:
                    resv_p = np.zeros(pw, dtype=np.int32)
                    resv_p[:n_w] = gang_resv
        aff_p = pmask_p = None
        if affinity is not None:
            # like the gang inputs: FRESH per-solve allocations — weighted
            # objectives appear only under an active policy, and keying the
            # donated-buffer cache on their presence would churn the
            # steady-state shape; both arrays are small ((B, W))
            aff_p = np.zeros((pb, pw), dtype=np.float32)
            aff_p[:n_b, :n_w] = affinity
            if has_pmask:
                pmask_p = np.zeros((pb, pw), dtype=np.int32)
                pmask_p[:n_b, :n_w] = (affinity > 0).astype(np.int32)

        # the visit classes; what precedes it in solve_host_prep is padding
        with TRACER.phase(phases, "solve_host_prep/visit"):
            scarcity = np.asarray(
                scarcity_weights(free_p.astype(np.int64).sum(axis=0))
            ).astype(np.float32)
            class_m, order_ids = host_visit_classes(
                free_p, needs_p, scarcity, all_mask=amask_p, affinity=aff_p
            )
            # bucket the mask-table dimension so steady-state ticks reuse
            # the compiled program; padding rows are all-class-0 (never
            # referenced)
            pm = _bucket(class_m.shape[0], 4)
            if pm > class_m.shape[0]:
                pad = np.zeros((pm - class_m.shape[0], pw), dtype=np.int32)
                class_m = np.concatenate([class_m, pad], axis=0)

        return {
            "free_p": free_p, "nt_p": nt_p, "life_p": life_p,
            "needs_p": needs_p, "sizes_p": sizes_p, "mt_p": mt_p,
            "total_p": total_p, "amask_p": amask_p,
            "gang_p": gang_p, "gok_p": gok_p, "goh_p": goh_p,
            "resv_p": resv_p, "pmask_p": pmask_p,
            "class_m": class_m, "order_ids": order_ids,
            "extents": (n_b, n_v, n_w),
            "shape_key": (pw, pb, pr, pv, pm, has_all, has_gang, pg,
                          has_pmask, resv_p is not None),
            "has_all": has_all, "has_gang": has_gang,
            "has_pmask": has_pmask,
            "phases": phases,
        }

    # -- host path ---------------------------------------------------------
    def _host_solve(self, prep) -> _ReadyCounts:
        phases = prep["phases"]
        with TRACER.phase(phases, "solve_dispatch"):
            counts = self._host_counts(prep)
            self.last_device = None
        with TRACER.phase(phases, "device_sync"):
            n_b, n_v, n_w = prep["extents"]
            out = np.ascontiguousarray(
                np.asarray(counts)[:n_b, :n_v, :n_w]
            )
        self._observe(
            "host", prep["shape_key"],
            phases["solve_dispatch"] + phases["device_sync"],
        )
        return _ReadyCounts(out)

    def _host_counts(self, prep):
        """The host solve on fully padded inputs: the native C++ scan
        (identical semantics, with saturation early-exits) when the lib is
        available, else numpy.  Gang rows are numpy-only — the native scan
        predates the all-or-nothing column groups, so a gang solve bypasses
        it rather than silently dropping the constraint."""
        from hyperqueue_tpu.utils.native import native_cut_scan

        if prep["has_gang"] or prep["has_pmask"]:
            # the native scan predates both the gang rows and the policy
            # mask: a solve carrying either bypasses it rather than
            # silently dropping the constraint
            self.last_backend = "host-numpy"
            counts, _free_after, _nt_after = greedy_cut_scan_numpy(
                prep["free_p"], prep["nt_p"], prep["life_p"],
                prep["needs_p"], prep["sizes_p"], prep["mt_p"],
                prep["class_m"], prep["order_ids"], total=prep["total_p"],
                all_mask=prep["amask_p"], gang_nodes=prep["gang_p"],
                gang_ok=prep["gok_p"], group_onehot=prep["goh_p"],
                policy_mask=prep["pmask_p"], gang_resv=prep["resv_p"],
            )
            return counts
        counts = native_cut_scan(
            prep["free_p"], prep["nt_p"], prep["life_p"], prep["needs_p"],
            prep["sizes_p"], prep["mt_p"], prep["class_m"],
            prep["order_ids"], total=prep["total_p"],
            all_mask=prep["amask_p"],
        )
        if counts is not None:
            self.last_backend = "host-native"
            return counts
        self.last_backend = "host-numpy"
        counts, _free_after, _nt_after = greedy_cut_scan_numpy(
            prep["free_p"], prep["nt_p"], prep["life_p"], prep["needs_p"],
            prep["sizes_p"], prep["mt_p"], prep["class_m"],
            prep["order_ids"], total=prep["total_p"],
            all_mask=prep["amask_p"],
        )
        return counts

    # -- device path (resident state + donated buffers) --------------------
    _device_backend_name = "device-jax"

    def _residency(self):
        if self._res is None:
            from hyperqueue_tpu.parallel.resident import DeviceResidency

            self._res = DeviceResidency()
        return self._res

    def invalidate_resident(self) -> None:
        """Drop the device-resident state (next device solve re-uploads in
        full).  The watchdog calls this whenever a solve is abandoned or
        degraded mid-flight — the device buffers may then hold outputs the
        host never accounted for."""
        if self._res is not None:
            self._res.invalidate()

    def resident_stats(self) -> dict:
        base = {"backend": self.last_backend,
                "backend_reason": self.last_backend_reason}
        if self._res is not None:
            base.update(self._res.stats())
        base["paranoid_checks"] = self.paranoid_checks
        for kind, steps in self.scan_steps.items():
            base[f"scan_steps_{kind}"] = steps
        return base

    def _device_solve(self, prep) -> _DeviceCounts:
        phases = prep["phases"]
        with TRACER.phase(phases, "solve_dispatch"):
            res = self._residency()
            # what this solve brings to the device, in one put and one
            # program: the dirty rows (or the whole state) and the inputs
            # whose content changes every tick
            with TRACER.phase(phases, "solve_dispatch/upload"):
                free_d, nt_d, life_d, total_d, placed = res.sync(
                    prep["free_p"], prep["nt_p"], prep["life_p"],
                    prep["total_p"], inputs=self._tick_inputs(prep),
                )
            # kernel and packer enqueued
            with TRACER.phase(phases, "solve_dispatch/launch"):
                counts, free_after, nt_after = self._kernel_dispatch(
                    res, free_d, nt_d, life_d, total_d, prep, placed
                )
                res.adopt_outputs(free_after, nt_after)
                layout = layout_for(
                    prep["extents"], counts.shape + free_after.shape[1:],
                    res.mesh_devices,
                )
                packed = pack_answer(
                    counts, free_after, nt_after, layout, mesh=res.mesh
                )
        self.last_backend = self._device_backend_name
        self.last_device = device_block(packed)
        self._resident_solves += 1
        if prep["gang_p"] is not None:
            for kind, steps in scan_step_kinds(
                    prep["gang_p"], prep["sizes_p"]).items():
                self.scan_steps[kind] += steps
                _SCAN_STEPS_BY_KIND.labels(kind).inc(steps)
        return _DeviceCounts(self, res, packed, counts, layout, prep)

    @staticmethod
    def _gang_inputs(prep) -> list:
        """The gang inputs of a solve with gang rows, as `sync` takes
        them: fresh content every tick, (B,), (W,) and (W, G), and under
        `--gang-drain busy` the (W,) reservation codes."""
        if prep["gang_p"] is None:
            return []
        inputs = [("gang_nodes", prep["gang_p"], 2),
                  ("gang_ok", prep["gok_p"], 1),
                  ("group_onehot", prep["goh_p"], 0)]
        if prep["resv_p"] is not None:
            inputs.append(("gang_resv", prep["resv_p"], 1))
        return inputs

    def _tick_inputs(self, prep) -> list:
        """What a solve brings besides the worker state, which crosses
        with it in the residency's one put (name, array, sharding kind).
        All of it changes from tick to tick at a deep backlog: the visit
        classes follow `free`, the sizes the queues, and the batch-shaped
        arrays the batch order, which follows both."""
        inputs = [("class_m", prep["class_m"], 3),
                  ("order_ids", prep["order_ids"], 2),
                  ("needs", prep["needs_p"], 2),
                  ("sizes", prep["sizes_p"], 2),
                  ("min_time", prep["mt_p"], 2)]
        if prep["amask_p"] is not None:
            inputs.append(("all_mask", prep["amask_p"], 2))
        return inputs + self._gang_inputs(prep)

    def _kernel_dispatch(self, res, free_d, nt_d, life_d, total_d, prep,
                         placed):
        """Enqueue the jitted kernel on the resident buffers (donating
        free/nt_free) and the inputs `sync` placed; the policy mask, whose
        content repeats while the policy does, rides the placement cache.
        Overridden by the multichip model to shard the worker axis."""
        return greedy_cut_scan(
            free_d, nt_d, life_d,
            placed["needs"], placed["sizes"], placed["min_time"],
            placed["class_m"], placed["order_ids"],
            total=total_d,
            all_mask=placed.get("all_mask"),
            gang_nodes=placed.get("gang_nodes"),
            gang_ok=placed.get("gang_ok"),
            group_onehot=placed.get("group_onehot"),
            gang_resv=placed.get("gang_resv"),
            policy_mask=res.place_cached("policy_mask", prep["pmask_p"]),
        )

    def _maybe_paranoid_check(self, prep, cells: SolveCells) -> None:
        """Resident-vs-fresh bit-exactness guard: re-run the SAME padded
        inputs through a fresh full-upload device solve and assert that
        its cells (read back dense and found on the host: a path that
        shares nothing with the packed answer) equal the resident
        solve's.  The padded buffers are untouched between dispatch and
        result (the pipeline maps a pending solve before preparing the
        next), so the comparison is exact by construction."""
        if (
            not self.paranoid_resident
            or self._resident_solves % self.paranoid_resident != 0
        ):
            return
        self.paranoid_checks += 1
        n_b, n_v, n_w = prep["extents"]
        fresh = cells_of_dense(np.ascontiguousarray(
            np.asarray(self._fresh_device_counts(prep))[:n_b, :n_v, :n_w]
        ))
        if not (
            np.array_equal(cells.flat, fresh.flat)
            and np.array_equal(cells.vals, fresh.vals)
        ):
            raise ResidentParanoidError(
                "paranoid-resident: device-resident counts diverge from a "
                "fresh full-upload solve of the same padded inputs"
            )

    def _fresh_device_counts(self, prep):
        """Full-upload reference solve (no residency, no placement cache);
        the donated jit consumes the fresh uploads, never the resident
        buffers."""
        counts, _f, _n = greedy_cut_scan(
            prep["free_p"].copy(), prep["nt_p"].copy(), prep["life_p"],
            prep["needs_p"], prep["sizes_p"], prep["mt_p"],
            prep["class_m"], prep["order_ids"],
            total=None if prep["total_p"] is None else prep["total_p"].copy(),
            all_mask=prep["amask_p"], gang_nodes=prep["gang_p"],
            gang_ok=prep["gok_p"], group_onehot=prep["goh_p"],
            policy_mask=prep["pmask_p"], gang_resv=prep["resv_p"],
        )
        return counts

    # -- padded-buffer management -----------------------------------------
    def _get_buffers(self, pw: int, pb: int, pr: int, pv: int,
                     has_all: bool) -> dict:
        """Persistent padded host buffers for one bucket shape.

        The kernel's inputs change every tick but their BUCKETED shapes
        repeat; reusing the arrays avoids a full allocate+memset per call
        and keeps the jit cache keyed on stable shapes.  A new key means a
        new XLA compilation on the device path — counted in
        `shape_allocations` so tests and the benchmark can assert that
        steady-state ticks trigger none.
        """
        key = (pw, pb, pr, pv, has_all)
        buf = self._buffers.get(key)
        if buf is not None:
            # true LRU: a hit moves the shape to the end so the steady-state
            # bucket is never the eviction victim when rare shapes pass by
            self._buffers.pop(key)
            self._buffers[key] = buf
        if buf is None:
            self.shape_allocations += 1
            buf = {
                "free": np.zeros((pw, pr), dtype=np.int32),
                "nt": np.zeros(pw, dtype=np.int32),
                "life": np.zeros(pw, dtype=np.int32),
                "needs": np.zeros((pb, pv, pr), dtype=np.int32),
                "sizes": np.zeros(pb, dtype=np.int32),
                "mt": np.zeros((pb, pv), dtype=np.int32),
                "extents": (0, 0, 0, 0),
            }
            if has_all:
                buf["total"] = np.zeros((pw, pr), dtype=np.int32)
                buf["amask"] = np.zeros((pb, pv, pr), dtype=np.int32)
            self._buffers[key] = buf
            # bound the cache: bucket shapes are few (powers of two), but
            # a pathological workload must not grow this without limit
            while len(self._buffers) > 8:
                self._buffers.pop(next(iter(self._buffers)))
        return buf

    def _worker_bucket(self, n_w: int) -> int:
        return _bucket(n_w, self.worker_floor)
