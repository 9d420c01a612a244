"""Multi-chip scheduling model: the sharded cut-scan as a production backend.

Selected with `--scheduler=multichip`. Same `solve` interface and identical
semantics as GreedyCutScanModel (the sharded kernel reproduces the single-chip
visit order exactly — see parallel/solve.py); the worker axis is sharded over
a jax.sharding.Mesh so that tick cost scales with W / n_devices.

In the reference the solver IS the production scheduler
(crates/tako/src/internal/scheduler/main.rs:40-46, solver.rs:16-461); this
model is the multi-device form of that seat, reached through the same
reactor.schedule -> run_tick -> model.solve path as every other backend.

Device handling: the mesh is built from however many devices the process
sees (all of them by default, or `n_devices`) — by the server at start, so a
backend that cannot initialise stops the server there, or else on first
solve.  With a single device the model runs the plain single-chip kernel: on
an accelerator that is the device kernel, forced, exactly as
`--scheduler=tpu` runs it; on the CPU backend (tests, virtual devices) it is
the parent's host solve.

Residency: the sharded solve inherits the device-resident tick state from
the parent model — the (W, R) shards stay on their devices across ticks,
per-tick uploads are the dirty-row delta and the inputs that change every
tick in one packed put, a row a device (ops/inputs.py: each device
scatters the rows that fall into its own shard, no collective), and
`sharded_cut_scan_donate` reuses the resident buffers for `free_after`/
`nt_after`.  `--scheduler=multichip` is an explicit operator choice, so
the adaptive host-vs-device cost model is bypassed: with a real mesh the
sharded kernel runs unconditionally (the watchdog still guards failures),
matching the documented contract that selecting multichip means "shard my
solve".
"""

from __future__ import annotations

import logging

from hyperqueue_tpu.models.greedy import GreedyCutScanModel, _bucket

logger = logging.getLogger(__name__)


class MultichipModel(GreedyCutScanModel):
    _device_backend_name = "device-sharded"

    def __init__(self, n_devices: int | None = None, **kwargs):
        # backend only matters with a single device (see get_mesh); with
        # a real mesh the sharded jax kernel is used unconditionally
        super().__init__(**kwargs)
        self._requested_devices = n_devices
        self._mesh = None  # built by get_mesh: the first jax.devices()

    def get_mesh(self):
        """The worker mesh (False = single device), built on first call."""
        if self._mesh is None:
            import jax

            from hyperqueue_tpu.parallel.solve import make_worker_mesh

            # a backend that fails to initialise raises out of here: the
            # operator asked for devices, and one host solve under that
            # name is not what they asked for
            available = len(jax.devices())
            n = (
                min(self._requested_devices, available)
                if self._requested_devices
                else available
            )
            if n <= 1:
                self._mesh = False  # sentinel: single-chip kernel
                if jax.default_backend() != "cpu":
                    # one accelerator: its kernel, not the host-vs-device
                    # cost model the parent's "auto" would run
                    self._use_numpy = False
                logger.info(
                    "multichip scheduler: 1 device visible, using the "
                    "single-chip kernel"
                )
            else:
                self._mesh = make_worker_mesh(n)
                logger.info(
                    "multichip scheduler: worker axis sharded over %d devices",
                    n,
                )
        return self._mesh

    def _worker_bucket(self, n_w: int) -> int:
        pw = _bucket(n_w, self.worker_floor)
        mesh = self.get_mesh()
        if mesh:
            d = mesh.devices.size
            pw = ((pw + d - 1) // d) * d  # shard_map needs W % D == 0
        return pw

    def _backend_decision(self, shape_key):
        # an operator who selected --scheduler=multichip asked for the
        # sharded device solve: run it whenever a mesh exists (the solver
        # watchdog still catches failures); without one, behave exactly
        # like the single-chip model (forced onto a lone accelerator by
        # get_mesh, host on the CPU backend)
        if self.get_mesh():
            return "device", "multichip-mesh"
        return super()._backend_decision(shape_key)

    def _residency(self):
        if self._res is None:
            from hyperqueue_tpu.parallel.resident import DeviceResidency
            from hyperqueue_tpu.parallel.solve import _mesh_shardings

            mesh = self.get_mesh()
            if mesh:
                self._res = DeviceResidency(shardings=_mesh_shardings(mesh))
            else:
                self._res = super()._residency()
        return self._res

    def _tick_inputs(self, prep) -> list:
        if not self.get_mesh():
            return super()._tick_inputs(prep)
        from hyperqueue_tpu.parallel.solve import pack_batch_table

        # the replicated per-batch inputs change together (with the batch
        # order) and the kernel takes them as one vector: the whole table
        # crosses with the state, and the worker-sharded inputs beside it,
        # each device its own columns
        table = pack_batch_table(
            prep["needs_p"], prep["sizes_p"], prep["mt_p"],
            prep["order_ids"], prep["amask_p"],
        )
        return [("batch_table", table, 2),
                ("class_m", prep["class_m"], 3)] + self._gang_inputs(prep)

    def _kernel_dispatch(self, res, free_d, nt_d, life_d, total_d, prep,
                         placed):
        mesh = self.get_mesh()
        if not mesh:
            return super()._kernel_dispatch(
                res, free_d, nt_d, life_d, total_d, prep, placed
            )
        from hyperqueue_tpu.parallel.solve import sharded_cut_scan_donate

        return sharded_cut_scan_donate(
            mesh, free_d, nt_d, life_d,
            placed["batch_table"], placed["class_m"],
            extents=prep["needs_p"].shape,
            has_all=prep["amask_p"] is not None,
            total=total_d,
            gang_nodes=placed.get("gang_nodes"),
            gang_ok=placed.get("gang_ok"),
            group_onehot=placed.get("group_onehot"),
            gang_resv=placed.get("gang_resv"),
            policy_mask=res.place_cached(
                "policy_mask", prep["pmask_p"], kind=3
            ),
        )

    def _fresh_program_args(self, prep):
        """(args, kwargs) of `sharded_cut_scan_donate` after the mesh, from
        the padded host buffers alone: no residency, no placement cache."""
        from hyperqueue_tpu.parallel.solve import pack_batch_table

        args = (
            prep["free_p"], prep["nt_p"], prep["life_p"],
            pack_batch_table(
                prep["needs_p"], prep["sizes_p"], prep["mt_p"],
                prep["order_ids"], prep["amask_p"],
            ),
            prep["class_m"],
        )
        kwargs = dict(
            extents=prep["needs_p"].shape,
            has_all=prep["amask_p"] is not None,
            total=prep["total_p"], gang_nodes=prep["gang_p"],
            gang_ok=prep["gok_p"], group_onehot=prep["goh_p"],
            policy_mask=prep["pmask_p"], gang_resv=prep["resv_p"],
        )
        return args, kwargs

    def _fresh_device_counts(self, prep):
        mesh = self.get_mesh()
        if not mesh:
            return super()._fresh_device_counts(prep)
        from hyperqueue_tpu.parallel.solve import sharded_cut_scan_donate

        # the resident tick's program; it consumes free and nt_free, so
        # those go in as copies
        (free, nt_free, *rest), kwargs = self._fresh_program_args(prep)
        counts, _f, _n = sharded_cut_scan_donate(
            mesh, free.copy(), nt_free.copy(), *rest, **kwargs
        )
        return counts
