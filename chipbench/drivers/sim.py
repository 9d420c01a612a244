"""The `sim` driver: the real Server under simulated workers, windowed.

`hyperqueue_tpu.sim.Simulation` boots the production `Server` (client plane,
journal, reactor, tick, fan-out) on its virtual-clock loop in this process,
with `scheduler="tpu"` and as many `SimWorker`s as the configuration has
workers, and a `SimClient` submits one array job, all of it up front, through
the real client plane's chunked submit (as `hq submit --array` does).
`Simulation.run()` runs to quiescence; this subclass replaces its `_main` with
a windowed one: submit, wait until the cluster is saturated (set-up), count
tasks finished over `--seconds` of WALL clock while the loop runs as fast as
the host lets it, stop, and audit the guarantee from the journal, the workers'
own record and the client's view.  Nothing paces the client: the array is far
more than any window drains.

Rates are per wall second.  The clock is virtual, the transport is in memory,
and the SimWorkers, the client and the invariant monitor's per-event tap
share the server's process: `assumed` in the configuration says so.
"""

from __future__ import annotations

import asyncio
import resource
import shutil
import tempfile
import time
import types
from collections import Counter

import numpy as np

from chipbench import generate, manifest, spans

# set-up ends once this share of the slots runs a task, and a virtual second
# later; the loop is polled at this virtual period
SATURATED_SHARE = 0.999
SETTLE_VIRTUAL_S = 1.0
POLL_VIRTUAL_S = 0.05


def warm_solve_shapes(n_workers: int, cpus: int) -> None:
    """Compile, before the server starts, every program the cell's solves can
    need: the kernel at the cell's bucket shape, the full upload, and the
    delta scatter at every row bucket (the number of workers whose state
    changed between two ticks decides the bucket, and the served path decides
    that number).  Drives the model's public `solve` with plain arrays."""
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel

    unit = generate.UNIT
    model = GreedyCutScanModel(backend="jax")
    free = np.full((n_workers, 1), cpus * unit, dtype=np.int32)
    slots = np.full(n_workers, cpus, dtype=np.int32)
    life = np.full(n_workers, 2**31 - 1, dtype=np.int32)
    needs = np.full((1, 1, 1), unit, dtype=np.int32)
    sizes = np.array([1], dtype=np.int32)
    min_time = np.zeros((1, 1), dtype=np.int32)
    rows = 1
    while True:
        model.solve(free.copy(), slots.copy(), life, needs, sizes, min_time)
        if rows > n_workers:
            break
        # change `rows` rows for the next solve: one delta bucket each
        free[:] = cpus * unit
        free[:min(rows, n_workers), 0] -= unit
        rows *= 2


def make_simulation(ctx, config, traffic, gc_settings):
    from hyperqueue_tpu.sim.client import SimSubmitStream
    from hyperqueue_tpu.sim.harness import Simulation
    from hyperqueue_tpu.sim.workloads import Workload

    scale = ctx.scale or {}
    n_workers = int(scale.get("workers", config["workers"]["count"]))
    (cpus,) = config["workers"]["cpus"]
    n_tasks = int(scale.get("tasks", traffic["tasks"]))
    dur_ms = float(config["task_duration_ms"])
    server = config["server"]
    ann = spans.annotate

    class Windowed(Simulation):

        async def _saturated(self, slots: int) -> None:
            """Until (nearly) every slot runs a task, then a little longer."""
            want = int(SATURATED_SHARE * slots)
            waited = 0.0
            poll = POLL_VIRTUAL_S
            while sum(len(w.running) for w in self.workers.values()) < want:
                await asyncio.sleep(poll)
                waited += poll
                if waited > 600.0:
                    raise SystemExit(
                        "chipbench: the cluster never saturated: "
                        f"{sum(len(w.running) for w in self.workers.values())}"
                        f" of {slots} slots busy after {waited:.0f} virtual s"
                    )
            await asyncio.sleep(SETTLE_VIRTUAL_S)

        async def _submit(self) -> None:
            """The client: one array job, the whole of it at once."""
            stream = SimSubmitStream(
                self.client, uid=f"chipbench-{ctx.seed}",
                header={"name": "chipbench", "submit_dir": "/sim"},
            )
            await stream.send_chunk(array={
                "body": {"sim": {"dur_range_ms": [dur_ms * 0.5, dur_ms * 1.5],
                                 "seed": ctx.seed}},
                "request": {"variants": [{"entries": [
                    {"name": "cpus", "amount": generate.UNIT},
                ]}]},
                "priority": 0,
                "id_range": [0, n_tasks],
            }, last=True)
            self.expected_tasks[stream.job_id] = n_tasks

        def _counts(self) -> dict:
            server = self.server
            return {
                "finished": self.monitor.finished_events,
                "ticks": server.core.tick_stats.ticks,
                "tick_total_ms": server.core.tick_stats.totals_ms.get(
                    "total", 0.0),
                "tick_phases_ms": dict(server.core.tick_stats.totals_ms),
                "decisions": len(server.core.flight.ticks()),
                "cpu_s": sum(resource.getrusage(resource.RUSAGE_SELF)[:2]),
                "virtual_t": self.loop.time(),
            }

        async def _main(self):
            self._server_down = asyncio.Event()
            await self.start_server()
            for _ in range(self.n_workers):
                self.add_worker()
            await self._submit()
            await self._saturated(self.n_workers * self.worker_cpus)
            # one server per process here, so the collector can be set as
            # `Server.start()` sets it outside the simulator
            spans.gc_as_server_started(gc_settings)
            ctx.setup_done()

            poll = POLL_VIRTUAL_S
            compiles0 = ctx.compiles.count
            before = self._counts()
            host = spans.HostReading()
            t_start = time.perf_counter()
            t_end = t_start + ctx.seconds
            ctx.window_opens(t_start)
            series = [(0.0, before["finished"])]
            while time.perf_counter() < t_end:
                with ann("chipbench/serve"):
                    await asyncio.sleep(poll)
                ctx.window_tick()
                now = time.perf_counter() - t_start
                if now - series[-1][0] >= 1.0:
                    series.append((now, self.monitor.finished_events))
            window_s = time.perf_counter() - t_start
            after = self._counts()
            host_in_window = host.delta()
            ctx.window_closed()
            compiles_in_window = ctx.compiles.count - compiles0
            memory_peak = ctx.memory_peak()
            acked = n_tasks
            info = await self.client.job_info(sorted(self.expected_tasks))

            # stop the workers and the server at once, as the parent does
            # at quiescence; nothing has to be terminal
            self._stopping = True
            self.client.close()
            for worker in self.workers.values():
                if not worker.dead:
                    worker.dead = True
                    if worker._task is not None:
                        worker._task.cancel()
                    if worker._link is not None:
                        worker._link.close()
            await asyncio.sleep(0.05)
            server = self.server
            decisions = server.core.flight.ticks()[
                before["decisions"]:after["decisions"]]
            solvers = [d["solver"] for d in decisions if d.get("solver")]
            if self._event_tap_task is not None:
                self._event_tap_task.cancel()
            server._event_listeners.clear()
            await server.shutdown()
            self.server = None
            self.outcome = {
                "acked": acked, "before": before, "after": after,
                "window_s": window_s, "memory_peak": memory_peak,
                "compiles_in_window": compiles_in_window,
                "host_in_window": host_in_window,
                "solves_by_backend": dict(Counter(
                    str(s.get("backend")) for s in solvers)),
                "solves_by_status": dict(Counter(
                    str(s.get("status")) for s in solvers)),
                "job_info": info,
                "finished_per_s": [
                    round((n1 - n0) / (t1 - t0))
                    for (t0, n0), (t1, n1) in zip(series, series[1:])
                ],
            }
            return types.SimpleNamespace(wall_s=0.0)  # run() stamps it

    return Windowed(
        Workload("chipbench-drain"), seed=ctx.seed, n_workers=n_workers,
        worker_cpus=cpus,
        scheduler="greedy-numpy" if ctx.rehearse else server["scheduler"],
        # under TMPDIR; the journal is read after the run, then removed
        server_dir=tempfile.mkdtemp(prefix="chipbench-sim-"),
        server_kwargs={
            "journal_flush_period": float(server["journal_flush_period"]),
            "journal_fsync": server["journal_fsync"],
        },
    ), n_workers, cpus


def _client_counters(info: dict) -> dict | None:
    """{n_tasks, <state>: count} of the one job, as the client reads it."""
    jobs = info.get("jobs") if isinstance(info, dict) else None
    if not jobs:
        return None
    job = jobs[0]
    counters = dict(job.get("counters") or {})
    n_tasks = int(job.get("n_tasks", 0))
    known = sum(counters.values())
    # the client's counters name running and terminal states; what is in
    # neither is waiting
    counters["waiting"] = max(0, n_tasks - known)
    counters["n_tasks"] = n_tasks
    return counters


def run(ctx) -> dict:
    from hyperqueue_tpu.events.journal import Journal

    config, traffic = ctx.cell["config"], ctx.cell["traffic"]
    if list(config["resources"]) != ["cpus"] or len(config["workers"]["cpus"]) != 1:
        raise SystemExit("chipbench: the sim driver runs cpus-only clusters "
                         "of one worker shape")
    gc_settings = spans.server_gc_settings()
    sim, n_workers, cpus = make_simulation(ctx, config, traffic, gc_settings)
    spans.gc_as_server_starts(gc_settings)
    if not ctx.rehearse:
        warm_solve_shapes(n_workers, cpus)
    sim.run()
    out = sim.outcome
    wanted_backend = ("host-native", "host-numpy") if ctx.rehearse \
        else ("device-jax",)
    off_device = sum(n for b, n in out["solves_by_backend"].items()
                     if b not in wanted_backend)
    not_ok = sum(n for s, n in out["solves_by_status"].items() if s != "ok")

    t = time.perf_counter()
    monitor = sim.monitor
    audited = manifest.load_by_path(
        manifest.HERE / "reference" / f"{config['audit']}.py", "audit"
    )(
        out["acked"], Journal.read_all(sim.journal_path),
        monitor.exec_started.keys(), monitor.exec_finished.keys(),
        _client_counters(out["job_info"]),
    )
    audit_s = time.perf_counter() - t
    shutil.rmtree(sim.server_dir, ignore_errors=True)

    before, after = out["before"], out["after"]
    finished = after["finished"] - before["finished"]
    checks = [(name, value, 0) for name, value in audited["numbers"].items()]
    checks += [
        ("solves_off_device", off_device, 0),
        ("solves_not_ok", not_ok, 0),
        ("compiles_in_window", out["compiles_in_window"], 0),
    ]
    return {
        "attempted": finished,
        "failed": audited["numbers"]["failed_or_canceled"],
        "window_s": out["window_s"],
        "end_to_end": {"tasks_per_s": finished / out["window_s"]},
        "observed": {
            "window_s": out["window_s"],
            "finished_in_window": finished,
            "tick_total_ms": after["tick_total_ms"] - before["tick_total_ms"],
            "cpu_s": after["cpu_s"] - before["cpu_s"],
            "kernel_module": "greedy_cut_scan_impl",
        },
        "checks": checks,
        "memory_peak_bytes": out["memory_peak"],
        "notes": {
            "solves_by_backend_in_window": out["solves_by_backend"],
            "solves_by_status_in_window": out["solves_by_status"],
            "ticks_in_window": after["ticks"] - before["ticks"],
            "finished_before_window": before["finished"],
            "submitted": out["acked"],
            "finished_per_s_by_second": out["finished_per_s"],
            "audit_counts": audited["counts"],
            "audit_s": round(audit_s, 3),
            "host_in_window": out["host_in_window"],
            "collector": gc_settings,
            "tick_total_ms_in_window": round(
                after["tick_total_ms"] - before["tick_total_ms"], 3),
            "tick_phases_ms_in_window": {
                k: round(v - before["tick_phases_ms"].get(k, 0.0), 1)
                for k, v in after["tick_phases_ms"].items()},
            "cpu_s_in_window": round(after["cpu_s"] - before["cpu_s"], 3),
            "virtual_s_in_window": round(
                after["virtual_t"] - before["virtual_t"], 3),
        },
    }
