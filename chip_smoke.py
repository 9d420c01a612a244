"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            one chip: served -> cluster -> width -> fused
    python chip_smoke.py --chips 4  the sharded solve over four chips and
                                    what it is compared with, then `fused`
                                    under `--scheduler multichip`

Every phase prints one JSON line when it ends; lines before it carry what
the next reader will want (sync probe, compile seconds, tick phases, bytes
uploaded).  None of those numbers is a claim.  Any failed check ends the
run at once with a non-zero exit code and the name of the check.  The last
line of a passing run is `{"ok": true, "device": {...}}` with the device as
JAX reports it.

A chip belongs to one process at a time, so this process does not import
JAX until every child that needs the chip has exited: `served` (a real
`hq server start --scheduler tpu` over TCP) and `cluster` (the simulator
CLI driving the real Server under 1 024 workers) each own the chip as a
child; `width` then runs here, and `fused` (a core and a model as
`Server(scheduler="tpu")` builds them: a multi-node task rides the device
solve as a gang row; with `--chips 4` the core and the model of
`Server(scheduler="multichip")`, the gang row in the sharded solve on the
mesh).  There is no CPU mode: without a TPU the
first child refuses to start and so does this script.  Tests rehearse the
phase functions on the CPU with the scheduler passed in.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
HQ = REPO / "bin" / "hq"


def fail(check: str, detail="") -> "NoReturn":  # noqa: F821
    print(f"chip_smoke: FAILED {check}: {detail}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(name: str, ok: bool, detail="") -> None:
    if not ok:
        fail(name, detail)


def emit(record: dict) -> None:
    print(json.dumps(record, default=str), flush=True)


def _child_env() -> dict:
    return {
        **os.environ,
        "PYTHONPATH": f"{REPO}:{os.environ.get('PYTHONPATH', '')}",
    }


def _cache_dir() -> Path:
    """Where the children keep compiled programs (utils/jaxdev.py says the
    same without this process importing jax)."""
    return Path(
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO / ".jax_cache"
    )


def _cache_entries() -> int:
    d = _cache_dir()
    return sum(1 for p in d.iterdir() if p.is_file()) if d.is_dir() else 0


def _tail(path: Path, n: int = 2000) -> str:
    try:
        return path.read_text(errors="replace")[-n:]
    except OSError:
        return ""


# --------------------------------------------------------------- served
# in this order, so that even two workers can run all three request classes
WORKER_SHAPES = (
    ("--cpus", "8", "--resource", "mem=sum(64)"),
    ("--cpus", "8", "--resource", "gpus=[0,1]"),
    ("--cpus", "8"),
)


def _hq(env, *args, timeout=600.0):
    done = subprocess.run(
        [str(HQ), *args], env=env, capture_output=True, text=True,
        timeout=timeout,
    )
    if done.returncode != 0:
        fail(f"served: hq {' '.join(args[:3])}",
             f"exit {done.returncode}: {done.stderr[-1500:]}")
    return done.stdout


def _wait_for(what: str, probe, timeout: float, interval=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = probe()
        if got:
            return got
        time.sleep(interval)
    fail(f"served: {what}", f"not within {timeout:.0f}s")


def _jax_libs_loaded(pid: int) -> list:
    """Workers and clients have no business on the chip: their address
    space must hold neither jax's runtime nor the TPU's.  Empty for a
    process that has already exited."""
    try:
        maps = Path(f"/proc/{pid}/maps").read_text()
    except OSError:
        return []
    return sorted(lib for lib in ("libtpu", "jaxlib", "libjax") if lib in maps)


def _variant_jobfile(path: Path, n_tasks: int) -> None:
    """`hq submit` has no syntax for request variants; a job file has."""
    task = (
        '[[task]]\nid = {i}\ncommand = ["true"]\n'
        '[[task.request]]\nresources = {{ cpus = "1", gpus = "0.5" }}\n'
        '[[task.request]]\nresources = {{ cpus = "2" }}\n'
    )
    with open(path, "w") as f:
        f.write('name = "gpu-or-cpus"\n')
        for i in range(n_tasks):
            f.write(task.format(i=i))


def served(scheduler: str, n_workers: int, n_tasks: int, workdir: Path,
           timeout: float = 600.0) -> dict:
    """Real processes over TCP: one server, `n_workers` zero-workers in
    three resource shapes, three jobs (one per request class) of
    `n_tasks` tasks in all.  Returns what the run showed; judges nothing
    but that every command ran."""
    workdir.mkdir(parents=True, exist_ok=True)
    env = {**_child_env(), "HQ_SERVER_DIR": str(workdir / "sd")}
    cache_before = _cache_entries()
    children: list[subprocess.Popen] = []
    logs = []

    def spawn(name, *args):
        log = open(workdir / f"{name}.log", "wb")
        logs.append(log)
        proc = subprocess.Popen(
            [str(HQ), *args], env=env, cwd=workdir, stdout=log,
            stderr=subprocess.STDOUT,
        )
        children.append(proc)
        return proc

    t0 = time.monotonic()
    try:
        server = spawn(
            "server", "server", "start", "--scheduler", scheduler,
            "--journal", str(workdir / "j.bin"),
        )
        access = workdir / "sd" / "hq-current" / "access.json"

        def server_up():
            if server.poll() is not None:
                fail("served: server start",
                     f"exit {server.returncode}: "
                     f"{_tail(workdir / 'server.log')}")
            return access.exists()

        _wait_for("server access file", server_up, timeout=180.0)
        t_up = time.monotonic()
        workers = [
            spawn(f"worker{i}", "worker", "start", "--zero-worker",
                  *WORKER_SHAPES[i % len(WORKER_SHAPES)])
            for i in range(n_workers)
        ]
        _wait_for(
            f"{n_workers} workers registered",
            lambda: len(json.loads(
                _hq(env, "worker", "list", "--output-mode", "json")
            )) == n_workers,
            timeout=120.0,
        )
        # one job per request class, submitted together so a tick sees
        # all three: cpus; fractional gpus with a cpu-only variant;
        # cpus + mem
        n_var = n_tasks // 5
        n_cpu = (n_tasks - n_var) // 2
        n_mem = n_tasks - n_var - n_cpu
        jobfile = workdir / "variants.toml"
        _variant_jobfile(jobfile, n_var)
        t_submit = time.monotonic()
        submits = [
            subprocess.Popen(
                [str(HQ), *args], env=env, cwd=workdir,
                stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
            )
            for args in (
                ("submit", "--array", f"1-{n_cpu}", "--cpus", "1", "--wait",
                 "--", "true"),
                ("job", "submit-file", str(jobfile), "--wait"),
                ("submit", "--array", f"1-{n_mem}", "--cpus", "2",
                 "--resource", "mem=4", "--wait", "--", "true"),
            )
        ]
        children.extend(submits)
        time.sleep(min(2.0, n_tasks / 1000))  # let the clients import
        client_loaded = sorted({
            lib for proc in submits for lib in _jax_libs_loaded(proc.pid)
        })
        for proc in submits:
            _out, err = proc.communicate(timeout=timeout)
            check("served: submit --wait", proc.returncode == 0,
                  f"exit {proc.returncode}: {err[-1500:]}")
        t_done = time.monotonic()

        jobs = json.loads(
            _hq(env, "job", "list", "--all", "--output-mode", "json")
        )
        stats = json.loads(
            _hq(env, "server", "stats", "--output-mode", "json")
        )
        from hyperqueue_tpu.client.connection import ClientSession

        with ClientSession(workdir / "sd") as session:
            metrics = session.request({"op": "metrics_render"})["text"]
        solves = {}
        for line in metrics.splitlines():
            if line.startswith("hq_solve_backend{"):
                label, value = line.rsplit(" ", 1)
                solves[label.split('"')[1]] = int(float(value))
        worker_loaded = _jax_libs_loaded(workers[0].pid)

        _hq(env, "server", "stop")
        try:
            server.wait(timeout=60.0)
        except subprocess.TimeoutExpired:
            fail("served: server stop", "server still running after 60s")
        return {
            "phase": "served",
            "scheduler": scheduler,
            "workers": n_workers,
            "tasks": sum(j["n_tasks"] for j in jobs),
            "finished": sum(j["counters"]["finished"] for j in jobs),
            "failed": sum(j["counters"]["failed"] for j in jobs),
            "canceled": sum(j["counters"]["canceled"] for j in jobs),
            "jobs": {j["id"]: (j["name"], j["status"]) for j in jobs},
            "device": stats["device"],
            "solve_backend": stats["solve_backend"],
            "solve_backend_reason": stats["solve_backend_reason"],
            "solves_by_backend": solves,
            "watchdog": {
                k: stats["watchdog"][k]
                for k in ("timeouts", "failures", "degraded_ticks",
                          "skipped_ticks", "last_error")
            },
            "resident": stats["resident"],
            "shape_allocations": stats["shape_allocations"],
            "tick_ms": stats["tick"],
            "worker_loaded": worker_loaded,
            "client_loaded": client_loaded,
            "server_exit": server.returncode,
            "cache_entries_added": _cache_entries() - cache_before,
            "server_start_s": round(t_up - t0, 3),
            "submit_to_done_s": round(t_done - t_submit, 3),
        }
    finally:
        for proc in children:
            if proc.poll() is None:
                proc.kill()
        for proc in children:
            proc.wait()
        for log in logs:
            log.close()


def check_accounting(rec: dict, n_tasks: int) -> None:
    """What holds on any backend: every task finished once, the solver
    never degraded, the workers stayed off jax."""
    phase = rec["phase"]
    check(f"{phase}: all tasks finished",
          rec["tasks"] == rec["finished"] == n_tasks
          and rec["failed"] == 0 and rec["canceled"] == 0,
          {k: rec[k] for k in ("tasks", "finished", "failed", "canceled")})
    wd = rec["watchdog"]
    check(f"{phase}: watchdog quiet",
          wd["timeouts"] == wd["failures"] == wd["degraded_ticks"] == 0,
          wd)
    check(f"{phase}: workers and clients stay off jax",
          not rec["worker_loaded"] and not rec["client_loaded"],
          (rec["worker_loaded"], rec["client_loaded"]))
    check(f"{phase}: server exited cleanly", rec["server_exit"] == 0,
          rec["server_exit"])


def check_served_on_chip(rec: dict) -> None:
    check("served: device.platform", (rec["device"] or {}).get("platform")
          == "tpu", rec["device"])
    check("served: solve_backend", rec["solve_backend"] == "device-jax",
          (rec["solve_backend"], rec["solve_backend_reason"]))
    check("served: no host solve over the run",
          set(rec["solves_by_backend"]) == {"device-jax"},
          rec["solves_by_backend"])
    res = rec["resident"]
    check("served: delta uploads after the first full one",
          res["full_uploads"] >= 1 and res["delta_uploads"] >= 1, res)


# -------------------------------------------------------------- cluster
def cluster(scheduler: str, n_workers: int, n_tasks: int) -> dict:
    """The real Server under `n_workers` simulated workers, one child:
    submit -> journal -> tick -> fan-out -> start -> completion."""
    cache_before = _cache_entries()
    t0 = time.monotonic()
    done = subprocess.run(
        [sys.executable, "-m", "hyperqueue_tpu.sim", "--scheduler",
         scheduler, "--workers", str(n_workers), "--tasks", str(n_tasks),
         "--fault-rate", "0", "--server-kills", "0", "--json"],
        env=_child_env(), cwd=REPO, capture_output=True, text=True,
        timeout=900.0,
    )
    check("cluster: simulator exit", done.returncode == 0,
          f"exit {done.returncode}: {done.stderr[-2000:]}")
    rec = json.loads(done.stdout.strip().splitlines()[-1])
    return {
        "phase": "cluster",
        "scheduler": scheduler,
        "workers": n_workers,
        **{k: rec[k] for k in ("n_tasks", "audit", "solves_by_backend",
                               "solves_by_status", "makespan_virtual_s",
                               "server_boots")},
        "cache_entries_added": _cache_entries() - cache_before,
        "wall_s": round(time.monotonic() - t0, 3),
    }


def check_cluster(rec: dict, n_tasks: int, backend: str) -> None:
    audit = rec["audit"]
    check("cluster: audit",
          audit["finished"] == audit["executions"] == n_tasks
          and audit["failed"] == 0, audit)
    check(f"cluster: every tick solved on {backend}",
          set(rec["solves_by_backend"]) == {backend}
          and set(rec["solves_by_status"]) == {"ok"},
          (rec["solves_by_backend"], rec["solves_by_status"]))


# ---------------------------------------------------------------- width
class CompileLog:
    """Compile seconds per jitted function and persistent-cache traffic,
    from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.seconds: dict[str, float] = {}
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.seconds[name] = self.seconds.get(name, 0.0) + seconds

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def snapshot(self) -> dict:
        return {
            "compile_s": {k: round(v, 3) for k, v in self.seconds.items()},
            "cache": dict(self.cache),
        }


def checked(model_cls, reference):
    """`model_cls` whose every device solve is compared, bitwise, with
    `reference(model, prep)` on the same padded inputs.  The reference is
    computed by verify(), which the caller runs after each tick: outside
    the timed span, and before the next solve rewrites the padded buffers
    (a pipelined solve's counts arrive a tick after its inputs)."""
    import numpy as np

    from hyperqueue_tpu.ops.answer import dense_of_cells

    class Handle:
        def __init__(self, inner, record):
            self.inner, self.record = inner, record

        def cells(self):
            self.record["got"] = got = self.inner.cells()
            return got

        def result(self):
            return dense_of_cells(self.cells())

    class Checked(model_cls):
        solves_checked = 0
        guard_ms = 0.0  # the resident-vs-fresh guard's share of the solve

        def _device_solve(self, prep):
            record = {"prep": prep, "want": None, "got": None}
            self.__dict__.setdefault("_open", []).append(record)
            self.shape_key = prep["shape_key"]
            return Handle(super()._device_solve(prep), record)

        def _maybe_paranoid_check(self, prep, cells):
            t = time.perf_counter()
            super()._maybe_paranoid_check(prep, cells)
            self.guard_ms = (time.perf_counter() - t) * 1e3

        def verify(self):
            for record in self.__dict__.get("_open", []):
                prep = record["prep"]
                if record["want"] is None:
                    n_b, n_v, n_w = prep["extents"]
                    record["want"] = np.asarray(
                        reference(self, prep)
                    )[:n_b, :n_v, :n_w]
                got, want = record["got"], record["want"]
                if got is None:
                    continue  # in flight: its counts come next tick
                # the cells the mapping read, whatever form they crossed in
                got = dense_of_cells(got)
                if not np.array_equal(got, want):
                    diff = np.argwhere(got != want)
                    at = tuple(diff[0])
                    fail("counts bitwise equal to the reference",
                         f"{len(diff)} cells differ, first (b, v, w) = "
                         f"{diff[0].tolist()}: got {got[at]}, want "
                         f"{want[at]}; shape_key {prep['shape_key']}")
                self.solves_checked += 1
            self._open = [r for r in self._open if r["got"] is None]

    return Checked


def numpy_reference(model, prep):
    from hyperqueue_tpu.ops.assign import greedy_cut_scan_numpy

    counts, _free, _nt = greedy_cut_scan_numpy(
        prep["free_p"], prep["nt_p"], prep["life_p"], prep["needs_p"],
        prep["sizes_p"], prep["mt_p"], prep["class_m"], prep["order_ids"],
        total=prep["total_p"], all_mask=prep["amask_p"],
        gang_nodes=prep["gang_p"], gang_ok=prep["gok_p"],
        group_onehot=prep["goh_p"], policy_mask=prep["pmask_p"],
    )
    return counts


def _uploads(model) -> dict:
    """The residency counters, zero before the first device solve."""
    stats = model.resident_stats()
    return {
        key: stats.get(key, 0)
        for key in ("upload_bytes_total", "puts_total",
                    "input_programs_total", "full_uploads", "delta_uploads",
                    "dirty_rows_last", "readbacks_total",
                    "readback_bytes_total", "answers_total",
                    "answers_compact", "answers_dense_small",
                    "answers_overflow")
    }


def check_answers(where: str, before: dict, after: dict, solves: int) -> dict:
    """One readback a solve, and one more for each answer whose compact
    form overflowed (the dense fallback); returns the counters' deltas."""
    moved = {key: after[key] - before[key] for key in after
             if key.startswith(("answers_", "readback"))}
    check(f"{where}: one packed answer a solve",
          moved["answers_total"] == solves
          and moved["readbacks_total"]
          == solves + moved["answers_overflow"], moved)
    return moved


def check_overflow_is_exact(where: str, mesh=None) -> dict:
    """A solve forced over K: synthetic padded counts with exactly K cells
    on one device unpack to numpy's cells; with K + 1 there (the total
    still under the whole buffer's capacity on a mesh) the buffer says it
    overflowed and the dense fallback's slice gives the same cells."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hyperqueue_tpu.ops import answer

    devices = 1 if mesh is None else int(mesh.devices.size)
    pb, pv, pw, pr = 16, 2, 512 * devices, 4
    extents = (13, 2, pw - 3)
    layout = answer.layout_for(extents, (pb, pv, pw, pr), devices)
    k = layout.capacity
    rng = np.random.default_rng(30)

    def put(arr, spec):
        if mesh is None:
            return jax.device_put(arr)
        return jax.device_put(arr, NamedSharding(mesh, spec))

    free = rng.integers(0, 99, (pw, pr)).astype(np.int32)
    nt = rng.integers(0, 9, pw).astype(np.int32)
    seen = {}
    for n_cells in (k, k + 1):
        counts = np.zeros((pb, pv, pw), np.int32)
        # all on device 0's live columns, spread over the live rows
        cols = min(k, extents[2])
        at = rng.choice(extents[0] * pv * cols, n_cells, replace=False)
        counts[at // (pv * cols), at // cols % pv, at % cols] = rng.integers(
            1, 2**30, n_cells
        )
        live = np.ascontiguousarray(
            counts[:extents[0], :extents[1], :extents[2]]
        )
        want = answer.cells_of_dense(live)
        counts_d = put(counts, P(None, None, "w"))
        buf = np.asarray(answer.pack_answer(
            counts_d, put(free, P("w", None)), put(nt, P("w")), layout, mesh
        ))
        cells, free_after, nt_after = answer.unpack_answer(buf, layout)
        check(f"{where}: the state part of the buffer is the state",
              np.array_equal(free_after, free)
              and np.array_equal(nt_after, nt), n_cells)
        if n_cells > k:
            check(f"{where}: K + 1 cells on one device overflow",
                  cells is None, buf[:, 0].tolist())
            cells = answer.cells_of_dense(np.asarray(
                answer.live_slicer(*extents)(counts_d)
            ))
        check(f"{where}: {n_cells} cells (K = {k}) cross exactly",
              cells is not None
              and np.array_equal(cells.flat, want.flat)
              and np.array_equal(cells.vals, want.vals), n_cells)
        seen[n_cells] = int(want.flat.size)
    return {"K": k, "devices": devices, "cells": seen}


def _tick_line(model, before: dict, phases_ms: dict, **head) -> dict:
    """Print one tick's line; returns the upload counters after it."""
    after = _uploads(model)
    emit({
        "note": "tick", **head,
        "phases_ms": {k: round(v, 3) for k, v in phases_ms.items()},
        "model_phases_ms": {
            k: round(v, 3) for k, v in model.last_phases.items()
        },
        # the guard's fresh solve lies in no phase (it follows device_sync)
        "guard_ms": round(model.guard_ms, 3),
        "uploaded_bytes":
            after["upload_bytes_total"] - before["upload_bytes_total"],
        "puts": after["puts_total"] - before["puts_total"],
        "input_programs":
            after["input_programs_total"] - before["input_programs_total"],
        "full_uploads": after["full_uploads"] - before["full_uploads"],
        "delta_uploads": after["delta_uploads"] - before["delta_uploads"],
        "dirty_rows": after["dirty_rows_last"],
        "backend": model.last_backend,
    })
    return after


def check_one_put(where: str, before: dict, after: dict, solves: int) -> None:
    """What a steady resident solve brings to the device crosses in one
    put and one unpack program (ops/inputs.py); the inputs that repeat hit
    the placement cache."""
    moved = (after["puts_total"] - before["puts_total"],
             after["input_programs_total"] - before["input_programs_total"])
    check(f"{where}: one put and one input program a steady solve",
          moved == (solves, solves), (moved, solves))


TICKS = 3  # per mode


WIDTH_CELL = "hetero-1k.backlog-1m"  # BENCHMARK.json: the world `width` ticks over


def width() -> dict:
    """The production tick over the benchmark's 1k world (1 024 workers,
    1 000 000 ready tasks, 64 classes: the state the cell WIDTH_CELL
    measures), the model forced to the device: TICKS ticks each solved
    synchronously from a fresh upload, device-resident, and through the
    pipeline."""
    import jax

    from chipbench import generate, manifest
    from chipbench.drivers.tick import build_program_state
    from hyperqueue_tpu.ids import task_id_task
    from hyperqueue_tpu.models.greedy import (
        GreedyCutScanModel,
        device_sync_ms,
    )
    from hyperqueue_tpu.scheduler.pipeline import TickPipeline
    from hyperqueue_tpu.scheduler.tick import create_batches, run_tick
    from hyperqueue_tpu.scheduler.watchdog import DEFAULT_TIMEOUT_S

    compiles = CompileLog()
    sync_ms = device_sync_ms(wait_s=120.0)
    emit({"note": "sync probe", "device_sync_ms": sync_ms,
          **compiles.snapshot()})

    t0 = time.monotonic()
    cell = manifest.cell(WIDTH_CELL)
    world = generate.world(cell["config"], cell["traffic"], seed=42)
    core, _rq_ids, _worker_ids = build_program_state(world, cell["config"])
    build_s = time.monotonic() - t0

    def priority_of(task_id):
        return (int(world.task_prio[task_id_task(task_id)]), 0)

    model = checked(GreedyCutScanModel, numpy_reference)(backend="jax")
    model.paranoid_resident = 1  # every resident solve vs a fresh upload

    def apply(assignments):
        for task_id, worker_id, rq_id, variant in assignments:
            worker = core.workers[worker_id]
            worker.assign(
                task_id, core.variant_amounts(rq_id, variant, worker)
            )

    def release(assignments):
        for task_id, worker_id, rq_id, variant in assignments:
            worker = core.workers[worker_id]
            worker.unassign(
                task_id, core.variant_amounts(rq_id, variant, worker)
            )

    def requeue(assignments):
        for task_id, _worker_id, rq_id, _variant in assignments:
            core.queues.add(rq_id, priority_of(task_id), task_id)

    def tick(pipeline=None):
        phases: dict = {}
        t0 = time.perf_counter()
        mapped = []
        if pipeline is not None and pipeline.pending is not None:
            mapped = pipeline.take_result(model=model, phases=phases)
        t1 = time.perf_counter()
        apply(mapped)
        t2 = time.perf_counter()
        snap = core.tick_cache.sync(core)
        t3 = time.perf_counter()
        batches = create_batches(core.queues)
        t4 = time.perf_counter()
        out = run_tick(
            core.queues, None, core.rq_map, core.resource_map, model,
            batches=batches, dense=snap, phases=phases,
            key_cache=core.tick_cache, pipeline=pipeline,
        )
        t5 = time.perf_counter()
        apply(out)
        t6 = time.perf_counter()
        phases.update(snapshot=(t3 - t2) * 1e3, batches=(t4 - t3) * 1e3,
                      apply=(t6 - t5 + t2 - t1) * 1e3, total=(t6 - t0) * 1e3)
        model.verify()
        return mapped + out, phases

    def line(mode, i, assignments, phases, before):
        return _tick_line(model, before, mode=mode, tick=i,
                          assigned=len(assignments), phases_ms=phases)

    # -- synchronous solve, each from a fresh full upload of the same state
    first_solve_s = None
    for i in range(TICKS):
        before = _uploads(model)
        t = time.perf_counter()
        out, phases = tick()
        if first_solve_s is None:
            first_solve_s = time.perf_counter() - t
            shapes_after_first = model.shape_allocations
            emit({"note": "first tick (compiles every program)",
                  "seconds": round(first_solve_s, 3),
                  "watchdog_deadline_s": DEFAULT_TIMEOUT_S,
                  **compiles.snapshot()})
        after = line("sync", i, out, phases, before)
        check("width: sync tick is a full upload",
              after["full_uploads"] - before["full_uploads"] == 1, after)
        # filling 1 024 empty workers may set more cells than K = 1 024:
        # then the dense fallback, compared with numpy like every solve
        check_answers("width: sync", before, after, 1)
        release(out)
        requeue(out)
        model.invalidate_resident()

    # -- device-resident steady state: assignments stay applied, a few
    # tasks finish between ticks, so only their workers' rows go up
    running: list = []

    def churn():
        done, running[:] = running[:64], running[64:]
        release(done)

    for i in range(TICKS):
        before = _uploads(model)
        out, phases = tick()
        running.extend(out)
        after = line("resident", i, out, phases, before)
        if i > 0:
            check("width: resident tick uploads a delta",
                  after["delta_uploads"] - before["delta_uploads"] == 1
                  and after["full_uploads"] == before["full_uploads"],
                  (before, after))
            moved = check_answers("width: resident", before, after, 1)
            check("width: a steady tick's answer crosses compact, once",
                  moved["answers_compact"] == 1
                  and moved["readbacks_total"] == 1, moved)
            check_one_put("width: resident", before, after, 1)
        churn()

    # -- dispatched through the pipeline: tick k maps solve k-1
    pipeline = TickPipeline()
    for i in range(TICKS):
        before = _uploads(model)
        out, phases = tick(pipeline)
        running.extend(out)
        line("pipelined", i, out, phases, before)
        churn()
    apply(pipeline.drain(model=model))
    model.verify()
    check("width: pipeline dispatched and mapped every tick",
          pipeline.dispatched == pipeline.mapped == TICKS,
          pipeline.stats())

    # which backend "auto" would pick on this machine, and why: the cost
    # model is not under test here, its first choices are just recorded
    auto = GreedyCutScanModel(backend="auto")
    picks = []
    for _ in range(4):
        out = run_tick(
            core.queues, None, core.rq_map, core.resource_map, auto,
            batches=create_batches(core.queues),
            dense=core.tick_cache.sync(core), key_cache=core.tick_cache,
        )
        requeue(out)
        picks.append([auto.last_backend, auto.last_backend_reason])
    emit({"note": "what --scheduler auto picks here", "picks": picks,
          "device_sync_ms": device_sync_ms()})

    check("width: no new bucket shape after the first tick",
          model.shape_allocations == shapes_after_first,
          (shapes_after_first, model.shape_allocations))
    check("width: every solve compared", model.solves_checked == 3 * TICKS,
          model.solves_checked)
    check("width: resident-vs-fresh guard ran on every solve",
          model.paranoid_checks == 3 * TICKS, model.paranoid_checks)
    check("width: solved on the device",
          model.last_backend == "device-jax", model.last_backend)
    forced = check_overflow_is_exact("width")
    device = jax.devices()[0]
    memory = device.memory_stats() or {}
    return {
        "phase": "width",
        "forced_over_K": forced,
        "world": WIDTH_CELL,
        "workers": len(core.workers),
        "ready_tasks": len(world.task_class),
        "classes": len(world.class_variants),
        "solves_bitwise_equal_to_numpy": model.solves_checked,
        "resident_vs_fresh_checks": model.paranoid_checks,
        "shape_key": model.shape_key,
        "shape_allocations": model.shape_allocations,
        "device": model.last_device,
        "device_sync_ms": sync_ms,
        "first_tick_s": round(first_solve_s, 3),
        "build_state_s": round(build_s, 3),
        "peak_bytes_in_use": memory.get("peak_bytes_in_use"),
        "resident": model.resident_stats(),
        **compiles.snapshot(),
    }


# ---------------------------------------------------------------- fused
def fused(scheduler: str, n_workers: int, n_tasks: int,
          watchdog_timeout: float | None = None) -> dict:
    """A core and a model as `Server(scheduler=...)` builds them, under
    `reactor.schedule`: the server's multi-node tasks ride the device solve
    as gang rows (`core.fused_solve`), beside the single-node classes.
    `watchdog_timeout` is `--solver-watchdog-timeout` (default: the
    server's 5 s)."""
    from __graft_entry__ import ClusterState
    from hyperqueue_tpu.server import reactor
    from hyperqueue_tpu.server.bootstrap import Server
    from hyperqueue_tpu.utils.metrics import REGISTRY

    with tempfile.TemporaryDirectory(prefix="hq-smoke-fused-") as tmp:
        server = Server(
            server_dir=Path(tmp), scheduler=scheduler,
            **({} if watchdog_timeout is None
               else {"solver_watchdog_timeout": watchdog_timeout}))
    core, model = server.core, server.model
    check("fused: the server's scheduler runs the fused tick",
          core.fused_solve is True, scheduler)
    state = ClusterState(n_workers, n_tasks, core=core)
    started = REGISTRY.get("hq_solve_gang_groups").labels()
    rows = REGISTRY.get("hq_solve_gang_rows_total").labels()
    before = (started.value, rows.value)
    ticks = []
    gang_ms: dict = {}
    for i in range(TICKS):
        t = time.perf_counter()
        assigned = reactor.schedule(core, state.comm, state.events, model,
                                    prefill=True)
        core.sanity_check()
        if i == 0:
            # the gang row's tick: its three gang inputs ride the packed
            # buffer, and nothing is put beside it
            stats = model.resident_stats()
            check("fused: the gang tick's inputs cross in one put a solve",
                  stats["puts_total"] == stats["input_programs_total"]
                  == stats["answers_total"], stats)
        # what this tick added to the gang phases (none once the gang runs)
        totals = {k: v for k, v in core.tick_stats.totals_ms.items()
                  if k.startswith("gangs")}
        ticks.append({
            "tick": i, "assigned": assigned,
            "tick_ms": round((time.perf_counter() - t) * 1e3, 3),
            "backend": model.last_backend,
            "gang_phases_ms": {k: round(v - gang_ms.get(k, 0.0), 4)
                               for k, v in totals.items()
                               if v > gang_ms.get(k, 0.0)},
        })
        gang_ms = totals
        check("fused: tick assigned work", assigned > 0, assigned)
        state.finish_some(256)
        state.submit_wave(n_tasks // 5)
    check("fused: gang placed on one group by the device solve",
          state.gang_placed(), state.gang.mn_workers)
    check("fused: one gang row sent, one gang started",
          (started.value - before[0], rows.value - before[1]) == (1, 1),
          (started.value - before[0], rows.value - before[1]))
    check("fused: the gang's phases were timed",
          {"gangs", "gangs/rows", "gangs/inputs", "gangs/apply"}
          <= set(ticks[0]["gang_phases_ms"]), ticks[0])
    running = sum(len(w.assigned_tasks) for w in core.workers.values())
    check("fused: single-node tasks run beside the gang", running > 0,
          running)
    return {
        "phase": "fused",
        "scheduler": scheduler,
        "workers": n_workers,
        "gang_workers": list(state.gang.mn_workers),
        "single_node_tasks_running": running,
        "ticks": ticks,
        "device": model.last_device,
        "resident": model.resident_stats(),
    }


def check_fused_on(backend: str, n_devices: int, rec: dict) -> None:
    check("fused: every tick solved on the device",
          {t["backend"] for t in rec["ticks"]} == {backend}, rec["ticks"])
    check(f"fused: the solve's arrays lie on {n_devices} device(s)",
          (rec["device"] or {}).get("count") == n_devices
          == rec["resident"]["mesh_devices"], rec["device"])
    check("fused: the gang inputs' bytes were counted",
          rec["resident"]["gang_input_bytes_total"] > 0, rec["resident"])


# -------------------------------------------------------------- sharded
def sharded(n_workers: int, n_tasks: int, n_devices: int) -> dict:
    """MultichipModel over `n_devices` chips through reactor.schedule on a
    real Core, compared on every solve with the single-chip jitted kernel
    on chip 0 fed the same padded inputs."""
    import re

    from __graft_entry__ import ClusterState
    from hyperqueue_tpu.models.greedy import GreedyCutScanModel
    from hyperqueue_tpu.models.multichip import MultichipModel
    from hyperqueue_tpu.parallel.solve import sharded_cut_scan_donate
    from hyperqueue_tpu.server import reactor

    compiles = CompileLog()
    shard_log: list = []
    placed_log: list = []
    preps: list = []

    class Model(checked(
        MultichipModel,
        # the single-chip program, fresh uploads to the default device
        lambda model, prep: GreedyCutScanModel._fresh_device_counts(
            model, prep
        ),
    )):
        def _kernel_dispatch(self, res, free_d, nt_d, life_d, total_d, prep,
                             placed):
            out = super()._kernel_dispatch(
                res, free_d, nt_d, life_d, total_d, prep, placed
            )
            placed_log.append(placed)
            counts, free_after, _nt_after = out
            shard_log.append({
                name: sorted(
                    (s.device.id, tuple(s.data.shape))
                    for s in arr.addressable_shards
                )
                for name, arr in (("counts", counts), ("free", free_after))
            })
            preps.append(prep)
            return out

    t0 = time.monotonic()
    state = ClusterState(n_workers, n_tasks)
    core, comm, events = state.core, state.comm, state.events
    build_s = time.monotonic() - t0
    model = Model(n_devices=n_devices)
    model.paranoid_resident = 1

    tick_ms = []
    solves = layouts_ran = 0
    for i in range(TICKS):
        before = _uploads(model)
        t = time.perf_counter()
        assigned = reactor.schedule(core, comm, events, model, prefill=True)
        tick_ms.append((time.perf_counter() - t) * 1e3)
        model.verify()
        core.sanity_check()
        after = _tick_line(
            model, before, mode="sharded", tick=i, assigned=assigned,
            tick_ms=round(tick_ms[-1], 3),
            phases_ms=core.tick_stats.last_ms, **compiles.snapshot())
        check("sharded: tick assigned work", assigned > 0, assigned)
        check_answers("sharded", before, after, len(shard_log) - solves)
        ran = len(model._res._ran)
        if ran == layouts_ran:
            # no new layout this tick (a first one may bring others, run
            # once to stay compiled: resident.py `_keep_compiled`)
            check_one_put("sharded", before, after, len(shard_log) - solves)
        layouts_ran = ran
        solves = len(shard_log)
        # completions and a new wave of submits before the next tick
        state.finish_some(256)
        state.submit_wave(n_tasks // 5)

    check("sharded: gang placed on one group", state.gang_placed(),
          state.gang.mn_workers)
    check("sharded: solve_backend", model.last_backend == "device-sharded",
          model.last_backend)
    check("sharded: mesh spans every chip",
          bool(model._mesh) and model._mesh.devices.size == n_devices,
          model._mesh)
    check("sharded: every solve compared with the single-chip kernel",
          model.solves_checked == len(shard_log) >= TICKS,
          (model.solves_checked, len(shard_log)))
    per_shard = model._worker_bucket(n_workers) // n_devices
    for shards in shard_log:
        for name, placed in shards.items():
            check(f"sharded: {name} on {n_devices} distinct devices",
                  len({dev for dev, _shape in placed}) == n_devices
                  and all(per_shard in shape for _dev, shape in placed),
                  placed)
    # DeviceResidency._put falls back to the default device when it has
    # no shardings: everything the model placed must span the mesh
    res = model._res
    check("sharded: residency has mesh shardings",
          res._shardings is not None, res._shardings)
    placed = {
        "free": res.free, "nt_free": res.nt_free, "lifetime": res.lifetime,
        "total": res.total,
        **{name: dev for name, (_host, dev) in res._rep_cache.items()},
        **placed_log[-1],
    }
    spans = {name: len(arr.devices()) for name, arr in placed.items()
             if arr is not None}
    check("sharded: no array on the default device alone",
          set(spans.values()) == {n_devices}, spans)

    # collectives of the program the last tick ran
    args, kwargs = model._fresh_program_args(preps[-1])
    text = sharded_cut_scan_donate.lower(
        model._mesh, *args, **kwargs
    ).compile().as_text()
    collectives = {
        op: len(re.findall(rf"= \S+ {op}(?:-start)?\(", text))
        for op in ("all-gather", "all-reduce", "collective-permute",
                   "all-to-all")
    }
    check("sharded: program has a collective", sum(collectives.values()) > 0,
          collectives)
    answers = model.resident_stats()
    check("sharded: an answer crossed compact",
          answers["answers_compact"] > 0, answers)
    forced = check_overflow_is_exact("sharded", model._mesh)
    return {
        "phase": "sharded",
        "forced_over_K": forced,
        "workers": n_workers,
        "worker_bucket": model._worker_bucket(n_workers),
        "ready_tasks_first_tick": n_tasks,
        "devices": n_devices,
        "solves_bitwise_equal_to_single_chip": model.solves_checked,
        "resident_vs_fresh_checks": model.paranoid_checks,
        "shards": shard_log[-1],
        "collectives_in_program": collectives,
        "tick_ms": [round(v, 3) for v in tick_ms],
        "shape_key": model.shape_key,
        "device": model.last_device,
        "build_state_s": round(build_s, 3),
        "resident": model.resident_stats(),
        **compiles.snapshot(),
    }


# ----------------------------------------------------------------- main
def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = parser.parse_args().chips
    if not HQ.exists():
        fail("checkout", f"{HQ} not found: run from a checkout of the repo")

    from hyperqueue_tpu.utils.native import load_native

    emit({"note": "native core",
          "native_core": "built and loaded" if load_native() is not None
          else "python twin (libhqcore.so could not be built or loaded)",
          "compile_cache_dir": str(_cache_dir()),
          "cache_entries_at_start": _cache_entries()})

    if chips == 1:
        with tempfile.TemporaryDirectory(prefix="hq-smoke-") as tmp:
            rec = served("tpu", n_workers=8, n_tasks=100_000,
                         workdir=Path(tmp))
        emit(rec)
        check_accounting(rec, 100_000)
        check_served_on_chip(rec)

        rec = cluster("tpu", n_workers=1024, n_tasks=100_000)
        emit(rec)
        check_cluster(rec, 100_000, "device-jax")

    # every child that needed the chip has exited: jax may load here
    import jax

    from hyperqueue_tpu.utils.jaxdev import configure_compile_cache

    configure_compile_cache()
    devices = jax.devices()
    check("a TPU is attached",
          devices[0].platform == "tpu" and len(devices) >= chips,
          [str(d) for d in devices])
    if chips == 1:
        emit(width())
        rec = fused("tpu", n_workers=1024, n_tasks=20_000)
        emit(rec)
        check_fused_on("device-jax", 1, rec)
    else:
        emit(sharded(n_workers=16384, n_tasks=120_000, n_devices=chips))
        # a server-built core: the gang rides the sharded solve as a gang
        # row (in `sharded` the reactor's host phase placed it).  On a cold
        # compile cache the first gang solve at this width compiles three
        # programs for four chips in more than the watchdog's default 5 s
        # (PR 33's first run here: SolveTimeout, the tick degraded to the
        # host), so the server is started as its operator would start it
        # there, with a deadline the first compile fits
        rec = fused("multichip", n_workers=16384, n_tasks=120_000,
                    watchdog_timeout=300.0)
        emit(rec)
        check_fused_on("device-sharded", chips, rec)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
