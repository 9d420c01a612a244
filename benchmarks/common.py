"""Shared harness for benchmark experiments.

Reference: benchmarks/src/ — a framework spawning server/worker processes and
recording results. Each experiment here prints one JSON line per measurement.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def detect_profilers() -> list[str]:
    """Available profiler modes, best first (reference
    benchmarks/src/clusterutils/profiler.py supports flamegraph / perf-stat
    / cachegrind wrappers; this image carries none of those binaries, so
    cProfile — already hooked into every server/worker process via the
    HQ_PROFILE env var — is the always-available mode, and py-spy/perf are
    picked up automatically when present)."""
    import shutil

    modes = []
    if shutil.which("py-spy"):
        modes.append("py-spy")
    if shutil.which("perf"):
        modes.append("perf-stat")
    modes.append("cprofile")
    return modes


def profile_report(profile_path, top=30) -> str:
    """Human-readable top-N cumulative report from an HQ_PROFILE dump."""
    import io
    import pstats

    out = io.StringIO()
    stats = pstats.Stats(str(profile_path), stream=out)
    stats.sort_stats("cumulative").print_stats(top)
    return out.getvalue()


class Cluster:
    def __init__(self, n_workers=1, cpus=4, zero_worker=True, extra_server=(),
                 extra_worker=(), profile_dir=None):
        """profile_dir: attach the cProfile profiler to every spawned
        server/worker process; each writes <profile_dir>/profile.<role> on
        exit (the HQ_PROFILE hook in client/cli.py)."""
        self.dir = Path(tempfile.mkdtemp(prefix="hq-bench-"))
        self.env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": f"{REPO}:{os.environ.get('PYTHONPATH', '')}",
            "HQ_SERVER_DIR": str(self.dir / "sd"),
        }
        if profile_dir is not None:
            Path(profile_dir).mkdir(parents=True, exist_ok=True)
            self.env["HQ_PROFILE"] = str(Path(profile_dir) / "profile")
        self.procs = []
        self._spawn("server", ["server", "start", *extra_server])
        deadline = time.time() + 30
        access = self.dir / "sd" / "hq-current" / "access.json"
        while not access.exists():
            if time.time() > deadline:
                raise TimeoutError("server did not start")
            time.sleep(0.05)
        worker_args = ["worker", "start"]
        if cpus is not None:
            worker_args += ["--cpus", str(cpus)]
        worker_args += list(extra_worker)
        if zero_worker:
            worker_args.append("--zero-worker")
        for i in range(n_workers):
            self._spawn(f"worker{i}", worker_args)
        time.sleep(2.5)

    def _spawn(self, name, args):
        self.procs.append(
            subprocess.Popen(
                [sys.executable, "-m", "hyperqueue_tpu", *args],
                env=self.env,
                cwd=self.dir,
                stdout=open(self.dir / f"{name}.log", "wb"),
                stderr=subprocess.STDOUT,
            )
        )

    def hq(self, args, timeout=600):
        result = subprocess.run(
            [sys.executable, "-m", "hyperqueue_tpu", *args],
            env=self.env,
            cwd=self.dir,
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if result.returncode != 0:
            raise RuntimeError(f"hq {args} failed: {result.stdout}\n{result.stderr}")
        return result.stdout

    def close(self):
        for p in reversed(self.procs):
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def measure_submit_wait(cluster, n_tasks, calibrate=True, extra=()):
    """Returns (wall_seconds, marginal_per_task_ms)."""
    cal = 0.0
    if calibrate:
        t0 = time.perf_counter()
        cluster.hq(["submit", "--wait", *extra, "--", "true"])
        cal = time.perf_counter() - t0
    t0 = time.perf_counter()
    cluster.hq(
        ["submit", "--array", f"1-{n_tasks}", "--wait", *extra, "--", "true"]
    )
    wall = time.perf_counter() - t0
    per_task = (wall - cal) / max(n_tasks - 1, 1) * 1000
    return wall, per_task


def emit(record: dict) -> None:
    """Print one JSON result line AND store it in the durable result
    database (benchmarks/results/db.jsonl, keyed by experiment+params+git
    rev — reference benchmarks/src/benchmark/database.py).  Set
    HQ_BENCH_NO_DB=1 to skip the store (throwaway runs).

    A `"profile"` key (the per-plane/per-phase share summary from the
    sampling profiler, ISSUE 19) is stored as row METADATA, not params:
    shares vary run to run, and a params dict would fork every row into
    its own config group and blind the regression gate."""
    profile = record.pop("profile", None)
    print(json.dumps(
        {**record, **({"profile": profile} if profile else {})}
    ), flush=True)
    if not os.environ.get("HQ_BENCH_NO_DB"):
        try:
            from database import Database
        except ImportError:
            from benchmarks.database import Database
        try:
            Database().store_emit(
                record, metadata={"profile": profile} if profile else None
            )
        except OSError as e:  # a read-only checkout must not kill the run
            print(f"# result-db store failed: {e}", file=sys.stderr)
