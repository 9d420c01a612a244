"""End-to-end test harness: drives real server/worker/CLI processes.

Mirrors the reference tier-3 Python suite (reference tests/conftest.py Env /
HqEnv fixtures): spawns `python -m hyperqueue_tpu` subprocesses with a temp
server dir, captures logs, asserts liveness, and polls with wait_until.
"""

from __future__ import annotations

import io
import os
import subprocess
import sys
import threading
import time
import traceback
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent

# Client commands run IN-PROCESS by default (cli.main called in a worker
# thread with captured stdio): a subprocess `python -m hyperqueue_tpu`
# costs ~0.75 s of interpreter+import startup on a busy 2-core box, and
# the suite issues thousands of client calls — polling loops included —
# so in-process execution cuts tier-1 wall time by several minutes AND
# makes wait_until polling actually poll at its nominal interval. The
# server/worker processes tests drive stay real subprocesses; the full
# wire protocol is still exercised. Set HQ_TEST_CLI_SUBPROCESS=1 to
# restore fork-per-command (debugging aid).
_CLI_IN_PROCESS = not os.environ.get("HQ_TEST_CLI_SUBPROCESS")


class _CliResult:
    """subprocess.run-shaped result for the in-process CLI path."""

    __slots__ = ("returncode", "stdout", "stderr")

    def __init__(self, returncode: int, stdout: str, stderr: str):
        self.returncode = returncode
        self.stdout = stdout
        self.stderr = stderr


def _capture_stream():
    """Text stream with a working `.buffer` (cli uses sys.stdout.buffer
    for raw output channels like `job cat`)."""
    raw = io.BytesIO()
    wrapper = io.TextIOWrapper(
        raw, encoding="utf-8", errors="replace", write_through=True
    )
    return raw, wrapper


def _run_cli_inprocess(
    args: list[str], server_dir: Path, cwd, timeout: float
) -> _CliResult:
    out_raw, out = _capture_stream()
    err_raw, err = _capture_stream()
    result: dict = {}
    # set when the caller gives up on a hung command: the zombie thread
    # must NOT restore process-global cwd/env/stdio minutes later while an
    # unrelated test (or its own replacement command) is mid-flight. The
    # lock makes check+restore atomic on both sides — without it a thread
    # finishing exactly at the join deadline could pass the is_set() check,
    # lose the CPU, and run its restore() after the caller moved on
    abandoned = threading.Event()
    restore_lock = threading.Lock()

    old_cwd = os.getcwd()
    old_sd = os.environ.get("HQ_SERVER_DIR")
    old_out, old_err = sys.stdout, sys.stderr

    def restore() -> None:
        sys.stdout, sys.stderr = old_out, old_err
        os.chdir(old_cwd)
        if old_sd is None:
            os.environ.pop("HQ_SERVER_DIR", None)
        else:
            os.environ["HQ_SERVER_DIR"] = old_sd

    def body() -> None:
        from hyperqueue_tpu.client.cli import main as cli_main

        os.environ["HQ_SERVER_DIR"] = str(server_dir)
        os.chdir(str(cwd))
        sys.stdout, sys.stderr = out, err
        try:
            try:
                cli_main([str(a) for a in args])
                result["rc"] = 0
            except SystemExit as e:
                if isinstance(e.code, int) or e.code is None:
                    result["rc"] = e.code or 0
                else:  # parser.error-style string payloads
                    err.write(f"{e.code}\n")
                    result["rc"] = 2
            except BaseException:  # noqa: BLE001 - mimic a crash rc
                traceback.print_exc(file=err)
                result["rc"] = 1
        finally:
            with restore_lock:
                if not abandoned.is_set():
                    restore()

    # daemon thread so a hung command can't wedge interpreter shutdown;
    # the TimeoutExpired mirrors the subprocess path's contract
    t = threading.Thread(target=body, daemon=True, name="hq-cli")
    t.start()
    t.join(timeout)
    if t.is_alive():
        with restore_lock:
            abandoned.set()
            restore()  # the zombie skips its own (late, corrupting) restore
        raise subprocess.TimeoutExpired(cmd=args, timeout=timeout)
    out.flush()
    err.flush()
    return _CliResult(
        result.get("rc", 1),
        out_raw.getvalue().decode("utf-8", "replace"),
        err_raw.getvalue().decode("utf-8", "replace"),
    )

# Subprocesses must never grab the real TPU during tests. Built per call so
# tests that mutate os.environ (PATH mocks, HQ_ALLOC_ID) are picked up.
def _env_base() -> dict:
    return {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": f"{REPO_ROOT}:{os.environ.get('PYTHONPATH', '')}",
    }


def start_fleet_proxy(root: Path, host: str = "127.0.0.1",
                      timeout: float = 10.0) -> int:
    """Run the fleet metrics proxy on an ephemeral port in a daemon
    thread; returns the bound port. Raises RuntimeError — carrying the proxy's
    own startup error when there is one — if it fails to bind."""
    import asyncio
    import threading

    from hyperqueue_tpu.client.fleet import start_metrics_proxy

    bound: dict = {}
    ready = threading.Event()

    def run() -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)

        async def go():
            server, port = await start_metrics_proxy(root, 0, host=host)
            bound["port"] = port
            ready.set()
            async with server:
                await server.serve_forever()

        try:
            loop.run_until_complete(go())
        except Exception as e:  # noqa: BLE001
            bound.setdefault("error", repr(e))
            ready.set()  # unblock the waiter; teardown noise after the
            # port is bound is harmless

    threading.Thread(target=run, daemon=True, name="fleet-proxy").start()
    if not ready.wait(timeout) or "port" not in bound:
        raise RuntimeError(
            "metrics proxy failed to start: "
            + bound.get("error", "timed out")
        )
    return bound["port"]


def wait_until(predicate, timeout=15.0, interval=0.05, message="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(interval)
    if callable(message):  # computed at failure time (live state snapshot)
        message = message()
    raise TimeoutError(f"timed out waiting for {message}")


class HqEnv:
    def __init__(self, tmp_path: Path):
        self.tmp = Path(tmp_path)
        self.server_dir = self.tmp / "server"
        self.work_dir = self.tmp / "work"
        self.work_dir.mkdir(parents=True, exist_ok=True)
        self.processes: list[tuple[str, subprocess.Popen]] = []

    def _spawn(
        self, name: str, args: list[str], cwd=None, env_extra=None
    ) -> subprocess.Popen:
        log = open(self.tmp / f"{name}.log", "wb")
        process = subprocess.Popen(
            [sys.executable, "-m", "hyperqueue_tpu", *args],
            env={**_env_base(), **(env_extra or {})},
            cwd=cwd or self.work_dir,
            stdout=log,
            stderr=subprocess.STDOUT,
        )
        self.processes.append((name, process))
        return process

    def start_server(self, *extra: str, env_extra=None) -> subprocess.Popen:
        before = {
            p.name for p in self.server_dir.iterdir() if p.name.isdigit()
        } if self.server_dir.exists() else set()
        n = sum(1 for name, _ in self.processes if name.startswith("server"))
        process = self._spawn(
            "server" if n == 0 else f"server{n}",
            ["server", "start", "--server-dir", str(self.server_dir), *extra],
            env_extra=env_extra,
        )

        def new_instance_ready():
            if process.poll() is not None:
                return True
            if not self.server_dir.exists():
                return False
            fresh = {
                p.name for p in self.server_dir.iterdir() if p.name.isdigit()
            } - before
            return any(
                (self.server_dir / d / "access.json").exists() for d in fresh
            )

        # a restart over a large journal replays + resubmits every
        # unfinished task before the access file appears; on a loaded
        # 2-core sandbox that alone can exceed the default 15 s
        wait_until(new_instance_ready, timeout=60.0,
                   message="server access file")
        assert process.poll() is None, self.read_log(
            "server" if n == 0 else f"server{n}"
        )
        return process

    # --- federation (ISSUE 11) -----------------------------------------
    def shard_dir(self, shard_id: int) -> Path:
        from hyperqueue_tpu.utils.serverdir import shard_path

        return shard_path(self.server_dir, shard_id)

    def start_shard(
        self, shard_id: int, shard_count: int, *extra: str, env_extra=None
    ) -> str:
        """Start one federation shard process; returns the process name
        (pass to kill_process). Waits for the shard's access record."""
        shard_dir = self.shard_dir(shard_id)
        before = {
            p.name for p in shard_dir.iterdir() if p.name.isdigit()
        } if shard_dir.exists() else set()
        n = sum(
            1 for name, _ in self.processes
            if name.startswith(f"shard{shard_id}-")
        )
        name = f"shard{shard_id}-{n}"
        process = self._spawn(
            name,
            ["server", "start", "--server-dir", str(self.server_dir),
             "--shards", str(shard_count), "--shard-id", str(shard_id),
             *extra],
            env_extra=env_extra,
        )

        def ready():
            if process.poll() is not None:
                return True
            if not shard_dir.exists():
                return False
            fresh = {
                p.name for p in shard_dir.iterdir() if p.name.isdigit()
            } - before
            return any(
                (shard_dir / d / "access.json").exists() for d in fresh
            )

        wait_until(ready, timeout=60.0, message=f"shard {shard_id} access")
        assert process.poll() is None, self.read_log(name)
        return name

    def start_standby(self, *extra: str, env_extra=None) -> str:
        """Start a warm standby (failover watcher + lending coordinator)
        over this env's federation root; returns the process name."""
        n = sum(
            1 for name, _ in self.processes if name.startswith("standby")
        )
        name = "standby" if n == 0 else f"standby{n}"
        self._spawn(
            name,
            ["server", "start", "--server-dir", str(self.server_dir),
             "--standby", *extra],
            env_extra=env_extra,
        )
        return name

    def start_worker(
        self, *extra: str, cpus: int | None = 4, env_extra=None
    ) -> subprocess.Popen:
        args = ["worker", "start", "--server-dir", str(self.server_dir)]
        if cpus is not None:
            args += ["--cpus", str(cpus)]
        args += list(extra)
        n = sum(1 for name, _ in self.processes if name.startswith("worker"))
        return self._spawn(f"worker{n}", args, env_extra=env_extra)

    def command(
        self, args: list[str], cwd=None, expect_fail=False, timeout=60.0,
        with_stderr=False,
    ) -> str:
        if _CLI_IN_PROCESS:
            result = _run_cli_inprocess(
                args, self.server_dir, cwd or self.work_dir, timeout
            )
        else:
            result = subprocess.run(
                [sys.executable, "-m", "hyperqueue_tpu", *args],
                env={**_env_base(), "HQ_SERVER_DIR": str(self.server_dir)},
                cwd=cwd or self.work_dir,
                capture_output=True,
                text=True,
                timeout=timeout,
            )
        if expect_fail:
            assert result.returncode != 0, (
                f"expected failure, got: {result.stdout}"
            )
        else:
            assert result.returncode == 0, (
                f"command {args} failed:\n{result.stdout}\n{result.stderr}"
            )
        if with_stderr:
            return result.stdout + result.stderr
        return result.stdout

    def read_log(self, name: str) -> str:
        path = self.tmp / f"{name}.log"
        return path.read_text() if path.exists() else "<no log>"

    def wait_workers(self, n: int, timeout=20.0):
        def check():
            out = self.command(["worker", "list", "--output-mode", "quiet"])
            return len([l for l in out.splitlines() if l.strip()]) >= n

        wait_until(check, timeout=timeout, message=f"{n} workers")

    def kill_process(self, name: str) -> None:
        for pname, process in self.processes:
            if pname == name and process.poll() is None:
                process.kill()
                process.wait()
                return
        raise KeyError(name)

    def close(self) -> None:
        for _, process in reversed(self.processes):
            if process.poll() is None:
                process.terminate()
        for _, process in self.processes:
            try:
                process.wait(timeout=5)
            except subprocess.TimeoutExpired:
                process.kill()
                process.wait()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
