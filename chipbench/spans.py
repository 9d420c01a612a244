"""Spans and counters the harness records from its own files, around the
calls into each layer of the program."""

from __future__ import annotations

import contextlib


def server_gc_settings() -> dict:
    """How `Server.start()` sets the collector outside the simulator, read
    from where the server sets it: the arguments of its `gc.set_threshold`
    call and whether it ends with `gc.freeze()`.  The program has no function
    for this that could be called without starting a server (PERF.md, Open
    questions), so the call is found in the source; a server that stops
    tuning the collector leaves this harness on the defaults too."""
    import ast
    import inspect
    import textwrap

    from hyperqueue_tpu.server.bootstrap import Server

    tree = ast.parse(textwrap.dedent(inspect.getsource(Server.start)))
    found = {"thresholds": None, "freeze": False}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "gc"):
            continue
        if node.func.attr == "set_threshold":
            found["thresholds"] = [ast.literal_eval(a) for a in node.args]
        elif node.func.attr == "freeze":
            found["freeze"] = True
    return found


def gc_as_server_starts(settings: dict) -> None:
    import gc

    if settings["thresholds"]:
        gc.set_threshold(*settings["thresholds"])


def gc_as_server_started(settings: dict) -> None:
    """End of start-up: what set-up allocated leaves the generations."""
    import gc

    if settings["freeze"]:
        gc.collect()
        gc.freeze()


class HostReading:
    """How steady the host was over a span, from the kernel's own counters:
    the process's CPU seconds and involuntary context switches, the
    machine's stolen time (/proc/stat) and the time some task waited for a
    CPU (/proc/pressure/cpu), and the time a fixed piece of interpreter work
    takes before and after the span.  A run that reads far off is explained
    by these or not at all.  Notes only, never a metric."""

    def __init__(self):
        self.spin_before_ms = self.spin_ms()
        self.start = self._now()

    @staticmethod
    def spin_ms() -> float:
        """Wall time of a fixed piece of interpreter work (a sum over a
        million integers): the speed of this core just now.  Taken outside
        the window, before it opens and after it has closed."""
        import time

        t = time.perf_counter()
        sum(range(1_000_000))
        return (time.perf_counter() - t) * 1e3

    @staticmethod
    def _now() -> dict:
        import os
        import resource
        import time

        usage = resource.getrusage(resource.RUSAGE_SELF)
        now = {"wall_s": time.perf_counter(), "cpu_s": time.process_time(),
               "involuntary_switches": usage.ru_nivcsw}
        try:
            with open("/proc/stat") as f:
                fields = f.readline().split()
            now["steal_s"] = int(fields[8]) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            pass
        try:
            with open("/proc/pressure/cpu") as f:
                some = f.readline()
            now["cpu_pressure_s"] = int(some.rsplit("total=", 1)[1]) / 1e6
        except (OSError, IndexError, ValueError):
            pass
        return now

    def delta(self) -> dict:
        end = self._now()
        read = {k: end[k] - v for k, v in self.start.items() if k in end}
        read["spin_ms_before_after"] = [self.spin_before_ms, self.spin_ms()]
        return read


def annotate(name: str):
    """A host span in the profiler's own trace (nothing when none runs)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:  # a rehearsal without jax
        return contextlib.nullcontext()
    return TraceAnnotation(name)


def annotated_model(model_cls):
    """`model_cls` with a span around the solve's dispatch and around the
    wait for its counts: the boundary between the tick and the solver.  Used
    in traced runs only."""

    class Handle:
        __slots__ = ("inner",)

        def __init__(self, inner):
            self.inner = inner

        def result(self):
            with annotate("chipbench/solve_wait"):
                return self.inner.result()

    class Annotated(model_cls):
        def solve_async(self, *args, **kwargs):
            with annotate("chipbench/solve_dispatch"):
                return Handle(super().solve_async(*args, **kwargs))

    return Annotated


class CompileLog:
    """Programs compiled or fetched from the persistent cache, from jax's own
    monitoring events: `count` must not move inside a measured window."""

    def __init__(self):
        self.count = 0
        self.seconds: dict[str, float] = {}
        self.cache = {"hits": 0, "misses": 0}

    def listen(self) -> "CompileLog":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            name = kw.get("fun_name", "?")
            self.seconds[name] = self.seconds.get(name, 0.0) + seconds

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1
