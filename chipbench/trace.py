"""From a profiler trace to numbers: device busy and idle time, kernel time,
the operations that took most time, and the idle gaps by what the host was
doing in them.

`Capture` records an `.xplane.pb` with the JAX profiler; `read_xplane()`
turns it into the plain form below, with nothing but JAX; `reduce()` is
arithmetic on that form, checked by the selfcheck on the small recorded trace
in `testdata/`.

Plain form: {"planes": [{"name": str, "lines": [{"name": str,
"events": [[name, start_ns, duration_ns], ...]}]}]}.
"""

from __future__ import annotations

import glob
import re
import shutil
import tempfile
from pathlib import Path

import numpy as np

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench/"
# the harness puts this span around what it traces: the traced window
TRACED_SPAN = SPAN_PREFIX + "traced"
_FINGERPRINT = re.compile(r"\(\d+\)$")
# control flow: these events contain the events of their bodies, so they
# count towards busy time (a union) but are left out of the list of ops
_CONTAINERS = re.compile(r"^%(while|conditional|call)\b")


class Capture:
    """One profiler trace: start(), stop() inside the window (cheap), read()
    once the window has closed.  The file goes under TMPDIR and is deleted
    once read."""

    def __init__(self):
        self.directory = None
        self.bytes = 0

    def start(self) -> None:
        import jax

        self.directory = tempfile.mkdtemp(prefix="chipbench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def read(self) -> dict | None:
        try:
            files = glob.glob(f"{self.directory}/plugins/profile/*/*.xplane.pb")
            if not files:
                return None
            self.bytes = Path(files[0]).stat().st_size
            return read_xplane(files[0])
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)


def _short(name: str) -> str:
    """A stable name: an op's HLO instruction name without its text
    (`%reduce-window.18`), a module's name without its fingerprint."""
    name = name.split(" = ", 1)[0]
    return _FINGERPRINT.sub("", name)


def read_xplane(path) -> dict:
    """Device planes whole; of the host planes only the harness's spans."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        lines = []
        for line in plane.lines:
            events = [
                [_short(e.name) if device else e.name,
                 float(e.start_ns), float(e.duration_ns)]
                for e in line.events
                if device or e.name.startswith(SPAN_PREFIX)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(starts, ends):
    """Union of [start, end) intervals as two sorted arrays."""
    order = np.argsort(starts, kind="stable")
    starts, ends = starts[order], ends[order]
    reach = np.maximum.accumulate(ends)
    first = np.ones(len(starts), dtype=bool)
    first[1:] = starts[1:] > reach[:-1]
    idx = np.nonzero(first)[0]
    return starts[idx], reach[np.append(idx[1:] - 1, len(starts) - 1)]


def _busy_before(t, starts, ends):
    """Busy seconds (of the union) before each instant of `t`, in ns."""
    total = np.concatenate([[0.0], np.cumsum(ends - starts)])
    k = np.searchsorted(starts, t, side="right")
    inside = np.where(k > 0, np.minimum(t, ends[np.maximum(k - 1, 0)])
                      - starts[np.maximum(k - 1, 0)], 0.0)
    return total[np.maximum(k - 1, 0)] * (k > 0) + np.maximum(inside, 0.0)


def _innermost(spans: list, lo: float, hi: float) -> list:
    """[(name, start, end)] segments of [lo, hi): at each instant the
    shortest harness span that covers it."""
    cuts = sorted({lo, hi, *(t for _n, s, e in spans for t in (s, e)
                             if lo < t < hi)})
    by_length = sorted(spans, key=lambda x: x[2] - x[1])
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        name = next((n for n, s, e in by_length if s <= mid < e), "no span")
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1][2] = b
        else:
            out.append([name, a, b])
    return out


def _line(plane: dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def reduce(trace: dict, kernel_module: str | None = None,
           window_ns: tuple | None = None) -> dict | None:
    """The numbers the per-layer readers and the result line take.  None
    when the trace holds no device plane.  `window_ns` clips to a span of the
    trace's own clock (by default the harness's `chipbench/traced` span,
    else first to last device event).  An idle gap
    is charged to the innermost harness span that the host was in."""
    devices = [p for p in trace["planes"]
               if p["name"].startswith(DEVICE_PLANE_PREFIX)]
    if not devices:
        return None
    spans = [
        (name, start, start + dur)
        for p in trace["planes"] if not p["name"].startswith(DEVICE_PLANE_PREFIX)
        for line in p["lines"] for name, start, dur in line["events"]
        if name.startswith(SPAN_PREFIX)
    ]
    lo = min(e[1] for p in devices for ln in p["lines"] for e in ln["events"])
    hi = max(e[1] + e[2] for p in devices for ln in p["lines"]
             for e in ln["events"])
    traced = [s for s in spans if s[0] == TRACED_SPAN]
    if window_ns is None and traced:
        window_ns = (traced[0][1], traced[-1][2])
    if window_ns is not None:
        lo, hi = max(lo, window_ns[0]), min(hi, window_ns[1])
    spans = [s for s in spans if s[0] != TRACED_SPAN]
    segments = _innermost(spans, lo, hi)
    seg_a = np.asarray([a for _n, a, _b in segments])
    seg_b = np.asarray([b for _n, _a, b in segments])
    per_device = []
    op_seconds: dict[str, float] = {}
    gap_seconds: dict[str, float] = {}
    kernel_ns, kernel_calls = 0.0, 0
    for plane in devices:
        ops = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
        if ops is None or not ops["events"]:
            continue
        names = [e[0] for e in ops["events"]]
        starts = np.asarray([e[1] for e in ops["events"]], dtype=np.float64)
        ends = starts + np.asarray([e[2] for e in ops["events"]])
        starts, ends = np.clip(starts, lo, hi), np.clip(ends, lo, hi)
        for name, seconds in zip(names, ((ends - starts) / 1e9).tolist()):
            if seconds > 0 and not _CONTAINERS.match(name):
                op_seconds[name] = op_seconds.get(name, 0.0) + seconds
        u_starts, u_ends = _union(starts, ends)
        per_device.append(float((u_ends - u_starts).sum()) / 1e9)
        idle = ((seg_b - seg_a)
                - (_busy_before(seg_b, u_starts, u_ends)
                   - _busy_before(seg_a, u_starts, u_ends))) / 1e9
        for (name, _a, _b), seconds in zip(segments, idle.tolist()):
            gap_seconds[name] = gap_seconds.get(name, 0.0) + max(seconds, 0.0)
        modules = _line(plane, MODULES_LINE)
        if modules is not None and kernel_module:
            for name, start, dur in modules["events"]:
                if kernel_module in name and lo <= start and start + dur <= hi:
                    kernel_ns += dur
                    kernel_calls += 1
    if not per_device:
        return None
    n = len(per_device)

    def top(d):
        return sorted(([k, v / n] for k, v in d.items() if v > 0),
                      key=lambda kv: -kv[1])[:10]

    return {
        "busy_s": sum(per_device) / n,
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "kernel_calls": kernel_calls,
        "kernel_s": kernel_ns / 1e9,
        "device_ops": top(op_seconds),
        "idle_gaps": top(gap_seconds),
    }


def idle_pct(reduced: dict | None):
    """1 - busy over the traced window, %; nothing without a window."""
    if not reduced or not reduced["window_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
