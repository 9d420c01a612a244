"""Run one cell of BENCHMARK.json once, in this process, on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: one JSON object with
`correct`, `attempted`, `failed`, `metrics`, `device` (with `--trace 1` also
`breakdown`) and, last, `checks`: every number compared beside its limit.
The same comparisons are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and prints no
result.

`--rehearse` (tests and the selfcheck) runs the same code on the host
backends at the size given by `--scale`, and prints no metric at all.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import manifest, spans, trace  # noqa: E402


def say(record: dict) -> None:
    print(json.dumps(record, default=str), flush=True)


class Context:
    """What a driver is given, and the hooks by which the harness times the
    set-up and traces a part of the window."""

    def __init__(self, cell, seed, seconds, traced, rehearse, scale):
        self.cell, self.seed, self.seconds = cell, seed, float(seconds)
        self.trace, self.rehearse, self.scale = traced, rehearse, scale
        self.compiles = spans.CompileLog()
        self.setup_s = None
        self.device = None
        self._capture = None
        self._traced_span = None
        self._trace_until = 0.0
        self.trace_plain = None
        self.trace_bytes = 0

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T0

    def window_opens(self, t_start: float) -> None:
        if not self.trace or self.rehearse:
            return
        self._capture = trace.Capture()
        self._capture.start()
        # a trace shorter than the window is stopped inside it (the stop
        # takes seconds, which per-tick readings do not see); one that
        # covers the window is stopped once the driver has read its totals
        traced_s = float(self.cell["traffic"]["trace_seconds"])
        self._trace_until = (time.perf_counter() + traced_s
                             if traced_s < self.seconds else float("inf"))
        self._traced_span = spans.annotate(trace.TRACED_SPAN)
        self._traced_span.__enter__()

    def window_tick(self) -> None:
        if self._traced_span is not None and \
                time.perf_counter() >= self._trace_until:
            self._stop_trace()

    def window_closed(self) -> None:
        if self._traced_span is not None:
            self._stop_trace()
        if self._capture is not None:
            self.trace_plain = self._capture.read()
            self.trace_bytes = self._capture.bytes

    def _stop_trace(self) -> None:
        self._traced_span.__exit__(None, None, None)
        self._traced_span = None
        self._capture.stop()

    def memory_peak(self):
        if self.device is None:
            return None
        stats = self.device.memory_stats() or {}
        return stats.get("peak_bytes_in_use")


def find_chips(n_chips: int):
    """The devices as JAX reports them; exits non-zero without a TPU."""
    import jax

    from hyperqueue_tpu.utils.jaxdev import configure_compile_cache

    cache_dir = configure_compile_cache()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chipbench: no accelerator: {e}")
    if devices[0].platform != "tpu" or len(devices) < n_chips:
        raise SystemExit(
            f"chipbench: needs {n_chips} TPU chip(s), found "
            f"{len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind})"
        )
    return devices, cache_dir


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--scale", type=json.loads, default=None,
                        help="JSON sizes for a rehearsal")
    args = parser.parse_args(argv)

    cell = manifest.cell(args.workload)
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace),
                  args.rehearse, args.scale)
    run = manifest.driver(cell["traffic"]["driver"])
    device_block = None
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    else:
        devices, cache_dir = find_chips(cell["chips"])
        ctx.device = devices[0]
        ctx.compiles.listen()
        device_block = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
        say({"note": "start", "workload": cell["name"], "seed": args.seed,
             "device": device_block, "compile_cache_dir": cache_dir})

    outcome = run(ctx)

    checks = {name: {"value": value, "limit": limit}
              for name, value, limit in outcome["checks"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    notes = {"note": "run", "workload": cell["name"], "seed": args.seed,
             "setup_s": ctx.setup_s, "window_s": outcome["window_s"],
             "device": device_block, **outcome["notes"],
             "compile": {"seconds": ctx.compiles.seconds,
                         "cache": ctx.compiles.cache}}
    if args.rehearse:
        say({"rehearsal": True, **notes})
        say({"rehearsal": True, "correct": correct,
             "attempted": outcome["attempted"], "failed": outcome["failed"],
             "checks": checks})
        return 0

    say(notes)
    result = {"correct": correct, "attempted": outcome["attempted"],
              "failed": outcome["failed"]}
    units = {m["name"]: m["unit"] for m in cell["end_to_end"] + cell["per_layer"]}
    device_block["memory_peak_bytes"] = outcome["memory_peak_bytes"]
    if not args.trace:
        values = {**outcome["end_to_end"], "setup_s": ctx.setup_s}
        names = [m["name"] for m in cell["end_to_end"]]
    else:
        observed = outcome["observed"]
        observed["device_kind"] = device_block["kind"]
        reduced = None
        if ctx.trace_plain is not None:
            reduced = trace.reduce(ctx.trace_plain,
                                   observed.get("kernel_module"))
        observed["trace"] = reduced
        if reduced is None:
            raise SystemExit("chipbench: the trace holds no device operation")
        device_block["busy_s"] = reduced["busy_s"]
        device_block["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        say({"note": "trace", "device": device_block,
             "trace_bytes": ctx.trace_bytes,
             **{k: reduced[k] for k in ("kernel_calls", "kernel_s", "devices")}})
        values = {}
        for m in cell["per_layer"]:
            value = manifest.metric_reader(m["name"])(observed)
            if value is not None:
                values[m["name"]] = value
        names = list(values)
    result["metrics"] = {
        name: {"value": values[name], "unit": units[name]} for name in names
    }
    result["device"] = device_block
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']}) on "
              f"{device_block['kind']} x{device_block['count']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
