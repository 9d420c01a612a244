"""`python -m chipbench.selfcheck` — the benchmark checks itself, on the CPU.

No chip, no timing, and no metric is printed: the manifest keeps to the
contract's names, units and limits; every cell's files resolve by name; the
generator gives the same world for the same seed and the same sizes for every
seed; the trace reduction gives the known answer on the recorded trace in
`testdata/`; an unknown device has no peaks; and every cell's driver runs a tiny size
end to end on the host backends, correct.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from chipbench import generate, kernel_cost, manifest, trace  # noqa: E402

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
TINY = {
    "tick": {"workers": 48, "ready_tasks": 12000, "settle": [[8, 0.01]]},
    "sim": {"workers": 24, "tasks": 50000},
}


class Checks:
    def __init__(self):
        self.done = 0
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> None:
        self.done += 1
        if not ok:
            self.failed.append(what)


def _line(text) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def check_manifest(check: Checks) -> dict:
    raw = manifest.MANIFEST.read_bytes()
    check(len(raw) <= 64 * 1024, "BENCHMARK.json is at most 64 KiB")
    m = json.loads(raw)
    check(set(m) == TOP_KEYS, f"top-level keys are {sorted(TOP_KEYS)}")
    check(isinstance(m["command"], list) and 1 <= len(m["command"]) <= 32
          and all(_line(w) for w in m["command"]), "command is a short list")
    paths = m["paths"]
    check(1 <= len(paths) <= 16, "1 to 16 paths")
    for word in m["command"]:
        check(not word.startswith("/") and ".." not in Path(word).parts,
              f"command word {word!r} stays inside the repo")
        if (manifest.ROOT / word).exists():
            check(any(Path(word).parts[0] == p for p in paths),
                  f"command file {word!r} lies under paths")
    rs = m["run_seconds"]
    check(isinstance(rs, int) and 1 <= rs <= 51, "run_seconds is 1..51")
    check((2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200,
          "a full check of 24 cells fits its time")

    names = [c["name"] for c in m["configs"]]
    check(len(set(names)) == len(names) and 1 <= len(names) <= 24,
          "1 to 24 configurations, no name twice")
    files = [c["file"] for c in m["configs"]]
    check(len(set(files)) == len(files), "no configuration file twice")
    for c in m["configs"]:
        check(set(c) == CONFIG_KEYS, f"config {c.get('name')}: exact keys")
        check(bool(manifest.NAME_RE.match(c["name"])), f"config name {c['name']}")
        check(_line(c["source"]) and _line(c["why"]),
              f"config {c['name']}: source and why are one short line")
        check(any(Path(c["file"]).parts[0] == p for p in paths)
              and (manifest.ROOT / c["file"]).is_file(),
              f"config {c['name']}: file under paths")
        check(len(c["reduced"]) <= 16
              and all(manifest.NAME_RE.match(k) for k in c["reduced"]),
              f"config {c['name']}: reduced keys are names")
        data = json.loads((manifest.ROOT / c["file"]).read_text())
        check(all(k in data for k in c["reduced"]),
              f"config {c['name']}: every reduced key is in its file")
        check(any(w["config"] == c["name"] for w in m["workloads"]),
              f"config {c['name']} is used by a cell")

    cells = m["workloads"]
    check(1 <= len(cells) <= 24, "1 to 24 cells")
    check(len({w["name"] for w in cells}) == len(cells), "no cell name twice")
    check(len({(w["config"], w["traffic"]) for w in cells}) == len(cells),
          "no (config, traffic) pair twice")
    check(sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2),
          "at most half of the cells take four chips")
    for w in cells:
        check(set(w) == WORKLOAD_KEYS, f"cell {w.get('name')}: exact keys")
        check(all(manifest.NAME_RE.match(w[k])
                  for k in ("name", "config", "traffic")),
              f"cell {w['name']}: names")
        check(w["chips"] in (1, 4) and _line(w["why"]),
              f"cell {w['name']}: chips and why")
        check(w["config"] in names, f"cell {w['name']}: its config exists")

    cell_names = {w["name"] for w in cells}
    metric_names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    check(len(set(metric_names)) == len(metric_names), "no metric name twice")
    check(1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128,
          "metric counts")
    check("setup_s" in metric_names, "setup_s is an end-to-end metric")
    for x in m["end_to_end"] + m["per_layer"]:
        e2e = x in m["end_to_end"]
        allowed = (E2E_KEYS if e2e else LAYER_KEYS) | {"workloads"}
        needed = E2E_KEYS if e2e else LAYER_KEYS
        check(needed <= set(x) <= allowed, f"metric {x.get('name')}: keys")
        check(bool(manifest.NAME_RE.match(x["name"]))
              and bool(manifest.UNIT_RE.match(x["unit"]))
              and x["better"] in ("lower", "higher")
              and x["source"] in manifest.SOURCES,
              f"metric {x['name']}: name, unit, better, source")
        check(set(x.get("workloads", cell_names)) <= cell_names,
              f"metric {x['name']}: its cells exist")
        if e2e:
            check(x["source"] in ("host_clock", "device_trace")
                  and 0.01 <= x["bound"] <= 0.25,
                  f"metric {x['name']}: source and bound")
        else:
            check(_line(x["layer"]), f"metric {x['name']}: layer")
            moved = [e for e in m["end_to_end"] if e["name"] == x["moves"]]
            check(len(moved) == 1, f"metric {x['name']}: moves one metric")
            if moved:
                reported = set(moved[0].get("workloads", cell_names))
                check(set(x.get("workloads", reported)) <= reported,
                      f"metric {x['name']}: its cells report what it moves")
            if x["name"].endswith("_roofline"):
                check(x["unit"] == "%", f"metric {x['name']}: unit %")
    for w in cells:
        mine = lambda xs: [x for x in xs  # noqa: E731
                           if w["name"] in x.get("workloads", cell_names)]
        check(len(mine(m["end_to_end"])) >= 2 and len(mine(m["per_layer"])) >= 1,
              f"cell {w['name']}: setup_s, one more metric, one per layer")
    return m


def check_files(check: Checks, m: dict) -> None:
    for w in m["workloads"]:
        cell = manifest.cell(w["name"], m)
        driver = cell["traffic"].get("driver")
        check((manifest.HERE / "drivers" / f"{driver}.py").is_file(),
              f"cell {w['name']}: driver {driver!r} exists")
        check((manifest.HERE / "reference"
               / f"{cell['config'].get('reference')}.py").is_file(),
              f"cell {w['name']}: its plain reference exists")
        check("guarantees" in cell["config"],
              f"cell {w['name']}: the configuration states its guarantees")
        for metric in cell["per_layer"]:
            path = manifest.HERE / "metrics" / f"{metric['name']}.py"
            check(path.is_file(), f"metric {metric['name']}: reader exists")
            if path.is_file():
                check(manifest.metric_reader(metric["name"])({}) is None,
                      f"metric {metric['name']}: nothing to read, no value")
    for path in sorted(manifest.HERE.rglob("*")):
        rel = path.relative_to(manifest.ROOT).as_posix()
        if "__pycache__" in rel or rel.endswith(".pyc"):
            continue
        check(all(ch.isalnum() or ch in "_.-/" for ch in rel),
              f"file name {rel!r} keeps to the allowed characters")


def check_generator(check: Checks, m: dict) -> None:
    for w in m["workloads"]:
        cell = manifest.cell(w["name"], m)
        if cell["traffic"]["driver"] != "tick":
            continue
        scale = {"workers": 32, "ready_tasks": 4000}
        a = generate.world(cell["config"], cell["traffic"], 2**31 + 5, scale)
        b = generate.world(cell["config"], cell["traffic"], 2**31 + 5, scale)
        c = generate.world(cell["config"], cell["traffic"], 7, scale)
        same = all(np.array_equal(getattr(a, f), getattr(b, f)) for f in (
            "worker_total", "class_needs", "task_class", "task_prio"))
        check(same, f"cell {w['name']}: same seed, same world")
        check(not np.array_equal(a.task_class, c.task_class),
              f"cell {w['name']}: another seed, another world")
        check(generate.shape_signature(a) == generate.shape_signature(c),
              f"cell {w['name']}: every seed has the same sizes")


def _busy_by_sweep(events, lo, hi) -> float:
    """Busy time of a list of [name, start, dur] by counting open intervals
    at every endpoint: a second way to the union."""
    points = []
    for _name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    busy, depth, last = 0.0, 0, None
    for t, step in sorted(points, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy / 1e9


def check_trace(check: Checks) -> None:
    toy = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["a", 100, 50], ["b", 120, 100], ["a", 300, 100]]},
            {"name": "XLA Modules", "events": [
                ["jit_k", 100, 120], ["jit_k", 300, 100]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["chipbench/x", 0, 260], ["chipbench/y", 230, 20],
            ["chipbench/z", 400, 100]]}]},
    ]}
    got = trace.reduce(toy, "jit_k")
    close = lambda a, b: abs(a - b) <= 1e-12 + 1e-9 * abs(b)  # noqa: E731
    check(close(got["busy_s"], 220e-9) and close(got["window_s"], 300e-9)
          and got["kernel_calls"] == 2 and close(got["kernel_s"], 220e-9),
          "trace, by hand: busy 220 of 300 ns, two kernel calls of 220 ns")
    gaps = dict(got["idle_gaps"])
    check(close(gaps.get("no span", 0), 40e-9)
          and close(gaps.get("chipbench/x", 0), 20e-9)
          and close(gaps.get("chipbench/y", 0), 20e-9),
          "trace, by hand: 80 idle ns charged to the innermost spans")
    check(trace.reduce({"planes": toy["planes"][1:]}) is None,
          "trace without a device plane: nothing to read")

    recorded = json.loads(
        (manifest.HERE / "testdata" / "tick_trace_cut.json").read_text())
    want = json.loads(
        (manifest.HERE / "testdata" / "tick_trace_cut.expected.json").read_text())
    got = trace.reduce(recorded, want["kernel_module"])
    check(got is not None and all(
        close(got[k], want[k]) for k in ("busy_s", "window_s", "kernel_s"))
        and got["kernel_calls"] == want["kernel_calls"],
        "recorded trace: the known busy time, window and kernel time")
    device = next(p for p in recorded["planes"]
                  if p["name"].startswith(trace.DEVICE_PLANE_PREFIX))
    ops = next(ln for ln in device["lines"] if ln["name"] == trace.OPS_LINE)
    spans = [e for p in recorded["planes"] if p is not device
             for ln in p["lines"] for e in ln["events"]
             if e[0] == trace.TRACED_SPAN]
    lo, hi = spans[0][1], spans[0][1] + spans[0][2]
    lo = max(lo, min(e[1] for ln in device["lines"] for e in ln["events"]))
    hi = min(hi, max(e[1] + e[2] for ln in device["lines"]
                     for e in ln["events"]))
    check(close(_busy_by_sweep(ops["events"], lo, hi), got["busy_s"]),
          "recorded trace: a sweep over the endpoints finds the same busy time")
    total_idle = sum(v for _k, v in got["idle_gaps"])
    check(total_idle <= got["window_s"] - got["busy_s"] + 1e-9,
          "recorded trace: the gaps listed are no more than the idle time")


def check_peaks(check: Checks) -> None:
    try:
        kernel_cost.peaks("no such chip")
        check(False, "an unknown device has no peaks")
    except KeyError:
        check(True, "an unknown device has no peaks")
    cost = kernel_cost.cut_scan_cost(B=256, V=2, W=1024, R=3)
    seconds, bound = kernel_cost.least_seconds(cost, "TPU v5 lite")
    check(cost["ops"] == 256 * 2 * 1024 * 19 and bound == "bytes"
          and 1e-6 < seconds < 1e-5, "the cut scan's cost at the cell's size")


def check_drivers(check: Checks, m: dict) -> None:
    from chipbench import run as run_py

    for w in m["workloads"]:
        cell = manifest.cell(w["name"], m)
        out = io.StringIO()
        try:
            with contextlib.redirect_stdout(out):
                run_py.main([
                    "--workload", w["name"], "--seed", str(2**31 + 11),
                    "--seconds", "1", "--rehearse", "--scale",
                    json.dumps(TINY[cell["traffic"]["driver"]]),
                ])
            line = json.loads(out.getvalue().strip().splitlines()[-1])
        except (SystemExit, Exception) as e:  # noqa: BLE001 - reported
            check(False, f"cell {w['name']}: the rehearsal ended with {e!r}")
            continue
        check(line.get("correct") is True and line.get("attempted", 0) > 0
              and line.get("failed") == 0 and "metrics" not in line,
              f"cell {w['name']}: tiny rehearsal correct, no metric printed")


def main() -> int:
    check = Checks()
    m = check_manifest(check)
    check_files(check, m)
    check_generator(check, m)
    check_trace(check)
    check_peaks(check)
    check_drivers(check, m)
    for what in check.failed:
        print(f"selfcheck: FAILED {what}")
    print(f"selfcheck: {check.done - len(check.failed)} of {check.done} "
          "checks passed")
    return 1 if check.failed else 0


if __name__ == "__main__":
    sys.exit(main())
