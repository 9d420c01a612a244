"""BENCHMARK.json and the files it names, resolved by name."""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
MANIFEST = ROOT / "BENCHMARK.json"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load() -> dict:
    return json.loads(MANIFEST.read_text())


def _by_name(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"chipbench: no {what} named {name!r} in {MANIFEST.name}")


def cell(name: str, manifest: dict | None = None) -> dict:
    """The cell's entry with its configuration and traffic files read."""
    manifest = manifest or load()
    workload = _by_name(manifest["workloads"], name, "workload")
    config = _by_name(manifest["configs"], workload["config"], "config")
    traffic_file = HERE / "traffic" / f"{workload['traffic']}.json"
    return {
        "name": name,
        "chips": workload["chips"],
        "why": workload["why"],
        "config": json.loads((ROOT / config["file"]).read_text()),
        "traffic": json.loads(traffic_file.read_text()),
        "end_to_end": [
            m for m in manifest["end_to_end"]
            if name in m.get("workloads", [name])
        ],
        "per_layer": [
            m for m in manifest["per_layer"]
            if name in m.get("workloads", [name])
        ],
    }


def load_by_path(path: Path, attr: str):
    """`attr` of the python file at `path` (metric names may hold dots, so
    the readers and drivers are loaded by path, not by import name)."""
    module_name = "chipbench._file_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(module_name, path)
    if spec is None or not path.exists():
        raise SystemExit(f"chipbench: {path} not found")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, attr)


def driver(name: str):
    return load_by_path(HERE / "drivers" / f"{name}.py", "run")


def metric_reader(name: str):
    return load_by_path(HERE / "metrics" / f"{name}.py", "read")


def reference(name: str):
    return load_by_path(HERE / "reference" / f"{name}.py", "Reference")
