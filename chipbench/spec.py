"""The benchmark's files, found by the names in BENCHMARK.json."""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _load(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    return json.loads(path.read_text())


def cell(name: str, root: Path = ROOT) -> Dict[str, dict]:
    """Everything one cell runs with: its BENCHMARK.json entry, the
    configuration and traffic files it names, its limits, and the
    metrics it reports."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    return {
        "workload": w,
        "config": _load(root / conf["file"]),
        "traffic": _load(HERE / "traffic" / f"{w['traffic']}.json"),
        "limits": _load(HERE / "workloads" / f"{name}.json")["limits"],
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def metric_reader(metric: str) -> Path:
    return HERE / "metrics" / f"{metric}.py"


def driver_file(driver: str) -> Path:
    return HERE / "drivers" / f"{driver}.py"
