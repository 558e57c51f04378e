"""Finds what belongs to a cell by the names in ``BENCHMARK.json``:
``configs/<config>.json`` with its reference ``configs/<config>.py``
beside it, ``traffic/<mix>.json``, ``lobench/kinds/<kind>.py`` and
``metrics/<name>.py``.  Adding a cell, a configuration, a mix or a
per-layer metric is adding files and entries; nothing here names one."""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
REPO = BENCH_DIR.parent


def _module_from(path: Path):
    name = "lobench_file_" + "".join(
        c if c.isalnum() else "_" for c in path.stem
    ) + "_" + path.parent.name
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def cell(name: str, bench: dict | None = None) -> dict:
    bench = bench or benchmark()
    for entry in bench["workloads"]:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config_path(bench: dict, config_name: str) -> Path:
    for entry in bench["configs"]:
        if entry["name"] == config_name:
            return REPO / entry["file"]
    raise SystemExit(f"no config {config_name!r} in BENCHMARK.json")


def config(path: Path):
    """(the configuration as it is run, its reference module)."""
    return json.loads(path.read_text()), _module_from(
        path.with_suffix(".py")
    )


def traffic(mix: str) -> dict:
    return json.loads((BENCH_DIR / "traffic" / f"{mix}.json").read_text())


def kind(name: str):
    return importlib.import_module(f"lobench.kinds.{name}")


def metric_reader(name: str):
    return _module_from(BENCH_DIR / "metrics" / f"{name}.py").read


def cell_metrics(bench: dict, cell_name: str, group: str) -> list:
    """The metrics of ``group`` that ``cell_name`` reports: those that
    list it, and those that list no cells at all."""
    return [
        m for m in bench[group]
        if "workloads" not in m or cell_name in m["workloads"]
    ]
