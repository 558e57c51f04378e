"""``BENCHMARK.json`` against the contract's limits, and every name in
it against the files the loader finds by that name."""

import json
import re

import tiny  # noqa: F401 — puts the benchmark on sys.path
from lobench import loader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_names_and_units():
    bench = loader.benchmark()
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= bench["run_seconds"] <= 51
    assert len((loader.REPO / "BENCHMARK.json").read_bytes()) < 64 * 1024
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in bench["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound",
                               "source", "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_cells_find_their_files():
    bench = loader.benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for work in bench["workloads"]:
        assert set(work) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(work["traffic"]) and work["chips"] in (1, 4)
        assert len(work["why"]) <= 200
        path = loader.config_path(bench, work["config"])
        config, reference = loader.config(path)
        assert hasattr(reference, "leaves")
        assert hasattr(reference, "program_params")
        assert config["reduced"] == []
        traffic = loader.traffic(work["traffic"])
        assert hasattr(loader.kind(traffic["kind"]), "run")
        reported = loader.cell_metrics(bench, work["name"], "end_to_end")
        assert len(reported) >= 2 and "setup_s" in {
            m["name"] for m in reported
        }
        assert loader.cell_metrics(bench, work["name"], "per_layer")
    for metric in bench["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert callable(loader.metric_reader(metric["name"]))
        assert set(metric["workloads"]) <= cells
        moved = e2e[metric["moves"]]
        assert set(metric["workloads"]) <= set(
            moved.get("workloads", cells)
        )


def test_config_files_state_what_is_run():
    bench = loader.benchmark()
    for entry in bench["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        config = json.loads((loader.REPO / entry["file"]).read_text())
        assert config["source"] == entry["source"]
        assert config["assumed"] and config["precision"]
