"""One run of one cell: find the cell's files by name, look for the
chip, hand over to the traffic kind, read the per-layer metrics, decide
``correct`` and print the result line."""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import shutil
import sys
import time

from lobench import loader, peaks, rest, trace


class Run:
    """What a traffic kind and the metric readers get."""

    def __init__(self, args, t0: float, bench: dict):
        self.t0 = t0  # time.perf_counter() when the process started
        self.bench = bench
        self.cell = loader.cell(args.workload, bench)
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.traced = bool(int(args.trace))
        self.config_path = loader.config_path(bench, self.cell["config"])
        self.config, self.reference = loader.config(self.config_path)
        self.cp = self.config["class_parameters"]
        self.traffic = loader.traffic(self.cell["traffic"])
        self.scratch = rest.scratch(loader.REPO)
        self.server = None
        self.setup_s: float | None = None
        self.laps: dict = {}  # set-up, part by part, in seconds
        self._lap_at = t0
        self.window_compiles = 0
        self.window_compile_events: list = []
        self._in_window = False
        self.device: dict = {}
        self.sample = None  # what ``correct`` compared, for the controls
        self.peaks: dict = {}

    # -- the device -------------------------------------------------------

    def look_for_chip(self) -> None:
        import jax

        dev = jax.devices()[0]
        if dev.platform != "tpu":
            raise SystemExit(
                f"jax found {dev.platform!r}, not a TPU: the benchmark "
                "measures nothing elsewhere"
            )
        if jax.device_count() < self.cell["chips"]:
            raise SystemExit(
                f"{self.cell['name']} needs {self.cell['chips']} chips, "
                f"jax found {jax.device_count()}"
            )
        self.describe_device()

    def describe_device(self) -> None:
        import jax

        dev = jax.devices()[0]
        self.peaks = peaks.peaks_for(dev.device_kind)
        self.device = {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count(),
        }

    def listen_for_compiles(self) -> None:
        """Count XLA compilations (and persistent-cache loads, which
        are compilations avoided, not absent) inside the window."""
        import jax

        def on_event(name: str, *_a, **_k) -> None:
            if self._in_window and (
                "backend_compile" in name or "cache_hits" in name
                or "cache_misses" in name
            ):
                self.window_compiles += 1
                self.window_compile_events.append(name)

        jax.monitoring.register_event_duration_secs_listener(on_event)
        jax.monitoring.register_event_listener(on_event)

    # -- the window -------------------------------------------------------

    def lap(self, name: str) -> None:
        """The part of set-up that ends here (the first starts with the
        process); they go to an earlier output line, not the result."""
        now = time.perf_counter()
        self.laps[name] = round(now - self._lap_at, 3)
        self._lap_at = now

    def open_window(self) -> None:
        # One full collection as set-up's last act (0.1 s on the chip
        # machine), so that none falls due inside the window.
        gc.collect()
        self.lap("collect")
        self.setup_s = time.perf_counter() - self.t0
        self._in_window = True

    def close_window(self) -> None:
        self._in_window = False

    @contextlib.contextmanager
    def maybe_trace(self):
        if not self.traced:
            yield None
            return
        with trace.capture(self.scratch / "trace") as cap:
            yield cap

    def note(self, **fields) -> None:
        """A line of its own on standard output, before the result."""
        print(json.dumps({"note": fields}, default=str), flush=True)

    def free_program(self) -> None:
        """Read the peak, then drop the server and what it holds on the
        device, so the reference has the chip to itself."""
        import jax

        # What the allocator holds for buffers, and beside it what the
        # runtime reserves for the loaded programs' scratch space: the
        # two pools are counted apart (PERF.md, PR 25).
        self.device["memory_peak_bytes"] = max(
            int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0))
            for stats in (
                d.memory_stats() or {}
                for d in jax.devices()[: self.cell["chips"]]
            )
        )
        if self.server is not None:
            self.server.shutdown()
            self.server = None
        gc.collect()
        jax.clear_caches()


def result_line(run: Run, record: dict) -> dict:
    group = "per_layer" if run.traced else "end_to_end"
    metrics: dict = {}
    for spec in loader.cell_metrics(run.bench, run.cell["name"], group):
        if group == "end_to_end":
            value = run.setup_s if spec["name"] == "setup_s" \
                else record["end_to_end"].get(spec["name"])
        else:
            value = loader.metric_reader(spec["name"])(record, run)
        if value is not None:
            metrics[spec["name"]] = {
                "value": float(value), "unit": spec["unit"],
            }
    compared = record.get("compared") or {}
    correct = bool(compared) and record["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in compared.values()
    )
    line = {
        "correct": correct,
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": metrics,
        "device": dict(run.device),
    }
    traced = record.get("trace")
    if traced:
        run.note(trace_modules={
            name: [len(runs), sum(runs) / len(runs)]
            for name, runs in traced["modules"].items()
        })
        line["device"]["busy_s"] = traced["busy_s"]
        line["device"]["window_s"] = traced["window_s"]
        line["breakdown"] = {
            "device_ops": traced["device_ops"],
            "idle_gaps": traced["idle_gaps"],
        }
    line["compared"] = compared
    return line


def execute(run: Run) -> dict:
    """Everything after the look for a chip (tests enter here)."""
    if not run.device:
        run.describe_device()
    run.listen_for_compiles()
    try:
        record = loader.kind(run.traffic["kind"]).run(run)
        record["window_compiles"] = run.window_compiles
        run.note(setup_s=run.setup_s, setup_split=run.laps)
        if run.window_compile_events:
            run.note(window_compile_events=run.window_compile_events[:20])
        line = result_line(run, record)
    finally:
        if run.server is not None:
            run.server.shutdown()
        shutil.rmtree(run.scratch, ignore_errors=True)
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    return line


def main(argv=None, t0: float | None = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    run = Run(args, t0, loader.benchmark())
    run.look_for_chip()
    line = execute(run)
    print(json.dumps(line), flush=True)
    return 0
