"""Cells at tiny widths for the CPU tests: the committed configuration
and traffic files, shrunk, through the same loader, kinds and readers.
Nothing here is read by a benchmark run."""

from __future__ import annotations

import argparse
import copy
import json
import shutil
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
for p in (str(BENCH_DIR), str(BENCH_DIR.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

from lobench import loader, rest, runner  # noqa: E402

SMALL = {
    "bert-base-uncased": {
        "vocab_size": 64, "hidden_dim": 32, "num_layers": 2,
        "num_heads": 2, "mlp_dim": 64, "max_len": 16,
    },
    "gpt2-xl": {
        "vocab_size": 96, "hidden_dim": 32, "num_layers": 2,
        "num_heads": 2, "mlp_dim": 64, "max_len": 64,
    },
}
TRAFFIC = {
    "fit-s512": {
        "rows": 16, "seq": 16, "batch_size": 4, "overhead_s": 0.0,
        "epoch_s": 1.0, "trace_epochs": 1,
        # bf16 against f32 on the CPU reads 1e-5, 0.004, 0.003, 0.011 here
        "limits": {"grad_norm_gap": 0.05,
                   "update_norm_gap": 0.012, "update_turn_gap": 0.05,
                   "window_steps_gap": 0, "window_nonfinite": 0,
                   "window_step_size": 7.6},
    },
    "gen-decode": {
        "clients": 3, "shapes": 6, "kv_bucket": 32,
        "prompt": {"mean": 8, "sigma": 0.5, "min": 4},
        "output": {"mean": 14, "sigma": 0.5, "min": 2},
        "total": {"above": 16, "at_most": 32},
        "trace_seconds": 1, "sample_requests": 4,
        "limits": {"logit_gap": 0.005},  # float32 on the CPU reads 0.0
    },
}
def tiny_run(tmp: Path, workload: str, monkeypatch, *, seed: int = 7,
             seconds: float = 3.0, trace: int = 0,
             small: dict | None = None) -> runner.Run:
    """A :class:`runner.Run` of ``workload`` with its configuration and
    traffic shrunk; the configuration is written to ``tmp`` with its
    reference beside it, as the loader expects."""
    bench = copy.deepcopy(loader.benchmark())
    cell = loader.cell(workload, bench)
    for entry in bench["configs"]:
        src = loader.REPO / entry["file"]
        config = json.loads(src.read_text())
        config["class_parameters"].update(SMALL[entry["name"]])
        if entry["name"] == cell["config"]:
            config["class_parameters"].update(small or {})
        dst = tmp / src.name
        dst.write_text(json.dumps(config))
        shutil.copy(src.with_suffix(".py"), dst.with_suffix(".py"))
        entry["file"] = str(dst)
    real = loader.traffic

    def traffic(mix: str) -> dict:
        return {**real(mix), **TRAFFIC[mix]}

    monkeypatch.setattr(loader, "traffic", traffic)
    monkeypatch.setattr(rest, "SCRATCH_NAME", str(tmp / "bench_run"))
    args = argparse.Namespace(
        workload=cell["name"], seed=seed, seconds=seconds, trace=trace
    )
    return runner.Run(args, time.perf_counter(), bench)
