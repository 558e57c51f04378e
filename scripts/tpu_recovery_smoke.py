"""Recovery smoke drill: boot → submit → kill -9 → recover → assert
resumed.

The on-chip twin of tests/test_journal_recovery.py's kill-9 drill: an
orchestrator child process boots a ServiceContext over a scratch
store, submits a 6-epoch checkpointed train fit, and SIGKILLs ITSELF once the managed checkpoint tree
reaches step >= 2 (a seeded `train.epoch` delay guarantees the kill
lands mid-fit); a second child boots over the same store — journal
replay re-dispatches the fit through the checkpoint-resume path — and
reports the resumed run's epoch spans.  PASS means: jobState
`finished`, engine epoch 2, first resumed epoch >= 2 and strictly
fewer epoch spans than a from-scratch run.

Runs on whatever backend the environment provides — the
journal/recovery plane is backend-agnostic, the drill just proves it
against the real wiring.  One process per chip holds: this parent never
imports jax, and the two children run one after the other (the first is
dead by SIGKILL before the second boots), so each has the chip alone.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

_CHILD_ORCHESTRATOR = r"""
import json, os, signal, sys, time
import numpy as np
from learningorchestra_tpu import faults
from learningorchestra_tpu.config import Config
from learningorchestra_tpu.services.context import ServiceContext
from learningorchestra_tpu.services.executor import ExecutorService
from learningorchestra_tpu.services.model import ModelService

cfg = Config.from_env()
cfg.store.backend = "python"
ctx = ServiceContext(cfg)
model = ModelService(ctx)
ex = ExecutorService(ctx)
rng = np.random.default_rng(0)
x = rng.standard_normal((64, 8)).astype("float32")
y = (x.sum(1) > 0).astype("int32")
model.create(
    "m", module_path="learningorchestra_tpu.models.mlp",
    class_name="MLPClassifier",
    class_parameters={"hidden_layer_sizes": [8], "num_classes": 2},
)
ctx.engine.wait("m", timeout=300)
faults.arm("train.epoch", "delay", delay_ms=500, after=2)
ex.create(
    "fit1", parent_name="m", method="fit",
    method_parameters={
        "x": x.tolist(), "y": y.tolist(), "epochs": 6,
        "checkpoint_every": 1, "checkpoint_min_interval_s": 0,
        "checkpoint_async": False,
    },
    artifact_type="train/tensorflow",
)
marker = ctx.checkpoint_dir("fit1") / "latest.json"
deadline = time.time() + 300
while time.time() < deadline:
    try:
        if json.loads(marker.read_text()).get("step", 0) >= 2:
            break
    except (OSError, ValueError):
        pass
    time.sleep(0.02)
else:
    print("NO_CHECKPOINT", flush=True)
    sys.exit(3)
print("KILLING", flush=True)
os.kill(os.getpid(), signal.SIGKILL)
"""

_CHILD_RECOVERY = r"""
import json, time
from learningorchestra_tpu.config import Config
from learningorchestra_tpu.services.context import ServiceContext

cfg = Config.from_env()
cfg.store.backend = "python"
ctx = ServiceContext(cfg)
deadline = time.time() + 300
meta = {}
while time.time() < deadline:
    meta = ctx.artifacts.metadata.read("fit1") or {}
    if meta.get("finished") or meta.get("jobState") == "failed":
        break
    time.sleep(0.1)
hist = ctx.artifacts.ledger.history("fit1")
trace = next(
    (r.get("trace") for r in reversed(hist) if r.get("trace")), None
)
epochs = sorted(
    s["attrs"]["epoch"]
    for s in (trace or {}).get("spans", [])
    if s.get("name") == "epoch"
)
print("RESULT " + json.dumps({
    "jobState": meta.get("jobState"),
    "engineEpoch": meta.get("engineEpoch"),
    "epochs": epochs,
}), flush=True)
ctx.close()
"""


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="lo_recovery_smoke_")
    env = dict(os.environ)
    env.update({
        "LO_TPU_STORE_ROOT": os.path.join(tmp, "store"),
        "LO_TPU_VOLUME_ROOT": os.path.join(tmp, "vol"),
    })
    env.pop("LO_TPU_WITNESS", None)

    print("recovery-smoke: phase 1 — boot, submit, kill -9 mid-fit")
    first = subprocess.run(
        [sys.executable, "-c", _CHILD_ORCHESTRATOR],
        env=env, capture_output=True, text=True, timeout=540,
    )
    if first.returncode != -signal.SIGKILL:
        print(first.stdout[-4000:])
        print(first.stderr[-4000:])
        print(f"FAIL: orchestrator exited rc={first.returncode} "
              "(expected SIGKILL)")
        return 1
    t0 = time.time()
    print("recovery-smoke: phase 2 — restart, replay journal, resume")
    second = subprocess.run(
        [sys.executable, "-c", _CHILD_RECOVERY],
        env=env, capture_output=True, text=True, timeout=540,
    )
    if second.returncode != 0 or "RESULT " not in second.stdout:
        print(second.stdout[-4000:])
        print(second.stderr[-4000:])
        print(f"FAIL: recovery child rc={second.returncode}")
        return 1
    result = json.loads(
        second.stdout.split("RESULT ", 1)[1].splitlines()[0]
    )
    epochs = result.get("epochs") or []
    ok = (
        result.get("jobState") == "finished"
        and result.get("engineEpoch") == 2
        and epochs
        and min(epochs) >= 2
        and max(epochs) == 5
        and len(epochs) < 6
    )
    print(json.dumps({
        "recovery_smoke": result,
        "recover_wall_s": round(time.time() - t0, 1),
        "resumed_from_epoch": min(epochs) if epochs else None,
    }))
    if not ok:
        print(f"FAIL: {result}")
        return 1
    print("recovery-smoke: PASS — resumed from epoch "
          f"{min(epochs)}, finished under engine epoch 2")
    return 0


if __name__ == "__main__":
    sys.exit(main())
