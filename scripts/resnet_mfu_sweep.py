"""ResNet-50 MFU sweep on chip (round 2 measured 14.0% at bs 64, in
f32; ROADMAP S5).  Kept as the grid for S1's benchmark to absorb.

Sweeps (batch, remat) over the bf16 ResNet-50 train step at 224x224 and
prints samples/s + MFU per point.  One process, on the chip:

    chiprun -- env PYTHONPATH=. python scripts/resnet_mfu_sweep.py

Timing uses the fused-epoch methodology: K vs 3K epochs in single
dispatches, differenced, so the per-call dispatch cost cancels.
remat=True trades ~1 forward of FLOPs for O(blocks) less
activation HBM — the knob that unlocks bs >= 256 at 224x224.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

assert jax.devices()[0].platform == "tpu", jax.devices()
print("device:", jax.devices()[0], flush=True)

from bench import (  # noqa: E402 — repo root on PYTHONPATH
    _fused_throughput,
    _model_flops_per_sample,
    _peak_flops,
)
from learningorchestra_tpu.models.vision import ResNet50  # noqa: E402

PEAK = _peak_flops(jax.devices()[0].device_kind)
rng = np.random.default_rng(0)

_p = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
assert float(jnp.sum(jax.jit(lambda a: a @ a)(_p))) != 0
print("probe matmul ok; sweep next", flush=True)

# (bs, remat, s2d_stem): the s2d points measure the stem prediction
# (never measured) — the classic conv7×7 stem wastes >90% of the MXU lanes
# on C_in=3; space-to-depth folds it into a ≥128-deep contraction.
GRID = [
    (64, False, False), (128, False, False), (128, False, True),
    (128, True, False), (256, True, False), (256, True, True),
    (512, True, False),
]

results = []
for bs, remat, s2d in GRID:
    n = 2 * bs
    x = rng.standard_normal((n, 224, 224, 3)).astype(np.float32)
    y = rng.integers(0, 1000, (n,), dtype=np.int32)
    est = ResNet50(remat=remat, s2d_stem=s2d)
    est._init_params(jnp.asarray(x[:1]))
    per_sample = _model_flops_per_sample(est, jnp.asarray(x[:1]))
    try:
        t0 = time.perf_counter()
        thr = _fused_throughput(est, x, y, bs, k=2)
        wall = time.perf_counter() - t0
    except Exception as exc:  # noqa: BLE001 — OOM points just report
        print(f"bs={bs} remat={remat} s2d={s2d}: FAILED {exc!r}",
              flush=True)
        continue
    mfu = thr * per_sample / PEAK
    row = {
        "bs": bs, "remat": remat, "s2d_stem": s2d,
        "samples_per_sec": round(thr, 1), "mfu": round(mfu, 4),
        "wall_s": round(wall, 1),
    }
    results.append(row)
    print(json.dumps(row), flush=True)

best = max(results, key=lambda r: r["mfu"], default=None)
print("BEST:", json.dumps(best), flush=True)
