"""BERT-base MFU sweep on chip (round 2 measured 27.4% at seq 128
bs 32; ROADMAP S4).  Kept as the grid for S1's benchmark to absorb.

Sweeps (batch, seq, remat, flash) over the bf16 BertModel train step
and prints samples/s + MFU per point.  One process, on the chip:

    chiprun -- env PYTHONPATH=. python scripts/bert_mfu_sweep.py

All timing uses the looped methodology: K vs 3K fused epochs in single
dispatches, differenced, so the per-call dispatch cost cancels.
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
from learningorchestra_tpu.models.text import BertModel  # noqa: E402

PEAK = _peak_flops(jax.devices()[0].device_kind)
rng = np.random.default_rng(0)

_p = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
assert float(jnp.sum(jax.jit(lambda a: a @ a)(_p))) != 0
print("probe matmul ok; lowering HLO check next", flush=True)

# One-time: prove the TRAIN path really lowers to the Pallas flash
# kernel on chip (not mha_reference) — Mosaic kernels appear as
# tpu_custom_call in the HLO.
_est = BertModel(max_len=128, num_layers=1)
_tok = jnp.asarray(rng.integers(0, 30522, (1, 128), dtype=np.int32))
_est._init_params(_tok)
_hlo = jax.jit(_est.module.apply).lower(_est.params, _tok).as_text()
print(json.dumps({
    "check": "flash_in_train_path",
    "tpu_custom_call": "tpu_custom_call" in _hlo or "CustomCall" in _hlo,
}), flush=True)

# (seq, bs) grid: seq 128 is the BASELINE config-4 shape; 512 is where
# the flash kernel pays off in-model.  bs rows chosen to bracket the
# HBM limit of one v5e chip for BERT-base + adam.
GRID = [
    (128, 16), (128, 32), (128, 64), (128, 128), (128, 256),
    (512, 8), (512, 16), (512, 32),
]
# At seq 128 the flash kernel's tiling overhead can lose to XLA's own
# fused attention — measure the use_flash=False point where it might:
# picking the faster attention per shape is a legitimate MFU lever.
FLASH_OFF_POINTS = {(128, 32), (128, 64), (128, 128), (128, 256),
                    (512, 16)}


def _variants(seq, bs):
    out = [(False, None), (True, None), ("dots", None)]
    if (seq, bs) in FLASH_OFF_POINTS:
        out.append((False, False))
    return out


results = []
for seq, bs in GRID:
    for remat, use_flash in _variants(seq, bs):
        n = max(4 * bs, 256)
        tok = rng.integers(0, 30522, (n, seq), dtype=np.int32)
        lab = rng.integers(0, 2, (n,), dtype=np.int32)
        est = BertModel(max_len=seq, remat=remat, use_flash=use_flash)
        est._init_params(jnp.asarray(tok[:1]))
        per_sample = _model_flops_per_sample(est, jnp.asarray(tok[:1]))
        try:
            t0 = time.perf_counter()
            thr = _fused_throughput(est, tok, lab, bs, k=2)
            wall = time.perf_counter() - t0
        except Exception as exc:  # noqa: BLE001 — OOM points just report
            print(f"seq={seq} bs={bs} remat={remat} "
                  f"flash={use_flash}: FAILED {exc!r}", flush=True)
            continue
        mfu = thr * per_sample / PEAK
        row = {
            "seq": seq, "bs": bs, "remat": remat,
            "use_flash": use_flash,
            "samples_per_sec": round(thr, 1), "mfu": round(mfu, 4),
            "wall_s": round(wall, 1),
        }
        results.append(row)
        print(json.dumps(row), flush=True)

best = max(results, key=lambda r: r["mfu"], default=None)
print("BEST:", json.dumps(best), flush=True)
