"""Device time of the decode engine's one-token step and of its
prompt-chunk step at several widths, on the chip: the measurement that
``serve/decode/pages.py`` ``PROMPT_CHUNK`` was chosen by (the widest
width whose step costs at most 1.04 times the one-token step's).

    chiprun -- python scripts/chip_chunk_sweep.py            # 4 8 16 32
    chiprun -- python scripts/chip_chunk_sweep.py 8 12 16    # those widths

GPT-2 XL whole (48 layers, float32, seeded by ``module.init``), 8 slots
over a 512 bucket standing where ``gpt2-xl.gen-decode``'s do (40-350
keys), slot 0 in its prompt; each program traced by itself (both are
``jit_step``), 40 runs.  Fails without a TPU.  One JSON line a width
goes to stdout and the table to ``chiprun_out/chunk_sweep.json``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "benchmarks"))  # the trace's reduction

import jax
import jax.numpy as jnp
import numpy as np

from learningorchestra_tpu.models.text import _DecoderLM
from learningorchestra_tpu.serve.decode.pages import build_step
from lobench import trace

SLOTS, KV, RUNS = 8, 512, 40


def main(widths) -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: a step's device time is read nowhere else")
    out = REPO / "chiprun_out"
    out.mkdir(exist_ok=True)
    module = _DecoderLM(vocab_size=50257, hidden_dim=1600, num_layers=48,
                        num_heads=25, mlp_dim=6400, max_len=1024)
    variables = jax.jit(module.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(37)
    pos = np.array([40, 96, 130, 170, 210, 250, 300, 350], np.int32)
    rows = np.zeros((SLOTS, KV), np.int32)
    for i, p in enumerate(pos):
        rows[i, : p + 65] = rng.integers(1, 50257, p + 65)
    # slot 0 has 64 prompt positions left, the others decode
    t0s = np.where(np.arange(SLOTS) == 0, pos + 64, pos).astype(np.int32)
    live = np.ones(SLOTS, bool)
    table = {}
    for chunk in (1, *widths):
        step, shapes = build_step(module, SLOTS, KV, chunk)
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        buf = jnp.asarray(rows)
        started = time.perf_counter()
        for _ in range(3):  # compile, then settle
            cache, buf, col = step(variables, cache, buf, pos, t0s, live)
        jax.block_until_ready(col)
        compile_s = time.perf_counter() - started
        logdir = out / f"chunk_sweep_trace_{chunk}"
        with trace.capture(logdir) as cap:
            for _ in range(RUNS):
                cache, buf, col = step(variables, cache, buf, pos, t0s, live)
            jax.block_until_ready(col)
        read = trace.read(cap)
        shutil.rmtree(logdir, ignore_errors=True)
        runs = read["modules"].get("jit_step", [])
        table[chunk] = {
            "compile_s": round(compile_s, 1), "runs": len(runs),
            "device_ms": 1e3 * sum(runs) / len(runs),
            "ops_ms_a_step": {
                k: 1e3 * v / len(runs) for k, v in sorted(
                    read["ops"].items(), key=lambda kv: -kv[1])[:8]
            },
        }
        table[chunk]["ratio"] = \
            table[chunk]["device_ms"] / table[1]["device_ms"]
        print(json.dumps({"chunk": chunk, **table[chunk]}), flush=True)
        del cache, buf, col, step
    (out / "chunk_sweep.json").write_text(json.dumps(table, indent=1))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    raise SystemExit(main([int(a) for a in sys.argv[1:]] or [4, 8, 16, 32]))
