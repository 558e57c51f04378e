"""Device time of the decode engine's one-token step and of its
prompt-chunk step at several widths, on the chip: the measurement that
``serve/decode/pages.py`` ``PROMPT_CHUNK`` and ``STATE_CHUNK`` were
chosen by (the widest width whose step costs at most 1.04 times the
one-token step's).

    python scripts/chip_chunk_sweep.py                # 4 8 16 32
    python scripts/chip_chunk_sweep.py 8 12 16        # those widths
    python scripts/chip_chunk_sweep.py retention      # 4 8 16 32

on a machine that holds one TPU chip.

GPT-2 XL whole (48 layers, float32, seeded by ``module.init``), 8 slots
over a 512 bucket standing where ``gpt2-xl.gen-decode``'s do (40-350
keys), slot 0 in its prompt.  ``retention``: Brumby-14B at the widths
of ``benchmarks/configs/brumby-14b-base.json`` (7 layers, bfloat16
leaves, float32 states), 16 slots over the 1,024-position buffer at
positions 60-960, and 1, 2 or 4 of them with 64 prompt positions left
in each chunk program (the others decode: a retention layer takes only
the slots that prefill through the matrix unit, so the chunk step's
cost grows with their number); every run of it is handed the same
buffer anew.  Each program traced by itself (all are ``jit_step``), 40
runs.  Fails without a TPU.  One JSON line a measurement goes to stdout
and the table to ``chiprun_out/chunk_sweep.json`` (``retention``:
``chunk_sweep_retention.json``).
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


def _timed(step, out, name, variables, cache, buf, pos, t0s, live,
           fresh_buf=False):
    """(the program's record, the cache it leaves): compiled and settled
    by three runs, then ``RUNS`` runs traced."""
    def rows():
        return jnp.asarray(buf) if fresh_buf else carried

    carried = jnp.asarray(buf)
    started = time.perf_counter()
    for _ in range(3):  # compile, then settle
        cache, carried, col = step(variables, cache, rows(), pos, t0s, live)
    jax.block_until_ready(col)
    compile_s = time.perf_counter() - started
    logdir = out / f"chunk_sweep_trace_{name}"
    with trace.capture(logdir) as cap:
        for _ in range(RUNS):
            cache, carried, col = step(
                variables, cache, rows(), pos, t0s, live)
        jax.block_until_ready(col)
    read = trace.read(cap)
    shutil.rmtree(logdir, ignore_errors=True)
    runs = read["modules"].get("jit_step", [])
    return {
        "compile_s": round(compile_s, 1), "runs": len(runs),
        "device_ms": 1e3 * sum(runs) / len(runs),
        "ops_ms_a_step": {
            k: 1e3 * v / len(runs) for k, v in sorted(
                read["ops"].items(), key=lambda kv: -kv[1])[:8]
        },
    }, cache


def dense(widths, out) -> dict:
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
        table[chunk], cache = _timed(step, out, chunk, variables, cache,
                                     rows, pos, t0s, live)
        table[chunk]["ratio"] = \
            table[chunk]["device_ms"] / table[1]["device_ms"]
        print(json.dumps({"chunk": chunk, **table[chunk]}), flush=True)
        del cache, step
    return table


def retention(widths, out) -> dict:
    from learningorchestra_tpu.models.retention import RetentionLM

    config = json.loads(
        (REPO / "benchmarks/configs/brumby-14b-base.json").read_text())
    module = RetentionLM(**config["class_parameters"]).module
    variables = jax.jit(module.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    slots, kv = 16, 1024
    rng = np.random.default_rng(39)
    pos = np.linspace(60, 960, slots).astype(np.int32)
    live = np.ones(slots, bool)
    table, cache = {}, None
    for chunk in (1, *widths):
        step, shapes = build_step(module, slots, kv, chunk)
        if cache is None:
            cache = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        for taking in ((0,) if chunk == 1 else (1, 2, 4)):
            prompt = np.arange(slots) < taking
            t0s = np.where(prompt, pos + 64, pos).astype(np.int32)
            rows = np.zeros((slots, kv), np.int32)
            for i, p in enumerate(pos):
                # a decoding slot's buffer ends at its position
                rows[i, : t0s[i] if prompt[i] else p + 1] = rng.integers(
                    1, 151936, t0s[i] if prompt[i] else p + 1)
            name = f"{chunk}_{taking}"
            table[name], cache = _timed(
                step, out, name, variables, cache, rows, pos, t0s, live,
                fresh_buf=True)
            table[name]["ratio"] = \
                table[name]["device_ms"] / table["1_0"]["device_ms"]
            print(json.dumps({"chunk": chunk, "prefilling": taking,
                              **table[name]}), flush=True)
        del step
    return table


def main(argv) -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: a step's device time is read nowhere else")
    out = REPO / "chiprun_out"
    out.mkdir(exist_ok=True)
    model = argv.pop(0) if argv and argv[0] == "retention" else "dense"
    widths = [int(a) for a in argv] or [4, 8, 16, 32]
    table = (retention if model == "retention" else dense)(widths, out)
    name = "chunk_sweep.json" if model == "dense" \
        else "chunk_sweep_retention.json"
    (out / name).write_text(json.dumps(table, indent=1))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    raise SystemExit(main(sys.argv[1:]))
