"""Device time of the three flash kernels alone, schedule by schedule,
on the chip: the measurement ``ops/attention.py`` ``flash_schedule``
was chosen by (one grid step takes ``hb`` heads over the whole
sequence where one block holds it).

    python scripts/chip_flash_sweep.py            # T = 512
    python scripts/chip_flash_sweep.py 512 1024   # those lengths

on a machine that holds one TPU chip.

BERT-base's attention in ``bert-base.fit-s512``: batch 32, 12 heads of
64, bfloat16, a key mask with padded keys.  For each length T: today's
schedule before this sweep (one head, blocks (256, 512)), then ``hb``
in {1, 2, 3, 4, 6, 12} x ``block_q`` in {256, 512, T} with one k block
of T, each as a forward program and a forward-plus-backward program
(``jax.grad``); today's and ``flash_schedule``'s choice again with the
causal mask (the one-block schedule cannot skip the blocks above the
diagonal); and ``mha_reference`` under ``jit`` (XLA's attention) as
the control.  Each program compiled and settled by three calls,
then ``RUNS`` calls traced; a kernel's time is the median of its
calls' device durations, a program's the median of its runs.  The
fixed cost of a grid step is the slope of each kernel's time over its
grid steps across ``hb`` at one block.  One JSON line a schedule goes
to stdout and the table to ``chiprun_out/flash_sweep.json``.  Fails
without a TPU.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "benchmarks"))  # the trace's reduction

import jax
import jax.numpy as jnp
import numpy as np

from learningorchestra_tpu.ops import attention
from lobench import counts, hostspans, trace
from lobench.peaks import peaks_for

B, H, D, RUNS = 32, 12, 64, 20
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
#: Tensors of (B, H, T, D) each kernel moves once at the least
#: (``lobench.hostspans.FLASH_TENSORS``).
TENSORS = {"flash_fwd": 4, "flash_dq": 5, "flash_dkv": 6}


def _inputs(t: int):
    rng = np.random.default_rng(42)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, t, D)), jnp.bfloat16)
        for _ in range(3)
    )
    lengths = rng.integers(t // 2, t + 1, B)
    km = jnp.asarray(
        (np.arange(t)[None, :] < lengths[:, None]).astype(np.float32)
    )[:, None, :]
    return q, k, v, km


def _programs(hb, block_q, block_k, causal=False):
    def fwd(q, k, v, km):
        return attention._flash_core(
            q, k, v, km, block_q, block_k, False, causal, None, hb)

    def fwd_bwd(q, k, v, km):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v, km).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    return jax.jit(fwd), jax.jit(fwd_bwd)


def _xla():
    def fwd(q, k, v, km):
        return attention.mha_reference(q, k, v, km[:, 0] > 0)

    def fwd_bwd(q, k, v, km):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v, km).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    return jax.jit(fwd), jax.jit(fwd_bwd)


def _traced(fn, args, out: Path, name: str) -> dict:
    """Median device ms of the program and of each kernel's call."""
    for _ in range(3):  # compile, then settle
        jax.block_until_ready(fn(*args))
    logdir = out / f"flash_sweep_trace_{name}"
    with trace.capture(logdir) as cap:
        for _ in range(RUNS):
            jax.block_until_ready(fn(*args))
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(cap.xplane()))
    runs, calls = [], {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:") \
                or "CUSTOM" in plane.name.upper():
            continue
        for line in plane.lines:
            for ev in line.events:
                if line.name == trace._MODULES_LINE:
                    runs.append(ev.duration_ns / 1e6)
                elif line.name == trace._OPS_LINE:
                    key = hostspans.instruction_name(ev.name)
                    if key in KERNELS:
                        calls.setdefault(key, []).append(
                            ev.duration_ns / 1e6)
        break  # the first device
    shutil.rmtree(logdir, ignore_errors=True)
    return {
        "program_ms": statistics.median(runs) if runs else None,
        "kernels_ms": {k: statistics.median(v) for k, v in calls.items()},
        "runs": len(runs),
    }


def _rooflines(kernels_ms: dict, t: int, peaks: dict) -> dict:
    share = {}
    for name, ms in kernels_ms.items():
        least, bound = counts.roofline_seconds(
            2 * 2.0 * t * t * D * B * H, TENSORS[name] * B * H * t * D * 2.0,
            peaks)
        share[name] = [100.0 * least / (ms / 1e3), bound]
    return share


def sweep(t: int, out: Path, peaks: dict) -> list:
    args = _inputs(t)
    rows = []
    today = attention._default_blocks(t, t)
    schedules = [("today", None, *today, False)] + [
        (f"h{hb}_q{bq}", hb, bq, t, False)
        for bq in sorted({256, 512, t}) if bq <= t
        for hb in (1, 2, 3, 4, 6, 12)
    ] + [
        ("today_causal", None, *today, True),
        ("chosen_causal", *attention.flash_schedule(t, t, H, D, None), True),
    ]
    for name, hb, bq, bk, causal in schedules:
        fwd, fwd_bwd = _programs(hb, bq, bk, causal)
        row = {"t": t, "schedule": name, "hb": hb, "block_q": bq,
               "block_k": bk, "causal": causal}
        try:
            row["fwd"] = _traced(fwd, args, out, f"{t}_{name}_f")
            row["fwd_bwd"] = _traced(fwd_bwd, args, out, f"{t}_{name}_fb")
        except Exception as exc:  # noqa: BLE001 — a schedule the chip refuses
            row["refused"] = f"{type(exc).__name__}: {str(exc)[:300]}"
        else:
            kms = row["fwd_bwd"]["kernels_ms"]
            row["kernels_ms"] = sum(kms.values())
            row["roofline_pct"] = _rooflines(kms, t, peaks)
            nq, nk = -(-t // bq), -(-t // bk)
            row["grid_steps"] = 3 * B * (H // (hb or 1)) * nq * nk
        print(json.dumps(row), flush=True)
        rows.append(row)
    xf, xfb = _xla()
    control = {"t": t, "schedule": "xla_mha_reference",
               "fwd": _traced(xf, args, out, f"{t}_xla_f"),
               "fwd_bwd": _traced(xfb, args, out, f"{t}_xla_fb")}
    print(json.dumps(control), flush=True)
    rows.append(control)
    return rows


def step_cost_us(rows: list, t: int) -> dict:
    """Each kernel's least-squares slope of time over grid steps across
    ``hb`` with one block over the sequence: the fixed cost of a step."""
    cost = {}
    one = [r for r in rows if r.get("block_q") == t and r.get("block_k") == t
           and not r["causal"] and "kernels_ms" in r]
    for name in KERNELS:
        pts = [(B * H / r["hb"], r["fwd_bwd"]["kernels_ms"][name] * 1e3)
               for r in one if name in r["fwd_bwd"]["kernels_ms"]]
        if len(pts) >= 2:
            x, y = np.array(pts).T
            cost[name] = float(np.polyfit(x, y, 1)[0])
    return cost


def main(argv) -> int:
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("no TPU: a kernel's device time is read nowhere else")
    out = REPO / "chiprun_out"
    out.mkdir(exist_ok=True)
    peaks = peaks_for(device.device_kind)
    table = {"device": device.device_kind, "shape": [B, H, "T", D]}
    for t in [int(a) for a in argv] or [512]:
        rows = sweep(t, out, peaks)
        table[str(t)] = {"rows": rows, "step_cost_us": step_cost_us(rows, t)}
        print(json.dumps({"t": t, "step_cost_us": table[str(t)]
                          ["step_cost_us"]}), flush=True)
    (out / "flash_sweep.json").write_text(json.dumps(table, indent=1))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    raise SystemExit(main(sys.argv[1:]))
