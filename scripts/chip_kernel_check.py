"""Compile and run each Pallas kernel family once on the chip against
its jnp reference — the on-chip half of tests/test_tpu_lowering.py
(lowering on a CPU host proves the Pallas → Mosaic MLIR step; the Mosaic
compile itself happens in libtpu, only here).

    chiprun -- python scripts/chip_kernel_check.py            # one chip
    chiprun -- python scripts/chip_kernel_check.py latent     # those cases
    chiprun --chips 4 -- python scripts/chip_kernel_check.py  # + ring flash

Fails without a TPU.  Every case runs; the exit code is non-zero if any
case failed to compile or missed its tolerance.  One JSON line per case
goes to stdout and the full list to ``chiprun_out/kernel_check.json``.
Tolerances: bf16 inputs carry ~3 significant digits, the flash kernels
round probabilities to bf16 before the second matmul, and gradients sum
T such terms — 2e-2 absolute on unit-variance inputs is the bound the
CPU interpret-mode tests already hold the same kernels to.
"""

from __future__ import annotations

import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from learningorchestra_tpu.ops.attention import (
    flash_attention,
    mha_reference,
)
from learningorchestra_tpu.ops import (
    decode_attention,
    latent_attention,
    retention,
)
from learningorchestra_tpu.ops.quant import (
    dequantize_rowwise,
    quantize_rowwise,
)

TOL = 2e-2


def _max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)
    )))


def _flash_case(t: int, d: int, mode: str, b: int = 2, h: int = 4) -> dict:
    """Forward and all three gradients of the flash kernel vs the
    reference, with a key-side padding mask."""
    rng = np.random.default_rng(t * 131 + d)
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.bfloat16)
        for _ in range(3)
    )
    mask = np.ones((b, t), np.float32)
    mask[-1, t - t // 5:] = 0.0  # padded tail on one row
    mask = jnp.asarray(mask)
    kw = {
        "full": {},
        "causal": {"causal": True},
        "window": {"causal": True, "window": max(8, t // 4)},
    }[mode]

    def loss(attend):
        def fn(q, k, v):
            out = attend(q, k, v, mask, **kw)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, out), grads = loss(flash_attention)(q, k, v)
    (_, ref), ref_grads = loss(mha_reference)(q, k, v)
    errs = {
        "fwd": _max_err(out, ref),
        **{
            name: _max_err(g, rg) / max(
                1.0, float(jnp.max(jnp.abs(rg.astype(jnp.float32))))
            )
            for name, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads)
        },
    }
    return {"errs": errs, "ok": all(e < TOL for e in errs.values())}


def _quant_case() -> dict:
    """30522x768 (BERT's embedding).  Round-to-nearest must land within
    half a quantization step of x; stochastic rounding (the hardware
    PRNG) within one step and unbiased on average; scales and the
    dequantize kernel must be exact against numpy."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((30522, 768)), jnp.float32)
    step = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
    out: dict = {}
    for name, stochastic, bound in (
        ("nearest", False, 0.5), ("stochastic", True, 1.0),
    ):
        values, scales = jax.jit(
            lambda a: quantize_rowwise(a, stochastic=stochastic, seed=3)
        )(x)
        back = jax.jit(dequantize_rowwise)(values, scales)
        off = (back - x) / step
        out[name] = {
            "max_steps_off": float(jnp.max(jnp.abs(off))),
            "mean_bias_steps": float(jnp.mean(off)),
            "dequant_exact": bool(np.array_equal(
                np.asarray(back),
                np.asarray(values, np.float32) * np.asarray(scales),
            )),
            "scale_err": _max_err(scales, step),
        }
        out[name]["ok"] = (
            out[name]["max_steps_off"] <= bound + 1e-3
            and abs(out[name]["mean_bias_steps"]) < 1e-2
            and out[name]["dequant_exact"]
            and out[name]["scale_err"] < 1e-6
        )
    out["ok"] = out["nearest"]["ok"] and out["stochastic"]["ok"]
    return out


def _decode_case(h: int, kvh: int, t: int, d: int, dtype) -> dict:
    """The decode step's pass over 8 slots x 512 KV pages against the
    plain insert + attend: slots at different lengths, one on the
    bucket's last rows, one free.  The pages must come out equal bit
    for bit; float32 pages are multiplied in float32."""
    rng = np.random.default_rng(h * 7 + d)
    b, tk = 8, 512
    pack = decode_attention.page_pack(d, tk)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    q, k, v = draw(b, h, t, d), draw(b, kvh, t, d), draw(b, kvh, t, d)
    pages = [
        decode_attention.pack_pages(draw(b, kvh, tk, d), pack)
        for _ in range(2)
    ]
    idx = jnp.asarray([0, 300, tk - t, 255, 384, 0, 100, 509], jnp.int32)
    buf = jnp.asarray(rng.integers(0, 5, (b, tk)) != 0).at[5].set(False)
    last = idx[:, None, None] + jnp.arange(t)[None, :, None]
    mask = buf[:, None] & (jnp.arange(tk)[None, None] <= last)
    # the kernel's pages are donated, as the engine's step donates them
    ref, got = (
        jax.jit(fn, donate_argnums=(3, 4))(
            q, k, v, *(p + 0 for p in pages), idx,
            mask if t > 1 else mask[:, 0],
        )
        for fn in (decode_attention.plain_attend, decode_attention.decode_attend)
    )
    err = _max_err(ref[0], got[0])
    same = all(bool(jnp.array_equal(r, g)) for r, g in zip(ref[1:], got[1:]))
    return {"out_err": err, "pages_equal": same,
            "ok": same and err < (1e-4 if dtype == jnp.float32 else TOL)}


def _latent_case(h: int, rank: int, rope: int, tk: int) -> dict:
    """The absorbed latent attend over 8 slots of packed bfloat16
    pages (two positions a row) against the plain form: slots at
    different lengths, one on the bucket's last position, one free."""
    rng = np.random.default_rng(h * 7 + rank)
    b = 8

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    q = draw(b, h, 1, rank + rope)
    pages = latent_attention.insert_rows(
        jnp.zeros(latent_attention.page_shape(b, tk, rank, rope),
                  jnp.bfloat16),
        draw(b, tk, rank), draw(b, tk, rope), jnp.zeros(b, jnp.int32),
    )
    idx = jnp.asarray(
        [0, 300, tk - 1, 511, 512, 0, 100, tk // 2 + 3], jnp.int32)
    buf = jnp.asarray(rng.integers(0, 5, (b, tk)) != 0).at[5].set(False)
    mask = buf & (jnp.arange(tk)[None, :] <= idx[:, None])
    scale = 0.1
    row = draw(b, 1, rank + rope)

    def plain(q, latent, key_pe, pages, idx, mask):
        pages = latent_attention.insert_rows(pages, latent, key_pe, idx)
        return latent_attention.plain_latent_attend(
            q, pages, mask, rank, scale), pages

    def kernel(q, latent, key_pe, pages, idx, mask):
        return latent_attention.latent_attend_kernel(
            q, latent, key_pe, pages, idx, mask, rank=rank, scale=scale)

    # the kernel's pages are donated, as the engine's step donates them
    ref, got = (
        jax.jit(fn, donate_argnums=3)(
            q, row[..., :rank], row[..., rank:], pages + 0, idx, mask)
        for fn in (plain, kernel)
    )
    err = _max_err(ref[0], got[0])
    same = bool(jnp.array_equal(ref[1], got[1]))
    return {"out_err": err, "pages_equal": same, "ok": same and err < TOL}


def _retention_case(slots: int, heads: int, group: int, dim: int) -> dict:
    """One step of the power retention recurrence over ``slots`` slots'
    states at the published shapes (8,256 products of a 128-wide key,
    128 values each, float32) against the plain update at ``highest``
    precision: slots live, dead (their states must come back bit for
    bit) and begun anew, in a mixed order.  The kernel alone is timed:
    the mean of 10 calls with every slot live, each consuming the
    states the last returned, and the least bytes (each state read and
    written once) over that time."""
    import time

    rng = np.random.default_rng(slots * 7 + dim)

    def draw(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)

    s_shape, z_shape = retention.state_shapes(slots, heads, dim, dim)
    state, norm = draw(*s_shape), draw(*z_shape)
    q = draw(slots, heads, group, dim).astype(jnp.bfloat16)
    k = draw(slots, heads, dim).astype(jnp.bfloat16)
    v = draw(slots, heads, dim)
    g = jnp.asarray(rng.uniform(0.9, 1.0, (slots, heads)), jnp.float32)
    live = np.ones(slots, bool)
    live[[0, 5, slots - 1] if slots > 6 else [0]] = False
    fresh = np.zeros(slots, bool)
    fresh[[1, 5, slots // 2] if slots > 6 else [1]] = True

    def run(fn):
        def step(state, norm, live, fresh):
            return fn(state, norm, retention.feature_map(q),
                      retention.feature_map(k), v, g, live, fresh)
        # the states are donated, as the engine's step donates them
        return jax.jit(step, donate_argnums=(0, 1))

    ref = run(retention.plain_retention_step)(
        state + 0, norm + 0, jnp.asarray(live), jnp.asarray(fresh))
    kernel = run(retention.retention_step_kernel)
    got = kernel(state + 0, norm + 0, jnp.asarray(live), jnp.asarray(fresh))
    scale = max(1.0, float(jnp.max(jnp.abs(ref[0]))))
    errs = {
        name: _max_err(r, o) / (scale if name in ("num", "den") else 1.0)
        for name, r, o in zip(("num", "den", "state", "norm"), ref, got)
    }
    dead_same = bool(
        jnp.array_equal(got[2][~live], state[~live])
        and jnp.array_equal(got[3][~live], norm[~live])
    )
    on, off = jnp.ones(slots, bool), jnp.zeros(slots, bool)
    carried = kernel(got[2], got[3], on, off)
    jax.block_until_ready(carried)
    t0 = time.perf_counter()
    for _ in range(10):
        carried = kernel(carried[2], carried[3], on, off)
    jax.block_until_ready(carried)
    seconds = (time.perf_counter() - t0) / 10
    moved = 2.0 * slots * heads * retention.state_rows(dim) * (dim + 1) * 4
    return {
        "errs": errs, "dead_states_equal": dead_same,
        "step_ms_with_feature_maps": 1e3 * seconds,
        "least_gb_per_s": moved / seconds / 1e9,
        "ok": dead_same and all(e < 1e-4 for e in errs.values()),
    }


def main() -> int:
    only = sys.argv[1] if len(sys.argv) > 1 else ""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: jax found {dev.platform}", file=sys.stderr)
        return 1
    print(json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count(),
    }), flush=True)
    cases = [
        (f"flash:{mode}:T{t}:D{d}",
         lambda t=t, d=d, mode=mode: _flash_case(t, d, mode))
        for d in (64, 128)
        for t in (128, 333, 2048)
        for mode in ("full", "causal", "window")
    ]
    # The long-sequence default blocks (512, 1024) with D = 128: the
    # dkv kernel's VMEM high-water mark.  One head: the reference
    # materializes (T, T) f32 scores per head, 1 GiB here.
    cases.append(("flash:causal:T16384:D128",
                  lambda: _flash_case(16384, 128, "causal", b=1, h=1)))
    cases.append(("quant:30522x768", _quant_case))
    cases.append(("decode_attend:mha25:t1:D64:f32", lambda: _decode_case(
        25, 25, 1, 64, jnp.float32)))
    cases.append(("decode_attend:32over4:t4:D128:bf16", lambda: _decode_case(
        32, 4, 4, 128, jnp.bfloat16)))
    cases.append(("latent_attend:64h:512+64:Tk2048:bf16",
                  lambda: _latent_case(64, 512, 64, 2048)))
    cases.append(("latent_attend:64h:512+64:Tk256:bf16",
                  lambda: _latent_case(64, 512, 64, 256)))
    cases.append(("retention_step:16slots:8x5h:8256x128:f32",
                  lambda: _retention_case(16, 8, 5, 128)))
    cases.append(("retention_step:2slots:8x5h:8256x128:f32",
                  lambda: _retention_case(2, 8, 5, 128)))
    if jax.device_count() >= 4:
        # chip_smoke.py's multi-chip phase owns the ring-flash check
        # (it raises on a miss).
        from chip_smoke import ring_flash_check

        cases.append(("ring_flash:sp4", lambda: {
            "errs": ring_flash_check(4), "ok": True,
        }))
    results = []
    for name, fn in cases:
        if only not in name:  # a substring picks the cases to run
            continue
        try:
            rec = {"case": name, **fn()}
        except Exception as exc:  # noqa: BLE001 — every case reports
            rec = {
                "case": name, "ok": False,
                "error": f"{type(exc).__name__}: {exc}"[:2000],
                "trace": traceback.format_exc()[-3000:],
            }
        results.append(rec)
        print(json.dumps({k: v for k, v in rec.items() if k != "trace"}),
              flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kernel_check.json", "w") as fh:
        json.dump(results, fh, indent=1)
    failed = [r["case"] for r in results if not r["ok"]]
    print(json.dumps({"failed": failed, "total": len(results)}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
