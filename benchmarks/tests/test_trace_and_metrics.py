"""The trace reduction on a small hand-built profile (the shape of
``jax.profiler.ProfileData``), and every per-layer reader on a canned
run record."""

from types import SimpleNamespace as NS

import pytest
import tiny  # noqa: F401
from lobench import loader, peaks, trace

MS = 1_000_000  # ns


def _ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def _profile():
    ops = [
        _ev("%fusion.1 = f32[8]{0:T(128)} fusion(f32[8] %a), kind=kLoop",
            10, 10),
        _ev("fusion.2", 15, 10),
        _ev('%attn.7 = bf16[4]{0} custom-call(bf16[4] %q), '
            'custom_call_target="tpu_custom_call"', 40, 20),
        _ev("%copy.3 = f32[8]{0} copy(f32[8] %x)", 100, 5),
        _ev("%while.5 = (s32[]) while((s32[]) %t), body=%b", 0, 150),
    ]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=ops),
        NS(name="XLA Modules", events=[
            _ev("jit_step(123)", 10, 50), _ev("jit_step(123)", 100, 10),
        ]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev(trace.ANCHOR, 0, 0),
        _ev("PjitFunction(step)", 26, 13),      # covers the 25..40 gap
        _ev("outer", 0, 200),
    ])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), host, device])


def test_reduce_busy_ops_modules_and_gaps():
    # the job span covers the 60..100 gap; anchor at wall 1000 s = 0 ns
    spans = [{"name": "job", "start": 1000.055, "durationS": 0.05},
             {"name": "later", "start": 1000.5, "durationS": 0.1}]
    out = trace.reduce(_profile(), window_s=0.2, anchor_wall_s=1000.0,
                       spans=spans)
    assert out["busy_s"] == pytest.approx(0.040)  # 10..25, 40..60, 100..105
    assert out["window_s"] == 0.2
    assert out["ops"] == pytest.approx(
        {"fusion": 0.020, "custom-call:tpu_custom_call": 0.020,
         "copy": 0.005}
    )
    assert out["modules"]["jit_step"] == pytest.approx([0.050, 0.010])
    assert out["device_ops"][0][1] == pytest.approx(0.020)
    # the window's two ends count: 0..10 before the first operation,
    # 105..200 after the last
    assert out["idle_gaps"] == [
        ["host:outer", pytest.approx(0.095)],
        ["span:job", pytest.approx(0.040)],
        ["host:PjitFunction_step_", pytest.approx(0.015)],
        ["host:outer", pytest.approx(0.010)],
    ]


def test_reduce_a_trace_recorded_on_the_chip():
    """``data/v5e_small.xplane.pb``: three runs of one small jitted
    program on a TPU v5e, 20 ms apart, recorded through
    ``trace.capture`` (my chip run, PR 25)."""
    from pathlib import Path

    from jax.profiler import ProfileData

    path = Path(__file__).parent / "data" / "v5e_small.xplane.pb"
    wall, window = 1790780385.9266489, 0.06513094399999986
    out = trace.reduce(
        ProfileData.from_file(str(path)), window_s=window,
        anchor_wall_s=wall,
        spans=[{"name": "job", "start": wall, "durationS": window}],
    )
    assert len(out["modules"]["jit_step"]) == 3
    assert out["busy_s"] == pytest.approx(2.479e-05, rel=1e-3)
    assert 0 < out["busy_s"] < sum(out["modules"]["jit_step"]) * 1.01
    assert out["device_ops"][0][0] == "fusion"
    assert sum(out["ops"].values()) == pytest.approx(out["busy_s"], rel=0.05)
    # three long gaps: between the three runs and after the last
    long_gaps = [g for g in out["idle_gaps"] if g[1] > 1e-3]
    assert len(long_gaps) == 3 and {g[0] for g in long_gaps} == {"span:job"}
    assert out["busy_s"] + sum(g[1] for g in out["idle_gaps"]) \
        == pytest.approx(window, rel=0.02)


def test_a_trace_with_no_device_reads_nothing():
    profile = NS(planes=[NS(name="/host:CPU", lines=[])])
    out = trace.reduce(profile, window_s=1.0)
    assert out["busy_s"] == 0.0 and out["device_ops"] == []


def test_op_and_host_names():
    assert trace.op_name("%fusion.123 = bf16[2]{0} fusion(...)") == "fusion"
    assert trace.op_name(
        "%multiply_add_fusion.12 = f32[8]{0:T(8,128)(2,1)S(1)} "
        "fusion(f32[8] %a), kind=kLoop"
    ) == "fusion:multiply_add_fusion"
    assert trace.op_name(
        "%slice-done.2 = f32[7]{0} async-done(((f32[7]{0}) %s"
    ) == "async-done:slice-done"
    assert trace.op_name("custom-call.4") == "custom-call"
    assert trace.host_name("PjitFunction(step)") == "PjitFunction_step_"


RUN = NS(
    cp={"hidden_dim": 768, "num_layers": 12, "num_heads": 12,
        "mlp_dim": 3072, "vocab_size": 30522, "num_classes": 2},
    peaks=peaks.PEAKS["TPU v5 lite"],
)
TRACE = {
    "busy_s": 6.0, "window_s": 10.0,
    "ops": {"custom-call:tpu_custom_call": 2.0, "fusion": 4.0,
            "custom-call:AllocateBuffer": 0.5},
    "modules": {"jit_step": [0.016, 0.016], "jit_other": [1.0]},
}
FIT = {
    "window_compiles": 1,
    "job": {"wall_s": 10.0, "epochs": 2, "tokens": 2 * 1024 * 512,
            "epoch_times": [3.0, 3.5], "rows": 1024, "seq": 512,
            "batch_size": 32, "compile_cache": {"misses": 2}},
    "trace": TRACE,
}
GEN = {
    "window_compiles": 0,
    "window": {"seconds": 50.0, "engine_steps": 2000,
               "live_samples": [8, 8, 7, 8], "prefill_ms_per_tok": [30, 28, 32],
               "processed_tokens": 16000, "mean_keys": 200.0},
    "trace": TRACE,
}


def _read(name, record, run=RUN):
    return loader.metric_reader(name)(record, run)


def test_fit_readers():
    assert _read("job_overhead_s", FIT) == pytest.approx(3.5)
    assert _read("fit_window_compiles", FIT) == 3
    assert _read("fit_tok_s", FIT) == pytest.approx(1048576 / 6.5)
    # 566.2 MFLOP a token x 161,319 tokens/s over 197 TFLOP/s
    assert _read("fit_mfu_pct", FIT) == pytest.approx(46.37, rel=1e-3)
    assert _read("fit_idle_pct", FIT) == pytest.approx(40.0)
    # least: 6*2*512*512*64*384 / 197e12 = 0.3924 ms a layer-step,
    # x 12 layers x 64 steps = 0.3014 s, over 2.0 s of custom calls
    assert _read("flash_roofline", FIT) == pytest.approx(15.07, rel=1e-3)


def test_gen_readers():
    gpt = NS(cp={"hidden_dim": 1600, "num_layers": 48, "num_heads": 25,
                 "mlp_dim": 6400, "vocab_size": 50257}, peaks=RUN.peaks)
    assert _read("decode_step_ms", GEN, gpt) == pytest.approx(25.0)
    assert _read("slots_live_mean", GEN, gpt) == pytest.approx(7.75)
    assert _read("prefill_ms_per_tok", GEN, gpt) == 30
    assert _read("gen_window_compiles", GEN, gpt) == 0
    assert _read("gen_idle_pct", GEN, gpt) == pytest.approx(40.0)
    # (48*(61.44e6 + 4*200*1600) + 160.8e6) * 320 tok/s / 197e12
    flops = 48 * (61_440_000 + 1_280_000) + 2 * 1600 * 50257
    assert _read("gen_mfu_pct", GEN, gpt) == pytest.approx(
        100 * flops * 320 / 197e12
    )
    # 6.224 GB + K,V of 7.75 x 200 keys (0.952 GB) at 819 GB/s = 8.76 ms
    assert _read("decode_hbm_roofline", GEN, gpt) == pytest.approx(
        100 * 8.762e-3 / 0.016, rel=2e-3
    )


@pytest.mark.parametrize("name", [
    m["name"] for m in loader.benchmark()["per_layer"]
])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    assert _read(name, {"window_compiles": 0}) is None
