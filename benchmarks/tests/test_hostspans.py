"""The readers of what the program itself names (PR 27): ``lo:`` host
annotations, the decode step program's runs and the named flash kernels
from a hand-built profile (the shape of ``jax.profiler.ProfileData``),
the fit job's span tree from a canned record, and both cells end to end
at tiny widths on the CPU, where the host side of it is real."""

import json
from types import SimpleNamespace as NS

import pytest
import tiny
from lobench import hostspans, loader, peaks, runner

MS = 1_000_000  # ns


def _ev(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=list(stats.items()))


def _custom_call(name, start_ms, dur_ms):
    return _ev(f"%{name} = (bf16[4]{{0}}) custom-call(bf16[4] %q), "
               'custom_call_target="tpu_custom_call"', start_ms, dur_ms)


def _profile():
    """Three runs of the step program, 100..116, 130..146, 160..176 ms:
    two gaps of 14 ms.  The worker's phases around them, by hand:

    gap 116..130: sync 110..118 (2 inside), emit 118..121 (3), admit
    121..122 (1), dispatch 122..128 (6): 12 named, 2 not.
    gap 146..160: sync 140..149 (3), emit 149..151 (2), admit 151..152
    (1), dispatch 152..160.5 (8 inside): 14 named, 0 not."""
    worker = [
        _ev("lo:decode.step", 100, 22, prompt=3, output=5, keys=800,
            slots=8, kv=512),
        _ev("lo:decode.dispatch", 100, 9),
        _ev("lo:decode.sync", 110, 8),
        _ev("lo:decode.emit", 118, 3),
        _ev("lo:decode.step", 121, 30, prompt=1, output=7, keys=900,
            slots=8, kv=512),
        _ev("lo:decode.admit", 121, 1),
        _ev("lo:decode.seat", 121.5, 0.1, wait_ms=12.0),
        _ev("lo:decode.seat", 121.7, 0.1, wait_ms=20.0),
        _ev("lo:decode.dispatch", 122, 6),
        _ev("lo:decode.sync", 140, 9),
        _ev("lo:decode.emit", 149, 2),
        _ev("lo:decode.admit", 151, 1),
        _ev("lo:decode.dispatch", 152, 8.5),
        _ev("lo:decode.wait", 300, 50),
        _ev("PjitFunction(step)", 122, 6),
    ]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit_step(123)", 100, 16), _ev("jit_step(123)", 130, 16),
            _ev("jit_step(123)", 160, 16), _ev("jit_other(9)", 200, 5),
        ]),
        NS(name="XLA Ops", events=[
            _custom_call("flash_fwd.1", 0, 10),
            _custom_call("flash_fwd.2", 10, 10),
            _custom_call("flash_dq.1", 20, 25),
            _custom_call("flash_dkv.7", 45, 40),
            _ev("%fusion.3 = f32[8]{0} fusion(f32[8] %a), kind=kLoop",
                90, 5),
        ]),
    ])
    second = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Modules", events=[_ev("jit_step(123)", 0, 16)]),
    ])
    host = NS(name="/host:CPU", lines=[
        NS(name="decode-lm", events=worker),
        NS(name="python3", events=[_ev("lo:publish", 400, 100)]),
    ])
    return NS(planes=[NS(name="/host:metadata", lines=[]), host, device,
                      second])


def test_reduce_finds_annotations_step_runs_and_kernels():
    spans = hostspans.reduce(_profile())
    assert [s for s, _e in spans.steps] == [100 * MS, 130 * MS, 160 * MS]
    assert spans.kernels == pytest.approx(
        {"flash_fwd": 0.020, "flash_dq": 0.025, "flash_dkv": 0.040}
    )
    assert spans.calls == {"flash_fwd": 2, "flash_dq": 1, "flash_dkv": 1}
    assert len(spans.named("decode.step")) == 2
    assert spans.named("publish")[0][1:3] == (400 * MS, 500 * MS)
    assert spans.named("decode.seat")[0][3] == {"wait_ms": 12.0}
    assert hostspans.instruction_name("flash_dkv.12") == "flash_dkv"
    assert hostspans.gap_split(spans) == pytest.approx({
        "gap": 14.0, "sync": 2.5, "emit": 2.5, "admit": 1.0,
        "dispatch": 7.0, "unnamed": 1.0,
    })


RUN = dict(
    cp={"hidden_dim": 768, "num_layers": 12, "num_heads": 12,
        "mlp_dim": 3072, "vocab_size": 30522, "num_classes": 2},
    peaks=peaks.PEAKS["TPU v5 lite"],
)
JOB = {"wall_s": 20.0, "epochs": 2, "rows": 1024, "seq": 512,
       "batch_size": 32, "epoch_times": [3.0, 3.5]}


def _span(_sid, _parent, name, start, dur):
    # as ``rest.spans`` hands them on: flat, no ids
    return {"name": name, "start": start, "durationS": dur}


SPANS = [
    _span(1, None, "queue_wait", 0.0, 0.1),
    _span(2, None, "job", 0.1, 19.6),
    _span(3, 2, "load_artifact", 0.2, 2.0),
    _span(4, 2, "resolve_params", 2.2, 0.5),
    _span(5, 2, "lease_wait", 2.7, 0.05),
    _span(6, 2, "lease", 2.75, 9.25),           # 2.75 .. 12.0
    _span(7, 6, "fit_init", 2.8, 0.3),
    _span(8, 7, "compile", 2.9, 0.1),           # inside fit_init
    _span(9, 6, "epoch", 3.1, 3.1),
    _span(10, 6, "epoch", 6.2, 3.6),
    _span(11, 6, "checkpoint_save", 9.8, 0.2),
    _span(12, 6, "checkpoint_save", 10.0, 1.9),  # lease self: 0.15
    _span(13, 2, "publish", 12.1, 7.0),
    _span(14, 2, "store_history", 19.1, 0.2),
    _span(15, 2, "commit", 19.3, 0.1),           # job self: 0.5
]


def _read(name, record, run):
    return loader.metric_reader(name)(record, run)


def test_every_new_reader_reads_a_number():
    run = NS(**RUN, _hostspans=hostspans.reduce(_profile()))
    fit = {"job": JOB, "spans": SPANS + SPANS}  # list and tree: twice
    assert _read("job_load_s", fit, run) == pytest.approx(2.5)
    assert _read("job_init_s", fit, run) == pytest.approx(0.3)
    assert _read("job_publish_s", fit, run) == pytest.approx(9.4)
    # beyond queue_wait + job on the client's clock 0.3, job's self
    # time 0.5, lease's 0.15
    assert _read("job_unnamed_s", fit, run) == pytest.approx(0.95)
    # the pieces and the two waits make up the overhead, but for what
    # the epoch spans have beyond the history's epoch times (0.2)
    assert 2.5 + 0.3 + 9.4 + 0.95 + 0.1 + 0.05 == pytest.approx(
        _read("job_overhead_s", fit, run) - (3.1 + 3.6 - 6.5)
    )
    # one kernel's two matmuls a head: 4*512*512*64*32*12 = 25.77
    # GFLOP = 0.1308 ms; forward moves 4 tensors of 25.2 MB = 0.1229 ms
    # (compute-bound), dq 5 = 0.1536 ms and dkv 6 = 0.1844 ms (memory-
    # bound); x 12 layers x 64 steps, over 20 / 25 / 40 ms
    assert _read("flash_fwd_roofline", fit, run) == pytest.approx(
        100 * 0.1308e-3 * 768 / 0.020, rel=1e-3)
    assert _read("flash_dq_roofline", fit, run) == pytest.approx(
        100 * 0.15360e-3 * 768 / 0.025, rel=1e-3)
    assert _read("flash_dkv_roofline", fit, run) == pytest.approx(
        100 * 0.18432e-3 * 768 / 0.040, rel=1e-3)
    gen = {"window": {"seconds": 8.0}}
    assert _read("decode_gap_sync_ms", gen, run) == pytest.approx(2.5)
    assert _read("decode_gap_emit_ms", gen, run) == pytest.approx(2.5)
    assert _read("decode_gap_admit_ms", gen, run) == pytest.approx(1.0)
    assert _read("decode_gap_dispatch_ms", gen, run) == pytest.approx(7.0)
    assert _read("decode_gap_unnamed_ms", gen, run) == pytest.approx(1.0)
    assert _read("decode_prefill_share_pct", gen, run) == pytest.approx(25.0)
    assert _read("decode_admit_wait_ms", gen, run) == pytest.approx(16.0)


NEW = ["job_load_s", "job_init_s", "job_publish_s", "job_unnamed_s",
       "decode_gap_sync_ms", "decode_gap_emit_ms", "decode_gap_admit_ms",
       "decode_gap_dispatch_ms", "decode_gap_unnamed_ms",
       "decode_prefill_share_pct", "decode_admit_wait_ms",
       "flash_fwd_roofline", "flash_dq_roofline", "flash_dkv_roofline"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_that_names_nothing_reads_nothing(name):
    """The parent's trace and span list: step runs and one unnamed
    custom call on the device, no ``lo:`` event, the spans it had."""
    profile = _profile()
    host, device = profile.planes[1], profile.planes[2]
    host.lines = [NS(name="python3",
                     events=[_ev("PjitFunction(step)", 122, 6)])]
    device.lines[1].events = [_custom_call("jvp__.1", 0, 10)]
    run = NS(**RUN, _hostspans=hostspans.reduce(profile))
    old = [s for s in SPANS if s["name"] in (
        "queue_wait", "job", "load_artifact", "lease", "compile", "epoch")]
    # ``load_artifact`` is older than this PR: the parent reads it too.
    expected = 2.0 if name == "job_load_s" else None
    assert _read(name, {"job": JOB, "spans": old,
                        "window": {"seconds": 8.0}}, run) == expected
    # and with no profile at all
    assert _read(name, {"job": JOB, "spans": old}, NS(**RUN)) == expected


def test_the_fourteen_are_in_the_benchmark_each_in_one_cell():
    by_name = {m["name"]: m for m in loader.benchmark()["per_layer"]}
    assert set(NEW) <= set(by_name)
    for name in NEW:
        assert len(by_name[name]["workloads"]) == 1


@pytest.fixture()
def cpu_in_the_peaks_table(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


@pytest.mark.parametrize("cell, read_on_cpu", [
    ("bert-base.fit-s512",
     {"job_load_s", "job_init_s", "job_publish_s", "job_unnamed_s"}),
    ("gpt2-xl.gen-decode",
     {"decode_prefill_share_pct", "decode_admit_wait_ms"}),
])
def test_traced_cell_end_to_end_reads_the_host_side(
        cell, read_on_cpu, tmp_path, monkeypatch, cpu_in_the_peaks_table,
        capsys):
    """No device plane on the CPU: of the new readers those that need
    step runs or kernels return nothing and are left out; those that
    read the program's own spans and annotations are all there."""
    run = tiny.tiny_run(tmp_path, cell, monkeypatch, trace=1)
    line = json.loads(json.dumps(runner.execute(run)))
    assert line["correct"] is True, line["compared"]
    mine = {m["name"] for m in run.bench["per_layer"]
            if m["name"] in NEW and cell in m["workloads"]}
    assert read_on_cpu <= mine
    assert set(line["metrics"]) & mine == read_on_cpu
    for name in read_on_cpu:
        assert line["metrics"][name]["value"] >= 0.0
    if cell.startswith("gpt2"):
        share = line["metrics"]["decode_prefill_share_pct"]["value"]
        assert 0.0 < share < 100.0
        notes = [json.loads(ln)["note"] for ln in
                 capsys.readouterr().out.splitlines() if '"note"' in ln]
        parse = next(n for n in notes if "hostspans_parse_s" in n)
        assert parse["lo_events"] > 0 and parse["step_runs"] == 0
