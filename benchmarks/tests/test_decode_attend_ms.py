"""``decode_attend_ms`` (PR 30) beside the readers of
``test_hostspans.py``, from the same hand-built profile: the device
time of the custom calls named ``decode_attend`` over the runs of the
step program, and nothing where the trace holds no such call."""

from types import SimpleNamespace as NS

from lobench import hostspans, loader
from test_hostspans import RUN, _custom_call, _profile


def _read(profile):
    run = NS(**RUN, _hostspans=hostspans.reduce(profile))
    return loader.metric_reader("decode_attend_ms")({}, run)


def test_kernel_milliseconds_a_step():
    profile = _profile()
    # two layers' calls in each of the three runs of the step program
    profile.planes[2].lines[1].events += [
        _custom_call(f"decode_attend.{layer}", start + layer, 0.25)
        for start in (100, 130, 160) for layer in (1, 2)
    ]
    assert _read(profile) == 0.5


def test_a_program_without_the_kernel_reads_nothing():
    assert _read(_profile()) is None  # flash kernels only: the parent
    assert loader.metric_reader("decode_attend_ms")({}, NS(**RUN)) is None


def test_it_is_in_the_benchmark_in_the_generate_cells():
    by_name = {m["name"]: m for m in loader.benchmark()["per_layer"]}
    entry = by_name["decode_attend_ms"]
    assert set(entry["workloads"]) == {
        "gpt2-xl.gen-decode", "sdar-30b-a3b-chat.gen-blocks",
    }
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "model step", "gen_tok_s", "device_trace",
    )
