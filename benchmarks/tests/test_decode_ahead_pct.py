"""``decode_ahead_pct`` (PR 32) beside the readers of
``test_hostspans.py``, from the same hand-built profile: the share of
the ``lo:decode.step`` annotations whose step was enqueued with the
step before it still unread, a turn that dispatched no step left out,
and nothing where no annotation carries the key."""

from types import SimpleNamespace as NS

import pytest
from lobench import hostspans, loader
from test_hostspans import RUN, _ev, _profile


def _read(profile):
    run = NS(**RUN, _hostspans=hostspans.reduce(profile))
    reader = loader.metric_reader("decode_ahead_pct")
    return reader({"window": {"seconds": 8.0}}, run)


def _steps(profile):
    return [ev for ev in profile.planes[1].lines[0].events
            if ev.name == "lo:decode.step"]


@pytest.mark.parametrize("ahead, expected", [
    ((1, 1), 100.0), ((0, 1), 50.0), ((0, 0), 0.0),
])
def test_share_of_the_steps_ahead(ahead, expected):
    profile = _profile()
    for ev, flag in zip(_steps(profile), ahead, strict=True):
        ev.stats.append(("ahead", flag))
    assert _read(profile) == pytest.approx(expected)


def test_a_turn_that_only_read_a_step_back_is_no_step():
    profile = _profile()
    for ev in _steps(profile):
        ev.stats.append(("ahead", 1))
    # the drain after a pool's last step: nothing dispatched
    profile.planes[1].lines[0].events.append(_ev(
        "lo:decode.step", 200, 5, prompt=0, output=0, keys=0, slots=0,
        kv=0, inplace=0, ahead=0,
    ))
    assert _read(profile) == pytest.approx(100.0)


def test_a_program_that_does_not_count_reads_nothing():
    # the parent's annotations: prompt, output, keys, slots, kv, inplace
    assert _read(_profile()) is None
    assert loader.metric_reader("decode_ahead_pct")({}, NS(**RUN)) is None


def test_it_is_in_the_benchmark_in_both_generate_cells():
    by_name = {m["name"]: m for m in loader.benchmark()["per_layer"]}
    entry = by_name["decode_ahead_pct"]
    assert entry["workloads"] == [
        "gpt2-xl.gen-decode", "sdar-30b-a3b-chat.gen-blocks",
    ]
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "decode engine", "gen_tok_s", "program_counter",
    )
