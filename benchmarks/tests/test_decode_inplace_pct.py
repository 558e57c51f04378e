"""``decode_inplace_pct`` (PR 28) beside the readers of
``test_hostspans.py``, from the same hand-built profile: the share of
the ``lo:decode.step`` annotations whose step updated the pool's pages
in place, and nothing where no annotation carries the key."""

from types import SimpleNamespace as NS

import pytest
from lobench import hostspans, loader
from test_hostspans import RUN, _profile


def _read(profile):
    run = NS(**RUN, _hostspans=hostspans.reduce(profile))
    reader = loader.metric_reader("decode_inplace_pct")
    return reader({"window": {"seconds": 8.0}}, run)


def _steps(profile):
    return [ev for ev in profile.planes[1].lines[0].events
            if ev.name == "lo:decode.step"]


@pytest.mark.parametrize("inplace, expected", [
    ((1, 1), 100.0), ((1, 0), 50.0), ((0, 0), 0.0),
])
def test_share_of_the_steps_in_place(inplace, expected):
    profile = _profile()
    for ev, flag in zip(_steps(profile), inplace, strict=True):
        ev.stats.append(("inplace", flag))
    assert _read(profile) == pytest.approx(expected)


def test_a_program_that_does_not_count_reads_nothing():
    # the parent's annotations: prompt, output, keys, slots, kv
    assert _read(_profile()) is None
    assert loader.metric_reader("decode_inplace_pct")({}, NS(**RUN)) is None


def test_it_is_in_the_benchmark_in_the_generate_cell():
    by_name = {m["name"]: m for m in loader.benchmark()["per_layer"]}
    entry = by_name["decode_inplace_pct"]
    assert entry["workloads"] == ["gpt2-xl.gen-decode"]
    assert (entry["layer"], entry["moves"]) == ("decode engine", "gen_tok_s")
