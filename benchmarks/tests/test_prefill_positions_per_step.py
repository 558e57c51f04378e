"""``prefill_positions_per_step`` (PR 37): prompt positions over prompt
slot-steps from the engine's ``lo:decode.step`` annotations; on a
CPU-traced run of the generate cell at tiny widths (prompts of four
positions and more, a chunk each), from the hand-built profile of
``test_hostspans.py``, and nothing where no annotation carries the
key."""

import json
from types import SimpleNamespace as NS

import pytest
import test_retention_generate  # noqa: F401 — registers the tiny sizes
import tiny
from lobench import hostspans, loader, runner
from test_hostspans import RUN, _profile, cpu_in_the_peaks_table  # noqa: F401

NAME = "prefill_positions_per_step"
CELL = "gpt2-xl.gen-decode"


def _read(profile):
    run = NS(**RUN, _hostspans=hostspans.reduce(profile))
    return loader.metric_reader(NAME)({"window": {"seconds": 8.0}}, run)


def _steps(profile):
    return [ev for ev in profile.planes[1].lines[0].events
            if ev.name == "lo:decode.step"]


@pytest.mark.parametrize("positions, expected", [
    ((3, 1), 1.0),        # one token a step: prompt 3 + 1
    ((40, 16), 14.0),     # chunks, the last of a prompt partly filled
])
def test_positions_over_prompt_slot_steps(positions, expected):
    profile = _profile()
    for ev, taken in zip(_steps(profile), positions, strict=True):
        ev.stats.append(("prompt_positions", taken))
    assert _read(profile) == pytest.approx(expected)


def test_a_program_that_does_not_count_reads_nothing():
    # the parent's annotations: prompt, output, keys, slots, kv
    assert _read(_profile()) is None
    assert loader.metric_reader(NAME)({}, NS(**RUN)) is None
    # no prompt slot-step in the trace: nothing to divide by
    profile = _profile()
    for ev in _steps(profile):
        ev.stats[:] = [("prompt", 0), ("output", 8),
                       ("prompt_positions", 0)]
    assert _read(profile) is None


def test_traced_cell_on_the_cpu_reads_more_than_one(
        tmp_path, monkeypatch, cpu_in_the_peaks_table):  # noqa: F811
    """The tiny mix's prompts are 4 positions and more and the engine
    takes each in a chunk: more than one position a prompt slot-step,
    and a correct run."""
    run = tiny.tiny_run(tmp_path, CELL, monkeypatch, trace=1)
    line = json.loads(json.dumps(runner.execute(run)))
    assert line["correct"] is True, line["compared"]
    assert line["metrics"][NAME]["value"] > 1.0
    assert line["metrics"][NAME]["unit"] == "positions"
    share = line["metrics"]["decode_prefill_share_pct"]["value"]
    assert 0.0 < share < 100.0


def test_it_is_in_the_benchmark_in_the_dense_generate_cell():
    by_name = {m["name"]: m for m in loader.benchmark()["per_layer"]}
    entry = by_name[NAME]
    assert CELL in entry["workloads"]
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "decode engine", "gen_tok_s", "program_counter",
    )
