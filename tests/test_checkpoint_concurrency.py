"""Concurrent checkpoint saves from threads of one process.

The job engine runs the trials of a ``/tune`` grid on worker threads,
each with its own checkpoint directory.  orbax keys the signals
between a save and its background write by one process-wide counter,
so saves that START at the same moment raced, whatever directory each
wrote to (train/checkpoint.py makes the step directory up front
instead); the barrier below is what makes them start together.
"""

import json
import threading
from functools import partial

import numpy as np
import pytest

from learningorchestra_tpu.train import checkpoint

JOIN_S = 120


def _tree(seed: int, step: int) -> dict:
    rng = np.random.default_rng(1000 * seed + step)
    return {
        "params": {"w": rng.normal(size=(16, 8)).astype(np.float32),
                   "b": rng.normal(size=(8,)).astype(np.float32)},
        "opt_state": {"count": np.asarray(step, np.int32)},
    }


def _run_threads(workers) -> None:
    """Start every worker behind one barrier; re-raise the first error."""
    barrier = threading.Barrier(len(workers))
    errors: list[BaseException] = []

    def wrap(fn):
        def run():
            try:
                barrier.wait(timeout=JOIN_S)
                fn()
            except BaseException as exc:  # noqa: BLE001 - re-raised below
                errors.append(exc)
        return run

    threads = [threading.Thread(target=wrap(fn)) for fn in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=JOIN_S)
    assert not any(t.is_alive() for t in threads), "a saver hung"
    if errors:
        raise errors[0]


def _assert_whole(directory, seed: int, step: int) -> None:
    marker = json.loads((directory / "latest.json").read_text())
    assert marker["step"] == step
    assert marker["history"] == {"seed": seed, "step": step}
    assert not list(directory.glob("*.orbax-checkpoint-tmp*"))
    state, got_step, history = checkpoint.resume_or_none(
        directory, _tree(seed, 0)
    )
    assert got_step == step and history["seed"] == seed
    want = _tree(seed, step)
    np.testing.assert_array_equal(state["params"]["w"], want["params"]["w"])
    np.testing.assert_array_equal(state["params"]["b"], want["params"]["b"])
    assert int(state["opt_state"]["count"]) == step


def _sibling_dirs(tmp_path, async_saves: tuple) -> None:
    """Four savers, two steps each, one directory each."""
    dirs = [tmp_path / f"trial_{i:04d}" for i in range(4)]

    def saver(i):
        def run():
            for step in (1, 2):
                checkpoint.save(
                    dirs[i], step, _tree(i, step),
                    {"seed": i, "step": step}, async_save=async_saves[i],
                )
            checkpoint.finalize_async(dirs[i])
        return run

    _run_threads([saver(i) for i in range(4)])
    for i, d in enumerate(dirs):
        _assert_whole(d, i, 2)


def _one_dir_two_threads(tmp_path) -> None:
    """Two threads save successive steps into ONE directory: the slot
    admits one save in flight, each save publishes the one before it,
    and ``latest.json`` never points behind a step already published."""
    d = tmp_path / "shared"
    turn = threading.Condition()
    state = {"next": 1}
    seen: list[int] = []

    def saver(parity):
        def run():
            for step in range(1, 7):
                if step % 2 != parity:
                    continue
                with turn:
                    assert turn.wait_for(
                        lambda: state["next"] == step, timeout=JOIN_S
                    )
                checkpoint.save(d, step, _tree(0, step),
                                {"seed": 0, "step": step}, async_save=True)
                marker = d / "latest.json"
                if marker.exists():
                    seen.append(json.loads(marker.read_text())["step"])
                with turn:
                    state["next"] = step + 1
                    turn.notify_all()
        return run

    _run_threads([saver(1), saver(0)])
    # Each async save publishes its predecessor before it starts.
    assert seen == [1, 2, 3, 4, 5]
    checkpoint.finalize_async(d)
    _assert_whole(d, 0, 6)
    assert sorted(p.name for p in d.glob("step_*")) == ["step_5", "step_6"]


def _async_then_sync(tmp_path) -> None:
    """A sync save after an async one flushes the pending marker first,
    so a later finalize cannot rewind ``latest.json``."""
    d = tmp_path / "mixed"
    checkpoint.save(d, 1, _tree(0, 1), {"seed": 0, "step": 1},
                    async_save=True)
    checkpoint.save(d, 2, _tree(0, 2), {"seed": 0, "step": 2})
    assert json.loads((d / "latest.json").read_text())["step"] == 2
    checkpoint.finalize_async(d)
    checkpoint.finalize_async()
    _assert_whole(d, 0, 2)


CASES = {
    "async-sibling-dirs": partial(_sibling_dirs, async_saves=(True,) * 4),
    "sync-sibling-dirs": partial(_sibling_dirs, async_saves=(False,) * 4),
    # A sync save advances the same counter: it must not disturb an
    # async save that starts beside it.
    "mixed-sibling-dirs": partial(
        _sibling_dirs, async_saves=(True, False, True, False)
    ),
    "async-one-dir-two-threads": _one_dir_two_threads,
    "async-then-sync-one-dir": _async_then_sync,
}


@pytest.mark.parametrize("case", list(CASES))
def test_concurrent_saves_leave_whole_checkpoints(tmp_path, case):
    CASES[case](tmp_path)
