"""The controls at a size a test run can hold: the reference in the
control's precision, or with a fault planted, put in the program's
place has to read wider than the program does, and past the limit the
tiny cells are held to.  ``benchmarks/controls.py`` reads the same on
the chip at each cell's own size."""

import sys

import numpy as np
import pytest
import tiny
from lobench import compare, peaks

sys.path.insert(0, str(tiny.BENCH_DIR))
import controls  # noqa: E402


@pytest.fixture(autouse=True)
def cpu_in_the_peaks_table(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def _fails(numbers: dict, limits: dict) -> list:
    return [k for k, v in numbers.items() if k in limits and v > limits[k]]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fit_control_and_fault_read_past_the_limits(seed, tmp_path,
                                                    monkeypatch):
    run = tiny.tiny_run(tmp_path, "bert-base.fit-s512", monkeypatch,
                        seed=seed)
    limits = run.traffic["limits"]
    same = controls.fit_stand_in(run)
    assert _fails(same, limits) == [] and max(same.values()) < 1e-6
    assert "loss_gap" in same and "loss_gap" not in limits  # read only
    assert _fails(controls.fit_stand_in(run, quant="int8"), limits)
    assert "grad_norm_gap" in _fails(
        controls.fit_stand_in(run, fault="half_batch"), limits
    )


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_generate_control_reads_past_the_limit(seed, tmp_path, monkeypatch):
    """At each position of the same rows, the token that int8 puts
    first lies below the reference's best by more than the limit."""
    run = tiny.tiny_run(
        tmp_path, "gpt2-xl.gen-decode", monkeypatch, seed=seed,
        small={"vocab_size": 4096, "hidden_dim": 64, "num_layers": 4,
               "num_heads": 4, "mlp_dim": 256},
    )
    rng = np.random.default_rng(seed)
    finished = [
        {"prompt": rng.integers(1, 4096, 8).tolist(),
         "tokens": rng.integers(1, 4096, 40).tolist()} for _ in range(4)
    ]
    tokens, first, last = compare.sample_rows(finished, 64, 4)
    limit = run.traffic["limits"]["logit_gap"]
    args = (run.reference, seed, run.cp, tokens, first, last)
    # the reference's own first choices read 0 against itself
    assert compare.served_gap(*args, quant=None, of_control=True) == 0.0
    assert compare.served_gap(*args, quant="int8", of_control=True) > limit


def _artifact(params, count=8):
    import optax
    from types import SimpleNamespace

    zeros = {k: np.zeros_like(v) for k, v in params.items()}
    return SimpleNamespace(params=params, opt_state=(
        optax.ScaleByAdamState(count=np.int32(count), mu=zeros, nu=zeros),
    ))


@pytest.mark.parametrize("fault, failing", [
    (None, []),
    ("nan", ["window_nonfinite"]),
    ("loss", ["window_nonfinite"]),
    ("other_weights", ["window_step_size"]),
    ("unchanged_count", ["window_steps_gap"]),
    ("no_artifact", ["window_nonfinite", "window_step_size",
                     "window_steps_gap"]),
])
def test_window_numbers_hold_what_the_timed_job_published(fault, failing):
    """Step count, finite values and Adam's bound on a parameter's
    move, each failed by the fault that is its to catch."""
    from lobench import loader

    limits = loader.traffic("fit-s512")["limits"]
    lr, rng = 2e-5, np.random.default_rng(0)
    w0 = {"a": rng.normal(0, 0.02, (8, 4)).astype(np.float32)}
    warm = _artifact(w0, count=4)
    moved = {"a": w0["a"] + np.float32(4 * lr)}  # one lr a step
    losses = [0.7, 0.69]
    timed = _artifact(moved, count=8)
    if fault == "nan":
        timed.params["a"][0, 0] = np.nan
    elif fault == "loss":
        losses[1] = float("nan")
    elif fault == "other_weights":
        timed = _artifact(
            {"a": rng.normal(0, 0.02, (8, 4)).astype(np.float32)}, count=8
        )
    elif fault == "unchanged_count":
        timed = _artifact(moved, count=4)
    elif fault == "no_artifact":
        timed = None
    numbers = compare.window_numbers(warm, timed, losses, lr, 8)
    assert sorted(_fails(numbers, limits)) == failing
    if fault is None:
        assert numbers["window_step_size"] == pytest.approx(1.0, rel=1e-3)
