"""Traffic kind ``fit_job``: one whole ``/train/tensorflow`` job a
window, as a pipeline author pays for it: submit -> ``finished``.

Set-up ingests the seed's rows over REST, creates the model, brings the
seed's weights in (``/function/python`` -> ``load_state_dict``), and
runs one 1-epoch job: it builds and warms the per-epoch program (keyed
on rows, batch and shuffle, not on epochs) and is what ``correct``
compares against the reference.  The window's job continues from that
job's artifact, so the window gets the very state and program set-up
drove; of what it publishes, ``correct`` holds the step count, that
every value is finite, and that no parameter moved farther than Adam
can move it (:func:`lobench.compare.window_numbers`).  Its epochs are a
function of ``--seconds`` and the traffic file's constants alone."""

from __future__ import annotations

import time

import numpy as np

from lobench import compare, rest, trace


def epochs_for(traffic: dict, seconds: float) -> int:
    return max(1, int((seconds - traffic["overhead_s"])
                      // traffic["epoch_s"]))


def make_rows(seed: int, traffic: dict, cp: dict):
    """Rows that all differ: token ids 1..vocab-1 (0 is the pad id) and
    one class label each."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(
        1, cp["vocab_size"], (traffic["rows"], traffic["seq"])
    )
    labels = rng.integers(0, cp["num_classes"], traffic["rows"])
    return tokens, labels


def set_up(run) -> dict:
    """Everything before the window; ``benchmarks/controls.py`` stops
    here to read the compared numbers on many seeds in one process."""
    traffic, config, cp = run.traffic, run.config, run.cp
    tokens, labels = make_rows(run.seed, traffic, cp)
    server, ctx = rest.boot(run.scratch, config.get("server"))
    run.server = server
    run.lap("boot")
    fit = {
        "x": "$rows_x", "y": "$rows.label",
        "batch_size": traffic["batch_size"],
    }
    rest.ingest(ctx, run.scratch, "rows", tokens, labels)
    run.lap("ingest")
    ctx.model.create(
        "model", module_path=config["module_path"],
        class_name=config["class"], class_parameters=cp,
    )
    rest.finished(ctx, "model")
    rest.submit_weights(ctx, "weights", run.config_path, run.seed, "state")
    ctx.train.create(
        "seeded", model_name="model", method="load_state_dict",
        method_parameters={"state": "$weights"},
    )
    rest.finished(ctx, "seeded")
    run.lap("weights")
    ctx.train.create(
        "warm", parent_name="seeded", model_name="model",
        method_parameters={**fit, "epochs": 1},
    )
    rest.finished(ctx, "warm")
    run.lap("warm_job")
    return {
        "ctx": ctx, "fit": fit, "tokens": tokens, "labels": labels,
        "warm_loss": float(rest.history(ctx, "warm")[-1]["loss"]),
    }


def run(run) -> dict:
    """``run`` is the harness's :class:`lobench.runner.Run`."""
    traffic = run.traffic
    state = set_up(run)
    ctx, fit = state["ctx"], state["fit"]
    # -- the window: one job --------------------------------------------
    epochs = traffic["trace_epochs"] if run.traced \
        else epochs_for(traffic, run.seconds)
    run.open_window()
    with run.maybe_trace() as cap:
        t0 = time.perf_counter()
        ctx.train.create(
            "timed", parent_name="warm", model_name="model",
            method_parameters={**fit, "epochs": epochs},
        )
        meta = ctx.observe.wait("timed", timeout=1100.0)
        wall = time.perf_counter() - t0
    run.close_window()
    ok = bool(meta.get("finished"))
    hist = rest.history(ctx, "timed") if ok else []
    # A re-fit of a trained artifact appends to its history: the last
    # ``epochs`` rows are the window's.
    hist = hist[-epochs:]
    tokens_trained = traffic["rows"] * traffic["seq"] * epochs
    record = {
        "attempted": 1,
        "failed": 0 if ok and len(hist) == epochs else 1,
        "end_to_end": {"train_tok_s": tokens_trained / wall},
        "job": {
            "wall_s": wall, "epochs": epochs, "tokens": tokens_trained,
            "epoch_times": [float(d["epoch_time"]) for d in hist],
            "losses": [float(d["loss"]) for d in hist],
            "compile_cache": meta.get("compileCache"),
            "rows": traffic["rows"], "seq": traffic["seq"],
            "batch_size": traffic["batch_size"],
        },
        "spans": rest.spans(ctx, "timed") if ok else [],
    }
    if cap is not None:
        record["trace"] = trace.read(cap, record["spans"])
    run.note(spans={
        s["name"]: round(s["durationS"], 4) for s in record["spans"]
    }, epochs=epochs, job_wall_s=round(wall, 3))
    # -- correct: what the timed path published, against the reference --
    timed = run.server.ctx.volumes.read_object(
        "train/tensorflow", "timed"
    ) if ok else None
    record["compared"] = compared(
        run, state, timed, record["job"]["losses"], steps_expected=(
            (1 + epochs) * (traffic["rows"] // traffic["batch_size"])
        ),
    )
    return record


def compared(run, state: dict, timed, window_losses: list,
             steps_expected: int | None) -> dict:
    warm = run.server.ctx.volumes.read_object("train/tensorflow", "warm")
    run.free_program()
    return compare.fit_epoch(
        run, state["tokens"], state["labels"], warm, state["warm_loss"],
        timed, window_losses, steps_expected,
    )
