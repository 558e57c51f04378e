"""Each traffic kind end to end at tiny widths on the CPU, through the
real REST path: everything a run does after its look for a chip.  The
result line has the contract's keys; the program and the reference
agree; and the same runs with the timed path broken underneath come out
as not correct."""

import json

import jax.numpy as jnp
import pytest
import tiny
from lobench import peaks, runner

CELLS = ["bert-base.fit-s512", "gpt2-xl.gen-decode"]


@pytest.fixture(autouse=True)
def cpu_in_the_peaks_table(monkeypatch):
    # execute() describes the device; only look_for_chip() insists on a TPU.
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def _line(run) -> dict:
    return json.loads(json.dumps(runner.execute(run)))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end(cell, trace, tmp_path, monkeypatch):
    run = tiny.tiny_run(tmp_path, cell, monkeypatch, trace=trace)
    line = _line(run)
    assert list(line)[:5] == [
        "correct", "attempted", "failed", "metrics", "device",
    ] and list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"]
    )
    group = "per_layer" if trace else "end_to_end"
    wanted = {
        m["name"] for m in run.bench[group]
        if "workloads" not in m or cell in m["workloads"]
    }
    # No device plane on the CPU: the trace's readers return nothing
    # and the harness leaves them out; the others are all there.
    from_trace = {"flash_roofline", "fit_idle_pct", "gen_idle_pct",
                  "decode_hbm_roofline"}
    assert set(line["metrics"]) == wanted - from_trace
    for name, m in line["metrics"].items():
        assert isinstance(m["value"], float) and m["unit"]
        if name.endswith("window_compiles"):
            assert m["value"] == 0
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}
    assert not run.scratch.exists()


def test_no_chip_no_result(capsys):
    with pytest.raises(SystemExit) as err:
        runner.main(["--workload", CELLS[0], "--seed", "3000000000",
                     "--seconds", "1", "--trace", "0"])
    assert err.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_the_seed_draws_token_ids_and_nothing_else(tmp_path, monkeypatch):
    """Two seeds give every client the same sequence of (prompt,
    output) sizes, from the traffic file alone, and different ids."""
    from lobench.kinds import closed_loop_generate as gen
    from lobench.kinds import fit_job

    run = tiny.tiny_run(tmp_path, CELLS[1], monkeypatch, seed=2**31 + 9)
    plans = gen.client_plans(run.traffic)
    assert plans == gen.client_plans(run.traffic)
    assert len(plans) == run.traffic["clients"]
    assert sorted(s for p in plans for s in p) == sorted(
        gen.draw_shapes(run.traffic)
    )
    other = gen.client_plans({**run.traffic, "shape_seed": 6})
    assert other != plans  # sizes and order are the traffic file's alone

    def sent(seed):  # what each client sends through two passes of its plan
        out = []
        for i, plan in enumerate(plans):
            draw = gen.prompt_ids(seed, i, 96)
            out.append([(draw(p), o) for p, o in plan * 2])
        return out

    a, b, c = sent(run.seed), sent(run.seed), sent(run.seed + 1)
    sizes = lambda cl: [[(len(p), o) for p, o in r] for r in cl]  # noqa: E731
    assert a == b and a != c and sizes(a) == sizes(c)
    prompts = [tuple(p) for cl in a for p, _ in cl]
    assert len(set(prompts)) == len(prompts)  # no prompt sent twice
    assert min(t for p in prompts for t in p) >= 1
    fit = tiny.tiny_run(tmp_path, CELLS[0], monkeypatch, seed=2**31 + 9)
    x1, y1 = fit_job.make_rows(fit.seed, fit.traffic, fit.cp)
    x2, y2 = fit_job.make_rows(fit.seed, fit.traffic, fit.cp)
    assert (x1 == x2).all() and (y1 == y2).all() and x1.min() >= 1
    assert fit_job.epochs_for({"overhead_s": 11.0, "epoch_s": 3.35}, 51) == 11


def test_committed_mix_is_the_traffic_files_own():
    """At the cell's own sizes: 8 clients, 8 requests each, the 64
    shapes dealt once in the order drawn, every request inside the one
    KV bucket that the file names and within the server's setting."""
    from lobench import loader
    from lobench.kinds import closed_loop_generate as gen

    traffic = loader.traffic("gen-decode")
    shapes = gen.draw_shapes(traffic)
    plans = gen.client_plans(traffic)
    assert [len(p) for p in plans] == [8] * 8
    assert [plans[i % 8][i // 8] for i in range(64)] == shapes
    totals = [p + o for p, o in shapes]
    assert traffic["kv_bucket"] // 2 < min(totals) \
        and max(totals) <= traffic["kv_bucket"]
    bench = loader.benchmark()
    config, _ = loader.config(loader.config_path(bench, "gpt2-xl"))
    assert max(o for _, o in shapes) \
        <= config["server"]["decode"]["max_new_tokens"]
    assert min(p for p, _ in shapes) >= traffic["prompt"]["min"]


# -- the timed path broken underneath: correct must come out false ----------


def _fresh_programs():
    from learningorchestra_tpu.train import compile_cache

    compile_cache.get_cache().clear()


def test_fit_state_left_unchanged(tmp_path, monkeypatch):
    from learningorchestra_tpu.train import neural

    _fresh_programs()
    monkeypatch.setattr(
        neural.optax, "apply_updates", lambda params, updates: params
    )
    line = _line(tiny.tiny_run(tmp_path, CELLS[0], monkeypatch))
    _fresh_programs()
    assert line["correct"] is False
    assert line["compared"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_fit_window_job_publishes_a_value_that_is_no_number(
        tmp_path, monkeypatch):
    """The warm job is sound, so the reference's numbers pass; the
    window's own artifact is what fails."""
    import jax
    from learningorchestra_tpu.store import volumes

    real = volumes.VolumeStorage.save_object

    def spoiled(self, artifact_type, name, obj):
        if name == "timed":
            obj.params = jax.tree_util.tree_map(
                lambda a: a.at[(0,) * a.ndim].set(jnp.nan)
                if a.ndim == 2 else a, obj.params,
            )
        return real(self, artifact_type, name, obj)

    monkeypatch.setattr(volumes.VolumeStorage, "save_object", spoiled)
    line = _line(tiny.tiny_run(tmp_path, CELLS[0], monkeypatch))
    assert line["correct"] is False
    compared = line["compared"]
    assert compared["window_nonfinite"]["value"] > 0
    assert compared["update_norm_gap"]["value"] \
        <= compared["update_norm_gap"]["limit"]


def test_fit_half_of_the_batch_left_out(tmp_path, monkeypatch):
    from learningorchestra_tpu.train import neural

    real = neural.NeuralEstimator._loss_and_metrics

    def halved(loss_kind):
        fn = real(loss_kind)

        def loss(logits, y, mask):
            keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
            return fn(logits, y, mask * keep)  # mean over the half left

        return loss

    _fresh_programs()
    monkeypatch.setattr(
        neural.NeuralEstimator, "_loss_and_metrics", staticmethod(halved)
    )
    line = _line(tiny.tiny_run(tmp_path, CELLS[0], monkeypatch))
    _fresh_programs()
    assert line["correct"] is False
    c = line["compared"]["grad_norm_gap"]
    assert c["value"] > c["limit"]


def test_generate_token_altered_where_it_is_produced(tmp_path, monkeypatch):
    from learningorchestra_tpu.serve.decode import engine

    real = engine.build_step

    def altered(module, nslots, kv):
        step, shapes = real(module, nslots, kv)

        def bad_step(variables, cache, buf, pos, t0s, live):
            cache, buf, col = step(variables, cache, buf, pos, t0s, live)
            produced = live & (pos + 1 >= t0s)
            col = jnp.where(produced, (col + 1) % 90 + 1, col)
            buf = buf.at[jnp.arange(nslots), pos + 1].set(col)
            return cache, buf, col

        return bad_step, shapes

    _fresh_programs()
    monkeypatch.setattr(engine, "build_step", altered)
    line = _line(tiny.tiny_run(tmp_path, CELLS[1], monkeypatch))
    _fresh_programs()
    assert line["correct"] is False
    c = line["compared"]["logit_gap"]
    assert c["value"] > c["limit"]
