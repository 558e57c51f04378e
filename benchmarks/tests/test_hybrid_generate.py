"""The cell ``falcon-h1-34b-instruct.gen-chat64`` (every layer a Mamba-2
mixer beside grouped-query attention: K/V pages, a float32 state and a
convolution tail a slot; bfloat16 weights job) at tiny widths on the
CPU through the real REST path.  The run with each fault of
``controls_hybrid.py`` underneath, and with the int8 control in the
engine's place, comes out as not correct."""

import importlib.util
import json

import jax.numpy as jnp
import numpy as np
import pytest
import test_retention_generate  # noqa: F401 — registers the tiny sizes
import tiny
from lobench import counts_hybrid, loader, peaks, runner

CELL = "falcon-h1-34b-instruct.gen-chat64"
CONFIG = "falcon-h1-34b-instruct"

# ``tiny.py`` shrinks every configuration in BENCHMARK.json by name and
# may not be edited here: the new names are added as this file is
# collected.  A vocabulary of thousands: the top logits then lie close
# enough together that int8 operands change some token.
tiny.SMALL.setdefault(CONFIG, {
    "vocab_size": 4096, "hidden_dim": 64, "num_layers": 2, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "mlp_dim": 96, "mamba_heads": 4,
    "mamba_head_dim": 16, "mamba_groups": 2, "mamba_state": 16,
    "mamba_chunk": 8, "max_len": 128, "param_dtype": "float32",
})
tiny.TRAFFIC.setdefault("gen-chat64", {
    "clients": 4, "shapes": 8, "kv_bucket": 128,
    "prompt": {"mean": 30, "sigma": 0.5, "min": 4},
    "output": {"mean": 60, "sigma": 0.5, "min": 2},
    "total": {"above": 64, "at_most": 128},
    "trace_seconds": 1, "sample_requests": 8,
    # float32 on the CPU reads under 1e-8 at a head multiplier of 1/128
    "limits": {"logit_gap": 1e-6},
})

#: The published multipliers shrink each branch beside the residual (at
#: these widths the mixers' share of a logit is a few percent, so a
#: fault in one seldom changes a token); the faults are read with every
#: multiplier 1 and a wider model, whose top logits lie close together.
LOUD = {"embedding_multiplier": 1.0, "attention_out_multiplier": 1.0,
        "key_multiplier": 1.0, "ssm_in_multiplier": 1.0,
        "ssm_multipliers": [1.0] * 5, "ssm_out_multiplier": 1.0,
        "mlp_multipliers": [1.0, 1.0], "lm_head_multiplier": 1.0,
        "hidden_dim": 256, "vocab_size": 16384, "mamba_heads": 8,
        "mamba_head_dim": 32, "mamba_state": 64}

#: The readers that need the device plane of a trace (a kernel's or a
#: step program's device time): nothing to read on the CPU.
FROM_DEVICE = {"gen_idle_pct", "hybridgen_hbm_roofline",
               "ssm_step_roofline", "ssm_step_ms", "decode_attend_ms",
               "decode_gap_sync_ms", "decode_gap_emit_ms",
               "decode_gap_admit_ms", "decode_gap_dispatch_ms",
               "decode_gap_unnamed_ms"}


def _controls():
    spec = importlib.util.spec_from_file_location(
        "controls_hybrid", loader.BENCH_DIR / "controls_hybrid.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(autouse=True)
def cpu_in_the_peaks_table(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def _line(run) -> dict:
    return json.loads(json.dumps(runner.execute(run)))


def _reported(run, trace):
    group = "per_layer" if trace else "end_to_end"
    return {m["name"] for m in run.bench[group]
            if "workloads" not in m or CELL in m["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_end_to_end(trace, tmp_path, monkeypatch):
    run = tiny.tiny_run(tmp_path, CELL, monkeypatch, trace=trace)
    line = _line(run)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["compared"]) == {"logit_gap"}
    assert set(line["metrics"]) == _reported(run, trace) - FROM_DEVICE
    if trace:
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["gen_window_compiles"] == 0
        # 2 layers x (4 heads x 16 x 16 + 3 x (64 + 2 x 2 x 16)) x 4 B
        assert m["state_bytes_per_slot"] == 2 * (4 * 16 * 16 + 3 * 128) * 4
        # 2 layers x K and V x 2 heads x 16 x 4 B
        assert m["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
        assert m["decode_pools_live"] == 1
        assert 0 < m["hybridgen_mfu_pct"] < 100
    assert not run.scratch.exists()


def test_committed_mix_is_the_traffic_files_own():
    """At the cell's own sizes: 256 shapes dealt round-robin to 64
    clients in the order drawn, every total in (256, 512]: one KV
    bucket; the file's stated numbers are the drawn ones'."""
    from lobench.kinds import closed_loop_generate as gen

    traffic = loader.traffic("gen-chat64")
    assert traffic["kind"] == "closed_loop_generate_bf16"
    shapes = gen.draw_shapes(traffic)
    plans = gen.client_plans(traffic)
    assert len(plans) == 64 and all(len(p) == 4 for p in plans)
    assert [plans[i % 64][i // 64] for i in range(256)] == shapes
    prompts, outputs = map(np.array, zip(*shapes))
    totals = prompts + outputs
    assert totals.min() > 256 and totals.max() <= traffic["kv_bucket"]
    assert (prompts.min(), prompts.max()) == (10, 433)
    assert (outputs.min(), outputs.max()) == (10, 489)
    assert round(prompts.mean()) == 137 and round(outputs.mean()) == 229
    assert totals[:64].max() == 512  # set-up's longest first request
    config = json.loads((loader.BENCH_DIR / "configs" /
                         f"{CONFIG}.json").read_text())
    assert config["server"]["decode"]["max_slots"] == traffic["clients"]
    assert config["server"]["decode"]["max_new_tokens"] >= outputs.max()


def test_counts_and_leaves_at_the_published_widths():
    path = loader.BENCH_DIR / "configs" / f"{CONFIG}.json"
    config, module = loader.config(path)
    cp = config["class_parameters"]
    assert counts_hybrid.layer_params(cp) == 430_120_032
    assert counts_hybrid.state_values(cp) * 4 == 4_194_304
    assert counts_hybrid.tail_values(cp) * 4 == 61_440
    # every leaf the weights job makes: what a step reads, and the
    # embedding
    total = sum(int(np.prod(shape)) for _n, shape, _i in module.leaves(cp))
    assert total == counts_hybrid.fixed_params(cp) \
        + cp["hidden_dim"] * cp["vocab_size"] == 5_254_594_112
    # the kernel's bytes: each state in and out, bound by its bytes
    assert 2 * 4_194_304 * 6 < counts_hybrid.ssm_bytes(cp, 1) \
        < 1.01 * 2 * 4_194_304 * 6
    # the file holds every number of the published config under its key
    for key, value in config["published"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == cp["num_layers"] == 6
    assert config["published"]["num_hidden_layers"] == 72


def test_the_program_tree_is_the_estimators_own():
    """``program_params`` names every leaf as ``HybridLM`` does, at tiny
    widths, with the shapes the module declares; the seeded leaves are
    the family's."""
    import jax
    from learningorchestra_tpu.toolkit import registry

    path = loader.BENCH_DIR / "configs" / f"{CONFIG}.json"
    config, module = loader.config(path)
    cp = {**config["class_parameters"], **tiny.SMALL[CONFIG]}
    est = registry.resolve(config["module_path"], config["class"])(**cp)
    want = jax.eval_shape(
        est.module.init, jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32)
    )
    flat = {name: np.zeros(shape, np.float32)
            for name, shape, _init in module.leaves(cp)}
    got = module.program_params(flat, cp)
    assert jax.tree_util.tree_structure(got) \
        == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape
    mixer = got["params"]["HybridBlock_1"]["Mamba2Mixer_0"]
    # the decays spaced evenly in the logarithm over [0.01, 1]; A_log
    # rounded to bfloat16 (-4.6 to 1/64 of a unit: 1.6% of A)
    np.testing.assert_allclose(np.exp(mixer["A_log"]),
                               [0.01, 0.0464, 0.2154, 1.0], rtol=2e-2)
    # a drawn 0 is the median: dt = sqrt(1e-3 x 1e-1) = 0.01
    dt = np.log1p(np.exp(mixer["dt_bias"]))
    np.testing.assert_allclose(dt, 0.01, rtol=1e-2)


# -- the timed path broken underneath: correct must come out false ----------


def _not_correct(line):
    assert line["correct"] is False
    c = line["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault", ["no_reset", "state_bf16",
                                   "tail_frozen", "kv_unwritten"])
def test_a_fault_under_the_caches_is_not_correct(fault, tmp_path,
                                                 monkeypatch):
    """``no_reset``: a client's second request sits where its first
    left a state.  ``state_bf16``: the state rounded to bfloat16 after
    every step.  ``tail_frozen``: the convolution's tail never shifted.
    ``kv_unwritten``: attention's rows never reach the pages."""
    with _controls().broken(fault):
        line = _line(tiny.tiny_run(tmp_path, CELL, monkeypatch, small=LOUD))
    _not_correct(line)


def test_int8_control_is_not_correct(tmp_path, monkeypatch):
    """The reference with int8 operands in the engine's place, on the
    rows of a sound run, reads past the limit; the stand-in of the
    reference itself reads nothing."""
    controls = _controls()
    run = tiny.tiny_run(tmp_path, CELL, monkeypatch, small=LOUD)
    line = _line(run)
    assert line["correct"] is True
    gap = controls.stand_in(run, "int8")
    assert gap > run.traffic["limits"]["logit_gap"]
    assert controls.verdict(run, gap) is False
    assert controls.stand_in(run, None) == 0.0
    assert controls.verdict(run, 0.0) is True


def test_a_program_without_the_class_fails_at_once(tmp_path, monkeypatch):
    from learningorchestra_tpu.toolkit import registry

    def gone(module_path, name):
        raise KeyError(f"no class {name!r} in {module_path!r}")

    monkeypatch.setattr(registry, "resolve", gone)
    run = tiny.tiny_run(tmp_path, CELL, monkeypatch)
    with pytest.raises(SystemExit, match=f"cannot run {CONFIG}"):
        runner.execute(run)
    assert run.server is None
