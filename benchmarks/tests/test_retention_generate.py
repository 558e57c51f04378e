"""The cell PR 36 added, ``brumby-14b-base.gen-anylen`` (power
retention layers whose recurrent state a slot takes the place of K/V
pages; bfloat16 weights job), at tiny widths on the CPU through the
real REST path.  The run with the timed path broken underneath (a
token altered where it is produced; a seated slot not begun from zero;
the gate left out; the state rounded to bfloat16 after every step) and
with the int8 control in the engine's place comes out as not correct
each time."""

import importlib.util
import json

import jax.numpy as jnp
import numpy as np
import pytest
import test_latent_generate  # noqa: F401 — registers the tiny sizes
import tiny
from lobench import counts_retention, loader, peaks, runner

CELL = "brumby-14b-base.gen-anylen"

# ``tiny.py`` shrinks every configuration in BENCHMARK.json by name and
# may not be edited here: the new names are added as this file is
# collected (and the older ones' by the import above).
# A vocabulary of thousands: the top logits then lie close enough
# together that int8 operands change some token, as at the real size.
tiny.SMALL.setdefault("brumby-14b-base", {
    "vocab_size": 4096, "hidden_dim": 64, "num_layers": 3, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "mlp_dim": 96, "max_len": 128,
    "param_dtype": "float32",
})
# Requests of up to 128 positions: the seeded gate (0.9975 a step)
# then leaves 0.73 of the first token's weight at the last, enough for
# a gate left out to show.
tiny.TRAFFIC.setdefault("gen-anylen", {
    "clients": 3, "shapes": 6, "kv_bucket": 128,
    "prompt": {"mean": 30, "sigma": 0.5, "min": 4},
    "output": {"mean": 40, "sigma": 0.5, "min": 2},
    "total": {"at_most": 128},
    "trace_seconds": 1, "sample_requests": 4,
    "limits": {"logit_gap": 2e-5},  # float32 on the CPU reads under 1e-6
})

#: The readers that need the device plane of a trace (a kernel's or a
#: step program's device time): nothing to read on the CPU.
FROM_DEVICE = {"gen_idle_pct", "retgen_hbm_roofline",
               "retention_step_roofline", "retention_step_ms",
               "decode_gap_sync_ms", "decode_gap_emit_ms",
               "decode_gap_admit_ms", "decode_gap_dispatch_ms",
               "decode_gap_unnamed_ms"}


#: Wide enough that the mixer weighs on the logits, and a vocabulary
#: whose top logits lie close together: at the tiniest widths the
#: embedding alone nearly decides a token, and a state off by a part in
#: 256 changes none.
WIDE = {"hidden_dim": 256, "mlp_dim": 64, "num_layers": 2,
        "vocab_size": 16384}


def _controls():
    spec = importlib.util.spec_from_file_location(
        "controls_retention", loader.BENCH_DIR / "controls_retention.py")
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
        # a turn that only reads the last step back dispatches none
        # and counts as not in place: rare, and never with 16 clients
        assert m["decode_inplace_pct"] > 95
        assert m["decode_ahead_pct"] > 50
        # 3 layers x 2 heads x 256 rows (136 products, padded) x (16
        # values + 1) x 4 B, whatever the requests' lengths
        assert m["state_bytes_per_slot"] == 3 * 2 * 256 * 17 * 4
        assert m["decode_pools_live"] == 1
        assert 0 < m["retgen_mfu_pct"] < 100
    assert not run.scratch.exists()


def test_committed_mix_is_the_traffic_files_own():
    """At the cell's own sizes: 128 shapes dealt round-robin to 16
    clients in the order drawn, every length up to 1,024 with no lower
    bound: five KV buckets' worth; the file's stated numbers are the
    drawn ones'."""
    from lobench.kinds import closed_loop_generate as gen

    traffic = loader.traffic("gen-anylen")
    assert traffic["kind"] == "closed_loop_generate_bf16"
    shapes = gen.draw_shapes(traffic)
    plans = gen.client_plans(traffic)
    assert len(plans) == 16 and all(len(p) == 8 for p in plans)
    assert [plans[i % 16][i // 16] for i in range(128)] == shapes
    totals = np.array([p + o for p, o in shapes])
    assert totals.min() == 41 and totals.max() == 999
    assert totals.max() <= traffic["kv_bucket"] == 1024
    edges = [0, 64, 128, 256, 512, 1024]
    assert [int(((totals > a) & (totals <= b)).sum())
            for a, b in zip(edges, edges[1:])] == [4, 8, 30, 38, 48]
    prompts, outputs = map(np.array, zip(*shapes))
    assert round(prompts.mean()) == 160 and round(outputs.mean()) == 258
    assert np.median(prompts) == 102 and np.median(outputs) == 178.5
    assert round(100 * (prompts - 1).sum() / (totals - 1).sum()) == 38
    assert totals[:16].max() == 724  # set-up's longest first request
    config = json.loads((loader.BENCH_DIR / "configs" /
                         "brumby-14b-base.json").read_text())
    assert config["server"]["decode"]["max_slots"] == traffic["clients"]
    assert config["server"]["decode"]["max_new_tokens"] >= outputs.max()


def test_counts_and_leaves_at_the_published_widths():
    path = loader.BENCH_DIR / "configs" / "brumby-14b-base.json"
    config, module = loader.config(path)
    cp = config["class_parameters"]
    assert counts_retention.layer_params(cp) == 330_352_904
    assert counts_retention.state_rows(cp) == 8256
    assert counts_retention.state_values_per_layer(cp) * 4 == 34_080_768
    assert counts_retention.state_bytes_per_slot(cp) == 238_565_376
    assert counts_retention.state_bytes_per_slot(cp, 8320) == 240_414_720
    # every leaf the weights job makes: what a step reads, and the
    # embedding
    total = sum(int(np.prod(shape)) for _n, shape, _i in module.leaves(cp))
    assert total == counts_retention.fixed_params(cp) \
        + cp["hidden_dim"] * cp["vocab_size"] == 3_868_300_088
    # a step of 16 live slots: the weights but the embedding once, and
    # every state in and out: 55% of its bytes the states'
    weights = counts_retention.step_bytes(cp, 0)
    assert weights == 2 * (total - cp["hidden_dim"] * cp["vocab_size"])
    states = counts_retention.retention_bytes(cp, 16)
    assert counts_retention.step_bytes(cp, 16) == weights + states
    assert 2 * 16 * 238_565_376 < states < 1.001 * 2 * 16 * 238_565_376
    assert round(100 * states / (weights + states)) == 55
    # the recurrence: 2 x (8 + 40) x 8,256 x 129 a slot and layer
    assert counts_retention.retention_flops(cp, 1) \
        == 7 * 2 * 48 * 8256 * 129
    # the file holds every number of the published config under its key
    for key, value in config["published"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == cp["num_layers"] == 7
    assert config["published"]["num_hidden_layers"] == 40


def test_the_program_tree_is_the_estimators_own():
    """``program_params`` names every leaf as ``RetentionLM`` does, at
    tiny widths, with the shapes the module declares; the gate's bias
    is the leaf plus 6."""
    import jax
    from learningorchestra_tpu.toolkit import registry

    path = loader.BENCH_DIR / "configs" / "brumby-14b-base.json"
    config, module = loader.config(path)
    cp = {**config["class_parameters"], **tiny.SMALL["brumby-14b-base"]}
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
    bias = got["params"]["RetentionBlock_1"]["PowerRetention_0"]["gate"][
        "bias"]
    assert bias.tolist() == [6.0, 6.0]


# -- the timed path broken underneath: correct must come out false ----------


def _not_correct(line):
    assert line["correct"] is False
    c = line["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_token_altered_where_it_is_produced(tmp_path, monkeypatch):
    from learningorchestra_tpu.serve.decode import engine
    from learningorchestra_tpu.train import compile_cache

    real = engine.build_step

    def altered(module, nslots, kv):
        step, shapes = real(module, nslots, kv)

        def bad_step(variables, cache, buf, pos, t0s, live):
            cache, buf, col = step(variables, cache, buf, pos, t0s, live)
            produced = live & (pos + 1 >= t0s)
            toks = jnp.where(produced, (col + 1) % 3000 + 1, col)
            buf = buf.at[jnp.arange(nslots), pos + 1].set(toks)
            return cache, buf, toks

        return bad_step, shapes

    monkeypatch.setattr(engine, "build_step", altered)
    compile_cache.get_cache().clear()
    try:
        _not_correct(_line(tiny.tiny_run(tmp_path, CELL, monkeypatch)))
    finally:
        compile_cache.get_cache().clear()


@pytest.mark.parametrize("fault", ["no_reset", "no_gate", "state_bf16"])
def test_a_fault_under_the_state_is_not_correct(fault, tmp_path,
                                                monkeypatch):
    """``no_reset``: a client's second request sits where its first
    left a state.  ``no_gate``: nothing decays.  ``state_bf16``: the
    state rounded to bfloat16 after every step."""
    with _controls().broken(fault):
        line = _line(tiny.tiny_run(tmp_path, CELL, monkeypatch, small=WIDE))
    _not_correct(line)


def test_int8_control_is_not_correct(tmp_path, monkeypatch):
    """The reference with int8 operands in the engine's place, on the
    rows of a sound run, reads past the limit, through
    ``controls_retention.stand_in``, what the chip reading calls; the
    stand-in of the reference itself reads nothing."""
    controls = _controls()
    run = tiny.tiny_run(tmp_path, CELL, monkeypatch, small=WIDE)
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
    with pytest.raises(SystemExit, match="cannot run brumby-14b-base"):
        runner.execute(run)
    assert run.server is None
