"""The cell PR 33 added, ``kimi-k2.6.gen-decode-kv2k`` (latent
attention, a sigmoid bias-corrected router over experts of which a
share is held, a shared expert, a leading dense layer; bfloat16
weights job), at tiny widths on the CPU through the real REST path.
The run with the timed path broken underneath (a token altered where
it is produced; the bias left out of the choice; the rotary key not
rotated) and with the int8 control in the engine's place comes out as
not correct each time."""

import importlib.util
import json

import jax.numpy as jnp
import numpy as np
import pytest
import test_block_generate  # noqa: F401 — registers its tiny sizes
import tiny
from lobench import counts_mla, loader, peaks, runner

CELL = "kimi-k2.6.gen-decode-kv2k"

# ``tiny.py`` shrinks every configuration in BENCHMARK.json by name and
# may not be edited here: the new names are added as this file is
# collected, so the older test files find them too.
# A vocabulary of thousands: the top logits then lie close enough
# together that int8 operands change some token, as at the real size.
tiny.SMALL.setdefault("kimi-k2.6", {
    "vocab_size": 4096, "hidden_dim": 32, "num_layers": 3, "num_heads": 4,
    "q_lora_rank": 16, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "mlp_dim": 64,
    "expert_dim": 16, "num_experts": 16, "experts_per_token": 4,
    "experts_held": [4, 8], "max_len": 64, "param_dtype": "float32",
})
tiny.TRAFFIC.setdefault("gen-decode-kv2k", {
    "clients": 3, "shapes": 6, "kv_bucket": 32,
    "prompt": {"mean": 8, "sigma": 0.5, "min": 4},
    "output": {"mean": 14, "sigma": 0.5, "min": 2},
    "total": {"above": 16, "at_most": 32},
    "trace_seconds": 1, "sample_requests": 4,
    "limits": {"logit_gap": 2e-5},  # float32 on the CPU reads under 1e-6
})

#: The new readers that need the device plane of a trace (a kernel's or
#: a step program's device time): nothing to read on the CPU.
FROM_DEVICE = {"gen_idle_pct", "latentgen_hbm_roofline",
               "latent_attend_roofline", "latent_attend_ms",
               "held_experts_roofline", "decode_gap_sync_ms",
               "decode_gap_emit_ms", "decode_gap_admit_ms",
               "decode_gap_dispatch_ms", "decode_gap_unnamed_ms"}


@pytest.fixture(autouse=True)
def cpu_in_the_peaks_table(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def _fresh_programs():
    from learningorchestra_tpu.train import compile_cache

    compile_cache.get_cache().clear()


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
        # and counts as not in place: rare, and never with 64 clients
        assert m["decode_inplace_pct"] > 95
        assert m["decode_ahead_pct"] > 50
        # 3 layers of one (16 + 4)-wide float32 row, nothing per head
        assert m["kv_bytes_per_token"] == 3 * 20 * 4
        # slots x 4 choices x 8 of 16 held, a routed layer and held expert
        assert 0 < m["expert_rows_per_step"] <= 4 * 4 * 8 / 16 / 8 * 4
        assert 0 < m["held_experts_hit_pct"] <= 100
        assert 0 < m["latentgen_mfu_pct"] < 100
    assert not run.scratch.exists()


def test_committed_mix_is_the_traffic_files_own():
    """At the cell's own sizes: 256 shapes dealt round-robin to 64
    clients in the order drawn, all in the 2,048 KV bucket and past
    the 1,024 one; the file's stated means are the drawn ones'."""
    from lobench.kinds import closed_loop_generate as gen

    traffic = loader.traffic("gen-decode-kv2k")
    assert traffic["kind"] == "closed_loop_generate_bf16"
    shapes = gen.draw_shapes(traffic)
    plans = gen.client_plans(traffic)
    assert len(plans) == 64 and all(len(p) == 4 for p in plans)
    assert [plans[i % 64][i // 64] for i in range(256)] == shapes
    assert all(1024 < p + o <= traffic["kv_bucket"] == 2048
               for p, o in shapes)
    prompts, outputs = zip(*shapes)
    assert round(np.mean(prompts)) == 393 and round(np.mean(outputs)) == 945
    assert np.median(prompts) == 221.5 and np.median(outputs) == 994.5
    config = json.loads((loader.BENCH_DIR / "configs" /
                         "kimi-k2.6.json").read_text())
    assert config["server"]["decode"]["max_slots"] == traffic["clients"]


def test_counts_and_leaves_at_the_published_widths():
    path = loader.BENCH_DIR / "configs" / "kimi-k2.6.json"
    config, module = loader.config(path)
    cp = config["class_parameters"]
    assert counts_mla.attention_params(cp) == 101_122_048
    assert counts_mla.expert_params(cp) == 44_040_192
    assert counts_mla.latent_width(cp) == 576
    assert counts_mla.attend_flops_per_key(cp) == 64 * (576 + 512) * 2
    # every leaf the weights job makes: what is read every step, the
    # 12 held experts of 6 routed layers, and the embedding
    total = sum(int(np.prod(shape)) for _n, shape, _i in module.leaves(cp))
    assert total == counts_mla.fixed_params(cp) \
        + 6 * 12 * counts_mla.expert_params(cp) \
        + cp["hidden_dim"] * cp["vocab_size"] == 4_849_591_552
    # a step that reaches every held expert reads all but the embedding
    assert counts_mla.step_bytes(cp, 6 * 12, 0) == 2 * (
        total - cp["hidden_dim"] * cp["vocab_size"]
    )
    # the cache: 7 layers x 576 values x 2 bytes a position
    assert counts_mla.step_bytes(cp, 0, 1) - counts_mla.step_bytes(
        cp, 0, 0) == 8064
    # the file holds every number of the published config under its key
    for key, value in config["published"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == cp["num_layers"] == 7
    assert config["n_routed_experts"] == cp["experts_held"][1] == 12
    assert config["vocab_size"] == cp["vocab_size"] == 20480


def test_the_program_tree_is_the_estimators_own():
    """``program_params`` names every leaf as ``LatentMoELM`` does, at
    tiny widths, with the shapes the module declares."""
    import jax
    from learningorchestra_tpu.toolkit import registry

    path = loader.BENCH_DIR / "configs" / "kimi-k2.6.json"
    config, module = loader.config(path)
    cp = {**config["class_parameters"], **tiny.SMALL["kimi-k2.6"]}
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


# -- the timed path broken underneath: correct must come out false ----------


def _broken(tmp_path, monkeypatch, small=None):
    _fresh_programs()
    try:
        line = _line(tiny.tiny_run(tmp_path, CELL, monkeypatch,
                                   small=small))
    finally:
        _fresh_programs()  # no later test may find the broken program
    assert line["correct"] is False
    c = line["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_token_altered_where_it_is_produced(tmp_path, monkeypatch):
    from learningorchestra_tpu.serve.decode import engine

    real = engine.build_step

    def altered(module, nslots, kv):
        step, shapes = real(module, nslots, kv)

        def bad_step(variables, cache, buf, pos, t0s, live):
            cache, buf, col = step(variables, cache, buf, pos, t0s, live)
            produced = live & (pos + 1 >= t0s)
            toks = jnp.where(produced, (col[:nslots] + 1) % 3000 + 1,
                             col[:nslots])
            buf = buf.at[jnp.arange(nslots), pos + 1].set(toks)
            return cache, buf, jnp.concatenate([toks, col[nslots:]])

        return bad_step, shapes

    monkeypatch.setattr(engine, "build_step", altered)
    _broken(tmp_path, monkeypatch)


def test_bias_left_out_of_the_choice(tmp_path, monkeypatch):
    """Experts chosen by the score alone: other experts for many
    tokens, so other tokens than the reference's."""
    from learningorchestra_tpu.ops import moe

    real = moe.route_top_k
    monkeypatch.setattr(
        moe, "route_top_k",
        lambda logits, k, scoring="softmax", bias=None, scale=1.0:
        real(logits, k, scoring, None, scale),
    )
    # 64 experts: their scores lie as near one another as the bias is
    # wide (std 0.0025), as the 384 of the real size do
    _broken(tmp_path, monkeypatch, small={
        "hidden_dim": 128, "num_experts": 64, "experts_held": [0, 32],
    })


def test_rotary_key_not_rotated(tmp_path, monkeypatch):
    """The cache holds ``k_pe`` as projected: the queries are rotated
    and the keys are not."""
    from learningorchestra_tpu.ops import latent_attention as la

    real = la.rotate

    def keys_unrotated(x, positions, inv_freq, scale=1.0):
        # the shared rotary key is the one operand with no head axis
        return x if x.ndim == 3 else real(x, positions, inv_freq, scale)

    monkeypatch.setattr(la, "rotate", keys_unrotated)
    # Wide enough that the scores' rotary part (weights of 0.02) tells
    # one key from another: at the tiniest widths attention is nearly
    # uniform and no token would change.
    _broken(tmp_path, monkeypatch, small={
        "hidden_dim": 256, "q_lora_rank": 128, "kv_lora_rank": 32,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 64, "v_head_dim": 16,
    })


def test_int8_control_is_not_correct(tmp_path, monkeypatch):
    """The reference with int8 operands in the engine's place, on the
    rows of a sound run, reads past the limit; so does it through
    ``controls_latent.stand_in``, what the chip reading calls."""
    spec = importlib.util.spec_from_file_location(
        "controls_latent", loader.BENCH_DIR / "controls_latent.py")
    controls = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(controls)
    # wide enough that attention and the experts weigh on the logits
    # (at the tiniest widths the embedding alone nearly decides a token)
    run = tiny.tiny_run(tmp_path, CELL, monkeypatch, small={
        "hidden_dim": 256, "q_lora_rank": 128, "kv_lora_rank": 32,
        "qk_rope_head_dim": 64, "expert_dim": 64, "mlp_dim": 256,
    })
    line = _line(run)
    assert line["correct"] is True
    assert controls.stand_in(run, "int8") \
        > run.traffic["limits"]["logit_gap"]


def test_a_program_without_the_class_fails_at_once(tmp_path, monkeypatch):
    from learningorchestra_tpu.toolkit import registry

    def gone(module_path, name):
        raise KeyError(f"no class {name!r} in {module_path!r}")

    monkeypatch.setattr(registry, "resolve", gone)
    run = tiny.tiny_run(tmp_path, CELL, monkeypatch)
    with pytest.raises(SystemExit, match="cannot run kimi-k2.6"):
        runner.execute(run)
    assert run.server is None
