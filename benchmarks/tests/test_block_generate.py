"""The cell PR 29 added, ``sdar-30b-a3b-chat.gen-blocks`` (generation by
diffusion over blocks, its own kind and comparison), at tiny widths on
the CPU through the real REST path.  The run with the timed
path broken underneath (a token altered where it is fixed; a block
committed with a mask left; the K/V of a denoising forward kept in
place of the commit's) and with the int8 control in the engine's place
comes out as not correct each time."""

import json

import numpy as np
import pytest
import tiny
from lobench import compare_blocks, counts_moe, loader, peaks, runner

BLOCKS = "sdar-30b-a3b-chat.gen-blocks"

# ``tiny.py`` shrinks every configuration in BENCHMARK.json by name and
# may not be edited here: the new names are added as this file is
# collected, so the older test files find them too.
# A vocabulary of thousands: the top logits then lie close enough
# together that int8 operands change some token, as at the real size.
tiny.SMALL.setdefault("sdar-30b-a3b-chat", {
    "vocab_size": 4096, "hidden_dim": 32, "num_layers": 2, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 8, "expert_dim": 16, "num_experts": 8,
    "experts_per_token": 2, "max_len": 64, "mask_token_id": 4000,
    "param_dtype": "float32",
})
tiny.TRAFFIC.setdefault("gen-blocks", {
    "clients": 3, "shapes": 6, "kv_bucket": 32, "max_new_tokens": 14,
    "prompt": {"mean": 8, "sigma": 0.5, "min": 4, "max": 16},
    "trace_seconds": 1, "sample_requests": 4,
    # float32 on the CPU reads under 1e-6
    "limits": {"logit_gap": 2e-5, "order_faults": 0},
})

@pytest.fixture(autouse=True)
def cpu_in_the_peaks_table(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def _fresh_programs():
    from learningorchestra_tpu.train import compile_cache

    compile_cache.get_cache().clear()


def _line(run) -> dict:
    return json.loads(json.dumps(runner.execute(run)))


def _reported(run, cell, trace):
    group = "per_layer" if trace else "end_to_end"
    return {m["name"] for m in run.bench[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_gen_blocks_end_to_end(trace, tmp_path, monkeypatch):
    run = tiny.tiny_run(tmp_path, BLOCKS, monkeypatch, trace=trace)
    line = _line(run)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["compared"]) == {"logit_gap", "order_faults"}
    # No device plane on the CPU: what reads one returns nothing.
    from_trace = {"gen_idle_pct", "blockgen_hbm_roofline",
                  "moe_experts_roofline", "decode_gap_sync_ms",
                  "decode_gap_emit_ms", "decode_gap_admit_ms",
                  "decode_gap_dispatch_ms", "decode_gap_unnamed_ms"}
    assert set(line["metrics"]) == _reported(run, BLOCKS, trace) - from_trace
    if trace:
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["gen_window_compiles"] == 0
        assert m["decode_inplace_pct"] == 100
        # 2 denoising forwards and a commit fix a block of 4; a
        # prompt's remainder in a first block lowers it a little
        assert 1.0 < m["tokens_per_forward"] <= 4 / 3 + 1e-9
        assert 0 < m["experts_hit_pct"] <= 100
        assert 0 < m["blockgen_mfu_pct"] < 100
    assert not run.scratch.exists()


def test_committed_mix_is_the_traffic_files_own():
    """At the cell's own sizes: 64 shapes dealt round-robin to 8
    clients in the order drawn, inside the one KV bucket."""
    from lobench.kinds import closed_loop_generate as gen

    traffic = loader.traffic("gen-blocks")
    assert traffic["max_new_tokens"] == 256
    traffic = {**traffic, "output": {"median": 256, "sigma": 0.0}}
    shapes = gen.draw_shapes(traffic)
    plans = gen.client_plans(traffic)
    assert [plans[i % 8][i // 8] for i in range(64)] == shapes
    assert all(4 <= p <= 256 and o == 256 for p, o in shapes)
    assert max(p + o for p, o in shapes) <= traffic["kv_bucket"]


def test_prompts_draw_neither_pad_nor_mask():
    from lobench.kinds import closed_loop_block_generate as blocks

    draw = blocks.prompt_ids(2**31 + 5, 1, 96, 40)
    ids = np.array(draw(5000))
    assert ids.min() == 1 and ids.max() == 95 and not (ids == 40).any()


def test_counts_at_the_published_widths():
    cp = json.loads((loader.BENCH_DIR / "configs" /
                     "sdar-30b-a3b-chat.json").read_text())[
        "class_parameters"]
    assert counts_moe.attention_params(cp) == 18_874_368
    assert counts_moe.expert_params(cp) == 4_718_592
    # every expert of every layer, the dense parts, head and embedding
    total = cp["num_layers"] * (
        counts_moe.dense_layer_params(cp) + 128 * counts_moe.expert_params(cp)
    ) + 2 * cp["hidden_dim"] * cp["vocab_size"] + cp["hidden_dim"]
    assert round(total / 1e9, 2) == 4.98
    # a step that reaches every expert reads all of it but the embedding
    assert counts_moe.step_bytes(cp, 7 * 128, 0) == 2 * (
        total - cp["hidden_dim"] * cp["vocab_size"]
    )


# -- the timed path broken underneath: correct must come out false ----------


def _broken(tmp_path, monkeypatch):
    _fresh_programs()
    line = _line(tiny.tiny_run(tmp_path, BLOCKS, monkeypatch))
    _fresh_programs()
    assert line["correct"] is False
    return line["compared"]


def test_token_altered_where_it_is_fixed(tmp_path, monkeypatch):
    from learningorchestra_tpu.serve.decode import blocks

    real = blocks.BlockState.denoise

    def altered(self, x0, conf):
        return real(self, (np.asarray(x0) + 1) % 3000 + 1, conf)

    monkeypatch.setattr(blocks.BlockState, "denoise", altered)
    c = _broken(tmp_path, monkeypatch)["logit_gap"]
    assert c["value"] > c["limit"]


def test_block_committed_with_a_mask_left(tmp_path, monkeypatch):
    from learningorchestra_tpu.serve.decode import blocks

    # after its last denoising forward a block counts as final though
    # that forward was made to fix one position fewer
    monkeypatch.setattr(
        blocks.BlockState, "final",
        property(lambda self: self.step >= self.plan.steps
                 or not self.masked.any()),
    )
    real = blocks.transfer_count
    monkeypatch.setattr(
        blocks, "transfer_count",
        lambda block, steps, step: real(block, steps, step)
        - (step == steps - 1),
    )
    c = _broken(tmp_path, monkeypatch)["order_faults"]
    assert c["value"] > 0


def test_kv_of_a_denoising_forward_kept_for_the_commit(tmp_path,
                                                       monkeypatch):
    """The commit forward runs over the block's last noisy state and
    not over its final tokens: the tokens sent are the sound ones, but
    every later block attends K/V that the commit should have
    replaced."""
    from learningorchestra_tpu.serve.decode import engine

    real = engine.build_step

    def stale(module, nslots, kv):
        step, shapes = real(module, nslots, kv)
        mask_id = 4000
        seen = {}

        def bad_step(variables, cache, buf, pos, t0s, live, block=None):
            sent = block.copy()
            for slot in range(nslots):
                before = seen.get((slot, int(pos[slot])))
                if before is not None and (before == mask_id).any() \
                        and not (block[slot] == mask_id).any():
                    sent[slot] = before
                seen[slot, int(pos[slot])] = block[slot].copy()
            return step(variables, cache, buf, pos, t0s, live, block=sent)

        return bad_step, shapes

    monkeypatch.setattr(engine, "build_step", stale)
    c = _broken(tmp_path, monkeypatch)["logit_gap"]
    assert c["value"] > c["limit"]


def test_int8_control_is_not_correct(tmp_path, monkeypatch):
    """The reference with int8 operands, choosing positions and tokens
    at the states of a sound run, fails a limit."""
    run = tiny.tiny_run(tmp_path, BLOCKS, monkeypatch)
    line = _line(run)
    assert line["correct"] is True
    low = compare_blocks.numbers(
        run.reference, run.seed, run.cp, run.traffic, run.sample,
        quant="int8",
    )
    limits = run.traffic["limits"]
    assert any(low[name] > limits[name] for name in limits), low
