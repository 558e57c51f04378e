"""The three breaks that bit the host's block strategy
(``benchmarks/tests/test_block_generate.py``), against the step
program's: a token altered where it is fixed, a block committed with a
mask left, the K/V of a denoising forward kept for the commit.  Each is
served through the engine at tiny widths on the CPU and read by both
comparisons: the tokens of ``tests/blockdiff_oracle.py``, and the
benchmark's own ``lobench.compare_blocks`` over the configuration's
plain reference, which must read a sound run as correct and each break
as wrong."""

import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from tests import blockdiff_oracle as oracle

REPO = Path(__file__).resolve().parent.parent
SEED = 2**31 + 34
# ``benchmarks/tests/test_block_generate.py``'s tiny widths: a
# vocabulary of thousands, so that the top logits lie close together.
SMALL = {
    "vocab_size": 4096, "hidden_dim": 32, "num_layers": 2, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 8, "expert_dim": 16, "num_experts": 8,
    "experts_per_token": 2, "max_len": 64, "mask_token_id": 4000,
    "param_dtype": "float32",
}
TRAFFIC = {"denoising_steps": 2, "remasking": "low_confidence_static",
           "kv_bucket": 32}
# float32 on the CPU reads under 1e-6 (the benchmark's tiny limit)
LIMITS = {"logit_gap": 2e-5, "order_faults": 0}
PROMPTS = [[17, 230, 4, 999, 3001], [5, 6, 7, 8, 9, 10, 11, 12],
           [3999, 4001, 2, 77, 1500, 12, 640, 8, 21, 3, 360]]
NEW = 14


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """(the configuration's estimator with the seed's weights, its plain
    reference, ``lobench.compare_blocks``, the class parameters); the
    benchmark's package importable for as long as this file runs."""
    sys.path.insert(0, str(REPO / "benchmarks"))
    try:
        from lobench import compare_blocks, loader, weights_bf16

        src = loader.config_path(loader.benchmark(), "sdar-30b-a3b-chat")
        config = json.loads(src.read_text())
        config["class_parameters"].update(SMALL)
        path = tmp_path_factory.mktemp("blocks_config") / src.name
        path.write_text(json.dumps(config))
        path.with_suffix(".py").write_text(
            src.with_suffix(".py").read_text()
        )
        _, reference = loader.config(path)
        est = weights_bf16.estimator_artifact(str(path), SEED)
        yield est, reference, compare_blocks, config["class_parameters"]
    finally:
        sys.path.remove(str(REPO / "benchmarks"))
        for name in [n for n in sys.modules if n.startswith("lobench")]:
            del sys.modules[name]


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.config import Config

    tmp = tmp_path_factory.mktemp("blocks_controls")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    server = APIServer(cfg)
    server.start_background()
    yield server
    server.shutdown()


@pytest.fixture
def fresh_programs():
    """No step program traced under a break may outlive its test, nor
    one traced before it serve it."""
    from learningorchestra_tpu.train import compile_cache

    compile_cache.get_cache().clear()
    yield
    compile_cache.get_cache().clear()


def _serve(server, est, name):
    """The requests served under ``name`` by a decoder of its own, as
    the comparison takes them."""
    server.ctx.volumes.save_object("train/tensorflow", name, est)
    server.ctx.artifacts.metadata.create(name, "train/tensorflow")
    server.ctx.artifacts.metadata.mark_finished(name)
    eng = server.serving.decode
    try:
        streams = [
            eng.generate(
                name, prompt, max_new_tokens=NEW, stream=True,
                denoising_steps=TRAFFIC["denoising_steps"],
                remasking=TRAFFIC["remasking"],
            )
            for prompt in PROMPTS
        ]
        for stream in streams:
            assert stream.wait_done(120) and stream.error is None
    finally:
        eng.drop_model(name)
    return [
        {"prompt": prompt, "tokens": list(stream.tokens),
         "steps": [doc.get("s", -1) for event, doc in stream.sse_events()
                   if event == "token"]}
        for prompt, stream in zip(PROMPTS, streams)
    ]


def _read(bench, served):
    """(what ``compare_blocks`` reads, whether the oracle's tokens and
    steps are the served ones)."""
    est, reference, compare_blocks, cp = bench
    values = compare_blocks.numbers(reference, SEED, cp, TRAFFIC, served)
    same = True
    for req in served:
        want, want_steps = oracle.generate(
            est, req["prompt"], NEW, TRAFFIC["denoising_steps"],
            TRAFFIC["remasking"],
        )
        t0 = len(req["prompt"])
        same &= req["prompt"] + req["tokens"] == want.tolist() and \
            req["steps"] == [want_steps[p] for p in range(t0, t0 + NEW)]
    return values, same


def test_a_sound_run_reads_correct(server, bench, fresh_programs):
    values, same = _read(bench, _serve(server, bench[0], "sound"))
    assert same
    assert all(values[name] <= limit for name, limit in LIMITS.items()), \
        values


def test_token_altered_where_it_is_fixed(server, bench, fresh_programs,
                                         monkeypatch):
    from learningorchestra_tpu.serve.decode import blocks

    real = blocks.denoise

    def altered(tokens, masked, fixed_at, step, x0, conf, *plan):
        return real(tokens, masked, fixed_at, step,
                    (x0 + 1) % 3000 + 1, conf, *plan)

    monkeypatch.setattr(blocks, "denoise", altered)
    values, same = _read(bench, _serve(server, bench[0], "altered"))
    assert not same
    assert values["logit_gap"] > LIMITS["logit_gap"], values


def test_block_committed_with_a_mask_left(server, bench, fresh_programs,
                                          monkeypatch):
    """A block counts as final with one position still masked: its
    commit forwards the mask id there and the token goes out never
    fixed."""
    from learningorchestra_tpu.serve.decode import blocks

    monkeypatch.setattr(blocks, "final",
                        lambda masked: masked.sum(-1) <= 1)
    served = _serve(server, bench[0], "unfinished")
    assert any(-1 in req["steps"] for req in served)
    values, same = _read(bench, served)
    assert not same
    assert values["order_faults"] > 0, values


def test_kv_of_a_denoising_forward_kept_for_the_commit(
        server, bench, fresh_programs, monkeypatch):
    """The commit forward runs over the block's last noisy state and
    not over its final tokens: the tokens sent are the sound ones, but
    every later block attends K/V that the commit should have
    replaced."""
    from learningorchestra_tpu.serve.decode import blocks, engine

    real = engine.build_step
    mask_id, q = SMALL["mask_token_id"], 4
    head = len(blocks.STATE_HEAD)

    def stale(module, nslots, kv):
        step, shapes = real(module, nslots, kv)
        seen = {}

        def bad_step(variables, cache, buf, state, slots):
            state = np.array(state)
            sound = {}
            for slot in range(nslots):
                live, seat = slots[:2, slot]
                pos, nth = state[slot, :head]
                if not live or seat or nth < 0:
                    continue  # the block at ``pos`` is not begun
                tokens = state[slot, head: head + q].copy()
                before = seen.get((slot, pos))
                if before is not None and (before == mask_id).any() \
                        and not state[slot, head + q: head + 2 * q].any():
                    state[slot, head: head + q] = before
                    sound[slot] = tokens
                seen[slot, pos] = tokens
            cache, buf, state, col = step(
                variables, cache, buf, jnp.asarray(state), slots
            )
            for slot, tokens in sound.items():
                col = col.at[slot, 3: 3 + q].set(tokens)
            return cache, buf, state, col

        return bad_step, shapes

    monkeypatch.setattr(engine, "build_step", stale)
    values, same = _read(bench, _serve(server, bench[0], "stale"))
    assert not same
    assert values["order_faults"] == 0
    assert values["logit_gap"] > LIMITS["logit_gap"], values
