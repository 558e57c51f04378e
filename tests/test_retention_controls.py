"""The faults under the retention state that ``correct`` has to catch
(``benchmarks/controls_retention.py`` ``BREAKS``: a seated slot not
begun from zero, the gate left out, the state rounded to bfloat16 after
every step), against the step programs as they run: the one-token
program and the chunk program that takes a prompt several positions a
step both go through ``ops.retention.retention_step``, which each break
wraps.  Requests are served through the engine at tiny widths on the
CPU, in two waves so that the second sits in the slots the first left
its states in, and read by the benchmark's own comparison
(``lobench.compare.served_gap``) over the configuration's plain
reference: a sound run reads correct, each break does not."""

import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
SEED = 2**31 + 39
# ``benchmarks/tests/test_retention_generate.py``'s tiny widths, wide
# enough that the mixer weighs on the logits, a vocabulary whose top
# logits lie close together.
SMALL = {
    "vocab_size": 16384, "hidden_dim": 256, "num_layers": 2,
    "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "mlp_dim": 64,
    "max_len": 128, "param_dtype": "float32",
}
# float32 on the CPU reads under 1e-6 (the benchmark's tiny limit)
LIMIT = 2e-5
# prompts of 4 to 45 positions: one-token and chunk steps both; with
# the seeded gate (0.9975 a step) 85 positions leave 0.81 of the first
# token's weight, enough for a gate left out to show
LENGTHS = [[30, 4, 45], [17, 9, 38]]
NEW = 40


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """(the configuration's estimator with the seed's weights, its plain
    reference, ``lobench.compare``, the class parameters, the
    benchmark's ``controls_retention``); the benchmark's package
    importable for as long as this file runs."""
    sys.path.insert(0, str(REPO / "benchmarks"))
    try:
        from lobench import compare, loader, weights_bf16

        src = loader.config_path(loader.benchmark(), "brumby-14b-base")
        config = json.loads(src.read_text())
        config["class_parameters"].update(SMALL)
        path = tmp_path_factory.mktemp("retention_config") / src.name
        path.write_text(json.dumps(config))
        path.with_suffix(".py").write_text(
            src.with_suffix(".py").read_text()
        )
        _, reference = loader.config(path)
        est = weights_bf16.estimator_artifact(str(path), SEED)
        spec = importlib.util.spec_from_file_location(
            "controls_retention", REPO / "benchmarks" / "controls_retention.py")
        controls = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(controls)
        yield est, reference, compare, config["class_parameters"], controls
    finally:
        sys.path.remove(str(REPO / "benchmarks"))
        for name in [n for n in sys.modules if n.startswith("lobench")]:
            del sys.modules[name]


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.config import Config

    tmp = tmp_path_factory.mktemp("retention_controls")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    server = APIServer(cfg)
    server.start_background()
    yield server
    server.shutdown()


def _serve(server, est, name):
    """The requests served under ``name`` by a decoder of its own, as
    the comparison takes them, and the decoder's counts."""
    server.ctx.volumes.save_object("train/tensorflow", name, est)
    server.ctx.artifacts.metadata.create(name, "train/tensorflow")
    server.ctx.artifacts.metadata.mark_finished(name)
    eng = server.serving.decode
    rng = np.random.default_rng(SEED)
    served = []
    try:
        for wave in LENGTHS:
            prompts = [rng.integers(1, SMALL["vocab_size"], p).tolist()
                       for p in wave]
            streams = [eng.generate(name, prompt, max_new_tokens=NEW,
                                    stream=True) for prompt in prompts]
            for prompt, stream in zip(prompts, streams):
                assert stream.wait_done(120) and stream.error is None
                served.append({"prompt": prompt,
                               "tokens": list(stream.tokens)})
        stats = eng.stats()["models"][name]
    finally:
        eng.drop_model(name)
    return served, stats


def _gap(bench, served) -> float:
    """``logit_gap`` of the served rows, as the cell's comparison reads
    it."""
    _est, reference, compare, cp, _controls = bench
    tokens, first, last = compare.sample_rows(served, SMALL["max_len"],
                                              len(served))
    return compare.served_gap(reference, SEED, cp, tokens, first, last)


def test_a_sound_run_reads_correct(server, bench):
    from learningorchestra_tpu.train import compile_cache

    compile_cache.get_cache().clear()
    served, stats = _serve(server, bench[0], "sound")
    assert all(len(req["tokens"]) == NEW for req in served)
    # both programs ran: prompts a chunk a step, then one token a step
    assert stats["chunkSteps"] > 0 and stats["steps"] > stats["chunkSteps"]
    assert stats["stateResets"] == len(served)
    assert _gap(bench, served) <= LIMIT


@pytest.mark.parametrize("fault", ["no_reset", "no_gate", "state_bf16"])
def test_a_fault_under_the_state_is_not_correct(server, bench, fault):
    """The benchmark's own break, with the step programs built anew on
    both sides of it: the run reads over the limit."""
    controls = bench[4]
    with controls.broken(fault):
        served, stats = _serve(server, bench[0], fault)
    assert stats["chunkSteps"] > 0
    assert _gap(bench, served) > LIMIT
