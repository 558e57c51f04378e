"""``LatentMoELM`` (latent attention, a leading dense layer, sigmoid-
routed experts with a shared one) at tiny widths on the CPU against the
plain reference of ``tests/latent_oracle.py``: the full forward,
prefill then decode through the latent cache (solo scan, the engine's
step at per-slot positions, slots admitted mid-flight), the cache's one
leaf a layer, the expert counters, and bfloat16 leaves through
artifact, registry and ``serve.load``."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import requests

from tests import latent_oracle as oracle
from tests.test_block_diffusion import (  # noqa: F401 — a fixture
    _publish,
    annotations,
)

PREFIX = "/api/learningOrchestra/v1"
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
TINY = dict(
    vocab_size=97, hidden_dim=64, num_layers=3, num_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, mlp_dim=96, first_dense_layers=1,
    expert_dim=32, num_experts=16, experts_per_token=4, shared_experts=1,
    routed_scale=2.5, rope_theta=50000.0, rope_scaling=YARN,
    norm_eps=1e-5, max_len=32,
)


def _estimator(param_dtype="float32", seed=0, **over):
    from learningorchestra_tpu.models.moe import LatentMoELM

    est = LatentMoELM(**{**TINY, **over}, param_dtype=param_dtype)
    params = est.module.init(
        jax.random.PRNGKey(seed), jnp.ones((1, 8), jnp.int32)
    )
    # Unit-variance-ish logits (near-ties would make token choices a
    # matter of rounding) and a bias that changes the experts chosen.
    params = jax.tree_util.tree_map(lambda a: a * 2.0, params)
    for i in range(est.first_dense_layers, est.num_layers):
        layer = params["params"][f"LatentExpertBlock_{i}"]["RoutedExperts_0"]
        layer["score_bias"] = (0.3 * jax.random.normal(
            jax.random.PRNGKey(100 + i), layer["score_bias"].shape
        )).astype(layer["score_bias"].dtype)
    est.params = jax.device_get(params)
    return est


@pytest.fixture(scope="module")
def est():
    return _estimator()


@pytest.fixture(scope="module")
def api(tmp_path_factory, est):
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.config import Config

    tmp = tmp_path_factory.mktemp("kimi_api")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    server = APIServer(cfg)
    port = server.start_background()
    _publish(server, "kimi", est)
    yield server, f"http://127.0.0.1:{port}{PREFIX}"
    server.shutdown()


def _stream(base, model, prompt, **body):
    resp = requests.post(
        f"{base}/serve/{model}/generate",
        json={"prompts": [prompt], "stream": True, **body},
        stream=True, timeout=120,
    )
    assert resp.status_code == 200, resp.text
    toks, event = [], None
    for raw in resp.iter_lines():
        line = raw.decode()
        if line.startswith("event:"):
            event = line[6:].strip()
        elif line.startswith("data:") and event == "token":
            toks.append(json.loads(line[5:])["t"])
        elif line.startswith("data:") and event == "error":
            raise AssertionError(line)
    return toks


# -- the module against the reference ---------------------------------------


@pytest.mark.parametrize("held", [None, (4, 4)])
def test_forward_matches_reference(held):
    """Whole, and as one chip's share of 4 of the 16 experts (what the
    absent experts would add is left out on both sides)."""
    est = _estimator(experts_held=held)
    if held is not None:
        for i in (1, 2):
            lp = est.params["params"][f"LatentExpertBlock_{i}"][
                "RoutedExperts_0"]
            assert lp["w_gate"].shape[0] == 4
            assert lp["router"].shape == (64, 16)
    tokens = np.random.default_rng(3).integers(1, 97, (2, 11))
    got = est.module.apply(est.params, jnp.asarray(tokens))
    np.testing.assert_allclose(
        got, oracle.forward(est, tokens), atol=5e-4, rtol=1e-3)


def test_pad_keys_are_never_seen(est):
    tokens = np.random.default_rng(4).integers(1, 97, (1, 9))
    padded = tokens.copy()
    padded[0, 2] = 0
    got = est.module.apply(est.params, jnp.asarray(padded))
    np.testing.assert_allclose(
        got, oracle.forward(est, padded), atol=5e-4, rtol=1e-3)
    assert not np.allclose(
        got[0, 5], est.module.apply(est.params, jnp.asarray(tokens))[0, 5])


def test_solo_decode_through_the_cache_is_the_full_forward(est):
    """Prefill then decode, one position a step through the absorbed
    form over the latent cache, gives the tokens of cache-free full
    forwards of the NON-absorbed reference."""
    prompt = [5, 6, 7, 8, 9]
    got = np.asarray(est.generate(np.array([prompt], np.int32),
                                  max_new_tokens=9))[0]
    assert got.tolist() == oracle.generate(est, prompt, 9)


# -- the engine's step ------------------------------------------------------


def test_the_pool_holds_one_latent_leaf_a_layer_and_says_so(est):
    from learningorchestra_tpu.serve.decode.pages import (
        PagePool, build_step, first_pages,
    )

    _, shapes = build_step(est.module, 4, 32)
    leaves = jax.tree_util.tree_leaves(shapes)
    assert len(leaves) == 3  # one a layer, nothing per head
    assert all(leaf.shape == (4, 32, 16 + 4) for leaf in leaves)
    pool = PagePool(32, 4)
    pool._alloc(shapes, 4)
    assert pool.page_bytes() == 3 * 4 * 32 * 20 * 4  # float32 here
    assert pool.token_bytes() == 3 * 20 * 4
    assert first_pages(pool.cache).shape == (4, 32, 20)


@pytest.mark.parametrize("nslots,starts", [
    (1, [0]), (4, [0, 0, 0, 0]), (4, [0, 3, 7, 1]),
])
def test_step_at_per_slot_positions_is_the_solo_decode(est, nslots,
                                                       starts):
    """``build_step``'s token step over the latent pages: every slot at
    its own position, some admitted while others are mid-flight, each
    produces the solo decode's tokens; the column carries the routed
    layers' three counts behind the tokens."""
    from learningorchestra_tpu.serve.decode.pages import build_step

    rng = np.random.default_rng(5)
    lens = [4, 6, 3, 5][:nslots]
    prompts = [rng.integers(1, 97, n).astype(np.int32) for n in lens]
    new = 7
    solo = [
        np.asarray(est.generate(p[None], max_new_tokens=new))[0]
        for p in prompts
    ]
    step, shapes = build_step(est.module, nslots, 32)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    buf = jnp.zeros((nslots, 32), jnp.int32)
    pos = np.zeros(nslots, np.int32)
    t0s = np.array(lens, np.int32)
    seated = np.zeros(nslots, bool)
    for turn in range(max(starts) + max(lens) + new):
        for i, s in enumerate(starts):
            if turn == s:  # admitted now, the others mid-flight
                buf = buf.at[i, : lens[i]].set(prompts[i])
                seated[i] = True
        live = seated & (pos < t0s + new - 1)
        cache, buf, col = step(
            est.params, cache, buf, np.where(live, pos, 0).astype(np.int32),
            np.where(seated, t0s, 33).astype(np.int32), live,
        )
        assert col.shape == (nslots + 3,)
        hit, busiest, rows = (int(v) for v in np.asarray(col)[nslots:])
        assert 0 < hit <= 2 * 16 and 1 <= busiest <= nslots
        assert rows == 2 * 4 * nslots  # all held: every choice lands
        pos[live] += 1
    for i in range(nslots):
        assert np.asarray(buf)[i, : lens[i] + new].tolist() \
            == solo[i].tolist()


# -- through the REST surface ----------------------------------------------


def test_engine_stream_is_the_solo_decode(api, est):
    server, base = api
    prompt = [7, 3, 9, 2, 6]
    toks = _stream(base, "kimi", prompt, maxNewTokens=9)
    solo = np.asarray(est.generate(np.array([prompt], np.int32),
                                   max_new_tokens=9))[0]
    assert prompt + toks == solo.tolist()
    assert solo.tolist() == oracle.generate(est, prompt, 9)


def test_slots_admitted_mid_flight_and_the_counters(api, est):
    server, base = api
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 97, n).tolist() for n in (9, 4, 6, 11, 5)]
    news = [12, 9, 11, 6, 14]
    out = [None] * 5

    def client(i):
        out[i] = _stream(base, "kimi", prompts[i], maxNewTokens=news[i])

    threads = [threading.Thread(target=client, args=(i,)) for i in range(5)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(120)
    for i in range(5):
        solo = np.asarray(est.generate(
            np.array([prompts[i]], np.int32), max_new_tokens=news[i]))[0]
        assert prompts[i] + out[i] == solo.tolist()
    stats = server.serving.decode.stats()["models"]["kimi"]
    assert stats["stepsInPlace"] == stats["steps"] > 0
    # every choice lands on a held expert: 4 a row and routed layer, and
    # a step's rows are its pool's slots (free ones are routed too)
    assert stats["expertRows"] > 0 and stats["expertRows"] % 8 == 0
    assert 0 < stats["expertsHit"] <= 2 * 16 * stats["steps"]
    assert stats["expertLoadMax"] >= 1
    assert stats["blockSteps"] == {"prefill": 0, "denoise": 0, "commit": 0}
    pool = stats["pools"][0]
    assert pool["kvBytesPerToken"] == 3 * 20 * 4
    assert pool["pageBytes"] == pool["slots"] * pool["kv"] * 3 * 20 * 4


def test_step_annotation_carries_the_expert_counters(api, annotations):
    _, base = api
    _stream(base, "kimi", [5, 6, 7, 8], maxNewTokens=6)
    turns = [md for name, md in annotations if name == "decode.step"]
    counted = [md for md in turns if md.get("expert_rows")]
    assert counted
    for md in counted:
        assert 0 < md["experts_hit"] <= 2 * 16 and md["load_max"] >= 1
    stepped = [md for md in turns if md.get("slots")]
    assert all(md["kv_bytes_per_token"] == 3 * 20 * 4 for md in stepped)
    assert all(md["inplace"] == 1 for md in stepped)


def test_a_dense_models_column_and_annotation_are_what_they_were():
    """No routed layer, no counts: ``(S,)`` as before."""
    from learningorchestra_tpu.models.text import DecoderLM
    from learningorchestra_tpu.serve.decode.pages import build_step

    lm = DecoderLM(vocab_size=24, hidden_dim=32, num_layers=1,
                   num_heads=4, max_len=16)
    lm.params = jax.device_get(lm.module.init(
        jax.random.PRNGKey(1), jnp.ones((1, 4), jnp.int32)))
    step, shapes = build_step(lm.module, 2, 16)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    _, _, col = step(lm.params, cache, jnp.zeros((2, 16), jnp.int32),
                     np.zeros(2, np.int32), np.full(2, 17, np.int32),
                     np.zeros(2, bool))
    assert col.shape == (2,)


# -- bfloat16 residency -----------------------------------------------------


def test_bf16_leaves_survive_artifact_and_load(api):
    """Parameters held in bfloat16 stay bfloat16, bit for bit, in the
    artifact, in the registry and on the device (no float32 copy); the
    latent pages follow them; a request is served from them."""
    server, base = api
    est16 = _estimator(param_dtype="bfloat16", seed=2)
    leaves = jax.tree_util.tree_leaves(est16.params)
    assert {str(a.dtype) for a in leaves} == {"bfloat16"}
    _publish(server, "kimi16", est16)
    loaded = server.ctx.volumes.read_object("train/tensorflow", "kimi16")
    for a, b in zip(leaves, jax.tree_util.tree_leaves(loaded.params)):
        assert str(b.dtype) == "bfloat16"
        assert np.array_equal(np.asarray(a).view(np.uint16),
                              np.asarray(b).view(np.uint16))
    requests.post(f"{base}/serve/kimi16/load", timeout=60).raise_for_status()
    entry = server.serving.registry.get("kimi16")
    resident = jax.tree_util.tree_leaves(entry.params)
    assert {str(a.dtype) for a in resident} == {"bfloat16"}
    assert entry.nbytes == sum(2 * a.size for a in leaves)
    toks = _stream(base, "kimi16", [7, 3, 9, 2, 8], maxNewTokens=8)
    assert len(toks) == 8
    pool = next(iter(
        server.serving.decode._decoders["kimi16"]._pools.values()))
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(pool.cache)} \
        == {"bfloat16"}
    assert pool.token_bytes() == 3 * 20 * 2


def test_a_pending_stream_takes_one_place_of_max_streams(monkeypatch):
    """``max_streams`` callers in a closed loop each find their place:
    a stream waiting for its slot is counted once, not as active and
    pending both."""
    import types

    from learningorchestra_tpu.config import DecodeConfig
    from learningorchestra_tpu.serve.batcher import QueueFull
    from learningorchestra_tpu.serve.decode import engine
    from learningorchestra_tpu.serve.decode.streams import DecodeStream

    # no worker: every stream stays pending
    monkeypatch.setattr(engine._ModelDecoder, "_run", lambda self: None)
    decoder = engine._ModelDecoder(
        types.SimpleNamespace(cfg=DecodeConfig(max_streams=3)), "m")
    prompt = np.array([1, 2], np.int32)
    for _ in range(3):
        decoder.submit(DecodeStream("m", prompt, 2, 4, eager=True))
    with pytest.raises(QueueFull, match="max_streams=3"):
        decoder.submit(DecodeStream("m", prompt, 2, 4, eager=True))
