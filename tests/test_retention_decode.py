"""``RetentionLM`` (power retention of degree 2 in every block) at tiny
widths on the CPU, float32, against the plain reference of
``tests/retention_oracle.py`` (the attention form, no state): the full
forward, the recurrence against the masked form, the kernel against
the plain step, and the decode engine serving every length from ONE
pool of states: prefill then decode, slots admitted mid-flight, a slot
reused, a pool grown."""

import functools
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import requests

from tests import retention_oracle as oracle
from tests.test_block_diffusion import (  # noqa: F401 — a fixture
    _publish,
    annotations,
)

PREFIX = "/api/learningOrchestra/v1"
TINY = dict(
    vocab_size=97, hidden_dim=64, num_layers=3, num_heads=4,
    num_kv_heads=2, head_dim=16, mlp_dim=96, rope_theta=1e6,
    norm_eps=1e-6, max_len=256,
)
#: a 16-wide key has 136 products; the state pads them to 2 rows of 128
ROWS, STATE_BYTES = 2, 3 * 2 * 2 * 128 * (16 + 1) * 4


def _estimator(param_dtype="float32", seed=0, **over):
    from learningorchestra_tpu.models.retention import RetentionLM

    est = RetentionLM(**{**TINY, **over}, param_dtype=param_dtype)
    params = est.module.init(
        jax.random.PRNGKey(seed), jnp.ones((1, 8), jnp.int32)
    )
    # Gates near 1 (0.97-0.99: a state lost, kept from the last
    # request or decayed wrongly changes tokens).
    for i in range(est.num_layers):
        gate = params["params"][f"RetentionBlock_{i}"][
            "PowerRetention_0"]["gate"]
        gate["bias"] = (gate["bias"] + 4.0).astype(gate["bias"].dtype)
    est.params = jax.device_get(params)
    return est


@pytest.fixture(scope="module")
def est():
    return _estimator()


@pytest.fixture(scope="module")
def api(tmp_path_factory, est):
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.config import Config

    tmp = tmp_path_factory.mktemp("retention_api")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    server = APIServer(cfg)
    port = server.start_background()
    _publish(server, "ret", est)
    yield server, f"http://127.0.0.1:{port}{PREFIX}"
    server.shutdown()


def _stream(base, model, prompt, **body):
    resp = requests.post(
        f"{base}/serve/{model}/generate",
        json={"prompts": [prompt], "stream": True, **body},
        stream=True, timeout=300,
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


def _gap(est, prompt, served) -> float:
    """How far under the reference's best logit the worst served token
    lies: 0 where every one is the token a state-free greedy decode by
    full forwards gives."""
    return float(oracle.served_gaps(est, prompt, served).max())


def _stats(server, model="ret"):
    return server.serving.decode.stats()["models"][model]


# -- the layer and the module against the reference -------------------------


def test_feature_map_squares_the_dot_product():
    """``phi(q) . phi(k) = (q . k)^2`` from ``d (d + 1) / 2`` products,
    not ``d^2``."""
    from learningorchestra_tpu.ops import retention

    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.standard_normal((5, 16)), jnp.float32)
            for _ in range(2))
    fq, fk = retention.feature_map(q), retention.feature_map(k)
    assert fq.shape == (5, ROWS, 128) and retention.state_rows(16) == 136
    assert int((np.asarray(fq[0]) != 0).sum()) <= 136
    np.testing.assert_allclose(
        jnp.sum(fq * fk, (-1, -2)), jnp.sum(q * k, -1) ** 2, rtol=1e-5)
    assert retention.state_rows(128) == 8256
    assert retention.state_shapes(1, 8, 128, 128) \
        == ((1, 8, 65, 128, 128), (1, 8, 65, 128))


@pytest.mark.parametrize("pad_at", [None, 4])
def test_forward_matches_reference(est, pad_at):
    """The full forward holds the equations to 1e-5; a pad key adds
    nothing to any later position's sums."""
    tokens = np.random.default_rng(3).integers(1, 97, (2, 23))
    if pad_at is not None:
        tokens[0, pad_at] = 0
    got = est.module.apply(est.params, jnp.asarray(tokens))
    np.testing.assert_allclose(
        got, oracle.forward(est, tokens), atol=1e-5, rtol=1e-5)


def test_200_steps_of_the_recurrence_are_the_masked_forms_row_200():
    """One layer: 200 positions fed one a step through the state give,
    at each, the row the attention form computes over all of them."""
    from learningorchestra_tpu.ops.retention import PowerRetention

    layer = PowerRetention(num_heads=4, num_kv_heads=2, head_dim=16,
                           rope_theta=1e6)
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((2, 200, 32)), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    params["gate"]["bias"] = params["gate"]["bias"] + 5.0  # g = 0.99
    full = layer.apply({"params": params}, x)
    stepper = layer.clone(decode=True)
    cache = stepper.init(jax.random.PRNGKey(0), x)["cache"]
    assert cache["retained_state"].shape == (2, 2, ROWS, 16, 128)

    @jax.jit
    def step(cache, row):
        out, mut = stepper.apply({"params": params, "cache": cache},
                                 row[:, None], mutable=["cache"])
        return mut["cache"], out[:, 0]

    rows = []
    for i in range(200):
        cache, out = step(cache, x[:, i])
        rows.append(out)
    assert int(cache["cache_index"]) == 200
    np.testing.assert_allclose(
        jnp.stack(rows, 1), full, atol=1e-5, rtol=1e-4)


LIVE_FRESH = [
    ([1, 1, 1, 1], [0, 1, 0, 0]), ([0, 1, 0, 1], [0, 0, 0, 1]),
    ([0, 0, 0, 0], [0, 0, 0, 0]), ([1, 0, 0, 1], [1, 0, 1, 0]),
    ([0, 0, 1, 0], [1, 1, 1, 1]),
]


@pytest.mark.parametrize("live,fresh", LIVE_FRESH)
def test_kernel_is_the_plain_step(live, fresh):
    """``retention_step_kernel`` (interpreted) against the plain update:
    live and dead slots in every order, dead ones' states bit for bit
    what they were, fresh ones begun from zero."""
    from learningorchestra_tpu.ops import retention

    rng = np.random.default_rng(7)
    b, h, g, d = 4, 2, 3, 32

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    s_shape, z_shape = retention.state_shapes(b, h, d, d)
    state, norm = draw(*s_shape), draw(*z_shape)
    args = (
        retention.feature_map(draw(b, h, g, d)),
        retention.feature_map(draw(b, h, d)), draw(b, h, d),
        jnp.asarray(rng.uniform(0.5, 1.0, (b, h)), jnp.float32),
        jnp.asarray(live, bool), jnp.asarray(fresh, bool),
    )
    ref = retention.plain_retention_step(state, norm, *args)
    got = retention.retention_step_kernel(state, norm, *args,
                                          interpret=True)
    for r, k in zip(ref, got):
        np.testing.assert_allclose(k, r, atol=1e-3, rtol=1e-5)
    dead = ~np.asarray(live, bool)
    assert np.array_equal(np.asarray(got[2])[dead], np.asarray(state)[dead])
    assert np.array_equal(np.asarray(got[3])[dead], np.asarray(norm)[dead])
    assert not np.asarray(got[0])[dead].any()


def test_solo_decode_through_the_state_is_the_full_forward(est):
    prompt = [5, 6, 7, 8, 9]
    got = np.asarray(est.generate(np.array([prompt], np.int32),
                                  max_new_tokens=12))[0]
    assert got[:5].tolist() == prompt
    assert got.tolist() == oracle.generate(est, prompt, 3) \
        + got[8:].tolist()
    assert _gap(est, prompt, got[5:]) <= 1e-4


# -- the pool ---------------------------------------------------------------


def test_the_pool_holds_states_and_no_length(est):
    """Two leaves a layer whatever the buffer's length; a token costs
    the pool nothing, a slot its states; the model says it is not keyed
    by length."""
    from learningorchestra_tpu.models.text import DecoderLM
    from learningorchestra_tpu.serve.decode.pages import (
        PagePool, build_step, first_pages, holds_pages, keyed_by_length,
    )

    for kv in (32, 256):
        _, shapes = build_step(est.module, 4, kv)
        leaves = jax.tree_util.tree_leaves(shapes)
        assert sorted(leaf.shape for leaf in leaves) == sorted(
            [(4, 2, ROWS, 16, 128), (4, 2, ROWS, 128)] * 3)
        assert not holds_pages(shapes)
    assert not keyed_by_length(est.module)
    assert keyed_by_length(DecoderLM(
        vocab_size=24, hidden_dim=32, num_layers=1, num_heads=4,
        max_len=16).module)
    pool = PagePool(256, 4)
    assert pool.token_bytes() is None and pool.slot_bytes() is None
    pool._alloc(shapes, 4)
    assert pool.page_bytes() == 4 * STATE_BYTES
    assert pool.token_bytes() is None
    assert pool.slot_bytes() == STATE_BYTES and not pool.holds_pages
    assert first_pages(pool.cache).shape == (4, 2, ROWS, 16, 128)


def _drive(est, pool, steps, programs):
    """``steps`` turns of the pool's step program, as the engine's
    ``_dispatch`` feeds it."""
    for _ in range(steps):
        step, _ = programs(pool.nslots)
        live = np.array([s is not None and pool.pos[i] < s.total - 1
                         for i, s in enumerate(pool.streams)], bool)
        if not live.any():
            return
        t0s = np.array([s.t0 if s is not None else pool.kv + 1
                        for s in pool.streams], np.int32)
        *pool.device, _col = step(
            est.params, *pool.device,
            np.where(live, pool.pos, 0).astype(np.int32), t0s, live)
        pool.pos[live] += 1


def test_grow_from_2_to_4_slots_mid_flight_keeps_every_state(est):
    from learningorchestra_tpu.serve.decode.pages import (
        PagePool, build_step,
    )
    from learningorchestra_tpu.serve.decode.streams import DecodeStream

    rng = np.random.default_rng(9)
    lens, new = [7, 12, 5], 14
    prompts = [rng.integers(1, 97, n).astype(np.int32) for n in lens]
    streams = [DecodeStream("m", p, len(p), len(p) + new, eager=True)
               for p in prompts]

    programs = functools.cache(
        lambda want: build_step(est.module, want, 64))

    def shapes_for(want):
        return programs(want)[1]

    pool = PagePool(64, 4)
    assert pool.admit(streams[0], shapes_for) == 0
    assert pool.admit(streams[1], shapes_for) == 1
    assert pool.nslots == 2
    _drive(est, pool, 9, programs)
    assert pool.admit(streams[2], shapes_for) == 2
    assert pool.nslots == 4
    assert pool.slot_bytes() == STATE_BYTES
    _drive(est, pool, 40, programs)
    for slot, prompt in enumerate(prompts):
        row = np.asarray(pool.buf)[slot, : len(prompt) + new]
        assert row[: len(prompt)].tolist() == prompt.tolist()
        assert row.all() and _gap(est, prompt, row[len(prompt):]) <= 1e-4


# -- through the REST surface -----------------------------------------------


def test_five_lengths_share_one_pool_and_every_token_is_the_references(
        api, est):
    """Totals of 9 to 200 tokens (three KV buckets of an attention
    model) admitted at different turns: one pool, keyed by no length;
    prefill a token a step, then decode; every served token's logit is
    the reference's best to 1e-4."""
    server, base = api
    rng = np.random.default_rng(11)
    shapes = [(3, 6), (40, 17), (9, 110), (120, 60), (180, 20)]
    prompts = [rng.integers(1, 97, p).tolist() for p, _ in shapes]
    out = [None] * len(shapes)

    def client(i):
        time.sleep(0.05 * i)
        out[i] = _stream(base, "ret", prompts[i], maxNewTokens=shapes[i][1])

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(shapes))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(300)
    for i, (_p, new) in enumerate(shapes):
        assert len(out[i]) == new
        assert _gap(est, prompts[i], out[i]) <= 1e-4
    stats = _stats(server)
    assert len(stats["pools"]) == 1
    pool = stats["pools"][0]
    assert pool["kv"] is None and pool["buffer"] == 256
    assert pool["kvBytesPerToken"] is None
    assert pool["stateBytesPerSlot"] == STATE_BYTES
    assert pool["pageBytes"] == pool["slots"] * STATE_BYTES
    assert stats["stepsInPlace"] == stats["steps"] > 0
    assert stats["stateResets"] >= len(shapes)
    assert stats["slotSteps"]["prompt"] >= sum(p - 1 for p, _ in shapes)


def test_a_reused_slot_begins_from_zero(api, est):
    """The second request sits in the slot the first one left its state
    in, and reads what a fresh engine gives."""
    server, base = api
    first, second = [9, 8, 7, 6, 5, 4], [11, 12, 13]
    _stream(base, "ret", first, maxNewTokens=30)
    resets = _stats(server)["stateResets"]
    got = _stream(base, "ret", second, maxNewTokens=25)
    assert len(got) == 25 and _gap(est, second, got) <= 1e-4
    stats = _stats(server)
    assert stats["stateResets"] == resets + 1
    assert [p["live"] for p in stats["pools"]] == [0]


def test_without_the_reset_a_reused_slot_reads_other_tokens(
        api, est, monkeypatch):
    """The fault the test above is there to catch: with the reset
    patched away the second request decays the first one's state into
    its own."""
    from learningorchestra_tpu.ops import retention
    from learningorchestra_tpu.train import compile_cache

    plain = retention.plain_retention_step

    def never_fresh(state, norm, fq, fk, v, g, live, fresh):
        return plain(state, norm, fq, fk, v, g, live,
                     jnp.zeros_like(fresh))

    server, base = api
    monkeypatch.setattr(retention, "plain_retention_step", never_fresh)
    server.serving.decode.drop_model("ret")
    compile_cache.get_cache().clear()
    try:
        first, second = [9, 8, 7, 6, 5, 4], [11, 12, 13]
        served = _stream(base, "ret", first, maxNewTokens=30)
        assert _gap(est, first, served) <= 1e-4  # a fresh pool's zeros
        got = _stream(base, "ret", second, maxNewTokens=25)
        assert _gap(est, second, got) > 0.01
    finally:
        monkeypatch.undo()
        server.serving.decode.drop_model("ret")
        compile_cache.get_cache().clear()


def test_steps_run_in_place_and_ahead(api):
    """On the CPU backend too: every step consumes the states it is
    handed, and all but the one that starts from a drained pool are
    enqueued before the step before them is read."""
    server, base = api
    _stream(base, "ret", [2, 7], maxNewTokens=2)  # a decoder to ask
    before = _stats(server)
    _stream(base, "ret", [3, 1, 4, 1, 5, 9, 2, 6], maxNewTokens=128)
    after = _stats(server)
    steps = after["steps"] - before["steps"]
    assert steps >= 128
    assert after["stepsInPlace"] - before["stepsInPlace"] == steps
    assert after["stepsAhead"] - before["stepsAhead"] >= 0.99 * steps


def test_step_annotation_says_states_resets_and_pools(api, annotations):
    _, base = api
    _stream(base, "ret", [5, 6, 7, 8], maxNewTokens=6)
    annotations.settle()
    turns = [md for name, md in annotations if name == "decode.step"]
    stepped = [md for md in turns if md.get("slots")]
    assert stepped
    assert all(md["state_bytes_per_slot"] == STATE_BYTES for md in stepped)
    assert all(md["pools"] == 1 and md["inplace"] == 1 for md in stepped)
    assert all(not md["kv_bytes_per_token"] and md["kv"] == 0
               for md in stepped)
    assert sum(md["state_resets"] for md in stepped) == 1


def test_a_page_pools_stats_and_annotation_keep_their_keys(api,
                                                            annotations):
    """A model with K/V pages beside it: keyed by its bucket, a token's
    bytes, no state's."""
    from learningorchestra_tpu.models.text import DecoderLM

    server, base = api
    lm = DecoderLM(vocab_size=24, hidden_dim=32, num_layers=1,
                   num_heads=4, max_len=16)
    lm.params = jax.device_get(lm.module.init(
        jax.random.PRNGKey(1), jnp.ones((1, 4), jnp.int32)))
    _publish(server, "paged", lm)
    assert len(_stream(base, "paged", [3, 4, 5], maxNewTokens=4)) == 4
    annotations.settle()
    pool, = _stats(server, "paged")["pools"]
    assert pool["kv"] == pool["buffer"] == 8
    assert pool["stateBytesPerSlot"] is None
    assert pool["kvBytesPerToken"] == 2 * 32 * 4
    assert _stats(server, "paged")["stateResets"] == 0
    stepped = [md for name, md in annotations
               if name == "decode.step" and md.get("slots")]
    assert all(md["kv"] == 8 and md["state_bytes_per_slot"] == 0
               and md["kv_bytes_per_token"] == 2 * 32 * 4
               and md["state_resets"] == 0 for md in stepped)


def test_bf16_leaves_are_served_with_float32_states(api):
    server, base = api
    est16 = _estimator(param_dtype="bfloat16", seed=2)
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(est16.params)} \
        == {"bfloat16"}
    _publish(server, "ret16", est16)
    requests.post(f"{base}/serve/ret16/load", timeout=60).raise_for_status()
    entry = server.serving.registry.get("ret16")
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(entry.params)} \
        == {"bfloat16"}
    assert len(_stream(base, "ret16", [7, 3, 9, 2, 8], maxNewTokens=8)) == 8
    pool = next(iter(
        server.serving.decode._decoders["ret16"]._pools.values()))
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(pool.cache)} \
        == {"float32"}
    assert pool.slot_bytes() == STATE_BYTES
