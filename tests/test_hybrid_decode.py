"""``HybridLM`` (every block a Mamba-2 mixer beside grouped-query
attention) at tiny widths on the CPU, float32, against the plain
reference of ``tests/hybrid_oracle.py`` (the mixer as its sequential
recurrence, attention as a masked softmax): the full forward in the
chunk form, the ``ssm_step`` kernel against the plain step, the decode
branch one position a call, and the decode engine serving from pools
that carry K/V pages, a float32 state and a convolution tail a slot:
slots at their own positions, a slot reused, a pool grown.

Tolerances: float32 throughout, so the program and the reference differ
by the order of their sums alone.  1e-5 on a forward's logits (a few
hundred float32 sums deep); 1e-4 on a served token's logit gap, which
is 0 where the engine serves the reference's greedy token and would be
the distance between two logits (here 1e-2 or more) where it does not;
1e-3 absolute on the kernel's read-out, a sum of 256 products of
unit normals (the state's own entries 1e-6)."""

import functools
import hashlib
import json
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import requests

from tests import hybrid_oracle as oracle
from tests.test_block_diffusion import (  # noqa: F401 — a fixture
    _publish,
    annotations,
)

PREFIX = "/api/learningOrchestra/v1"
#: Chunks of 8 positions, so sequences of 21 and 23 end inside one; the
#: multipliers of the published model's kind, none of them 1.
TINY = dict(
    vocab_size=97, hidden_dim=64, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, mlp_dim=96, rope_theta=1e4,
    mamba_heads=4, mamba_head_dim=16, mamba_groups=2, mamba_state=8,
    mamba_conv=4, mamba_chunk=8, embedding_multiplier=2.0,
    attention_in_multiplier=0.9, attention_out_multiplier=0.5,
    key_multiplier=0.5, ssm_in_multiplier=0.5,
    ssm_multipliers=(0.9, 0.8, 0.7, 1.2, 0.6), ssm_out_multiplier=0.7,
    mlp_multipliers=(0.6, 0.5), lm_head_multiplier=0.5, max_len=256,
)
#: a slot's state and tail over both layers: 4 heads x 8 x 16 float32,
#: 3 x (64 + 2 x 16) float32 a layer
STATE_BYTES = 2 * (4 * 8 * 16 + 3 * 96) * 4
#: a position's K and V pages over both layers: 2 heads x 16, float32
KV_BYTES = 2 * 2 * 2 * 16 * 4


def _estimator(param_dtype="float32", seed=0, **over):
    from learningorchestra_tpu.models.hybrid import HybridLM

    est = HybridLM(**{**TINY, **over}, param_dtype=param_dtype)
    est.params = jax.device_get(est.module.init(
        jax.random.PRNGKey(seed), jnp.ones((1, 8), jnp.int32)))
    return est


@pytest.fixture(scope="module")
def est():
    return _estimator()


@pytest.fixture(scope="module")
def api(tmp_path_factory, est):
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.config import Config

    tmp = tmp_path_factory.mktemp("hybrid_api")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    server = APIServer(cfg)
    port = server.start_background()
    _publish(server, "hyb", est)
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
    lies: 0 where every one is the token a cache-free greedy decode by
    full forwards gives."""
    return float(oracle.served_gaps(est, prompt, served).max())


def _stats(server, model="hyb"):
    return server.serving.decode.stats()["models"][model]


# -- the mixer and the module against the reference -------------------------


@pytest.mark.parametrize("pads", [(), (5,), (0, 8, 9)],
                         ids=["none", "inside", "first-and-chunk-edge"])
def test_forward_matches_reference(est, pads):
    """The full forward (the mixer in its chunk form, chunks of 8 over
    21 positions) holds the sequential recurrence to 1e-5; a pad
    position adds nothing to the convolution or the state."""
    tokens = np.random.default_rng(3).integers(1, 97, (2, 21))
    for at in pads:
        tokens[0, at] = 0
    got = est.module.apply(est.params, jnp.asarray(tokens))
    np.testing.assert_allclose(
        got, oracle.forward(est, tokens), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("chunk,length", [(1, 5), (4, 13), (8, 8), (16, 37)])
def test_chunk_form_is_the_sequential_recurrence(chunk, length):
    """``ssd_chunked`` against ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t
    x_t^T``, ``y_t = S_t^T C_t`` stepped one position at a time by
    ``plain_ssm_step``: chunk boundaries inside the sequence and at its
    end, zero step sizes (pads) among them."""
    from learningorchestra_tpu.ops import ssm

    rng = np.random.default_rng(chunk * 7 + length)
    b, h, g, n, p = 2, 4, 2, 8, 16

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    x, bm, cm = draw(b, length, h, p), draw(b, length, g, n), \
        draw(b, length, g, n)
    dt = rng.uniform(1e-3, 0.5, (b, length, h))
    dt[0, length // 2] = 0.0
    dt = jnp.asarray(dt, jnp.float32)
    a = -jnp.arange(1, h + 1, dtype=jnp.float32)
    got = ssm.ssd_chunked(x, bm, cm, dt, a, chunk)
    state = jnp.zeros((b, h, n, p), jnp.float32)
    on = jnp.ones((b,), bool)
    for t in range(length):
        y, state = ssm.plain_ssm_step(
            state, x[:, t], bm[:, t], cm[:, t], dt[:, t], a,
            jnp.zeros((h,), jnp.float32), on, ~on)
        np.testing.assert_allclose(got[:, t], y, atol=1e-5, rtol=1e-5)


LIVE_FRESH = [
    ([1, 1, 1, 1], [0, 1, 0, 0]), ([0, 1, 0, 1], [0, 0, 0, 1]),
    ([0, 0, 0, 0], [0, 0, 0, 0]), ([1, 0, 0, 1], [1, 0, 1, 0]),
    ([0, 0, 1, 0], [1, 1, 1, 1]),
]


@pytest.mark.parametrize("live,fresh", LIVE_FRESH)
def test_kernel_is_the_plain_step(live, fresh):
    """``ssm_step_kernel`` (interpreted) at a head's published shape,
    256 x 128, against the plain update: live and dead slots in every
    order, dead ones' states bit for bit what they were and their
    read-outs zero, fresh ones begun from zero."""
    from learningorchestra_tpu.ops import ssm

    rng = np.random.default_rng(7)
    s, h, g, n, p = 4, 4, 2, 256, 128

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    state = draw(s, h, n, p)
    assert ssm.kernel_fits(state)
    args = (draw(s, h, p), draw(s, g, n), draw(s, g, n),
            jnp.asarray(rng.uniform(0.0, 0.1, (s, h)), jnp.float32),
            -jnp.arange(1, h + 1, dtype=jnp.float32), draw(h),
            jnp.asarray(live, bool), jnp.asarray(fresh, bool))
    ref = ssm.plain_ssm_step(state, *args)
    got = ssm.ssm_step_kernel(state, *args, interpret=True)
    for r, k in zip(ref, got):
        np.testing.assert_allclose(k, r, atol=1e-3, rtol=1e-5)
    dead = ~np.asarray(live, bool)
    assert np.array_equal(np.asarray(got[1])[dead], np.asarray(state)[dead])
    assert not np.asarray(got[0])[dead].any()


def test_decode_branch_one_position_a_call_is_the_full_forward():
    """``Mamba2Mixer``'s decode branch fed 30 positions one a call (the
    convolution's tail and the state carried in the cache, a pad at
    position 11 of row 0) gives, at each, the full forward's row."""
    from learningorchestra_tpu.ops.ssm import Mamba2Mixer

    layer = Mamba2Mixer(num_heads=4, head_dim=16, n_groups=2, state_dim=8,
                        chunk_size=8, in_multiplier=0.5,
                        part_multipliers=(0.9, 0.8, 0.7, 1.2, 0.6))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 30, 32)), jnp.float32)
    mask = np.ones((2, 30), bool)
    mask[0, 11] = False
    variables = layer.init(jax.random.PRNGKey(0), x)
    full = layer.apply(variables, x, key_mask=jnp.asarray(mask))
    stepper = layer.clone(decode=True)
    cache = stepper.init(jax.random.PRNGKey(0), x)["cache"]
    assert cache["ssm_state"].shape == (2, 4, 8, 16)
    assert cache["conv_tail"].shape == (2, 3, 64 + 2 * 16)

    @jax.jit
    def step(cache, row, seen):
        out, mut = stepper.apply({**variables, "cache": cache},
                                 row[:, None], key_mask=seen,
                                 mutable=["cache"])
        return mut["cache"], out[:, 0]

    rows = []
    for i in range(30):
        seen = jnp.asarray(mask & (np.arange(30) <= i)[None, :])
        cache, out = step(cache, x[:, i], seen)
        rows.append(out)
    assert int(cache["cache_index"]) == 30
    np.testing.assert_allclose(jnp.stack(rows, 1), full, atol=1e-5,
                               rtol=1e-4)


def test_solo_decode_through_pages_and_state_is_the_full_forward(est):
    prompt = [5, 6, 7, 8, 9]
    got = np.asarray(est.generate(np.array([prompt], np.int32),
                                  max_new_tokens=12))[0]
    assert got[:5].tolist() == prompt
    assert _gap(est, prompt, got[5:]) <= 1e-4


def test_key_scale_one_leaves_attention_as_it_was():
    """``key_scale`` 1 is the default and adds nothing: the same
    program, the same numbers."""
    from learningorchestra_tpu.ops.layers import MultiHeadSelfAttention

    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 9, 32)),
                    jnp.float32)
    plain = MultiHeadSelfAttention(num_heads=4, num_kv_heads=2,
                                   qkv_features=32, rope=True)
    scaled = plain.clone(key_scale=0.5)
    variables = plain.init(jax.random.PRNGKey(0), x)
    assert plain.key_scale == 1.0
    assert jax.jit(plain.apply).lower(variables, x).as_text() \
        == jax.jit(plain.clone(key_scale=1.0).apply).lower(
            variables, x).as_text()
    assert not np.allclose(plain.apply(variables, x),
                           scaled.apply(variables, x))


def _step_text(module, kv, chunk=1):
    """The digest of a pool's step program at 2 slots, lowered, without
    locations."""
    from learningorchestra_tpu.serve.decode import blocks
    from learningorchestra_tpu.serve.decode.pages import (
        build_step, step_width,
    )

    step, pages = build_step(module, 2, kv, chunk)
    variables = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    q = step_width(module)
    host = [(3, 2)] if q == 1 else [
        (2, len(blocks.STATE_HEAD) + 3 * q), (len(blocks.SLOT_ROWS), 2)]
    text = step.program.lower(
        variables, pages, jax.ShapeDtypeStruct((2, kv), jnp.int32),
        *(jax.ShapeDtypeStruct(shape, jnp.int32) for shape in host),
    ).as_text()
    return hashlib.sha256(
        re.sub(r"\s*loc\([^\n]*", "", text).encode()).hexdigest()[:16]


def _dense():
    from learningorchestra_tpu.models.text import DecoderLM

    return DecoderLM(vocab_size=24, hidden_dim=32, num_layers=1,
                     num_heads=4, max_len=16).module


def _latent():
    from tests.test_kimi_decode import _estimator as kimi

    return kimi().module


def _retention():
    from tests.test_retention_decode import _estimator as ret

    return ret().module


def _block():
    from tests.test_block_diffusion import _estimator as blockdiff

    return blockdiff().module


#: The step programs of the dense, latent, block and retention models as
#: the commit before the hybrid pool lowered them (the one-token
#: program; the chunk program where the model has one): the pool's
#: change for a cache of both kinds leaves every other model's program
#: as it was.
PARENT_STEPS = {
    "dense": (_dense, 16, 1, "c40937b2a9bc981f"),
    "dense-chunk": (_dense, 16, 8, "1b7230cff34a9aeb"),
    "latent": (_latent, 32, 1, "a872837b3d9c4f20"),
    "block": (_block, 32, 1, "087807090c2eb8d0"),
    "retention": (_retention, 32, 1, "d9be09b4aeb9da5f"),
    "retention-chunk": (_retention, 32, 8, "196cbd2ad427fc51"),
}


@pytest.mark.parametrize("name", list(PARENT_STEPS))
def test_other_pools_lower_as_before(name):
    make, kv, chunk, want = PARENT_STEPS[name]
    assert _step_text(make(), kv, chunk) == want


# -- the pool ---------------------------------------------------------------


def test_the_pool_holds_pages_and_states(est):
    """A layer's K/V pages grow with the bucket, its state and tail do
    not; the model is keyed by length and steps one position a slot; a
    token costs the pool its pages' bytes and a slot its states'."""
    from learningorchestra_tpu.serve.decode.pages import (
        PagePool, build_step, chunk_width, holds_pages, holds_states,
        keyed_by_length,
    )

    for kv in (32, 256):
        _, shapes = build_step(est.module, 4, kv)
        mixer = shapes["HybridBlock_1"]["Mamba2Mixer_0"]
        assert mixer["ssm_state"].shape == (4, 4, 8, 16)
        assert mixer["conv_tail"].shape == (4, 3, 96)
        assert {leaf.dtype for leaf in mixer.values()} \
            == {jnp.dtype("float32")}
        assert holds_pages(shapes) and holds_states(shapes)
    assert keyed_by_length(est.module) and chunk_width(est.module) == 1
    pool = PagePool(256, 4)
    pool._alloc(shapes, 4)
    assert pool.page_bytes() == 4 * (STATE_BYTES + 256 * KV_BYTES)
    assert pool.token_bytes() == KV_BYTES
    assert pool.slot_bytes() == STATE_BYTES
    assert pool.holds_pages and pool.holds_states


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


def test_grow_from_2_to_4_slots_mid_flight_keeps_every_page_and_state(est):
    """Two slots 9 steps in when a third request grows the pool: every
    slot's pages, state and tail are padded, not remade, and every
    request's tokens are the reference's."""
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

    pool = PagePool(64, 4)
    assert pool.admit(streams[0], lambda w: programs(w)[1]) == 0
    assert pool.admit(streams[1], lambda w: programs(w)[1]) == 1
    _drive(est, pool, 9, programs)
    before = jax.tree_util.tree_map(np.asarray, pool.cache)
    assert pool.admit(streams[2], lambda w: programs(w)[1]) == 2
    assert pool.nslots == 4
    for old, grown in zip(jax.tree_util.tree_leaves(before),
                          jax.tree_util.tree_leaves(pool.cache)):
        assert grown.shape[0] == 4
        assert np.array_equal(np.asarray(grown)[:2], old)
    assert pool.slot_bytes() == STATE_BYTES
    assert pool.token_bytes() == KV_BYTES
    _drive(est, pool, 40, programs)
    for slot, prompt in enumerate(prompts):
        row = np.asarray(pool.buf)[slot, : len(prompt) + new]
        assert row[: len(prompt)].tolist() == prompt.tolist()
        assert row.all() and _gap(est, prompt, row[len(prompt):]) <= 1e-4


# -- through the REST surface -----------------------------------------------


def test_slots_at_their_own_positions_serve_the_references_tokens(api, est):
    """Requests of 9 to 200 tokens admitted at different turns: a pool
    a KV bucket, each slot at its own position, prompts taken one
    position a step; every served token's logit is the reference's best
    to 1e-4."""
    server, base = api
    rng = np.random.default_rng(11)
    shapes = [(3, 6), (9, 20), (40, 17), (120, 60), (10, 100)]
    prompts = [rng.integers(1, 97, p).tolist() for p, _ in shapes]
    out = [None] * len(shapes)

    def client(i):
        time.sleep(0.05 * i)
        out[i] = _stream(base, "hyb", prompts[i], maxNewTokens=shapes[i][1])

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
    assert sorted(p["kv"] for p in stats["pools"]) == [16, 32, 64, 128, 256]
    for pool in stats["pools"]:
        assert pool["kvBytesPerToken"] == KV_BYTES
        assert pool["stateBytesPerSlot"] == STATE_BYTES
        assert pool["pageBytes"] == pool["slots"] * (
            STATE_BYTES + pool["kv"] * KV_BYTES)
    assert stats["stepsInPlace"] == stats["steps"] > 0
    assert stats["stateResets"] >= len(shapes)
    assert stats["chunkSteps"] == 0


def test_a_reused_slot_begins_from_zero_state_and_tail(api, est):
    """The second request sits in the slot the first one left its state
    and its convolution's tail in, and reads what a fresh engine
    gives."""
    server, base = api
    first, second = [9, 8, 7, 6, 5, 4], [11, 12, 13]
    _stream(base, "hyb", first, maxNewTokens=20)
    resets = _stats(server)["stateResets"]
    got = _stream(base, "hyb", second, maxNewTokens=20)
    assert len(got) == 20 and _gap(est, second, got) <= 1e-4
    stats = _stats(server)
    assert stats["stateResets"] == resets + 1
    assert all(p["live"] == 0 for p in stats["pools"])


def _never_fresh(real):
    def stub(*args):
        *args, fresh = args
        return real(*args, jnp.zeros_like(fresh))

    return stub


@pytest.mark.parametrize("reset", ["ssm_step", "conv_step"],
                         ids=["state", "tail"])
def test_without_each_reset_a_reused_slot_reads_other_tokens(
        api, est, monkeypatch, reset):
    """The faults the test above is there to catch: with the state's
    reset, or the tail's, patched away, the second request carries the
    first one's into its own."""
    from learningorchestra_tpu.ops import ssm
    from learningorchestra_tpu.train import compile_cache

    server, base = api
    monkeypatch.setattr(ssm, reset, _never_fresh(getattr(ssm, reset)))
    server.serving.decode.drop_model("hyb")
    compile_cache.get_cache().clear()
    try:
        first, second = [9, 8, 7, 6, 5, 4], [11, 12, 13]
        served = _stream(base, "hyb", first, maxNewTokens=20)
        assert _gap(est, first, served) <= 1e-4  # a fresh pool's zeros
        got = _stream(base, "hyb", second, maxNewTokens=20)
        assert _gap(est, second, got) > 1e-3
    finally:
        monkeypatch.undo()
        server.serving.decode.drop_model("hyb")
        compile_cache.get_cache().clear()


def test_step_annotation_carries_both_byte_counts(api, annotations):
    _, base = api
    _stream(base, "hyb", [5, 6, 7, 8], maxNewTokens=6)
    annotations.settle()
    stepped = [md for name, md in annotations
               if name == "decode.step" and md.get("slots")]
    assert stepped
    assert all(md["state_bytes_per_slot"] == STATE_BYTES
               and md["kv_bytes_per_token"] == KV_BYTES and md["kv"] == 16
               and md["pools"] == 1 and md["inplace"] == 1
               and md["chunk"] == 0 for md in stepped)
    assert sum(md["state_resets"] for md in stepped) == 1


def test_bf16_leaves_are_served_with_float32_states(api):
    server, base = api
    est16 = _estimator(param_dtype="bfloat16", seed=2)
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(est16.params)} \
        == {"bfloat16"}
    _publish(server, "hyb16", est16)
    requests.post(f"{base}/serve/hyb16/load", timeout=60).raise_for_status()
    assert len(_stream(base, "hyb16", [7, 3, 9, 2, 8], maxNewTokens=8)) == 8
    pool = next(iter(
        server.serving.decode._decoders["hyb16"]._pools.values()))
    states = [leaf for layer in pool.cache.values()
              for leaf in layer["Mamba2Mixer_0"].values()]
    assert {str(a.dtype) for a in states} == {"float32"}
    pages = [leaf for layer in pool.cache.values()
             for leaf in layer["MultiHeadSelfAttention_0"].values()]
    assert {str(a.dtype) for a in pages} == {"bfloat16"}
