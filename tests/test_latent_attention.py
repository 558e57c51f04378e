"""Multi-head latent attention (``ops/latent_attention.py``) at tiny
widths on the CPU, against the plain reference of
``tests/latent_oracle.py``: YaRN's frequencies and temperature in
closed form, the layer's full forward, the absorbed decode branch
through its one-leaf cache, and the kernel (interpreted) against the
plain form."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learningorchestra_tpu.ops import latent_attention as la
from learningorchestra_tpu.ops.layers import apply_rope
from tests import latent_oracle as oracle
from tests.test_kimi_decode import YARN, _estimator

LAYER = dict(num_heads=4, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
             rope_theta=50000.0, rope_scaling=tuple(sorted(
                 (k, v) for k, v in YARN.items() if k != "type")),
             norm_eps=1e-5)


def test_yarn_inv_freq_is_the_closed_form_at_factor_64():
    """Kimi-K2's group at a head of 64: pairs 0-8 keep theta^(-2i/64)
    (they turn over 32 times in 4,096 positions), pairs 20-31 take it
    over 64 (under one turn), a linear ramp between."""
    got = np.asarray(la.yarn_inv_freq(64, 50000.0, 64.0, 4096, 32.0, 1.0))
    plain = 50000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(got[20:], plain[20:] / 64.0, rtol=1e-6)
    ramp = (np.arange(9, 20) - 8) / 12.0
    np.testing.assert_allclose(
        got[9:20], plain[9:20] * (ramp / 64.0 + 1.0 - ramp), rtol=1e-5)
    np.testing.assert_allclose(
        got, oracle.yarn_inv_freq(64, 50000.0, YARN), rtol=1e-5)


def test_yarn_temperature_and_softmax_scale():
    m = la.yarn_mscale(64.0, 1.0)
    assert m == pytest.approx(0.1 * math.log(64.0) + 1.0)
    assert m == pytest.approx(1.4159, abs=1e-4)
    assert la.yarn_mscale(1.0, 1.0) == 1.0
    layer = la.LatentAttention(**{**LAYER, "qk_nope_head_dim": 128,
                                  "qk_rope_head_dim": 64})
    _, rot, scale = layer._yarn()
    assert rot == pytest.approx(1.0)  # mscale / mscale_all_dim
    assert scale == pytest.approx(192 ** -0.5 * m * m)


def test_without_scaling_rotate_is_plain_rope():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5, 8))
    pos = jnp.array([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]])
    inv = la.yarn_inv_freq(8, 10000.0)
    np.testing.assert_allclose(
        la.rotate(x, pos, inv), apply_rope(x, pos, 10000.0), atol=1e-6)
    np.testing.assert_allclose(
        la.rotate(x, pos[0], inv), apply_rope(x, pos[0], 10000.0),
        atol=1e-6)


@pytest.fixture(scope="module")
def layer_and_params():
    layer = la.LatentAttention(**LAYER)
    params = layer.init(jax.random.PRNGKey(3), jnp.ones((1, 6, 64)))
    params = jax.tree_util.tree_map(lambda a: a * 1.5, params)
    return layer, params


def _oracle_attention(params, x, key_mask):
    est = _estimator()
    return jnp.stack([
        oracle.attention(est, params["params"], row, jnp.asarray(mask))
        for row, mask in zip(x, key_mask)
    ])


def test_full_forward_matches_the_per_head_reference(layer_and_params):
    layer, params = layer_and_params
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 64))
    mask = np.ones((2, 9), bool)
    mask[1, 3] = False  # a pad key nobody sees
    got = layer.apply(params, x, key_mask=jnp.asarray(mask))
    want = _oracle_attention(params, x, mask)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_the_cache_is_one_leaf_of_latent_plus_rotary_key(layer_and_params):
    layer, _ = layer_and_params
    shapes = jax.eval_shape(
        layer.clone(decode=True).init, jax.random.PRNGKey(0),
        jnp.zeros((3, 32, 64)),
    )["cache"]
    assert set(shapes) == {"cached_latent", "cache_index"}
    assert shapes["cached_latent"].shape == (3, 32, 16 + 4)


@pytest.fixture(scope="module")
def packed_layer_and_params():
    """Kimi-K2's latent and rotary widths cut to a quarter: two
    positions share a row of the page leaf (``page_pack`` 2)."""
    layer = la.LatentAttention(**{**LAYER, "kv_lora_rank": 128,
                                  "qk_rope_head_dim": 64})
    params = layer.init(jax.random.PRNGKey(3), jnp.ones((1, 6, 64)))
    return layer, jax.tree_util.tree_map(lambda a: a * 1.5, params)


def test_two_positions_share_a_row_where_the_widths_allow():
    assert la.page_pack(512, 64, 2048) == 2
    assert la.page_shape(64, 2048, 512, 64) == (64, 1024, 1152)
    assert la.page_pack(16, 4, 32) == 1 and la.page_pack(128, 64, 7) == 1
    # latents first, rotary keys behind them: every part on whole tiles
    latent = jnp.arange(2 * 8 * 128, dtype=jnp.float32).reshape(2, 8, 128)
    key_pe = -jnp.arange(2 * 8 * 64, dtype=jnp.float32).reshape(2, 8, 64)
    pages = la.insert_rows(jnp.zeros((2, 4, 384)), latent, key_pe,
                           jnp.zeros(2, jnp.int32))
    assert np.array_equal(pages[0, 1, :128], latent[0, 2])
    assert np.array_equal(pages[0, 1, 128:256], latent[0, 3])
    assert np.array_equal(pages[1, 0, 256 + 64:], key_pe[1, 1])
    got = la.unpack_pages(pages, 128, 64)
    assert np.array_equal(got[0], latent) and np.array_equal(got[1], key_pe)


@pytest.mark.parametrize("starts", [[0, 0], [0, 3], [5, 1]])
@pytest.mark.parametrize("which", ["layer_and_params",
                                   "packed_layer_and_params"])
def test_absorbed_decode_through_the_cache_is_the_full_forward(
        request, which, starts):
    """Rows fed one position a step, each slot at its own position (a
    slot that starts later is admitted mid-flight), through the latent
    cache: every output equals the full forward's at that position."""
    layer, params = request.getfixturevalue(which)
    t = 12
    x = jax.random.normal(jax.random.PRNGKey(5), (2, t, 64))
    full = layer.apply(params, x)
    dec = layer.clone(decode=True)
    cache = {
        "cached_latent": jnp.zeros(la.page_shape(
            2, 16, layer.kv_lora_rank, layer.qk_rope_head_dim)),
        "cache_index": jnp.zeros((2,), jnp.int32),
    }
    pos = np.zeros(2, np.int32)
    seen = np.zeros((2, t), bool)
    for turn in range(t + max(starts)):
        live = np.array([turn >= s and pos[i] < t
                         for i, s in enumerate(starts)])
        idx = np.where(live, pos, 0).astype(np.int32)
        row = x[jnp.arange(2), idx][:, None]
        kmask = np.arange(16)[None, :] <= idx[:, None]
        kmask &= live[:, None]  # a free slot's row is all pad
        out, mut = dec.apply(
            {**params, "cache": {**cache, "cache_index": jnp.asarray(idx)}},
            row, key_mask=jnp.asarray(kmask), mutable=["cache"],
        )
        cache = mut["cache"]
        for i in range(2):
            if live[i]:
                np.testing.assert_allclose(
                    out[i, 0], full[i, pos[i]], atol=2e-5, rtol=1e-4)
                seen[i, pos[i]] = True
                pos[i] += 1
    assert seen.all()


def test_a_row_beyond_the_bucket_is_dropped():
    pages = jnp.zeros((2, 4, 3))
    out = la.insert_rows(pages, jnp.ones((2, 1, 2)), jnp.ones((2, 1, 1)),
                         jnp.array([1, 4]))
    assert np.asarray(out)[0, 1].tolist() == [1, 1, 1]
    assert not np.asarray(out)[1].any()
    assert np.asarray(out).sum() == 3


def test_a_query_with_no_key_reads_exactly_zero():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 1, 20))
    pages = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 20))  # pack 1
    mask = jnp.array([[True] * 3 + [False] * 5, [False] * 8])
    out = la.plain_latent_attend(q, pages, mask, 16, 0.3)
    assert out.shape == (2, 4, 1, 16)
    assert np.asarray(out)[0].any() and not np.asarray(out)[1].any()


@pytest.mark.parametrize("tk,idx", [
    (256, [0, 100, 255]), (1024, [3, 511, 512]), (1024, [1023, 700, 0]),
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernel_interpreted_is_the_plain_form(tk, idx, dtype):
    """``latent_attend_kernel`` (interpret mode) over pages of 128 + 64
    values a position, two positions a row: one read of the pages, the
    values their own latents, blocks beyond a slot's position never
    scored."""
    rank, rp, heads = 128, 64, 8
    key = jax.random.split(jax.random.PRNGKey(tk + idx[0]), 3)
    q = jax.random.normal(key[0], (3, heads, 1, rank + rp)).astype(dtype)
    rows = jax.random.normal(key[1], (3, tk, rank + rp)).astype(dtype)
    pages = la.insert_rows(
        jnp.zeros(la.page_shape(3, tk, rank, rp), dtype),
        rows[..., :rank], rows[..., rank:], jnp.zeros(3, jnp.int32))
    assert pages.shape == (3, tk // 2, 2 * (rank + rp))
    idx = jnp.asarray(idx, jnp.int32)
    mask = (jnp.arange(tk)[None, :] <= idx[:, None]) \
        & (jax.random.uniform(key[2], (3, tk)) > 0.1)
    assert la.kernel_fits(q, pages, mask, rank)
    new = jax.random.normal(key[2], (3, 1, rank + rp)).astype(dtype)
    got, got_pages = la.latent_attend_kernel(
        q, new[..., :rank], new[..., rank:], pages, idx, mask, rank=rank,
        scale=0.11, interpret=True)
    want_pages = la.insert_rows(pages, new[..., :rank], new[..., rank:],
                                idx)
    want = la.plain_latent_attend(q, want_pages, mask, rank, 0.11)
    # the step's row is in the pages, every other value as it was
    assert np.array_equal(np.asarray(got_pages, np.float32),
                          np.asarray(want_pages, np.float32))
    assert not np.array_equal(np.asarray(got_pages, np.float32),
                              np.asarray(pages, np.float32))
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
