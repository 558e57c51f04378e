"""The decode step's pass over the KV pages (ops/decode_attention.py):
the Pallas kernel, run by the interpreter on the CPU, against the plain
``where`` insert + ``grouped_decode_attend`` it replaces on the TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learningorchestra_tpu.ops import decode_attention as da
from learningorchestra_tpu.ops import layers

TK = 512  # two blocks of positions a slot


def _inputs(b, h, kvh, t, hd, dtype, seed):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    pack = da.page_pack(hd, TK)
    return (
        draw(b, h, t, hd), draw(b, kvh, t, hd), draw(b, kvh, t, hd),
        da.pack_pages(draw(b, kvh, TK, hd), pack),
        da.pack_pages(draw(b, kvh, TK, hd), pack),
        jnp.asarray(rng.integers(0, 4, size=(b, TK)) != 0),
    )


def _mask(buf, idx, t, block):
    """The key mask as the layer hands it over: the caller's ``buf !=
    0`` and the layer's validity, a query position where t > 1."""
    slot = jnp.arange(TK)[None, :]
    if t == 1:
        return buf & (slot <= idx[:, None])
    last = idx[:, None, None] + (
        t - 1 if block else jnp.arange(t)[None, :, None]
    )
    return buf[:, None, :] & (slot[:, None, :] <= last)


_kernel = jax.jit(
    lambda *args: da.decode_attend(*args, interpret=True)
)


@pytest.mark.parametrize("t,h,kvh,hd,dtype,block", [
    (1, 5, 5, 64, jnp.float32, False),   # GPT-2 XL's kind: MHA, two a row
    (1, 32, 4, 128, jnp.bfloat16, False),
    (1, 8, 2, 64, jnp.bfloat16, False),
    (1, 4, 4, 128, jnp.float32, False),
    (4, 32, 4, 128, jnp.bfloat16, True),  # SDAR's kind: 32 over 4, a block
    (4, 32, 4, 128, jnp.bfloat16, False),
    (4, 8, 2, 64, jnp.float32, True),
    (4, 8, 2, 64, jnp.float32, False),
    (4, 4, 4, 128, jnp.float32, False),
    (4, 5, 5, 64, jnp.bfloat16, True),
    # a prompt chunk (``pages.PROMPT_CHUNK`` causal rows a slot)
    (8, 5, 5, 64, jnp.float32, False),
    (8, 8, 2, 64, jnp.bfloat16, False),
])
def test_kernel_equals_the_plain_path(t, h, kvh, hd, dtype, block):
    """Slots at different lengths: at 0, mid-tile, astride two tiles
    and two blocks of positions, on the bucket's last row (a chunk's
    rows beyond it are dropped, not moved), one free (all pad, its
    rows written at 0) and one whose keys are all masked."""
    idx = jnp.asarray([0, 5, 254, TK - 1 if t == 1 else TK - 2, 0, 300],
                      jnp.int32)
    q, k, v, k_pages, v_pages, buf = _inputs(6, h, kvh, t, hd, dtype, 3)
    buf = buf.at[4].set(False)  # the free slot: an all-pad row
    mask = _mask(buf, idx, t, block)
    mask = mask.at[5].set(False)  # a live slot that sees no key
    ref_out, ref_k, ref_v = da.plain_attend(q, k, v, k_pages, v_pages, idx, mask)
    out, new_k, new_v = _kernel(q, k, v, k_pages, v_pages, idx, mask)
    # the pages: the ``where`` insert's, bit for bit
    assert jnp.array_equal(new_k, ref_k) and jnp.array_equal(new_v, ref_v)
    assert not jnp.array_equal(new_k, k_pages)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out, np.float32),
        atol=tol, rtol=tol,
    )
    # fully masked rows give exactly 0
    assert not np.any(np.asarray(out[4:], np.float32))
    assert not np.any(np.asarray(ref_out[4:], np.float32))


def test_rows_beyond_the_bucket_are_dropped_not_moved():
    """``dynamic_update_slice`` would clamp the chunk's start and
    overwrite valid rows; the insert keeps them."""
    rows = jnp.arange(1, 5, dtype=jnp.float32).reshape(1, 1, 4, 1)
    pages = jnp.zeros((1, 1, 8, 1))
    got = da.insert_rows(pages, rows, jnp.asarray([6], jnp.int32))
    assert got[0, 0, :, 0].tolist() == [0, 0, 0, 0, 0, 0, 1, 2]
    clamped = jax.lax.dynamic_update_slice(pages, rows, (0, 0, 6, 0))
    assert clamped[0, 0, :, 0].tolist() == [0, 0, 0, 0, 1, 2, 3, 4]


def test_short_slots_read_no_further_than_their_last_key():
    """Blocks beyond a slot's last key are not scored: poison there
    (NaN would spread through a product with probability 0) changes
    nothing."""
    idx = jnp.asarray([10, 200], jnp.int32)
    q, k, v, k_pages, v_pages, buf = _inputs(2, 5, 5, 1, 64, jnp.float32, 4)
    mask = _mask(buf, idx, 1, False)
    out, _, _ = _kernel(q, k, v, k_pages, v_pages, idx, mask)
    # slot 1 ends in the second block of positions: poison from the
    # third on
    beyond = 2 * da._block_rows(k_pages.shape[2], 2, 8)
    assert beyond < k_pages.shape[2]
    poisoned = [p.at[:, :, beyond:].set(jnp.nan) for p in (k_pages, v_pages)]
    again, new_k, _ = _kernel(q, k, v, *poisoned, idx, mask)
    assert jnp.array_equal(out, again)
    assert bool(jnp.all(jnp.isnan(new_k[:, :, beyond:])))


@pytest.mark.parametrize("hd,tk,pack", [
    (64, 512, 2), (128, 512, 1), (8, 16, 16), (8, 12, 1), (96, 512, 1),
    (256, 512, 1),
])
def test_page_pack(hd, tk, pack):
    assert da.page_pack(hd, tk) == pack
    pages = jnp.arange(2 * 3 * tk * hd, dtype=jnp.float32).reshape(
        2, 3, tk, hd
    )
    packed = da.pack_pages(pages, pack)
    assert packed.shape == (2, 3, tk // pack, pack * hd)
    assert jnp.array_equal(da.unpack_pages(packed, hd), pages)


def test_the_kernel_is_chosen_from_platform_and_shapes(monkeypatch):
    q = jnp.zeros((2, 4, 1, 64))
    assert da.kernel_fits(q, jnp.zeros((2, 4, 256, 128)))
    assert not da.kernel_fits(q, jnp.zeros((2, 4, 12, 128)))  # tiles
    assert not da.kernel_fits(
        jnp.zeros((2, 4, 1, 96)), jnp.zeros((2, 4, 512, 96))
    )  # lanes
    assert not da.kernel_fits(
        q.astype(jnp.bfloat16), jnp.zeros((2, 4, 256, 128))
    )
    # off the TPU the plain path, whatever the shapes
    called = []
    monkeypatch.setattr(
        da, "decode_attend", lambda *a, **k: called.append(1)
    )
    pages = jnp.zeros((2, 4, 256, 128))
    da.cached_attend(
        q, q, q, pages, pages, jnp.zeros(2, jnp.int32),
        jnp.ones((2, 512), bool),
    )
    assert not called
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    da.cached_attend(
        q, q, q, pages, pages, jnp.zeros(2, jnp.int32),
        jnp.ones((2, 512), bool),
    )
    assert called == [1]


@pytest.mark.parametrize("kwargs,t", [
    (dict(num_heads=4, window=40), 1),
    (dict(num_heads=8, num_kv_heads=2, rope=True, window=40), 1),
    (dict(num_heads=4, num_kv_heads=2, rope=True, qk_norm=True, block=4,
          use_bias=False), 4),
    (dict(num_heads=4, window=6), 4),
], ids=["window", "gqa-rope-window", "block-chunk", "causal-chunk-window"])
def test_the_layer_through_the_kernel(kwargs, t, monkeypatch):
    """``MultiHeadSelfAttention``'s decode branch with a per-row index,
    over several steps: the kernel in the plain path's place gives the
    outputs and the pages of the plain path (its masks — the window,
    the in-chunk causal and block masks, the caller's — are applied as
    they are)."""
    kwargs.setdefault("causal", "block" not in kwargs)
    layer = layers.MultiHeadSelfAttention(
        qkv_features=256, decode=True, **kwargs
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, TK, 256)), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(1), x)
    cache = {
        name: leaf for name, leaf in variables["cache"].items()
        if name != "cache_index"
    }
    assert cache["cached_key"].shape[-1] == 128
    buf = jnp.asarray(rng.integers(0, 5, size=(3, TK)) != 0)

    def steps(cache):
        outs = []
        for pos in ([0, 0, 0], [t, 60, 252], [2 * t, 64, 256]):
            idx = jnp.asarray(pos, jnp.int32)
            out, mut = layer.apply(
                {"params": variables["params"],
                 "cache": {**cache, "cache_index": idx}},
                x[:, 7:7 + t], key_mask=buf, mutable=["cache"],
            )
            cache = {
                name: leaf for name, leaf in mut["cache"].items()
                if name != "cache_index"
            }
            outs.append(out)
        return jnp.stack(outs), cache

    ref_out, ref_cache = jax.jit(lambda pages: steps(pages))(cache)
    monkeypatch.setattr(
        layers, "cached_attend",
        lambda *args: da.decode_attend(*args, interpret=True),
    )
    out, new_cache = jax.jit(lambda pages: steps(pages))(cache)
    np.testing.assert_allclose(out, ref_out, atol=2e-5, rtol=2e-5)
    for name in ("cached_key", "cached_value"):
        assert jnp.array_equal(new_cache[name], ref_cache[name]), name
