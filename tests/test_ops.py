"""Pallas kernel library — flash attention vs the jnp reference.

Runs on CPU via Pallas interpret mode (auto-selected off-TPU); the same
kernels compile for TPU unchanged (verified on hardware; block shapes
follow the Mosaic (8, 128) tiling rules).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # compile-heavy: full tier only

from learningorchestra_tpu.ops import flash_attention, mha_reference

B, H, T, D = 2, 3, 48, 16
BLOCK = dict(block_q=16, block_k=16)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(7)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((B, H, T, D), dtype=np.float32)
    )
    return mk(), mk(), mk()


class TestFlashAttentionForward:
    def test_matches_reference_unmasked(self, qkv):
        q, k, v = qkv
        out = flash_attention(q, k, v, **BLOCK)
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_matches_reference_masked(self, qkv):
        q, k, v = qkv
        rng = np.random.default_rng(3)
        mask = jnp.asarray(rng.random((B, T)) > 0.4)
        out = flash_attention(q, k, v, mask, **BLOCK)
        ref = mha_reference(q, k, v, mask)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_fully_masked_rows_are_zero(self, qkv):
        q, k, v = qkv
        mask = jnp.zeros((B, T), bool).at[1, :3].set(True)
        out = flash_attention(q, k, v, mask, **BLOCK)
        assert float(jnp.max(jnp.abs(out[0]))) == 0.0
        np.testing.assert_allclose(
            out, mha_reference(q, k, v, mask), atol=2e-5
        )

    def test_unaligned_lengths_pad_correctly(self, qkv):
        q, k, v = qkv
        qs, ks, vs = q[:, :, :37], k[:, :, :41], v[:, :, :41]
        out = flash_attention(qs, ks, vs, **BLOCK)
        assert out.shape == qs.shape
        np.testing.assert_allclose(
            out, mha_reference(qs, ks, vs), atol=2e-5, rtol=2e-5
        )

    def test_bfloat16_inputs(self, qkv):
        q, k, v = (t.astype(jnp.bfloat16) for t in qkv)
        out = flash_attention(q, k, v, **BLOCK)
        assert out.dtype == jnp.bfloat16
        ref = mha_reference(*qkv)
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref, atol=3e-2, rtol=3e-2
        )

    def test_jit_compatible(self, qkv):
        q, k, v = qkv
        f = jax.jit(lambda q, k, v: flash_attention(q, k, v, **BLOCK))
        np.testing.assert_allclose(
            f(q, k, v), mha_reference(q, k, v), atol=2e-5, rtol=2e-5
        )


class TestFlashAttentionBackward:
    def _grads(self, fn, q, k, v):
        return jax.grad(
            lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))), argnums=(0, 1, 2)
        )(q, k, v)

    def test_grads_match_reference(self, qkv):
        q, k, v = qkv
        rng = np.random.default_rng(5)
        mask = jnp.asarray(rng.random((B, T)) > 0.3)
        g1 = self._grads(
            lambda q, k, v: flash_attention(q, k, v, mask, **BLOCK), q, k, v
        )
        g2 = self._grads(
            lambda q, k, v: mha_reference(q, k, v, mask), q, k, v
        )
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)

    def test_fully_masked_grads_zero_and_finite(self, qkv):
        q, k, v = qkv
        mask = jnp.zeros((B, T), bool).at[1].set(True)
        grads = self._grads(
            lambda q, k, v: flash_attention(q, k, v, mask, **BLOCK), q, k, v
        )
        for g in grads:
            assert bool(jnp.all(jnp.isfinite(g)))
            assert float(jnp.max(jnp.abs(g[0]))) == 0.0  # masked batch

    def test_grads_under_jit(self, qkv):
        q, k, v = qkv
        f = jax.jit(
            jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, **BLOCK) ** 2
                )
            )
        )
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(mha_reference(q, k, v) ** 2)
        )(q, k, v)
        np.testing.assert_allclose(f(q, k, v), g_ref, atol=5e-5, rtol=5e-5)


class TestModelIntegration:
    def test_bert_encoder_flash_vs_reference(self):
        """The full encoder produces the same logits on both attention
        paths (forced flash-in-interpret vs jnp reference)."""
        from learningorchestra_tpu.models.text import BertEncoder

        def build(use_flash):
            return BertEncoder(
                vocab_size=64, hidden_dim=32, num_layers=1, num_heads=2,
                mlp_dim=64, max_len=16, use_flash=use_flash,
            )

        rng = np.random.default_rng(0)
        tokens = rng.integers(1, 64, (2, 16), dtype=np.int32)
        tokens[0, 10:] = 0  # pad tail
        params = build(False).init(jax.random.PRNGKey(0), jnp.asarray(tokens))
        out_ref = build(False).apply(params, jnp.asarray(tokens))
        out_flash = build(True).apply(params, jnp.asarray(tokens))
        np.testing.assert_allclose(out_flash, out_ref, atol=1e-4, rtol=1e-4)

    def test_bert_estimator_trains_with_flash(self):
        from learningorchestra_tpu.models.text import TransformerClassifier

        est = TransformerClassifier(
            vocab_size=32, hidden_dim=16, num_layers=1, num_heads=2,
            max_len=8,
        )
        rng = np.random.default_rng(1)
        x = rng.integers(1, 32, (16, 8), dtype=np.int32)
        y = rng.integers(0, 2, (16,), dtype=np.int32)
        est.fit(x, y, epochs=1, batch_size=8)
        assert np.isfinite(est.history["loss"][-1])


    def test_mesh_trainer_runs_the_kernel_per_shard(self):
        """A flash model under DistributedTrainer on a dp x tp mesh:
        the kernel runs per shard (shard_map under the trainer's
        ambient mesh — on the chip GSPMD refuses to partition it) and
        the fit matches the jnp-attention fit of the same model."""
        from learningorchestra_tpu.models.text import BertModel
        from learningorchestra_tpu.parallel.distributed import (
            DistributedTrainer,
        )
        from learningorchestra_tpu.parallel.mesh import (
            MeshSpec,
            build_mesh,
        )

        rng = np.random.default_rng(3)
        x = rng.integers(1, 32, (16, 8), dtype=np.int32)
        y = rng.integers(0, 2, (16,), dtype=np.int32)
        mesh = build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
        losses = {}
        for use_flash in (True, False):
            est = BertModel(
                vocab_size=32, hidden_dim=16, num_layers=1, num_heads=2,
                max_len=8, use_flash=use_flash, learning_rate=1e-3,
            )
            est.compute_dtype = "float32"
            DistributedTrainer(est, mesh=mesh).fit(
                x, y, epochs=2, batch_size=8, shuffle=False
            )
            losses[use_flash] = est.history["loss"]
        np.testing.assert_allclose(
            losses[True], losses[False], atol=1e-5, rtol=1e-5
        )

    def test_per_shard_specs_follow_divisibility(self):
        """Batch over the data axes and heads over tp only where they
        divide the dimension; a batch of one (the init pass) repeats
        the kernel across the axis instead."""
        from jax.sharding import Mesh

        from learningorchestra_tpu.ops.attention import (
            flash_attention,
            mha_reference,
        )

        mesh = Mesh(
            np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp")
        )
        rng = np.random.default_rng(4)
        for shape in ((4, 4, 16, 8), (1, 3, 16, 8)):
            q, k, v = (
                jnp.asarray(rng.standard_normal(shape), jnp.float32)
                for _ in range(3)
            )
            with jax.set_mesh(mesh):
                out = jax.jit(
                    lambda q, k, v: flash_attention(q, k, v, causal=True)
                )(q, k, v)
            np.testing.assert_allclose(
                out, mha_reference(q, k, v, causal=True),
                atol=1e-5, rtol=1e-5,
            )


class TestCausalFlashAttention:
    """Causal (decoder) masking in the flash kernel vs the reference,
    forward + backward, with and without key padding masks."""

    def test_causal_matches_reference(self):
        import jax
        import jax.numpy as jnp

        from learningorchestra_tpu.ops.attention import (
            flash_attention,
            mha_reference,
        )

        rng = np.random.default_rng(3)
        for b, h, t, d in [(2, 2, 64, 16), (1, 2, 80, 8), (2, 1, 33, 16)]:
            q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
            k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
            v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
            mask = jnp.asarray(
                rng.integers(0, 2, (b, t)).astype(np.float32)
            ).at[:, 0].set(1.0)
            for km in (None, mask):
                out = flash_attention(
                    q, k, v, km, causal=True, block_q=32, block_k=32
                )
                ref = mha_reference(q, k, v, km, causal=True)
                np.testing.assert_allclose(
                    np.asarray(out), np.asarray(ref), atol=2e-5
                )

                def loss_f(q, k, v, km=km):
                    return jnp.sum(flash_attention(
                        q, k, v, km, causal=True, block_q=32, block_k=32
                    ) ** 2)

                def loss_r(q, k, v, km=km):
                    return jnp.sum(
                        mha_reference(q, k, v, km, causal=True) ** 2
                    )

                g1 = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
                g2 = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
                for a, b2 in zip(g1, g2):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b2), atol=5e-5
                    )

    def test_causal_is_actually_causal(self):
        """Future tokens must not influence earlier outputs: perturbing
        position t changes outputs only at positions >= t."""
        import jax.numpy as jnp

        from learningorchestra_tpu.ops.attention import flash_attention

        rng = np.random.default_rng(4)
        b, h, t, d = 1, 1, 32, 8
        q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
        out = np.asarray(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16
        ))
        k2 = k.at[0, 0, 20].add(5.0)
        v2 = v.at[0, 0, 20].add(5.0)
        out2 = np.asarray(flash_attention(
            q, k2, v2, causal=True, block_q=16, block_k=16
        ))
        np.testing.assert_allclose(out[:, :, :20], out2[:, :, :20],
                                   atol=1e-6)
        assert np.abs(out[:, :, 20:] - out2[:, :, 20:]).max() > 1e-3


class TestSlidingWindowAttention:
    """Banded causal attention (window=W): each query sees its last W
    positions; off-band blocks skip compute entirely on the flash path."""

    def _qkv(self, t, d=8, seed=0):
        rng = np.random.default_rng(seed)
        mk = lambda: jnp.asarray(  # noqa: E731
            rng.standard_normal((2, 2, t, d)), jnp.float32
        )
        km = jnp.asarray(rng.random((2, t)) > 0.1)
        return mk(), mk(), mk(), km

    @pytest.mark.parametrize("t,w,bq,bk", [
        (64, 16, 8, 8),    # window spans multiple blocks
        (64, 1, 8, 16),    # degenerate: each token sees itself only
        (40, 100, 8, 8),   # window > T: equals plain causal
        (128, 13, 16, 8),  # window not a block multiple
    ])
    def test_matches_reference(self, t, w, bq, bk):
        q, k, v, km = self._qkv(t, seed=t + w)
        out = flash_attention(
            q, k, v, km, causal=True, window=w,
            block_q=bq, block_k=bk, interpret=True,
        )
        ref = mha_reference(q, k, v, km, causal=True, window=w)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    def test_gradients_match_reference(self):
        q, k, v, km = self._qkv(64, seed=3)

        def g(fn):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v) * v),
                argnums=(0, 1, 2),
            ))(q, k, v)

        gf = g(lambda q, k, v: flash_attention(
            q, k, v, km, causal=True, window=16,
            block_q=8, block_k=8, interpret=True,
        ))
        gr = g(lambda q, k, v: mha_reference(
            q, k, v, km, causal=True, window=16,
        ))
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4
            )

    def test_window_requires_causal(self):
        q, k, v, _ = self._qkv(16)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, window=4, interpret=True)
        with pytest.raises(ValueError, match=">= 1"):
            flash_attention(q, k, v, causal=True, window=0,
                            interpret=True)

    def test_windowed_decoder_lm_cache_generate(self):
        """A sliding-window DecoderLM must train, and its KV-cache
        generate must match the naive full-forward loop (the decode
        branch enforces the window via the key mask)."""
        from learningorchestra_tpu.models.text import DecoderLM
        from tests.lm_oracle import naive_greedy_decode

        rng = np.random.default_rng(4)
        x = rng.integers(1, 32, (8, 12)).astype(np.int32)
        tgt = np.concatenate([x[:, 1:], np.zeros((8, 1), np.int32)], 1)
        est = DecoderLM(
            vocab_size=32, hidden_dim=32, num_layers=2, num_heads=2,
            max_len=16, attention_window=4,
        )
        est.fit(x, tgt, epochs=2, batch_size=8, verbose=0)
        assert np.isfinite(est.history["loss"][-1])
        out = est.generate(x[:2, :6], max_new_tokens=4)
        np.testing.assert_array_equal(
            out, naive_greedy_decode(est, x[:2, :6], 10)
        )

    def test_band_grid_is_narrowed(self):
        """The streamed k axis must shrink to O(window/block) slots —
        the whole point: off-band K/V blocks are never DMA'd."""
        from learningorchestra_tpu.ops.attention import _win_k_slots

        # T=128k tokens, 1024-blocks, window 4096: 6 slots vs 128.
        assert _win_k_slots(512, 1024, 4096, 128) == 6
        # Window wider than the sequence: full causal grid.
        assert _win_k_slots(8, 8, 10_000, 4) == 4
        # Tiny window: 2-3 blocks regardless of T.
        assert _win_k_slots(8, 8, 1, 1024) == 2


class TestDecodeStandaloneValidity:
    def test_decode_without_key_mask_matches_causal_forward(self):
        """ADVICE r2: decode mode with key_mask=None must not hand
        probability mass to uninitialized (zero) cache slots.  The
        layer owns cache_index, so it ANDs the validity mask itself —
        the documented init-then-feed-one-token flow is correct
        standalone, no caller-side mask required."""
        from learningorchestra_tpu.ops.layers import MultiHeadSelfAttention

        b, t, f = 2, 6, 8
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.standard_normal((b, t, f)), jnp.float32)

        full = MultiHeadSelfAttention(
            num_heads=2, qkv_features=f, causal=True, use_flash=False
        )
        variables = full.init(jax.random.PRNGKey(0), x)
        ref = full.apply(variables, x)

        dec = MultiHeadSelfAttention(num_heads=2, qkv_features=f, decode=True)
        # Same submodule names -> the causal model's params drive the
        # decode module; init on the full-length input sizes the cache.
        cache = dec.init(jax.random.PRNGKey(0), x)["cache"]
        outs = []
        for i in range(t):
            out, mut = dec.apply(
                {"params": variables["params"], "cache": cache},
                x[:, i:i + 1], mutable=["cache"],
            )
            cache = mut["cache"]
            outs.append(out)
        got = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_decode_window_without_key_mask(self):
        """Same standalone guarantee for sliding-window decode: the
        window narrowing composes with the validity mask."""
        from learningorchestra_tpu.ops.layers import MultiHeadSelfAttention

        b, t, f, w = 2, 8, 8, 3
        rng = np.random.default_rng(12)
        x = jnp.asarray(rng.standard_normal((b, t, f)), jnp.float32)

        full = MultiHeadSelfAttention(
            num_heads=2, qkv_features=f, causal=True, window=w,
            use_flash=False,
        )
        variables = full.init(jax.random.PRNGKey(0), x)
        ref = full.apply(variables, x)

        dec = MultiHeadSelfAttention(
            num_heads=2, qkv_features=f, decode=True, causal=True,
            window=w,
        )
        cache = dec.init(jax.random.PRNGKey(0), x)["cache"]
        outs = []
        for i in range(t):
            out, mut = dec.apply(
                {"params": variables["params"], "cache": cache},
                x[:, i:i + 1], mutable=["cache"],
            )
            cache = mut["cache"]
            outs.append(out)
        got = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


class TestFusedQKV:
    def test_fused_matches_separate_projections(self):
        """fused_qkv is a layout change, not a math change: stacking
        the three projection kernels into the fused weight reproduces
        the unfused layer's output exactly."""
        from learningorchestra_tpu.ops.layers import MultiHeadSelfAttention

        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((2, 12, 16)), jnp.float32)
        sep = MultiHeadSelfAttention(
            num_heads=4, qkv_features=16, use_flash=False,
            fused_qkv=False,
        )
        ps = sep.init(jax.random.PRNGKey(0), x)
        ref = sep.apply(ps, x)

        fused = MultiHeadSelfAttention(
            num_heads=4, qkv_features=16, use_flash=False,
            fused_qkv=True,
        )
        pf = fused.init(jax.random.PRNGKey(0), x)
        att = ps["params"]
        pf = {"params": {
            "qkv": {
                "kernel": jnp.concatenate([
                    att["query"]["kernel"], att["key"]["kernel"],
                    att["value"]["kernel"],
                ], axis=1),
                "bias": jnp.concatenate([
                    att["query"]["bias"], att["key"]["bias"],
                    att["value"]["bias"],
                ], axis=0),
            },
            "out": att["out"],
        }}
        got = fused.apply(pf, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-6
        )

    def test_fused_is_one_projection_dot(self):
        """The point of the fusion: one dot_general for Q, K and V
        (4 total with scores/values/out) instead of three."""
        from learningorchestra_tpu.ops.layers import MultiHeadSelfAttention

        x = jnp.zeros((2, 8, 16), jnp.float32)
        counts = {}
        for flag in (False, True):
            m = MultiHeadSelfAttention(
                num_heads=4, qkv_features=16, use_flash=False,
                fused_qkv=flag,
            )
            p = m.init(jax.random.PRNGKey(0), x)
            counts[flag] = str(
                jax.make_jaxpr(m.apply)(p, x)
            ).count("dot_general")
        assert counts[True] == counts[False] - 2, counts


class TestQKVMigration:
    def test_legacy_artifact_loads_into_fused_model(self):
        """A state_dict saved by the separate-projection layout loads
        into today's fused default with bit-identical predictions
        (ops.layers.migrate_separate_qkv on the load path)."""
        from learningorchestra_tpu.models.text import TransformerClassifier

        rng = np.random.default_rng(9)
        x = rng.integers(1, 32, (16, 8)).astype(np.int32)
        y = rng.integers(0, 2, (16,)).astype(np.int32)

        # Simulate the legacy artifact: a fused model trained today,
        # its params rewritten to the separate layout (the inverse
        # block-split), then saved.
        est = TransformerClassifier(
            vocab_size=32, hidden_dim=16, num_layers=1, num_heads=4,
            max_len=8,
        )
        est.fit(x, y, epochs=1, batch_size=8)
        ref = est.predict(x)
        state = est.state_dict()

        def split_qkv(node):
            if not isinstance(node, dict):
                return node
            if "qkv" in node and isinstance(node["qkv"], dict):
                node = dict(node)
                fused = node.pop("qkv")
                kern, bias = fused["kernel"], fused["bias"]
                h = 4
                node["query"] = {"kernel": kern[:, :h],
                                 "bias": bias[:h]}
                node["key"] = {"kernel": kern[:, h:2 * h],
                               "bias": bias[h:2 * h]}
                node["value"] = {"kernel": kern[:, 2 * h:],
                                 "bias": bias[2 * h:]}
            return {k: split_qkv(v) for k, v in node.items()}

        legacy = dict(state)
        legacy["params"] = split_qkv(
            jax.tree_util.tree_map(np.asarray, state["params"])
        )
        legacy["opt_state"] = None  # legacy serving artifact shape

        fresh = TransformerClassifier(
            vocab_size=32, hidden_dim=16, num_layers=1, num_heads=4,
            max_len=8,
        )
        fresh.load_state_dict(legacy)
        np.testing.assert_allclose(
            fresh.predict(x), ref, rtol=1e-5, atol=1e-5
        )
