"""NeuralEstimator tests — keras-fit contract over jitted loops."""

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # compile-heavy: full tier only

from learningorchestra_tpu.models import (
    LSTMClassifier,
    MLPClassifier,
    MnistCNN,
    TransformerClassifier,
)


@pytest.fixture(scope="module")
def xor_data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(512, 2)).astype(np.float32)
    y = ((x[:, 0] > 0) ^ (x[:, 1] > 0)).astype(np.int64)
    return x, y


def test_mlp_learns_xor(xor_data):
    x, y = xor_data
    m = MLPClassifier(hidden_layer_sizes=(32, 32), num_classes=2,
                      learning_rate=5e-3)
    m.fit(x, y, epochs=60, batch_size=64)
    assert m.history["accuracy"][-1] > 0.9
    assert m.score(x, y) > 0.9


def test_fit_history_and_validation(xor_data):
    x, y = xor_data
    m = MLPClassifier(hidden_layer_sizes=(16,), num_classes=2)
    m.fit(x, y, epochs=3, batch_size=32, validation_split=0.25)
    assert len(m.history["loss"]) == 3
    assert len(m.history["val_loss"]) == 3
    assert "val_accuracy" in m.history


def test_callbacks_invoked(xor_data):
    x, y = xor_data
    seen = []
    m = MLPClassifier(hidden_layer_sizes=(8,), num_classes=2)
    m.fit(
        x, y, epochs=2, batch_size=64,
        callbacks=[lambda epoch, metrics, model: seen.append(epoch)],
    )
    assert seen == [0, 1]


def test_ragged_final_batch_masked():
    """n not divisible by batch_size: padding rows must not poison
    metrics (keras drops nothing; neither do we)."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(70, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)
    m = MLPClassifier(hidden_layer_sizes=(8,), num_classes=2)
    m.fit(x, y, epochs=2, batch_size=32)
    ev = m.evaluate(x, y, batch_size=32)
    assert 0.0 <= ev["accuracy"] <= 1.0


def test_predict_shapes(xor_data):
    x, y = xor_data
    m = MLPClassifier(hidden_layer_sizes=(8,), num_classes=2)
    m.fit(x, y, epochs=1, batch_size=64)
    logits = m.predict(x)
    assert logits.shape == (len(x), 2)
    classes = m.predict_classes(x)
    assert classes.shape == (len(x),)


def test_cnn_and_text_models_smoke():
    rng = np.random.default_rng(2)
    ximg = rng.normal(size=(32, 28, 28)).astype(np.float32)
    yimg = rng.integers(0, 10, 32)
    MnistCNN().fit(ximg, yimg, epochs=1, batch_size=16)

    tokens = rng.integers(1, 50, size=(16, 12))
    yt = rng.integers(0, 2, 16)
    LSTMClassifier(vocab_size=50, embed_dim=8, hidden_dim=8).fit(
        tokens, yt, epochs=1, batch_size=8
    )
    TransformerClassifier(
        vocab_size=50, hidden_dim=16, num_layers=1, num_heads=2, max_len=12
    ).fit(tokens, yt, epochs=1, batch_size=8)


def test_state_roundtrip(xor_data):
    import dill

    x, y = xor_data
    m = MLPClassifier(hidden_layer_sizes=(16,), num_classes=2)
    m.fit(x, y, epochs=5, batch_size=64)
    acc1 = m.score(x, y)
    m2 = dill.loads(dill.dumps(m))
    assert abs(m2.score(x, y) - acc1) < 1e-6
    # Training continues from restored state.
    m2.fit(x, y, epochs=1, batch_size=64)


class TestCheckpointing:
    """Managed in-loop checkpoints + resume (train/checkpoint.py)."""

    def _data(self):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 4)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        return x, y

    def test_checkpoint_and_resume(self, tmp_path):
        from learningorchestra_tpu.models.mlp import MLPClassifier
        from learningorchestra_tpu.train import checkpoint as ckpt

        x, y = self._data()
        ckdir = tmp_path / "ck"

        est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=3)
        est.fit(x, y, epochs=3, batch_size=16, checkpoint_dir=str(ckdir))
        assert (ckdir / "latest.json").exists()
        full_state = jax.device_get(est.params)

        # Fresh estimator resumes at epoch 3: fitting to the same target
        # epoch count runs zero additional epochs and reproduces params.
        est2 = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=3)
        est2.fit(x, y, epochs=3, batch_size=16, checkpoint_dir=str(ckdir))
        assert len(est2.history["loss"]) == 3  # restored, not re-run
        for a, b in zip(
            jax.tree_util.tree_leaves(full_state),
            jax.tree_util.tree_leaves(jax.device_get(est2.params)),
        ):
            np.testing.assert_array_equal(a, b)

        # Interrupted-then-resumed run continues to the new target.
        est3 = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=3)
        est3.fit(x, y, epochs=5, batch_size=16, checkpoint_dir=str(ckdir))
        assert len(est3.history["loss"]) == 5

        loaded = ckpt.load_latest(
            str(ckdir), {"params": est3.params, "opt_state": est3.opt_state}
        )
        assert loaded is not None and loaded[1] == 5

    def test_resume_false_ignores_checkpoints(self, tmp_path):
        from learningorchestra_tpu.models.mlp import MLPClassifier

        x, y = self._data()
        ckdir = tmp_path / "ck2"
        MLPClassifier(hidden_layer_sizes=[8], num_classes=2).fit(
            x, y, epochs=2, checkpoint_dir=str(ckdir)
        )
        est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2)
        est.fit(x, y, epochs=2, checkpoint_dir=str(ckdir), resume=False)
        assert len(est.history["loss"]) == 2

    def test_pruning_keeps_recent(self, tmp_path):
        from learningorchestra_tpu.models.mlp import MLPClassifier

        x, y = self._data()
        ckdir = tmp_path / "ck3"
        MLPClassifier(hidden_layer_sizes=[8], num_classes=2).fit(
            x, y, epochs=5, checkpoint_dir=str(ckdir), checkpoint_every=1,
            checkpoint_min_interval_s=0.0,
        )
        steps = sorted(p.name for p in ckdir.glob("step_*"))
        assert steps == ["step_4", "step_5"]


def test_bert_remat_trains_and_matches():
    """remat=True must change memory, not math."""
    from learningorchestra_tpu.models.text import BertModel

    rng = np.random.default_rng(0)
    x = rng.integers(1, 32, (8, 8), dtype=np.int32)
    y = rng.integers(0, 2, (8,), dtype=np.int32)
    kwargs = dict(vocab_size=32, hidden_dim=16, num_layers=2, num_heads=2,
                  max_len=8, seed=7)
    plain = BertModel(**kwargs)
    plain.fit(x, y, epochs=1, batch_size=8, shuffle=False)
    for mode in (True, "dots"):
        remat = BertModel(remat=mode, **kwargs)
        remat.fit(x, y, epochs=1, batch_size=8, shuffle=False)
        np.testing.assert_allclose(
            plain.history["loss"], remat.history["loss"], rtol=1e-4,
            err_msg=f"remat={mode}",
        )


def test_resnet_remat_trains_and_matches():
    """remat=True must change memory, not math — and keep the param
    tree byte-identical (explicit block names pin the historical
    auto-names) so stored artifacts survive toggling the knob.  A
    narrow 2-block _ResNet keeps this fast; ResNet18/50 share the
    exact same module code."""
    from learningorchestra_tpu.models.vision import _ResNet, _ResNetBlock
    from learningorchestra_tpu.train.neural import NeuralEstimator

    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 16, 16, 3)).astype(np.float32)
    y = rng.integers(0, 2, (4,), dtype=np.int32)

    def make(remat):
        return NeuralEstimator(
            _ResNet(stage_sizes=(1, 1), block=_ResNetBlock,
                    num_classes=2, width=8, remat=remat),
            loss="softmax_ce", learning_rate=1e-3, seed=3,
        )

    plain, remat = make(False), make(True)
    plain.fit(x, y, epochs=1, batch_size=4, shuffle=False)
    remat.fit(x, y, epochs=1, batch_size=4, shuffle=False)
    assert jax.tree_util.tree_structure(plain.params) \
        == jax.tree_util.tree_structure(remat.params)
    assert "_ResNetBlock_0" in plain.params["params"]
    np.testing.assert_allclose(
        plain.history["loss"], remat.history["loss"], rtol=1e-4
    )


def test_space_to_depth_rearrange():
    """space_to_depth folds each 2×2 pixel block into channels in
    row-major tap order — the invariant the s2d stem's conv relies on
    to see the same receptive field as conv7×7/s2."""
    import jax.numpy as jnp

    from learningorchestra_tpu.models.vision import space_to_depth

    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 4, 6, 3)).astype(np.float32)
    out = np.asarray(space_to_depth(jnp.asarray(x), 2))
    assert out.shape == (2, 2, 3, 12)
    for b in range(2):
        for i in range(2):
            for j in range(3):
                block = x[b, 2 * i:2 * i + 2, 2 * j:2 * j + 2]
                np.testing.assert_array_equal(
                    out[b, i, j], block.reshape(-1)
                )
    # Odd tails zero-pad instead of crashing (28x28 MNIST -> 14x14,
    # 5x5 -> 3x3).
    odd = space_to_depth(jnp.ones((1, 5, 5, 1)), 2)
    assert odd.shape == (1, 3, 3, 4)
    assert float(odd[0, 2, 2, 3]) == 0.0  # padded corner tap


def test_resnet_s2d_stem_trains_and_keeps_classic_params():
    """The MXU-friendly stem is a pure opt-in: same output shapes and
    a finite training step, while the DEFAULT model's parameter tree
    stays byte-identical so stored artifacts keep loading."""
    from learningorchestra_tpu.models.vision import _ResNet, _ResNetBlock
    from learningorchestra_tpu.train.neural import NeuralEstimator

    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 16, 16, 3)).astype(np.float32)
    y = rng.integers(0, 2, (4,), dtype=np.int32)

    def make(s2d):
        return NeuralEstimator(
            _ResNet(stage_sizes=(1, 1), block=_ResNetBlock,
                    num_classes=2, width=8, s2d_stem=s2d),
            loss="softmax_ce", learning_rate=1e-3, seed=3,
        )

    classic, s2d = make(False), make(True)
    classic.fit(x, y, epochs=1, batch_size=4, shuffle=False)
    s2d.fit(x, y, epochs=1, batch_size=4, shuffle=False)
    assert np.isfinite(s2d.history["loss"][-1])
    # Classic param tree untouched by the new knob (artifact compat).
    params = classic.params["params"]
    assert "Conv_0" in params and "stem_s2d" not in params
    assert params["Conv_0"]["kernel"].shape == (7, 7, 3, 8)
    # The s2d stem contracts over 4·4·(4·C): 192 deep for RGB.
    s2d_kernel = s2d.params["params"]["stem_s2d"]["kernel"]
    assert s2d_kernel.shape == (4, 4, 12, 8)
    # Identical downstream shapes: predictions agree in shape, and the
    # first residual block's kernels are shaped the same.
    assert classic.predict(x).shape == s2d.predict(x).shape
    assert (
        classic.params["params"]["_ResNetBlock_0"]["Conv_0"][
            "kernel"].shape
        == s2d.params["params"]["_ResNetBlock_0"]["Conv_0"][
            "kernel"].shape
    )


@pytest.mark.parametrize("cls_name", ["VGG16", "MobileNet"])
def test_new_vision_models_train_step(cls_name):
    from learningorchestra_tpu import models as zoo
    from learningorchestra_tpu.toolkit import registry

    # Reachable through the reference-style keras.applications path.
    cls = registry.resolve("tensorflow.keras.applications", cls_name)
    assert cls is getattr(zoo, cls_name)
    est = cls(num_classes=3, learning_rate=1e-3)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 3, (8,), dtype=np.int32)
    est.fit(x, y, epochs=1, batch_size=4)
    assert np.isfinite(est.history["loss"][-1])
    assert est.predict(x).shape == (8, 3)


def test_decoder_lm_learns_and_generates():
    """DecoderLM: causal next-token training on a deterministic cyclic
    sequence; greedy generate must continue the cycle."""
    from learningorchestra_tpu.models.text import DecoderLM

    period = 5
    seq = 16
    n = 64
    rng = np.random.default_rng(0)
    starts = rng.integers(0, period, n)
    base = (starts[:, None] + np.arange(seq + 1)[None, :]) % period + 1
    x, y = base[:, :-1].astype(np.int32), base[:, 1:].astype(np.int32)

    est = DecoderLM(
        vocab_size=8, hidden_dim=32, num_layers=2, num_heads=4,
        max_len=seq, learning_rate=3e-3,
    )
    est.fit(x, y, epochs=60, batch_size=16, shuffle=True)
    assert est.history["accuracy"][-1] > 0.95

    gen = est.generate(x[:4, :8], max_new_tokens=4)
    expect = (base[:4, 8:12]).astype(np.int32)
    np.testing.assert_array_equal(gen[:, 8:], expect)


def test_decoder_lm_registered():
    from learningorchestra_tpu.toolkit import registry

    assert registry.exists("learningorchestra_tpu.models.text", "DecoderLM")


def test_decoder_lm_validation_and_pad_masking():
    """Sequence-target validation keeps (B, T) shape, and padded target
    positions neither train nor count toward accuracy."""
    from learningorchestra_tpu.models.text import DecoderLM

    period = 4
    seq = 12
    n = 48
    rng = np.random.default_rng(1)
    starts = rng.integers(0, period, n)
    base = (starts[:, None] + np.arange(seq + 1)[None, :]) % period + 1
    x, y = base[:, :-1].astype(np.int32), base[:, 1:].astype(np.int32)
    # Right-pad half of each target with pad id 0.
    y_padded = y.copy()
    y_padded[:, seq // 2:] = 0

    est = DecoderLM(
        vocab_size=8, hidden_dim=32, num_layers=1, num_heads=4,
        max_len=seq, learning_rate=3e-3,
    )
    est.fit(
        x, y_padded, epochs=30, batch_size=16, shuffle=True,
        validation_data=(x[:8], y_padded[:8]),
    )
    # Validation path ran with 2-D targets (would crash pre-fix).
    assert "val_loss" in est.history
    # Pad-masked accuracy reflects only real positions; the cyclic task
    # on the unpadded half is learnable to high accuracy.
    assert est.history["accuracy"][-1] > 0.9


def test_kv_cache_generate_matches_full_forward():
    """The one-scan KV-cache decode must reproduce the naive
    full-re-forward greedy loop token for token."""
    import jax
    import jax.numpy as jnp

    from learningorchestra_tpu.models.text import DecoderLM

    rng = np.random.default_rng(0)
    x = rng.integers(1, 32, (8, 10)).astype(np.int32)
    tgt = np.concatenate([x[:, 1:], np.zeros((8, 1), np.int32)], 1)
    est = DecoderLM(
        vocab_size=32, hidden_dim=32, num_layers=2, num_heads=2,
        max_len=16,
    )
    est.fit(x, tgt, epochs=2, batch_size=8, verbose=0)
    out = est.generate(x[:2, :4], max_new_tokens=4)

    from tests.lm_oracle import naive_greedy_decode

    np.testing.assert_array_equal(
        out, naive_greedy_decode(est, x[:2, :4], 8)
    )


def test_generate_sampling_modes():
    """temperature/top_k sampling: deterministic per seed, reduces to
    greedy at top_k=1, differs from greedy at high temperature."""
    from learningorchestra_tpu.models.text import DecoderLM

    rng = np.random.default_rng(0)
    x = rng.integers(1, 64, (8, 10)).astype(np.int32)
    tgt = np.concatenate([x[:, 1:], np.zeros((8, 1), np.int32)], 1)
    est = DecoderLM(
        vocab_size=64, hidden_dim=32, num_layers=2, num_heads=2,
        max_len=16,
    )
    est.fit(x, tgt, epochs=1, batch_size=8, verbose=0)
    prompts = x[:2, :4]

    greedy = est.generate(prompts, max_new_tokens=8)
    # top_k=1 sampling == greedy regardless of temperature.
    np.testing.assert_array_equal(
        greedy,
        est.generate(prompts, max_new_tokens=8, temperature=3.0,
                     top_k=1, seed=5),
    )
    # Same seed -> same sample; it's a real distribution (high
    # temperature over 64 tokens differs from greedy).
    s1 = est.generate(prompts, max_new_tokens=8, temperature=5.0, seed=1)
    s2 = est.generate(prompts, max_new_tokens=8, temperature=5.0, seed=1)
    np.testing.assert_array_equal(s1, s2)
    assert not np.array_equal(s1, greedy)
    # temperature=None (default) stays the greedy path.
    np.testing.assert_array_equal(
        greedy, est.generate(prompts, max_new_tokens=8, temperature=None)
    )
    # Nucleus: a tiny top_p keeps only the argmax token -> greedy even
    # at high temperature; deterministic per seed at moderate top_p.
    np.testing.assert_array_equal(
        greedy,
        est.generate(prompts, max_new_tokens=8, temperature=5.0,
                     top_p=1e-6, seed=3),
    )
    n1 = est.generate(prompts, max_new_tokens=8, temperature=5.0,
                      top_p=0.9, seed=2)
    n2 = est.generate(prompts, max_new_tokens=8, temperature=5.0,
                      top_p=0.9, seed=2)
    np.testing.assert_array_equal(n1, n2)
    # top_p=1.0 truncates nothing: same draw as plain sampling.
    np.testing.assert_array_equal(
        est.generate(prompts, max_new_tokens=8, temperature=5.0, seed=1,
                     top_p=1.0),
        s1,
    )


def test_generate_sampling_guards():
    from learningorchestra_tpu.models.text import DecoderLM

    rng = np.random.default_rng(1)
    x = rng.integers(1, 16, (4, 6)).astype(np.int32)
    est = DecoderLM(
        vocab_size=16, hidden_dim=16, num_layers=1, num_heads=2,
        max_len=12, mlp_dim=16,
    )
    est.fit(x, x, epochs=1, batch_size=4, verbose=0)
    with pytest.raises(ValueError, match="temperature"):
        est.generate(x[:1, :3], top_k=5)
    with pytest.raises(ValueError, match="temperature"):
        est.generate(x[:1, :3], top_p=0.9)
    with pytest.raises(ValueError, match="top_p must be"):
        est.generate(x[:1, :3], temperature=1.0, top_p=1.5)
    # Sampling never emits pad id 0.
    out = est.generate(x[:2, :3], max_new_tokens=8, temperature=10.0,
                       seed=3)
    assert (out[:, 3:] != 0).all()


def test_gqa_decoder_cache_generate():
    """Grouped-query attention: fewer KV heads, cache shrinks, decode
    stays exact vs the full-forward oracle; MQA (1 KV head) included."""
    import jax

    from learningorchestra_tpu.models.text import DecoderLM
    from tests.lm_oracle import naive_greedy_decode

    rng = np.random.default_rng(6)
    x = rng.integers(1, 32, (8, 10)).astype(np.int32)
    tgt = np.concatenate([x[:, 1:], np.zeros((8, 1), np.int32)], 1)
    for kv_heads in (2, 1):
        est = DecoderLM(
            vocab_size=32, hidden_dim=32, num_layers=2, num_heads=4,
            max_len=16, mlp_dim=16, num_kv_heads=kv_heads,
        )
        est.fit(x, tgt, epochs=1, batch_size=8, verbose=0)
        # The fused QKV kernel carries H + 2*kv_heads head slots —
        # fewer KV heads shrink the projection (and the decode cache).
        kshape = est.params["params"]["TransformerBlock_0"][
            "MultiHeadSelfAttention_0"]["qkv"]["kernel"].shape
        assert kshape[1] == 4 + 2 * kv_heads, kshape
        out = est.generate(x[:2, :4], max_new_tokens=4)
        np.testing.assert_array_equal(
            out, naive_greedy_decode(est, x[:2, :4], 8)
        )


def test_gqa_invalid_head_split():
    import jax.numpy as jnp

    from learningorchestra_tpu.models.text import DecoderLM

    est = DecoderLM(
        vocab_size=16, hidden_dim=16, num_layers=1, num_heads=4,
        max_len=8, mlp_dim=16, num_kv_heads=3,
    )
    with pytest.raises(ValueError, match="divisible"):
        est._init_params(jnp.zeros((1, 4), jnp.int32))


def test_rope_decoder_trains_and_decodes_exactly():
    """RoPE decoder (optionally with GQA + window): trains, and the
    KV-cache decode — which rotates q/k at the cache index — matches
    the naive full-forward oracle token for token."""
    from learningorchestra_tpu.models.text import DecoderLM
    from tests.lm_oracle import naive_greedy_decode

    rng = np.random.default_rng(7)
    x = rng.integers(1, 32, (8, 10)).astype(np.int32)
    tgt = np.concatenate([x[:, 1:], np.zeros((8, 1), np.int32)], 1)
    for kwargs in (
        {},
        {"num_kv_heads": 1, "attention_window": 4},
    ):
        est = DecoderLM(
            vocab_size=32, hidden_dim=32, num_layers=2, num_heads=2,
            max_len=16, mlp_dim=16, positional="rope", **kwargs,
        )
        est.fit(x, tgt, epochs=2, batch_size=8, verbose=0)
        assert np.isfinite(est.history["loss"][-1])
        # No learned position table in the param tree.
        emb = est.params["params"]
        assert "Embed_1" not in emb, list(emb)
        out = est.generate(x[:2, :4], max_new_tokens=4)
        np.testing.assert_array_equal(
            out, naive_greedy_decode(est, x[:2, :4], 8)
        )


def test_rope_shift_invariance():
    """Attention scores under RoPE depend only on relative distance."""
    import jax.numpy as jnp

    from learningorchestra_tpu.ops.layers import apply_rope

    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((1, 2, 6, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 6, 8)), jnp.float32)

    def scores(offset):
        pos = jnp.arange(6) + offset
        return jnp.einsum(
            "bhqd,bhkd->bhqk", apply_rope(q, pos), apply_rope(k, pos)
        )

    np.testing.assert_allclose(
        np.asarray(scores(0)), np.asarray(scores(1000)), atol=2e-4
    )


def test_gradient_accumulation_matches_large_batch():
    """accumulate_steps=2 at batch 8 walks the same trajectory as
    batch 16 (the N masked-mean grads average to the large-batch
    mean), and switching back to 1 restores plain stepping."""
    from learningorchestra_tpu.models.mlp import MLPClassifier

    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)

    big = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=1)
    big.fit(x, y, epochs=3, batch_size=16, shuffle=False, verbose=0)

    acc = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=1)
    acc.fit(x, y, epochs=3, batch_size=8, shuffle=False, verbose=0,
            accumulate_steps=2)

    import jax

    # bf16 compute: grads round differently under the two batch
    # groupings, so trajectories agree to compute-dtype tolerance.
    for a, b in zip(jax.tree_util.tree_leaves(big.params),
                    jax.tree_util.tree_leaves(acc.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4
        )
    # Back to plain stepping: state rebuilds without error.
    acc.fit(x, y, epochs=1, batch_size=8, verbose=0)
    assert np.isfinite(acc.history["loss"][-1])


def test_gradient_accumulation_validation():
    from learningorchestra_tpu.models.mlp import MLPClassifier

    est = MLPClassifier(hidden_layer_sizes=[4], num_classes=2)
    with pytest.raises(ValueError, match=">= 1"):
        est.fit(np.zeros((4, 2), np.float32), np.zeros(4, np.int32),
                accumulate_steps=0)


def test_compile_resets_accumulation():
    """compile(optimizer=...) after an accumulated fit must not leak
    the old wrapper or its state into the next fit."""
    import optax

    from learningorchestra_tpu.models.mlp import MLPClassifier

    rng = np.random.default_rng(2)
    x = rng.standard_normal((16, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    est = MLPClassifier(hidden_layer_sizes=[4], num_classes=2)
    est.fit(x, y, epochs=1, batch_size=4, accumulate_steps=2, verbose=0)
    est.compile(optimizer=optax.sgd(0.05))
    # Plain fit after compile: fresh sgd state, no MultiSteps leftovers.
    est.fit(x, y, epochs=1, batch_size=4, verbose=0)
    assert np.isfinite(est.history["loss"][-1])
    # Accumulated fit after compile wraps the NEW optimizer.
    est.fit(x, y, epochs=1, batch_size=4, accumulate_steps=2, verbose=0)
    assert np.isfinite(est.history["loss"][-1])


def test_accumulation_preserves_adam_moments():
    """Toggling accumulate_steps between fits keeps the inner
    optimizer's moments (no silent warmup reset)."""
    import jax

    from learningorchestra_tpu.models.mlp import MLPClassifier

    rng = np.random.default_rng(3)
    x = rng.standard_normal((16, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    est = MLPClassifier(hidden_layer_sizes=[4], num_classes=2)
    est.fit(x, y, epochs=2, batch_size=4, accumulate_steps=2, verbose=0)
    inner_mu = jax.tree_util.tree_leaves(
        est.opt_state.inner_opt_state[0].mu
    )
    est._set_accumulation(1)
    plain_mu = jax.tree_util.tree_leaves(est.opt_state[0].mu)
    for a, b in zip(inner_mu, plain_mu):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # And wrapping again seeds the inner state from the plain moments.
    est._set_accumulation(4)
    rewrapped = jax.tree_util.tree_leaves(
        est.opt_state.inner_opt_state[0].mu
    )
    for a, b in zip(plain_mu, rewrapped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_accumulation_state_dict_roundtrip():
    """state_dict carries accumulate_steps so a fresh estimator can
    load and keep fitting without an opt-state structure mismatch."""
    from learningorchestra_tpu.models.mlp import MLPClassifier

    rng = np.random.default_rng(4)
    x = rng.standard_normal((16, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    est = MLPClassifier(hidden_layer_sizes=[4], num_classes=2)
    est.fit(x, y, epochs=1, batch_size=4, accumulate_steps=2, verbose=0)
    state = est.state_dict()

    est2 = MLPClassifier(hidden_layer_sizes=[4], num_classes=2)
    est2.load_state_dict(state)
    est2.fit(x, y, epochs=1, batch_size=4, accumulate_steps=2, verbose=0)
    assert np.isfinite(est2.history["loss"][-1])


def test_distributed_fit_resets_accumulation():
    """A DistributedTrainer fit does not inherit a wrapper left by an
    earlier single-device accumulated fit."""
    from learningorchestra_tpu.models.mlp import MLPClassifier
    from learningorchestra_tpu.parallel import (
        DistributedTrainer,
        MeshSpec,
        build_mesh,
    )

    rng = np.random.default_rng(5)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    est = MLPClassifier(hidden_layer_sizes=[4], num_classes=2)
    est.fit(x, y, epochs=1, batch_size=8, accumulate_steps=4, verbose=0)
    assert est._accumulate_steps == 4

    tr = DistributedTrainer(est, mesh=build_mesh(MeshSpec(dp=8)))
    tr.fit(x, y, epochs=1, batch_size=8)
    assert est._accumulate_steps == 1  # explicit default, no leak
    assert np.isfinite(tr.history["loss"][-1])


def test_lm_history_includes_perplexity():
    """Multi-batch on purpose: perplexity must be exp(mean CE), not the
    Jensen-biased mean of per-batch exponentials."""
    from learningorchestra_tpu.models.text import DecoderLM

    rng = np.random.default_rng(9)
    x = rng.integers(1, 16, (24, 6)).astype(np.int32)
    tgt = np.concatenate([x[:, 1:], np.zeros((24, 1), np.int32)], 1)
    est = DecoderLM(vocab_size=16, hidden_dim=16, num_layers=1,
                    num_heads=2, max_len=8, mlp_dim=16)
    est.fit(x, tgt, epochs=2, batch_size=8, verbose=0)
    ppl = est.history["perplexity"]
    assert len(ppl) == 2
    np.testing.assert_allclose(
        ppl, np.exp(est.history["loss"]), rtol=1e-5
    )
    ev = est.evaluate(x, tgt)
    assert "perplexity" in ev and np.isfinite(ev["perplexity"])


def test_generate_rejects_overlong_prompt():
    """ADVICE r2: a prompt longer than max_len must raise a clear
    ValueError up front, not an opaque shape-broadcast trace error
    (RoPE models advertise extrapolation, making this easy to hit)."""
    from learningorchestra_tpu.models.text import DecoderLM

    est = DecoderLM(
        vocab_size=32, hidden_dim=32, num_layers=1, num_heads=2,
        max_len=8,
    )
    x = np.ones((1, 12), np.int32)
    with pytest.raises(ValueError, match="exceeds max_len"):
        est.generate(x, max_new_tokens=4)


def test_async_checkpointing_contract(tmp_path):
    """Async saves (default-on): the marker only ever names a fully
    committed step; fit() returning means the last checkpoint is
    durable; resume from an async-checkpointed fit works; and a reader
    in the same process sees the newest step (load flushes pending)."""
    import json

    from learningorchestra_tpu.models.mlp import MLPClassifier
    from learningorchestra_tpu.train import checkpoint as ckpt

    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    ck = str(tmp_path / "ck")

    a = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=0)
    a.fit(x, y, epochs=3, batch_size=16, checkpoint_dir=ck,
          checkpoint_min_interval_s=0.0)
    # fit() returned -> the final save is durable and published.
    marker = json.loads((tmp_path / "ck" / "latest.json").read_text())
    assert marker["step"] == 3
    assert (tmp_path / "ck" / "step_3").exists()

    # Resume continues from the async-written step.
    b = MLPClassifier(hidden_layer_sizes=[8], num_classes=2, seed=0)
    b.fit(x, y, epochs=5, batch_size=16, checkpoint_dir=ck,
          checkpoint_min_interval_s=0.0)
    assert len(b.history["loss"]) == 5  # stitched 3 + 2

    # Pending-save flush: a save left in flight is visible to the next
    # reader in this process (load_latest finalizes first).
    state = {"params": a.params, "opt_state": a.opt_state}
    ckpt.save(ck, 9, state, history={"loss": [0.1]}, async_save=True)
    loaded = ckpt.load_latest(ck, state)
    assert loaded is not None and loaded[1] == 9
    marker = json.loads((tmp_path / "ck" / "latest.json").read_text())
    assert marker["step"] == 9

    # Sync fallback still works (the multi-process path).
    ckpt.save(ck, 10, state, history=None, async_save=False)
    assert json.loads(
        (tmp_path / "ck" / "latest.json").read_text()
    )["step"] == 10


@pytest.mark.parametrize("async_save", [False, True])
def test_checkpoint_files_stay_under_the_file_size_limit(
        tmp_path, async_save):
    """Under a process file-size limit (``ulimit -f``) smaller than the
    state, a save still commits — orbax's default of one data file of
    up to 2 GB fails there with EFBIG — every file it wrote is under
    the limit, and the step restores bit-identical."""
    import resource

    import optax

    from learningorchestra_tpu.train import checkpoint as ckpt

    limit = 1 << 20
    rng = np.random.default_rng(0)  # incompressible: orbax compresses
    params = {"w": rng.standard_normal((700, 1000)).astype(np.float32),
              "b": np.zeros(3, np.float32)}
    state = {"params": params, "opt_state": optax.adam(1e-3).init(params)}
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (limit, hard))
    try:
        ckpt.save(tmp_path, 1, state, history={"loss": [0.5]},
                  async_save=async_save)
        loaded = ckpt.load_latest(tmp_path, state)
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))
    sizes = [p.stat().st_size for p in tmp_path.rglob("*") if p.is_file()]
    assert sum(sizes) > 2 * limit and max(sizes) <= limit
    restored, step, _ = loaded
    assert step == 1
    np.testing.assert_array_equal(restored["params"]["w"], params["w"])
    assert type(restored["opt_state"]) is type(state["opt_state"])


class TestOptimizerAndScheduleSpecs:
    """REST-JSON optimizer/learning-rate specs (train/neural.py
    resolve_optimizer / resolve_learning_rate) — the declarative form
    of the reference's compile_code contract."""

    def _data(self):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((32, 4)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        return x, y

    def test_schedule_specs_resolve(self):
        from learningorchestra_tpu.train.neural import (
            resolve_learning_rate,
        )

        assert resolve_learning_rate(1e-3) == 1e-3
        sched = resolve_learning_rate({
            "schedule": "warmup_cosine", "peakValue": 1e-2,
            "warmupSteps": 10, "decaySteps": 100,
        })
        assert callable(sched)
        # Warmup climbs from 0 to peak, then decays.
        assert float(sched(0)) == 0.0
        assert abs(float(sched(10)) - 1e-2) < 1e-8
        assert float(sched(100)) < 1e-2
        # snake_case works too; piecewise converts JSON string keys.
        pw = resolve_learning_rate({
            "schedule": "piecewise", "init_value": 1.0,
            "boundaries_and_scales": {"5": 0.1},
        })
        assert abs(float(pw(4)) - 1.0) < 1e-8
        assert abs(float(pw(6)) - 0.1) < 1e-8
        with pytest.raises(ValueError, match="unknown learning-rate"):
            resolve_learning_rate({"schedule": "bogus"})
        with pytest.raises(ValueError, match="warmup_steps"):
            resolve_learning_rate({
                "schedule": "warmup_cosine", "peakValue": 1e-2,
                "decaySteps": 100,
            })

    def test_estimator_trains_with_schedule_spec(self):
        from learningorchestra_tpu.models.mlp import MLPClassifier

        x, y = self._data()
        est = MLPClassifier(
            hidden_layer_sizes=[8], num_classes=2,
            learning_rate={
                "schedule": "warmup_cosine", "peakValue": 5e-2,
                "warmupSteps": 4, "decaySteps": 64,
            },
        )
        est.fit(x, y, epochs=4, batch_size=8, verbose=0)
        assert np.isfinite(est.history["loss"][-1])
        assert est.history["loss"][-1] < est.history["loss"][0]

    def test_compile_accepts_strings_and_dict_specs(self):
        from learningorchestra_tpu.models.mlp import MLPClassifier
        from learningorchestra_tpu.train.neural import resolve_optimizer

        x, y = self._data()
        est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2)
        est.fit(x, y, epochs=1, batch_size=8, verbose=0)
        est.compile(optimizer="sgd", learning_rate=0.05)
        est.fit(x, y, epochs=1, batch_size=8, verbose=0)
        assert np.isfinite(est.history["loss"][-1])
        est.compile(optimizer={
            "name": "adamw", "learningRate": 1e-3, "weightDecay": 1e-2,
        })
        est.fit(x, y, epochs=1, batch_size=8, verbose=0)
        assert np.isfinite(est.history["loss"][-1])
        # learningRate alone (camelCase, REST body) rebuilds the SAME
        # optimizer kind (adamw, recorded above) at the new schedule.
        est.compile(learningRate={"schedule": "cosine",
                                  "initValue": 1e-2, "decaySteps": 32})
        assert est._optimizer_spec["name"] == "adamw"
        est.fit(x, y, epochs=1, batch_size=8, verbose=0)
        assert np.isfinite(est.history["loss"][-1])
        with pytest.raises(ValueError, match="unknown optimizer"):
            resolve_optimizer("sparkles")
        # An opaque optax object can't take a separate rate — loud, not
        # silent (the object's own rate would win).
        import optax

        with pytest.raises(ValueError, match="bake the rate"):
            est.compile(optimizer=optax.sgd(0.1), learning_rate=0.01)
        est.compile(optimizer=optax.sgd(0.1))
        with pytest.raises(ValueError, match="baked in"):
            est.compile(learning_rate=0.01)


class TestEarlyStopping:
    def _data(self, n=64):
        rng = np.random.default_rng(5)
        x = rng.standard_normal((n, 4)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        return x, y

    def test_stops_on_plateau_and_restores_best(self):
        from learningorchestra_tpu.models.mlp import MLPClassifier
        from learningorchestra_tpu.train.neural import EarlyStopping

        x, y = self._data()
        est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2,
                            learning_rate=0.0)  # lr 0: loss can't improve
        es = EarlyStopping(monitor="loss", patience=1,
                           restore_best_weights=True)
        est.fit(x, y, epochs=50, batch_size=16, callbacks=[es])
        # epoch 0 sets best; epochs 1..2 don't improve -> stop early.
        assert len(est.history["loss"]) < 50
        assert est.stop_training
        assert es.best_epoch == 0
        # Restored params are the best snapshot; moments were dropped.
        assert est.opt_state is None
        # A later fit re-inits optimizer state and still works — even
        # when it changes the accumulation wrapping (None opt_state must
        # not crash _set_accumulation's moment-carrying surgery).
        est.fit(x, y, epochs=1, batch_size=16, accumulate_steps=2)
        assert np.isfinite(est.history["loss"][-1])
        est.compile(learning_rate=0.05)
        est.fit(x, y, epochs=2, batch_size=16)
        assert np.isfinite(est.history["loss"][-1])

    def test_restore_best_checkpoint_survives_resume(self, tmp_path):
        """restore-best early stop must write the RESTORED params as
        the latest checkpoint (fresh moments), so resume=True continues
        from the best snapshot (ADVICE r3).  checkpoint_every is set
        beyond the run so the ONLY save opportunity is the stop epoch —
        the exact save the pre-fix opt_state-None guard skipped (which
        this test catches: no checkpoint at all would be written)."""
        import jax

        from learningorchestra_tpu.models.mlp import MLPClassifier
        from learningorchestra_tpu.train import checkpoint as ckpt
        from learningorchestra_tpu.train.neural import EarlyStopping

        x, y = self._data()
        est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2,
                            learning_rate=0.5)  # big lr: loss plateaus
        es = EarlyStopping(monitor="loss", patience=2,
                           restore_best_weights=True)
        est.fit(x, y, epochs=60, batch_size=16, callbacks=[es],
                checkpoint_dir=str(tmp_path / "ck"),
                checkpoint_every=1000, checkpoint_min_interval_s=0.0)
        assert est.stop_training and est.opt_state is None
        assert len(est.history["loss"]) < 60  # actually stopped early

        template = {
            "params": est.params,
            "opt_state": jax.jit(est.optimizer.init)(est.params),
        }
        loaded = ckpt.load_latest(tmp_path / "ck", template)
        assert loaded is not None, (
            "early stop with restore-best wrote no checkpoint"
        )
        state, _step, _hist = loaded
        # The checkpointed params ARE the restored best snapshot.
        for a, b in zip(jax.tree_util.tree_leaves(est.params),
                        jax.tree_util.tree_leaves(state["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_absent_monitor_warns_once(self):
        import logging

        from learningorchestra_tpu.models.mlp import MLPClassifier
        from learningorchestra_tpu.train.neural import EarlyStopping

        x, y = self._data()
        est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2)
        es = EarlyStopping(monitor="val_loss", patience=1)
        # The framework root logger doesn't propagate (log.py); hook
        # the component logger directly.
        records = []
        handler = logging.Handler()
        handler.emit = records.append
        logger = logging.getLogger("lo.train")
        logger.addHandler(handler)
        try:
            est.fit(x, y, epochs=3, batch_size=16, callbacks=[es])
        finally:
            logger.removeHandler(handler)
        hits = [r for r in records
                if "EarlyStopping monitor" in r.getMessage()]
        assert len(hits) == 1  # once, not every epoch
        assert len(est.history["loss"]) == 3  # ran all epochs

    def test_rest_json_spec_and_val_monitor(self):
        from learningorchestra_tpu.models.mlp import MLPClassifier

        x, y = self._data()
        # lr 0 freezes val_loss, so the stop point is deterministic:
        # epoch 0 sets best, epochs 1-2 don't improve -> exactly 3.
        est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2,
                            learning_rate=0.0)
        est.fit(
            x, y, epochs=30, batch_size=16, validation_split=0.25,
            early_stopping={"monitor": "val_loss", "patience": 2,
                             "minDelta": 0.0},
        )
        assert "val_loss" in est.history
        assert len(est.history["loss"]) == 3
        # stop_training resets on a fresh fit (no early_stopping now).
        est.fit(x, y, epochs=2, batch_size=16)
        assert not est.stop_training
        assert len(est.history["loss"]) == 3 + 2

    def test_reused_instance_resets(self):
        from learningorchestra_tpu.models.mlp import MLPClassifier
        from learningorchestra_tpu.train.neural import EarlyStopping

        x, y = self._data()
        es = EarlyStopping(monitor="loss", patience=1,
                           restore_best_weights=True)
        est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2,
                            learning_rate=0.0)
        est.fit(x, y, epochs=10, batch_size=16, callbacks=[es])
        assert est.stop_training and es.wait >= 1
        # Second fit with the SAME instance starts from a clean slate —
        # it must run (not instantly stop with the stale snapshot).
        est2 = MLPClassifier(hidden_layer_sizes=[8], num_classes=2,
                             learning_rate=0.0)
        est2.fit(x, y, epochs=10, batch_size=16, callbacks=[es])
        assert es.best_epoch == 0 and len(est2.history["loss"]) >= 2

    def test_early_stop_checkpoint_policy(self, tmp_path):
        """The stop epoch counts as final under the ONE shared save
        policy: it saves when checkpointing is enabled, and
        checkpoint_every=0 disables ALL saves — stop included."""
        import json

        from learningorchestra_tpu.models.mlp import MLPClassifier

        x, y = self._data()
        ck = tmp_path / "ck"
        est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2,
                            learning_rate=0.0)
        est.fit(x, y, epochs=50, batch_size=16, checkpoint_dir=str(ck),
                checkpoint_every=10, checkpoint_min_interval_s=0.0,
                early_stopping={"monitor": "loss", "patience": 1})
        ran = len(est.history["loss"])
        assert ran == 2  # stopped long before epoch 10's periodic save
        marker = json.loads((ck / "latest.json").read_text())
        assert marker["step"] == ran  # the stop epoch saved

        ck2 = tmp_path / "ck2"
        est2 = MLPClassifier(hidden_layer_sizes=[8], num_classes=2,
                             learning_rate=0.0)
        est2.fit(x, y, epochs=50, batch_size=16,
                 checkpoint_dir=str(ck2), checkpoint_every=0,
                 early_stopping={"monitor": "loss", "patience": 1})
        assert not (ck2 / "latest.json").exists()  # fully disabled

        # early_stopping=False is the JSON off-toggle, not a crash.
        est3 = MLPClassifier(hidden_layer_sizes=[8], num_classes=2,
                             learning_rate=0.0)
        est3.fit(x, y, epochs=3, batch_size=16, early_stopping=False)
        assert len(est3.history["loss"]) == 3

    def test_streaming_fit_early_stops(self, tmp_path):
        from learningorchestra_tpu.models.mlp import MLPClassifier
        from learningorchestra_tpu.store.sharded import (
            ShardedDataset,
            ShardedDatasetWriter,
        )

        x, y = self._data(96)
        w = ShardedDatasetWriter(
            tmp_path / "ds", [f"f{i}" for i in range(4)] + ["label"],
            rows_per_shard=32,
        )
        for i in range(96):
            w.append(list(x[i]) + [int(y[i])])
        w.close()
        ds = ShardedDataset(tmp_path / "ds")
        est = MLPClassifier(hidden_layer_sizes=[8], num_classes=2,
                            learning_rate=0.0)
        est.fit(ds.feature_view(["label"]), ds.view("label"),
                epochs=50, batch_size=32,
                early_stopping={"monitor": "loss", "patience": 1})
        assert len(est.history["loss"]) < 50
