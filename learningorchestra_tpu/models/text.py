"""Text models: LSTM sentiment classifier, Transformer encoder, BERT.

Covers the reference's demo NLP workloads (IMDb sentiment — README.md:53,
BASELINE.md config 3) and the BERT-base fine-tune target (BASELINE.md
config 4).  Inputs are int32 token-id matrices ``(batch, seq_len)``.

TPU notes: attention and the LSTM recurrence are expressed with
``nn.scan``/`lax` control flow (static trip counts, XLA-compilable); the
attention projections are feature-dim matmuls that shard cleanly on a
``tp`` mesh axis.
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from learningorchestra_tpu.ops.layers import (
    MultiHeadSelfAttention,
    remat_block,
)
from learningorchestra_tpu.toolkit.registry import register
from learningorchestra_tpu.train.neural import NeuralEstimator

_MODULE = "learningorchestra_tpu.models.text"


class _LSTMClassifier(nn.Module):
    vocab_size: int
    embed_dim: int
    hidden_dim: int
    num_classes: int

    @nn.compact
    def __call__(self, tokens):
        tokens = tokens.astype(jnp.int32)
        x = nn.Embed(self.vocab_size, self.embed_dim)(tokens)
        lstm = nn.RNN(nn.OptimizedLSTMCell(self.hidden_dim))
        x = lstm(x)  # (B, T, H)
        # Mean-pool over non-pad positions (pad id 0).
        mask = (tokens != 0).astype(x.dtype)[..., None]
        pooled = (x * mask).sum(1) / jnp.maximum(mask.sum(1), 1.0)
        return nn.Dense(self.num_classes)(pooled)


@register(_MODULE)
class LSTMClassifier(NeuralEstimator):
    def __init__(
        self,
        vocab_size: int = 20000,
        embed_dim: int = 128,
        hidden_dim: int = 128,
        num_classes: int = 2,
        learning_rate: float = 1e-3,
        seed: int = 0,
    ):
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.hidden_dim = hidden_dim
        self.num_classes = num_classes
        super().__init__(
            _LSTMClassifier(
                vocab_size=vocab_size,
                embed_dim=embed_dim,
                hidden_dim=hidden_dim,
                num_classes=num_classes,
            ),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
            # The LSTM recurrence accumulates across T steps; bf16
            # cell-state drift is the classic failure mode, so this
            # family opts out of the zoo-wide mixed precision.
            compute_dtype="float32",
        )


def embed_tokens(tokens, vocab_size, hidden_dim, max_len, dtype,
                 positions=None):
    """Token + learned positional embedding (pad id 0 convention).

    A helper, not a submodule: called inside a ``@nn.compact``
    ``__call__`` the two ``nn.Embed`` layers auto-name in the CALLER's
    scope (``Embed_0``/``Embed_1``), so every transformer family —
    BERT, decoder LM, MoE, pipelined — shares one embedding definition
    without perturbing existing parameter trees.

    ``positions`` overrides the default ``arange`` positions — KV-cache
    decoding feeds one token at a time at its true buffer position.
    """
    if positions is None:
        positions = jnp.arange(tokens.shape[-1])[None, :]
    tok = nn.Embed(vocab_size, hidden_dim, dtype=dtype)(tokens)
    pos = nn.Embed(max_len, hidden_dim, dtype=dtype)(positions)
    return tok + pos


def cls_head(x, hidden_dim, num_classes):
    """[CLS]-pool position 0 through a tanh projection + classifier.
    Same call-site-scoping contract as :func:`embed_tokens`."""
    cls = jnp.tanh(nn.Dense(hidden_dim)(x[:, 0]))
    return nn.Dense(num_classes)(cls)


class TransformerBlock(nn.Module):
    """Pre-LN block over the framework's own attention layer: the Pallas
    flash kernel on TPU (ops/attention.py), jnp reference elsewhere —
    the reference system materialises full (T, T) scores inside wrapped
    keras models; this never does."""

    hidden_dim: int
    num_heads: int
    mlp_dim: int
    num_kv_heads: int | None = None  # grouped-query attention
    dtype: jnp.dtype | None = None  # None = promote (bf16 when the train step casts params)
    use_flash: bool | None = None  # None = auto by backend
    causal: bool = False  # decoder blocks mask future positions
    window: int | None = None  # sliding-window attention (causal only)
    rope: bool = False  # rotary position embeddings
    decode: bool = False  # KV-cache autoregressive inference

    @nn.compact
    def __call__(self, x, key_mask=None):
        y = nn.LayerNorm(dtype=self.dtype)(x)
        y = MultiHeadSelfAttention(
            num_heads=self.num_heads,
            qkv_features=self.hidden_dim,
            num_kv_heads=self.num_kv_heads,
            dtype=self.dtype,
            use_flash=self.use_flash,
            causal=self.causal,
            window=self.window,
            rope=self.rope,
            decode=self.decode,
        )(y, key_mask=key_mask)
        x = x + y
        y = nn.LayerNorm(dtype=self.dtype)(x)
        y = nn.Dense(self.mlp_dim, dtype=self.dtype)(y)
        y = nn.gelu(y)
        y = nn.Dense(self.hidden_dim, dtype=self.dtype)(y)
        return x + y


class BertEncoder(nn.Module):
    """BERT-style bidirectional transformer encoder (pre-LN)."""

    vocab_size: int = 30522
    hidden_dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 512
    dtype: jnp.dtype | None = None  # None = promote (bf16 when the train step casts params)
    use_flash: bool | None = None
    # jax.checkpoint each block: activations rematerialize in the
    # backward pass — trades ~1 extra forward of FLOPs for O(layers)
    # less HBM, the standard long-sequence/large-batch headroom knob.
    remat: bool | str = False

    @nn.compact
    def __call__(self, tokens):
        tokens = tokens.astype(jnp.int32)
        x = embed_tokens(
            tokens, self.vocab_size, self.hidden_dim, self.max_len,
            self.dtype,
        )
        # Key-side padding mask (pad id 0).  Key-side masking is exact
        # for every non-pad query row; pad query rows produce values no
        # one reads — the [CLS] head pools position 0 only.
        pad_mask = tokens != 0  # (B, T)
        block_cls = remat_block(TransformerBlock, self.remat)
        for i in range(self.num_layers):
            # Explicit names keep the parameter tree identical whether
            # remat is on or off (auto-naming would differ:
            # CheckpointTransformerBlock_i vs TransformerBlock_i) AND
            # match the historical auto-names, so stored artifacts
            # survive toggling the memory knob.
            x = block_cls(
                hidden_dim=self.hidden_dim,
                num_heads=self.num_heads,
                mlp_dim=self.mlp_dim,
                dtype=self.dtype,
                use_flash=self.use_flash,
                name=f"TransformerBlock_{i}",
            )(x, key_mask=pad_mask)
        return nn.LayerNorm(dtype=self.dtype)(x)


class _BertClassifier(nn.Module):
    encoder: BertEncoder
    num_classes: int

    @nn.compact
    def __call__(self, tokens):
        x = self.encoder(tokens)
        return cls_head(x, self.encoder.hidden_dim, self.num_classes)


@register(_MODULE)
class BertModel(NeuralEstimator):
    """BERT encoder + classification head (fine-tune surface).

    Defaults are BERT-base (L=12, H=768, A=12) per BASELINE.md config 4;
    shrink for tests with num_layers/hidden_dim kwargs.
    """

    def __init__(
        self,
        vocab_size: int = 30522,
        hidden_dim: int = 768,
        num_layers: int = 12,
        num_heads: int = 12,
        mlp_dim: int | None = None,
        max_len: int = 512,
        num_classes: int = 2,
        learning_rate: float = 2e-5,
        seed: int = 0,
        remat: bool | str = False,
        use_flash: bool | None = None,
    ):
        self.vocab_size = vocab_size
        self.hidden_dim = hidden_dim
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.mlp_dim = mlp_dim or hidden_dim * 4
        self.max_len = max_len
        self.num_classes = num_classes
        self.remat = remat
        encoder = BertEncoder(
            vocab_size=vocab_size,
            hidden_dim=hidden_dim,
            num_layers=num_layers,
            num_heads=num_heads,
            mlp_dim=self.mlp_dim,
            max_len=max_len,
            remat=remat,
            use_flash=use_flash,
        )
        super().__init__(
            _BertClassifier(encoder=encoder, num_classes=num_classes),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
        )


@register(_MODULE)
class TransformerClassifier(BertModel):
    """Small-transformer alias with test-friendly defaults."""

    def __init__(
        self,
        vocab_size: int = 20000,
        hidden_dim: int = 128,
        num_layers: int = 2,
        num_heads: int = 4,
        max_len: int = 256,
        num_classes: int = 2,
        learning_rate: float = 1e-3,
        seed: int = 0,
    ):
        super().__init__(
            vocab_size=vocab_size,
            hidden_dim=hidden_dim,
            num_layers=num_layers,
            num_heads=num_heads,
            max_len=max_len,
            num_classes=num_classes,
            learning_rate=learning_rate,
            seed=seed,
        )


class _DecoderLM(nn.Module):
    """GPT-style causal transformer: pre-LN decoder blocks over the
    causal flash kernel, tied to a per-token LM head."""

    vocab_size: int
    hidden_dim: int
    num_layers: int
    num_heads: int
    mlp_dim: int
    max_len: int
    dtype: jnp.dtype | None = None  # None = promote (bf16 when the train step casts params)
    use_flash: bool | None = None
    remat: bool | str = False
    decode: bool = False
    window: int | None = None  # sliding-window attention
    num_kv_heads: int | None = None  # grouped-query attention
    positional: str = "learned"  # 'learned' | 'rope'

    @nn.compact
    def __call__(self, tokens, positions=None, key_mask=None):
        tokens = tokens.astype(jnp.int32)
        if self.positional == "rope":
            # Rotary encodes position inside attention (ops/layers.py);
            # no learned table — the model extrapolates past max_len.
            x = nn.Embed(
                self.vocab_size, self.hidden_dim, dtype=self.dtype
            )(tokens)
        else:
            x = embed_tokens(
                tokens, self.vocab_size, self.hidden_dim, self.max_len,
                self.dtype, positions=positions,
            )
        if key_mask is None:
            key_mask = tokens != 0  # (B, T), pad id 0
        block_cls = remat_block(TransformerBlock, self.remat)
        for i in range(self.num_layers):
            x = block_cls(
                hidden_dim=self.hidden_dim,
                num_heads=self.num_heads,
                mlp_dim=self.mlp_dim,
                num_kv_heads=self.num_kv_heads,
                dtype=self.dtype,
                use_flash=self.use_flash,
                causal=True,
                window=self.window,
                rope=self.positional == "rope",
                decode=self.decode,
                name=f"TransformerBlock_{i}",
            )(x, key_mask=key_mask)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        return nn.Dense(self.vocab_size, dtype=self.dtype)(x)  # (B,T,V)


class GreedyDecodeMixin:
    """Autoregressive decoding for any estimator whose module maps
    token ids (B, T) to per-token vocab logits (B, T, V) and supports
    ``decode=True`` KV caching."""

    def generate(self, prompts, max_new_tokens: int = 32,
                 temperature: float | None = None,
                 top_k: int | None = None,
                 top_p: float | None = None, seed: int = 0):
        """Continuation of int32 prompts (B, T0): greedy by default,
        sampled with ``temperature`` (optionally ``top_k``-truncated
        and/or ``top_p`` nucleus-truncated — keep the smallest set of
        tokens whose probabilities sum past ``top_p``).

        KV-cache decoding: the whole generation (prompt prefill +
        continuation) is ONE jitted ``lax.scan`` over buffer positions
        — each step embeds a single token at its true position, attends
        against the per-layer K/V cache, and appends the next token.
        Cost per new token is O(T·H) instead of the O(T²·H) full
        re-forward of the naive loop, and the device round-trip count
        is 1, not T.  ``temperature`` is a runtime argument (no recompile);
        ``top_k`` changes the compiled graph and keys the fn cache."""
        import jax
        import numpy as np
        from jax import lax

        sample = temperature is not None and temperature > 0.0
        if top_k is not None and not sample:
            raise ValueError(
                "top_k requires a positive temperature (top_k without "
                "sampling silently degrades to greedy)"
            )
        if top_p is not None:
            if not sample:
                raise ValueError(
                    "top_p requires a positive temperature"
                )
            if not 0.0 < top_p <= 1.0:
                raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_k == 1:
            # Deterministic by definition — use the greedy path (also
            # sidesteps tie-breaking drift vs argmax in low precision).
            sample, top_k = False, None
        prompts = np.asarray(prompts, dtype=np.int32)
        bsz, t0 = prompts.shape
        if t0 > self.max_len:
            # Without this, total < t0 below and the buffer scatter
            # fails with an opaque shape-broadcast trace error.
            raise ValueError(
                f"prompt length {t0} exceeds max_len={self.max_len}; "
                "truncate the prompt or build the model with a larger "
                "max_len"
            )
        total = min(self.max_len, t0 + max_new_tokens)

        # One (jitted scan, cache shapes) pair per prompt shape,
        # resolved through the CROSS-JOB compiled-program cache
        # (train/compile_cache): decode scans get fingerprints,
        # hit/miss stats, warm-start hints and the cache's bounded
        # eviction like every other program — two estimator instances
        # of one architecture share the executable (params enter as an
        # argument, never a baked-in constant), where the old private
        # per-instance LRU of 8 compiled one each, invisibly.
        from learningorchestra_tpu.train import compile_cache as cc

        shape_sig = (bsz, total, t0, sample, top_k, top_p is not None)
        cache_key = cc.program_key(
            "decode",
            module=cc.module_fingerprint(self.module),
            optimizer=None,
            loss="-",
            dtype="-",
            shapes=("decode", *shape_sig),
        )
        label = (
            f"decode:{type(self.module).__name__}:b{bsz}:t{total}"
        )

        def _build_decode():
            decode_mod = self.module.clone(decode=True)
            # Cache shapes via eval_shape (no real forward, no
            # throwaway params); the trained params drive the scan.
            cache_shapes = jax.eval_shape(
                decode_mod.init, jax.random.PRNGKey(0),
                jnp.zeros((bsz, total), jnp.int32),
            )["cache"]

            use_top_p = top_p is not None

            def decode(variables, cache, buf, temp, p_nucleus, key):
                def step(carry, i):
                    cache, buf = carry
                    tok = lax.dynamic_slice(buf, (0, i), (bsz, 1))
                    pos = jnp.full((bsz, 1), i, jnp.int32)
                    # Valid keys: non-pad tokens at positions already
                    # fed to the cache (prompt tokens beyond i are in
                    # the buffer but not yet cached).  Sliding-window
                    # models narrow this further inside the attention
                    # layer itself (ops/layers.py decode branch).
                    kmask = (jnp.arange(total)[None, :] <= i) \
                        & (buf != 0)
                    logits, mut = decode_mod.apply(
                        {**variables, "cache": cache}, tok,
                        positions=pos, key_mask=kmask,
                        mutable=["cache"],
                    )
                    step_logits = logits[:, 0].astype(jnp.float32)
                    if not sample:
                        nxt = jnp.argmax(step_logits, -1)
                    else:
                        # Never sample pad id 0: a mid-stream pad would
                        # be masked out of all later attention
                        # (buf != 0) and read as end-of-sequence.
                        step_logits = step_logits.at[:, 0].set(-jnp.inf)
                        if top_k is not None:
                            kth = lax.top_k(step_logits, top_k)[0][
                                ..., -1:]
                            step_logits = jnp.where(
                                step_logits < kth, -jnp.inf, step_logits
                            )
                        scaled = step_logits / temp
                        if use_top_p:
                            # Nucleus: drop tokens outside the smallest
                            # prefix (by descending prob) summing past
                            # p.  The threshold prob is found via sort+
                            # cumsum; p is a runtime arg (no recompile).
                            probs = jax.nn.softmax(scaled, -1)
                            srt = jnp.sort(probs, -1)[..., ::-1]
                            csum = jnp.cumsum(srt, -1)
                            cut = jnp.sum(
                                csum < p_nucleus, -1, keepdims=True
                            )
                            thresh = jnp.take_along_axis(srt, cut, -1)
                            scaled = jnp.where(
                                probs < thresh, -jnp.inf, scaled
                            )
                        nxt = jax.random.categorical(
                            jax.random.fold_in(key, i),
                            scaled, axis=-1,
                        )
                    nxt = nxt.astype(jnp.int32)
                    prev = lax.dynamic_slice(buf, (0, i + 1), (bsz, 1))
                    col = jnp.where(i + 1 >= t0, nxt[:, None], prev)
                    buf = lax.dynamic_update_slice(buf, col, (0, i + 1))
                    return (mut["cache"], buf), None

                (cache, buf), _ = lax.scan(
                    step, (cache, buf), jnp.arange(total - 1)
                )
                return buf

            return jax.jit(decode), cache_shapes

        decode, cache_shapes = cc.get_cache().get_or_build(
            cache_key, _build_decode, label=label
        )
        cache0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes
        )
        buf0 = jnp.zeros((bsz, total), jnp.int32).at[:, :t0].set(
            jnp.asarray(prompts)
        )
        return np.asarray(decode(
            dict(self.params), cache0, buf0,
            jnp.float32(temperature if sample else 1.0),
            jnp.float32(top_p if top_p is not None else 1.0),
            jax.random.PRNGKey(seed),
        ))


@register(_MODULE)
class DecoderLM(GreedyDecodeMixin, NeuralEstimator):
    """Causal (decoder-only) language model — beyond-parity headroom:
    the reference has no attention at all (SURVEY §5.7); this pairs the
    causal Pallas flash kernel with the keras-fit surface.

    ``fit(x, y)`` with x = token ids (B, T) and y = next-token targets
    (B, T) (typically ``x[:, 1:]`` padded); the softmax_ce loss averages
    per-token over T (train/neural.py sequence handling).
    ``generate`` greedy-decodes continuations.
    """

    def __init__(
        self,
        vocab_size: int = 32000,
        hidden_dim: int = 256,
        num_layers: int = 4,
        num_heads: int = 8,
        mlp_dim: int | None = None,
        max_len: int = 1024,
        learning_rate: float = 3e-4,
        seed: int = 0,
        remat: bool | str = False,
        attention_window: int | None = None,
        num_kv_heads: int | None = None,
        positional: str = "learned",
    ):
        if positional not in ("learned", "rope"):
            raise ValueError(f"positional must be learned|rope, "
                             f"got {positional!r}")
        self.vocab_size = vocab_size
        self.hidden_dim = hidden_dim
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.mlp_dim = mlp_dim or hidden_dim * 4
        self.max_len = max_len
        self.remat = remat
        self.attention_window = attention_window
        self.num_kv_heads = num_kv_heads
        self.positional = positional
        super().__init__(
            _DecoderLM(
                vocab_size=vocab_size,
                hidden_dim=hidden_dim,
                num_layers=num_layers,
                num_heads=num_heads,
                mlp_dim=self.mlp_dim,
                max_len=max_len,
                remat=remat,
                window=attention_window,
                num_kv_heads=num_kv_heads,
                positional=positional,
            ),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
        )
