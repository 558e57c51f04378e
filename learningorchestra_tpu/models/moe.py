"""Mixture-of-experts transformer family (expert parallelism).

Beyond-parity headroom: the reference zoo is dense keras/sklearn only
(reference: microservices/model_image/model.py:92-162 instantiates
``keras.applications`` classes; binary_executor_image ships dense keras
JSON) — it has no conditional-compute models.  These pair the routed
expert FFN (ops/moe.py) with the framework's attention stack: MoE
blocks interleave with dense blocks (GShard's every-other-layer
pattern), experts shard over the ``ep`` mesh axis, tokens reach them
via XLA-inserted all_to_all.

Scaling shape: parameters grow with ``num_experts`` while per-token
FLOPs stay ~constant (top-k of E experts run per token), so the model
family covers the "more capacity, same step time" axis the dense zoo
cannot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from learningorchestra_tpu.models.text import (
    GreedyDecodeMixin,
    TransformerBlock,
    cls_head,
    embed_tokens,
)
from learningorchestra_tpu.ops.latent_attention import LatentAttention
from learningorchestra_tpu.ops.layers import (
    GatedMlp,
    MultiHeadSelfAttention,
    RMSNorm,
)
from learningorchestra_tpu.ops.moe import MoEMlp, RoutedExperts
from learningorchestra_tpu.toolkit.registry import register
from learningorchestra_tpu.train.neural import NeuralEstimator

_MODULE = "learningorchestra_tpu.models.moe"


class MoETransformerBlock(nn.Module):
    """Pre-LN transformer block whose FFN is a routed expert layer."""

    hidden_dim: int
    num_heads: int
    mlp_dim: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.5
    dtype: jnp.dtype | None = None  # None = promote (bf16 when the train step casts params)
    use_flash: bool | None = None
    causal: bool = False
    window: int | None = None  # sliding-window attention (causal only)
    decode: bool = False

    @nn.compact
    def __call__(self, x, key_mask=None):
        y = nn.LayerNorm(dtype=self.dtype)(x)
        y = MultiHeadSelfAttention(
            num_heads=self.num_heads,
            qkv_features=self.hidden_dim,
            dtype=self.dtype,
            use_flash=self.use_flash,
            causal=self.causal,
            window=self.window,
            decode=self.decode,
        )(y, key_mask=key_mask)
        x = x + y
        y = nn.LayerNorm(dtype=self.dtype)(x)
        y = MoEMlp(
            num_experts=self.num_experts,
            hidden_dim=self.hidden_dim,
            mlp_dim=self.mlp_dim,
            top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            dtype=self.dtype,
        )(y)
        return x + y


class _MoETransformer(nn.Module):
    """Encoder/decoder trunk with MoE FFNs every ``moe_every`` blocks.

    ``head``: 'cls' pools position 0 through a tanh head (classifier),
    'lm' emits per-token vocab logits (causal LM).
    """

    vocab_size: int
    hidden_dim: int
    num_layers: int
    num_heads: int
    mlp_dim: int
    max_len: int
    num_experts: int
    num_classes: int
    head: str = "cls"
    moe_every: int = 2
    top_k: int = 2
    capacity_factor: float = 1.5
    dtype: jnp.dtype | None = None  # None = promote (bf16 when the train step casts params)
    use_flash: bool | None = None
    decode: bool = False
    window: int | None = None  # sliding-window attention (lm head only)

    @nn.compact
    def __call__(self, tokens, positions=None, key_mask=None):
        tokens = tokens.astype(jnp.int32)
        causal = self.head == "lm"
        x = embed_tokens(
            tokens, self.vocab_size, self.hidden_dim, self.max_len,
            self.dtype, positions=positions,
        )
        if key_mask is None:
            key_mask = tokens != 0
        for i in range(self.num_layers):
            # MoE on the LAST block of each moe_every group so a
            # 1-layer net is still dense-first (router sees features).
            if (i + 1) % self.moe_every == 0:
                x = MoETransformerBlock(
                    hidden_dim=self.hidden_dim,
                    num_heads=self.num_heads,
                    mlp_dim=self.mlp_dim,
                    num_experts=self.num_experts,
                    top_k=self.top_k,
                    capacity_factor=self.capacity_factor,
                    dtype=self.dtype,
                    use_flash=self.use_flash,
                    causal=causal,
                    window=self.window if causal else None,
                    decode=self.decode,
                    name=f"MoEBlock_{i}",
                )(x, key_mask=key_mask)
            else:
                x = TransformerBlock(
                    hidden_dim=self.hidden_dim,
                    num_heads=self.num_heads,
                    mlp_dim=self.mlp_dim,
                    dtype=self.dtype,
                    use_flash=self.use_flash,
                    causal=causal,
                    window=self.window if causal else None,
                    decode=self.decode,
                    name=f"TransformerBlock_{i}",
                )(x, key_mask=key_mask)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        if self.head == "lm":
            return nn.Dense(self.vocab_size, dtype=self.dtype)(x)
        return cls_head(x, self.hidden_dim, self.num_classes)


@register(_MODULE)
class MoETransformerClassifier(NeuralEstimator):
    """Sequence classifier with routed-expert FFNs."""

    def __init__(
        self,
        vocab_size: int = 20000,
        hidden_dim: int = 128,
        num_layers: int = 2,
        num_heads: int = 4,
        mlp_dim: int | None = None,
        max_len: int = 256,
        num_experts: int = 8,
        top_k: int = 2,
        capacity_factor: float = 1.5,
        moe_every: int = 2,
        num_classes: int = 2,
        learning_rate: float = 1e-3,
        seed: int = 0,
    ):
        self.vocab_size = vocab_size
        self.hidden_dim = hidden_dim
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.mlp_dim = mlp_dim or hidden_dim * 4
        self.max_len = max_len
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.moe_every = moe_every
        self.num_classes = num_classes
        super().__init__(
            _MoETransformer(
                vocab_size=vocab_size,
                hidden_dim=hidden_dim,
                num_layers=num_layers,
                num_heads=num_heads,
                mlp_dim=self.mlp_dim,
                max_len=max_len,
                num_experts=num_experts,
                num_classes=num_classes,
                head="cls",
                moe_every=moe_every,
                top_k=top_k,
                capacity_factor=capacity_factor,
            ),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
        )


@register(_MODULE)
class MoEDecoderLM(GreedyDecodeMixin, NeuralEstimator):
    """Causal LM with routed-expert FFNs (sparse GPT shape)."""

    def __init__(
        self,
        vocab_size: int = 32000,
        hidden_dim: int = 256,
        num_layers: int = 4,
        num_heads: int = 8,
        mlp_dim: int | None = None,
        max_len: int = 1024,
        num_experts: int = 8,
        top_k: int = 2,
        capacity_factor: float = 1.5,
        moe_every: int = 2,
        learning_rate: float = 3e-4,
        seed: int = 0,
        attention_window: int | None = None,
    ):
        self.vocab_size = vocab_size
        self.hidden_dim = hidden_dim
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.mlp_dim = mlp_dim or hidden_dim * 4
        self.max_len = max_len
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.moe_every = moe_every
        self.attention_window = attention_window
        super().__init__(
            _MoETransformer(
                vocab_size=vocab_size,
                hidden_dim=hidden_dim,
                num_layers=num_layers,
                num_heads=num_heads,
                mlp_dim=self.mlp_dim,
                max_len=max_len,
                num_experts=num_experts,
                num_classes=vocab_size,
                head="lm",
                moe_every=moe_every,
                top_k=top_k,
                capacity_factor=capacity_factor,
                window=attention_window,
            ),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
        )


class RoutedExpertBlock(nn.Module):
    """Pre-RMSNorm block: grouped-query attention with per-head q/k
    norms and rotary positions, then dropless routed SwiGLU experts."""

    hidden_dim: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    expert_dim: int
    num_experts: int
    top_k: int
    experts_held: tuple | None = None
    block: int | None = None
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, key_mask=None):
        def norm(name):
            return RMSNorm(self.norm_eps, dtype=self.dtype,
                           param_dtype=self.param_dtype, name=name)

        y = MultiHeadSelfAttention(
            num_heads=self.num_heads,
            qkv_features=self.hidden_dim,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            use_bias=False,
            qk_norm=True,
            qk_norm_eps=self.norm_eps,
            rope=True,
            rope_theta=self.rope_theta,
            causal=self.block is None,
            block=self.block,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            decode=self.decode,
        )(norm("attn_norm")(x), key_mask=key_mask)
        x = x + y
        y = RoutedExperts(
            num_experts=self.num_experts,
            expert_dim=self.expert_dim,
            top_k=self.top_k,
            held=self.experts_held,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )(norm("moe_norm")(x))
        return x + y


class _BlockDiffusionMoE(nn.Module):
    """Token embedding, :class:`RoutedExpertBlock` layers, final
    RMSNorm and an untied bias-free head.  ``block_length`` positions
    form a block: attention is causal over blocks and full inside one,
    and a position's own logits predict it (no shift).  With
    ``block_length`` None the same stack is a plain causal LM."""

    vocab_size: int
    hidden_dim: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    expert_dim: int
    num_experts: int
    top_k: int
    max_len: int
    block_length: int | None = 4
    experts_held: tuple | None = None
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, tokens, positions=None, key_mask=None):
        # ``positions`` is the decode step's; attention takes them from
        # its own cache index.
        del positions
        tokens = tokens.astype(jnp.int32)
        x = nn.Embed(
            self.vocab_size, self.hidden_dim, dtype=self.dtype,
            param_dtype=self.param_dtype,
        )(tokens)
        if key_mask is None:
            key_mask = tokens != 0  # (B, T), pad id 0
        for i in range(self.num_layers):
            x = RoutedExpertBlock(
                hidden_dim=self.hidden_dim,
                num_heads=self.num_heads,
                num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim,
                expert_dim=self.expert_dim,
                num_experts=self.num_experts,
                top_k=self.top_k,
                experts_held=self.experts_held,
                block=self.block_length,
                rope_theta=self.rope_theta,
                norm_eps=self.norm_eps,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                decode=self.decode,
                name=f"RoutedExpertBlock_{i}",
            )(x, key_mask=key_mask)
        x = RMSNorm(self.norm_eps, dtype=self.dtype,
                    param_dtype=self.param_dtype, name="final_norm")(x)
        return nn.Dense(
            self.vocab_size, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name="head",
        )(x)  # (B, T, V)


@register(_MODULE)
class BlockDiffusionMoELM(NeuralEstimator):
    """Sparse-expert LM that generates by diffusion over blocks
    (``serve/decode/blocks.py`` has the procedure): RMSNorm, grouped-
    query attention with q/k norms and rotary positions, dropless
    top-k routed SwiGLU experts, an untied head.

    ``param_dtype`` is the dtype its parameters are held in, in the
    artifact, in the serving registry and on the device alike
    (``bfloat16`` as such models are published); K/V pages follow it.
    Router, norm statistics, attention's softmax and the confidences
    are float32 whatever it says.

    ``generate`` here is the plain procedure, one full forward of the
    whole buffer a denoising step; the decode engine serves the same
    tokens through its page pools, a block a step.
    """

    def __init__(
        self,
        vocab_size: int = 32000,
        hidden_dim: int = 256,
        num_layers: int = 4,
        num_heads: int = 8,
        num_kv_heads: int = 2,
        head_dim: int = 32,
        expert_dim: int = 128,
        num_experts: int = 8,
        experts_per_token: int = 2,
        max_len: int = 1024,
        block_length: int = 4,
        denoising_steps: int = 4,
        remasking: str = "low_confidence_dynamic",
        confidence_threshold: float = 0.9,
        mask_token_id: int | None = None,
        experts_held: tuple | None = None,
        rope_theta: float = 1e6,
        norm_eps: float = 1e-6,
        param_dtype: str = "bfloat16",
        learning_rate: float = 3e-4,
        seed: int = 0,
    ):
        if max_len % block_length:
            raise ValueError("max_len must be a multiple of block_length")
        self.vocab_size = vocab_size
        self.hidden_dim = hidden_dim
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.expert_dim = expert_dim
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.max_len = max_len
        self.block_length = block_length
        self.denoising_steps = denoising_steps
        self.remasking = remasking
        self.confidence_threshold = confidence_threshold
        self.mask_token_id = vocab_size - 1 if mask_token_id is None \
            else mask_token_id
        self.experts_held = None if experts_held is None \
            else tuple(experts_held)
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.param_dtype = param_dtype
        dtype = jnp.dtype(param_dtype)
        super().__init__(
            _BlockDiffusionMoE(
                vocab_size=vocab_size,
                hidden_dim=hidden_dim,
                num_layers=num_layers,
                num_heads=num_heads,
                num_kv_heads=num_kv_heads,
                head_dim=head_dim,
                expert_dim=expert_dim,
                num_experts=num_experts,
                top_k=experts_per_token,
                max_len=max_len,
                block_length=block_length,
                experts_held=self.experts_held,
                rope_theta=rope_theta,
                norm_eps=norm_eps,
                dtype=dtype,
                param_dtype=dtype,
            ),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
            compute_dtype=param_dtype,
        )

    def generate(self, prompts, max_new_tokens: int = 32,
                 denoising_steps: int | None = None,
                 remasking: str | None = None,
                 confidence_threshold: float | None = None,
                 temperature=None, top_k=None, top_p=None, seed: int = 0):
        """Greedy continuation of int32 prompts (B, T0) by diffusion
        over blocks, the whole buffer forwarded anew each step."""
        import jax
        import numpy as np

        from learningorchestra_tpu.serve.decode import blocks

        if temperature is not None or top_k is not None \
                or top_p is not None:
            raise ValueError(
                "block-diffusion generation is greedy: no temperature, "
                "top_k or top_p"
            )
        plan = blocks.BlockPlan(self, denoising_steps, remasking,
                                confidence_threshold)
        prompts = np.asarray(prompts, np.int32)
        bsz, t0 = prompts.shape
        b = plan.block
        total = min(self.max_len, -(-(t0 + max_new_tokens) // b) * b)
        apply = jax.jit(self.module.apply)
        out = np.full((bsz, total), plan.mask_id, np.int32)
        out[:, :t0] = prompts
        for row in out:
            for start in range(t0 // b * b, total, b):
                # the block as it begins: mask ids from t0 on
                state = (row[start: start + b],
                         np.arange(start, start + b) >= t0,
                         np.full(b, -1, np.int32), np.int32(0))
                while not blocks.final(state[1]):
                    logits = jnp.asarray(
                        apply(self.params, row[None])[0, start: start + b],
                        jnp.float32,
                    )
                    top = logits.max(-1, keepdims=True)
                    *state, _ = blocks.denoise(
                        *state, logits.argmax(-1),
                        1.0 / jnp.exp(logits - top).sum(-1),
                        plan.steps, plan.dynamic, plan.threshold,
                    )
                    row[start: start + b] = state[0]
        return out[:, : min(total, t0 + max_new_tokens)]


class LatentExpertBlock(nn.Module):
    """Pre-RMSNorm block of a DeepSeek-V3-shaped model: latent
    attention (``ops/latent_attention.py``), then either a dense gated
    FFN of ``mlp_dim`` (``routed=False``: a leading dense layer) or
    sigmoid-routed SwiGLU experts with a shared expert of
    ``shared_dim`` that every token passes beside them."""

    attention: tuple  # LatentAttention's fields, as sorted items
    routed: bool
    mlp_dim: int
    expert_dim: int
    num_experts: int
    top_k: int
    shared_dim: int
    routed_scale: float = 1.0
    experts_held: tuple | None = None
    norm_eps: float = 1e-6
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, key_mask=None):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)

        def norm(name):
            return RMSNorm(self.norm_eps, name=name, **kw)

        x = x + LatentAttention(
            **dict(self.attention), norm_eps=self.norm_eps,
            decode=self.decode, **kw,
        )(norm("attn_norm")(x), key_mask=key_mask)
        y = norm("ffn_norm")(x)
        if not self.routed:
            return x + GatedMlp(self.mlp_dim, **kw)(y)
        out = RoutedExperts(
            num_experts=self.num_experts,
            expert_dim=self.expert_dim,
            top_k=self.top_k,
            held=self.experts_held,
            scoring="sigmoid",
            score_bias=True,
            routed_scale=self.routed_scale,
            **kw,
        )(y)
        if self.shared_dim:
            with jax.named_scope("moe_shared"):
                out = out + GatedMlp(
                    self.shared_dim, name="shared_expert", **kw
                )(y)
        return x + out


class _LatentMoE(nn.Module):
    """Token embedding, ``first_dense`` dense :class:`LatentExpertBlock`
    layers and routed ones after them, final RMSNorm and an untied
    bias-free head: a causal LM."""

    vocab_size: int
    hidden_dim: int
    num_layers: int
    first_dense: int
    attention: tuple  # LatentAttention's fields, as sorted items
    mlp_dim: int
    expert_dim: int
    num_experts: int
    top_k: int
    shared_dim: int
    routed_scale: float = 1.0
    experts_held: tuple | None = None
    norm_eps: float = 1e-6
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, tokens, positions=None, key_mask=None):
        # ``positions`` is the decode step's; attention takes them from
        # its own cache index.
        del positions
        tokens = tokens.astype(jnp.int32)
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        x = nn.Embed(self.vocab_size, self.hidden_dim, **kw)(tokens)
        if key_mask is None:
            key_mask = tokens != 0  # (B, T), pad id 0
        for i in range(self.num_layers):
            x = LatentExpertBlock(
                attention=self.attention,
                routed=i >= self.first_dense,
                mlp_dim=self.mlp_dim,
                expert_dim=self.expert_dim,
                num_experts=self.num_experts,
                top_k=self.top_k,
                shared_dim=self.shared_dim,
                routed_scale=self.routed_scale,
                experts_held=self.experts_held,
                norm_eps=self.norm_eps,
                decode=self.decode,
                name=f"LatentExpertBlock_{i}",
                **kw,
            )(x, key_mask=key_mask)
        x = RMSNorm(self.norm_eps, name="final_norm", **kw)(x)
        return nn.Dense(
            self.vocab_size, use_bias=False, name="head", **kw
        )(x)  # (B, T, V)


@register(_MODULE)
class LatentMoELM(GreedyDecodeMixin, NeuralEstimator):
    """Causal LM of the DeepSeek-V3 / Kimi-K2 shape: multi-head latent
    attention (a ``kv_lora_rank + qk_rope_head_dim`` cache row a token,
    YaRN rotary frequencies from ``rope_scaling``, the published
    group), ``first_dense_layers`` dense SwiGLU layers of ``mlp_dim``,
    then layers of ``num_experts`` sigmoid-routed SwiGLU experts
    (chosen by score + bias, weighed by the score over the chosen's sum
    times ``routed_scale``) beside ``shared_experts`` shared ones.

    ``experts_held`` = (first, count) is this chip's share of an
    expert-parallel deployment: the router scores all ``num_experts``
    and the layer adds what its own experts give.  ``param_dtype`` is
    the dtype the parameters are held in, in the artifact, in the
    serving registry and on the device alike; the latent cache follows
    it.  Router, norm statistics and softmax are float32 whatever it
    says.  Served through the decode engine like any next-token model.
    """

    def __init__(
        self,
        vocab_size: int = 32000,
        hidden_dim: int = 256,
        num_layers: int = 4,
        num_heads: int = 8,
        q_lora_rank: int = 96,
        kv_lora_rank: int = 64,
        qk_nope_head_dim: int = 32,
        qk_rope_head_dim: int = 16,
        v_head_dim: int = 32,
        mlp_dim: int = 1024,
        first_dense_layers: int = 1,
        expert_dim: int = 128,
        num_experts: int = 16,
        experts_per_token: int = 4,
        shared_experts: int = 1,
        routed_scale: float = 1.0,
        experts_held: tuple | None = None,
        rope_theta: float = 10000.0,
        rope_scaling: dict | None = None,
        norm_eps: float = 1e-6,
        max_len: int = 1024,
        param_dtype: str = "bfloat16",
        learning_rate: float = 3e-4,
        seed: int = 0,
    ):
        self.vocab_size = vocab_size
        self.hidden_dim = hidden_dim
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.mlp_dim = mlp_dim
        self.first_dense_layers = first_dense_layers
        self.expert_dim = expert_dim
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.shared_experts = shared_experts
        self.routed_scale = routed_scale
        self.experts_held = None if experts_held is None \
            else tuple(experts_held)
        self.rope_theta = rope_theta
        self.rope_scaling = None if rope_scaling is None \
            else dict(rope_scaling)
        self.norm_eps = norm_eps
        self.max_len = max_len
        self.param_dtype = param_dtype
        dtype = jnp.dtype(param_dtype)
        attention = dict(
            num_heads=num_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=float(rope_theta),
            # hashable, for the module's fingerprint and jit
            rope_scaling=None if rope_scaling is None else tuple(sorted(
                (k, v) for k, v in rope_scaling.items()
                if not isinstance(v, str)
            )),
        )
        super().__init__(
            _LatentMoE(
                vocab_size=vocab_size,
                hidden_dim=hidden_dim,
                num_layers=num_layers,
                first_dense=first_dense_layers,
                attention=tuple(sorted(attention.items())),
                mlp_dim=mlp_dim,
                expert_dim=expert_dim,
                num_experts=num_experts,
                top_k=experts_per_token,
                shared_dim=shared_experts * expert_dim,
                routed_scale=float(routed_scale),
                experts_held=self.experts_held,
                norm_eps=norm_eps,
                dtype=dtype,
                param_dtype=dtype,
            ),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
            compute_dtype=param_dtype,
        )
