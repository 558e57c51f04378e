"""Causal LM whose every block runs a Mamba-2 mixer (``ops/ssm.py``) and
grouped-query attention side by side on the same normed input, then a
SwiGLU FFN; the published muP multipliers scale each branch.  Its decode
cache holds, a layer, K/V pages that grow with the sequence AND a state
and a convolution tail a slot that do not: the decode engine keys its
pools by length, as for any model with pages, and each pool carries
both (``serve/decode/pages.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from learningorchestra_tpu.models.text import GreedyDecodeMixin
from learningorchestra_tpu.ops.layers import MultiHeadSelfAttention, RMSNorm
from learningorchestra_tpu.ops.ssm import Mamba2Mixer
from learningorchestra_tpu.toolkit.registry import register
from learningorchestra_tpu.train.neural import NeuralEstimator

_MODULE = "learningorchestra_tpu.models.hybrid"


class HybridBlock(nn.Module):
    """``h = RMSNorm(x)``; ``x + ssm_out * Mamba(h) + attn_out *
    Attn(attn_in * h)``; then ``+ down_mult * (u W_up * silu(gate_mult
    * u W_gate)) W_down`` with ``u = RMSNorm(x)``."""

    attention: tuple  # MultiHeadSelfAttention's fields, as sorted items
    mamba: tuple  # Mamba2Mixer's fields, as sorted items
    mlp_dim: int
    # (attention in, attention out, ssm out, mlp gate, mlp down)
    multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    norm_eps: float = 1e-5
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, key_mask=None):
        attn_in, attn_out, ssm_out, gate_mult, down_mult = self.multipliers
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        f32 = jnp.float32
        h = RMSNorm(self.norm_eps, name="input_norm", **kw)(x)
        mixed = Mamba2Mixer(
            **dict(self.mamba), norm_eps=self.norm_eps, decode=self.decode,
            **kw,
        )(h, key_mask=key_mask)
        if attn_in != 1.0:
            h = (h.astype(f32) * attn_in).astype(h.dtype)
        attended = MultiHeadSelfAttention(
            **dict(self.attention), qkv_features=x.shape[-1],
            causal=True, rope=True, use_bias=False, decode=self.decode,
            **kw,
        )(h, key_mask=key_mask)
        # the multipliers and the sums in float32: one rounding a sum
        x = (x.astype(f32) + ssm_out * mixed.astype(f32)
             + attn_out * attended.astype(f32)).astype(x.dtype)

        def dense(features, name):
            return nn.Dense(features, use_bias=False, name=name, **kw)

        u = RMSNorm(self.norm_eps, name="ffn_norm", **kw)(x)
        hid = dense(self.mlp_dim, "up")(u).astype(f32) * jax.nn.silu(
            gate_mult * dense(self.mlp_dim, "gate")(u).astype(f32))
        down = dense(x.shape[-1], "down")(hid.astype(u.dtype))
        return (x.astype(f32) + down_mult * down.astype(f32)).astype(x.dtype)


class _HybridLM(nn.Module):
    """Token embedding times ``embedding_multiplier``, :class:`HybridBlock`
    layers, final RMSNorm and an untied bias-free head whose float32
    logits are scaled by ``lm_head_multiplier``: a causal LM."""

    vocab_size: int
    hidden_dim: int
    num_layers: int
    attention: tuple
    mamba: tuple
    mlp_dim: int
    multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    norm_eps: float = 1e-5
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, tokens, positions=None, key_mask=None):
        # ``positions`` is the decode step's; both mixers take them from
        # their own cache index.
        del positions
        tokens = tokens.astype(jnp.int32)
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        x = nn.Embed(self.vocab_size, self.hidden_dim, **kw)(tokens)
        x = (x.astype(jnp.float32) * self.embedding_multiplier) \
            .astype(x.dtype)
        if key_mask is None:
            key_mask = tokens != 0  # (B, T), pad id 0
        for i in range(self.num_layers):
            x = HybridBlock(
                attention=self.attention, mamba=self.mamba,
                mlp_dim=self.mlp_dim, multipliers=self.multipliers,
                norm_eps=self.norm_eps, decode=self.decode,
                name=f"HybridBlock_{i}", **kw,
            )(x, key_mask=key_mask)
        x = RMSNorm(self.norm_eps, name="final_norm", **kw)(x)
        head = self.param(
            "head", nn.initializers.lecun_normal(),
            (self.hidden_dim, self.vocab_size), self.param_dtype,
        )
        logits = jnp.einsum(
            "btd,dv->btv", x, head.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        return logits * self.lm_head_multiplier  # (B, T, V) float32


@register(_MODULE)
class HybridLM(GreedyDecodeMixin, NeuralEstimator):
    """Causal LM of the Falcon-H1 shape: every block a Mamba-2 mixer
    beside grouped-query attention (rotary, keys scaled), a SwiGLU FFN,
    the muP multipliers of the release.

    ``param_dtype`` is the dtype the parameters are held in, in the
    artifact, in the serving registry and on the device alike; the
    mixer's state, convolution and norm, every RMSNorm's statistics and
    the logits are float32 whatever it says.  Served through the decode
    engine like any next-token model: a pool a KV bucket, each slot
    holding a layer's K/V pages, its state and its convolution tail.
    """

    def __init__(
        self,
        vocab_size: int = 32000,
        hidden_dim: int = 256,
        num_layers: int = 4,
        num_heads: int = 8,
        num_kv_heads: int = 2,
        head_dim: int = 32,
        mlp_dim: int = 1024,
        rope_theta: float = 10000.0,
        mamba_heads: int = 8,
        mamba_head_dim: int = 32,
        mamba_groups: int = 2,
        mamba_state: int = 64,
        mamba_conv: int = 4,
        mamba_chunk: int = 128,
        embedding_multiplier: float = 1.0,
        attention_in_multiplier: float = 1.0,
        attention_out_multiplier: float = 1.0,
        key_multiplier: float = 1.0,
        ssm_in_multiplier: float = 1.0,
        ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0),
        ssm_out_multiplier: float = 1.0,
        mlp_multipliers: tuple = (1.0, 1.0),
        lm_head_multiplier: float = 1.0,
        norm_eps: float = 1e-5,
        max_len: int = 1024,
        param_dtype: str = "bfloat16",
        learning_rate: float = 3e-4,
        seed: int = 0,
    ):
        self.vocab_size = vocab_size
        self.hidden_dim = hidden_dim
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.mlp_dim = mlp_dim
        self.rope_theta = rope_theta
        self.mamba_heads = mamba_heads
        self.mamba_head_dim = mamba_head_dim
        self.mamba_groups = mamba_groups
        self.mamba_state = mamba_state
        self.mamba_conv = mamba_conv
        self.mamba_chunk = mamba_chunk
        self.embedding_multiplier = embedding_multiplier
        self.attention_in_multiplier = attention_in_multiplier
        self.attention_out_multiplier = attention_out_multiplier
        self.key_multiplier = key_multiplier
        self.ssm_in_multiplier = ssm_in_multiplier
        self.ssm_multipliers = ssm_multipliers
        self.ssm_out_multiplier = ssm_out_multiplier
        self.mlp_multipliers = mlp_multipliers
        self.lm_head_multiplier = lm_head_multiplier
        self.norm_eps = norm_eps
        self.max_len = max_len
        self.param_dtype = param_dtype
        dtype = jnp.dtype(param_dtype)
        attention = dict(
            num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, rope_theta=float(rope_theta),
            key_scale=float(key_multiplier),
        )
        mamba = dict(
            num_heads=mamba_heads, head_dim=mamba_head_dim,
            n_groups=mamba_groups, state_dim=mamba_state,
            conv_width=mamba_conv, chunk_size=mamba_chunk,
            in_multiplier=float(ssm_in_multiplier),
            part_multipliers=tuple(float(m) for m in ssm_multipliers),
        )
        gate_mult, down_mult = (float(m) for m in mlp_multipliers)
        super().__init__(
            _HybridLM(
                vocab_size=vocab_size,
                hidden_dim=hidden_dim,
                num_layers=num_layers,
                attention=tuple(sorted(attention.items())),
                mamba=tuple(sorted(mamba.items())),
                mlp_dim=mlp_dim,
                multipliers=(
                    float(attention_in_multiplier),
                    float(attention_out_multiplier),
                    float(ssm_out_multiplier), gate_mult, down_mult,
                ),
                embedding_multiplier=float(embedding_multiplier),
                lm_head_multiplier=float(lm_head_multiplier),
                norm_eps=norm_eps,
                dtype=dtype,
                param_dtype=dtype,
            ),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
            compute_dtype=param_dtype,
        )
