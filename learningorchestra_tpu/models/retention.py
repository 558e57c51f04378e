"""Dense causal LM whose mixer is power retention
(``ops/retention.py``): pre-RMSNorm blocks of retention and a gated
FFN, an untied bias-free head.  Where an attention model's decode cache
holds rows that grow with the sequence, this one's holds a state a
slot: the decode engine serves every length from one pool
(``serve/decode/pages.py``).
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from learningorchestra_tpu.models.text import GreedyDecodeMixin
from learningorchestra_tpu.ops.layers import GatedMlp, RMSNorm
from learningorchestra_tpu.ops.retention import PowerRetention
from learningorchestra_tpu.toolkit.registry import register
from learningorchestra_tpu.train.neural import NeuralEstimator

_MODULE = "learningorchestra_tpu.models.retention"


class RetentionBlock(nn.Module):
    """``h = x + retention(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``
    with the SwiGLU FFN of ``mlp_dim``."""

    retention: tuple  # PowerRetention's fields, as sorted items
    mlp_dim: int
    norm_eps: float = 1e-6
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x, key_mask=None):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        x = x + PowerRetention(
            **dict(self.retention), norm_eps=self.norm_eps,
            decode=self.decode, **kw,
        )(RMSNorm(self.norm_eps, name="mixer_norm", **kw)(x),
          key_mask=key_mask)
        return x + GatedMlp(self.mlp_dim, **kw)(
            RMSNorm(self.norm_eps, name="ffn_norm", **kw)(x)
        )


class _RetentionLM(nn.Module):
    """Token embedding, :class:`RetentionBlock` layers, final RMSNorm
    and an untied bias-free head: a causal LM."""

    vocab_size: int
    hidden_dim: int
    num_layers: int
    retention: tuple  # PowerRetention's fields, as sorted items
    mlp_dim: int
    norm_eps: float = 1e-6
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, tokens, positions=None, key_mask=None):
        # ``positions`` is the decode step's; the mixer takes them from
        # its own cache index.
        del positions
        tokens = tokens.astype(jnp.int32)
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        x = nn.Embed(self.vocab_size, self.hidden_dim, **kw)(tokens)
        if key_mask is None:
            key_mask = tokens != 0  # (B, T), pad id 0
        for i in range(self.num_layers):
            x = RetentionBlock(
                retention=self.retention, mlp_dim=self.mlp_dim,
                norm_eps=self.norm_eps, decode=self.decode,
                name=f"RetentionBlock_{i}", **kw,
            )(x, key_mask=key_mask)
        x = RMSNorm(self.norm_eps, name="final_norm", **kw)(x)
        return nn.Dense(
            self.vocab_size, use_bias=False, name="head", **kw
        )(x)  # (B, T, V)


@register(_MODULE)
class RetentionLM(GreedyDecodeMixin, NeuralEstimator):
    """Causal LM of the Brumby shape: the Qwen3 dense block (RMSNorm,
    grouped heads with q/k head norms and rotary positions, SwiGLU)
    with every attention layer a power retention layer of degree 2,
    gated a key/value head.

    ``param_dtype`` is the dtype the parameters are held in, in the
    artifact, in the serving registry and on the device alike; the
    retention state, the gate, norm statistics and logits are float32
    whatever it says.  Served through the decode engine like any
    next-token model, from ONE pool whatever the requests' lengths:
    its cache has no length axis.
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
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.mlp_dim = mlp_dim
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.max_len = max_len
        self.param_dtype = param_dtype
        dtype = jnp.dtype(param_dtype)
        retention = dict(
            num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, rope_theta=float(rope_theta),
        )
        super().__init__(
            _RetentionLM(
                vocab_size=vocab_size,
                hidden_dim=hidden_dim,
                num_layers=num_layers,
                retention=tuple(sorted(retention.items())),
                mlp_dim=mlp_dim,
                norm_eps=norm_eps,
                dtype=dtype,
                param_dtype=dtype,
            ),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
            compute_dtype=param_dtype,
        )
