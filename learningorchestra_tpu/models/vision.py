"""Vision models: MNIST CNN and ResNet.

The reference reaches ResNet50 through ``tensorflow.keras.applications``
in the model service (reference: microservices/model_image/model.py:92-162,
README demo pipelines at README.md:53).  Here they are Flax modules:

- convolutions in NHWC (TPU-native layout; XLA tiles convs onto the MXU);
- GroupNorm instead of BatchNorm — batch-statistics-free, so the module is
  a pure function of (params, x): no mutable state collections to thread
  through jit/shard_map, and normalization is independent of the
  data-parallel batch split (BatchNorm under DP needs cross-replica stats
  sync, a host of complexity the reference's Horovod path simply got wrong
  by using per-replica stats).
"""

from __future__ import annotations

import math
from typing import Sequence

import jax.numpy as jnp
from flax import linen as nn

from learningorchestra_tpu.ops.layers import remat_block
from learningorchestra_tpu.toolkit.registry import register
from learningorchestra_tpu.train.neural import NeuralEstimator

_MODULE = "learningorchestra_tpu.models.vision"


class _MnistCNN(nn.Module):
    num_classes: int = 10

    @nn.compact
    def __call__(self, x):
        # Accept (B, 784) flat or (B, 28, 28) or (B, 28, 28, 1).
        if x.ndim == 2:
            x = x.reshape((x.shape[0], 28, 28, 1))
        elif x.ndim == 3:
            x = x[..., None]
        x = nn.Conv(32, (3, 3))(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = nn.Conv(64, (3, 3))(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128)(x))
        return nn.Dense(self.num_classes)(x)


@register(_MODULE)
class MnistCNN(NeuralEstimator):
    def __init__(
        self,
        num_classes: int = 10,
        learning_rate: float = 1e-3,
        seed: int = 0,
    ):
        self.num_classes = num_classes
        super().__init__(
            _MnistCNN(num_classes=num_classes),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
        )


class _ResNetBlock(nn.Module):
    filters: int
    strides: tuple = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(self.filters, (3, 3), self.strides, use_bias=False)(x)
        y = nn.GroupNorm(num_groups=min(32, self.filters))(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), use_bias=False)(y)
        y = nn.GroupNorm(num_groups=min(32, self.filters))(y)
        if residual.shape != y.shape:
            residual = nn.Conv(
                self.filters, (1, 1), self.strides, use_bias=False
            )(x)
            residual = nn.GroupNorm(num_groups=min(32, self.filters))(
                residual
            )
        return nn.relu(y + residual)


class _BottleneckBlock(nn.Module):
    filters: int
    strides: tuple = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(self.filters, (1, 1), use_bias=False)(x)
        y = nn.GroupNorm(num_groups=min(32, self.filters))(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), self.strides, use_bias=False)(y)
        y = nn.GroupNorm(num_groups=min(32, self.filters))(y)
        y = nn.relu(y)
        y = nn.Conv(4 * self.filters, (1, 1), use_bias=False)(y)
        y = nn.GroupNorm(num_groups=min(32, 4 * self.filters))(y)
        if residual.shape != y.shape:
            residual = nn.Conv(
                4 * self.filters, (1, 1), self.strides, use_bias=False
            )(x)
            residual = nn.GroupNorm(num_groups=min(32, 4 * self.filters))(
                residual
            )
        return nn.relu(y + residual)


def space_to_depth(x, block: int = 2):
    """[B, H, W, C] → [B, H/block, W/block, C·block²] by folding each
    spatial block into channels (odd tails zero-padded).

    The MXU sees convolutions as [spatial·C_in → C_out] contractions;
    an RGB stem's C_in=3 pads to 8 of the 128 systolic lanes, wasting
    >90% of the array on ~12% of ResNet's FLOPs.  Folding 2×2 pixels
    into channels turns the stem into a ≥128-deep contraction at a
    quarter of the spatial positions — the standard public TPU ResNet
    recipe (never timed here: ROADMAP S5).
    """
    b, h, w, c = x.shape
    pad_h = (-h) % block
    pad_w = (-w) % block
    if pad_h or pad_w:
        x = jnp.pad(x, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
        h, w = h + pad_h, w + pad_w
    x = x.reshape(b, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // block, w // block, c * block * block)


class _ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block: type
    num_classes: int = 1000
    width: int = 64
    # jax.checkpoint each residual block: activations rematerialize in
    # the backward pass — the batch-size headroom knob for conv nets,
    # where activation HBM (B x H x W x C per block) dominates params.
    remat: bool | str = False
    # Opt-in MXU-friendly stem: space-to-depth(2) + 4×4/s1 conv in the
    # folded space — the same receptive field (8×8 ⊇ 7×7) and the same
    # output shape as conv7×7/s2, but a 4·4·4C-deep contraction
    # instead of a 3-channel one.  Default OFF: the parameter shape
    # differs, and stored artifacts trained with the classic stem must
    # keep loading.
    s2d_stem: bool = False

    @nn.compact
    def __call__(self, x):
        if x.ndim == 3:
            x = x[..., None]
        if self.s2d_stem:
            x = space_to_depth(x, 2)
            x = nn.Conv(self.width, (4, 4), (1, 1), use_bias=False,
                        name="stem_s2d")(x)
        else:
            x = nn.Conv(self.width, (7, 7), (2, 2), use_bias=False)(x)
        x = nn.GroupNorm(num_groups=min(32, self.width))(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        block_cls = remat_block(self.block, self.remat)
        idx = 0
        for stage, n_blocks in enumerate(self.stage_sizes):
            for block_i in range(n_blocks):
                strides = (2, 2) if stage > 0 and block_i == 0 else (1, 1)
                # Explicit names pinned to the historical auto-names
                # (sequential across stages) so stored artifacts survive
                # toggling the memory knob — same convention as
                # BertEncoder's remat (models/text.py).
                x = block_cls(
                    self.width * (2**stage), strides=strides,
                    name=f"{self.block.__name__}_{idx}",
                )(x)
                idx += 1
        x = x.mean(axis=(1, 2))  # global average pool
        return nn.Dense(self.num_classes)(x)


@register(_MODULE)
class ResNet18(NeuralEstimator):
    def __init__(
        self,
        num_classes: int = 1000,
        learning_rate: float = 1e-3,
        seed: int = 0,
        remat: bool | str = False,
        s2d_stem: bool = False,
    ):
        self.num_classes = num_classes
        self.remat = remat
        self.s2d_stem = s2d_stem
        super().__init__(
            _ResNet(
                stage_sizes=(2, 2, 2, 2),
                block=_ResNetBlock,
                num_classes=num_classes,
                remat=remat,
                s2d_stem=s2d_stem,
            ),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
        )


@register(_MODULE)
class ResNet50(NeuralEstimator):
    def __init__(
        self,
        num_classes: int = 1000,
        learning_rate: float = 1e-3,
        seed: int = 0,
        remat: bool | str = False,
        s2d_stem: bool = False,
    ):
        self.num_classes = num_classes
        self.remat = remat
        self.s2d_stem = s2d_stem
        super().__init__(
            _ResNet(
                stage_sizes=(3, 4, 6, 3),
                block=_BottleneckBlock,
                num_classes=num_classes,
                remat=remat,
                s2d_stem=s2d_stem,
            ),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
        )


# -- VGG ---------------------------------------------------------------------


class _VGG(nn.Module):
    """VGG-16 layout (Simonyan & Zisserman config D), GroupNorm'd."""

    num_classes: int
    stage_sizes: Sequence[int] = (2, 2, 3, 3, 3)
    widths: Sequence[int] = (64, 128, 256, 512, 512)

    @nn.compact
    def __call__(self, x):
        if x.ndim == 3:
            x = x[..., None]
        for blocks, width in zip(self.stage_sizes, self.widths):
            for _ in range(blocks):
                x = nn.Conv(width, (3, 3), padding="SAME")(x)
                x = nn.GroupNorm(num_groups=math.gcd(32, width))(x)
                x = nn.relu(x)
            # SAME-padded pooling: small inputs (e.g. 28x28 MNIST) must
            # not shrink to a zero-size axis (VALID would: 28->...->0,
            # making global average pooling return NaN).
            x = nn.max_pool(x, (2, 2), strides=(2, 2), padding="SAME")
        x = x.mean(axis=(1, 2))  # GAP replaces the 4096-wide FC stack
        x = nn.relu(nn.Dense(1024)(x))
        return nn.Dense(self.num_classes)(x)


@register(_MODULE)
class VGG16(NeuralEstimator):
    def __init__(
        self,
        num_classes: int = 1000,
        learning_rate: float = 1e-3,
        seed: int = 0,
    ):
        self.num_classes = num_classes
        super().__init__(
            _VGG(num_classes=num_classes),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
        )


# -- MobileNet ---------------------------------------------------------------


class _DepthwiseSeparable(nn.Module):
    """Depthwise (feature_group_count=C) + pointwise conv pair."""

    filters: int
    strides: tuple = (1, 1)

    @nn.compact
    def __call__(self, x):
        channels = x.shape[-1]
        x = nn.Conv(
            channels, (3, 3), strides=self.strides, padding="SAME",
            feature_group_count=channels,
        )(x)
        # gcd: group count must DIVIDE the channel count, which
        # arbitrary width multipliers (0.75 -> 48 channels) break for a
        # fixed 32.
        x = nn.GroupNorm(num_groups=math.gcd(32, channels))(x)
        x = nn.relu(x)
        x = nn.Conv(self.filters, (1, 1))(x)
        x = nn.GroupNorm(num_groups=math.gcd(32, self.filters))(x)
        return nn.relu(x)


class _MobileNet(nn.Module):
    """MobileNetV1 layout — depthwise-separable stacks."""

    num_classes: int
    width_multiplier: float = 1.0

    @nn.compact
    def __call__(self, x):
        if x.ndim == 3:
            x = x[..., None]

        def w(c):
            return max(8, int(c * self.width_multiplier))

        x = nn.Conv(w(32), (3, 3), strides=(2, 2), padding="SAME")(x)
        x = nn.relu(nn.GroupNorm(num_groups=math.gcd(32, w(32)))(x))
        plan = [
            (w(64), (1, 1)), (w(128), (2, 2)), (w(128), (1, 1)),
            (w(256), (2, 2)), (w(256), (1, 1)), (w(512), (2, 2)),
            *([(w(512), (1, 1))] * 5),
            (w(1024), (2, 2)), (w(1024), (1, 1)),
        ]
        for filters, strides in plan:
            x = _DepthwiseSeparable(filters=filters, strides=strides)(x)
        x = x.mean(axis=(1, 2))
        return nn.Dense(self.num_classes)(x)


@register(_MODULE)
class MobileNet(NeuralEstimator):
    def __init__(
        self,
        num_classes: int = 1000,
        width_multiplier: float = 1.0,
        learning_rate: float = 1e-3,
        seed: int = 0,
    ):
        self.num_classes = num_classes
        self.width_multiplier = width_multiplier
        super().__init__(
            _MobileNet(
                num_classes=num_classes,
                width_multiplier=width_multiplier,
            ),
            loss="softmax_ce",
            learning_rate=learning_rate,
            seed=seed,
        )
