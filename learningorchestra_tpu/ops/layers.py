"""Flax layers backed by the Pallas kernel library.

``MultiHeadSelfAttention`` is the transformer models' attention layer:
QKV/output projections as feature-dim matmuls (shardable on a ``tp``
mesh axis) around the flash-attention kernel.  Off-TPU it dispatches to
the jnp reference instead of interpret mode — interpret-mode Pallas is
orders of magnitude slower and only meant for kernel tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from learningorchestra_tpu.ops.attention import (
    flash_attention,
    mha_reference,
)
from learningorchestra_tpu.ops.decode_attention import (
    cached_attend,
    grouped_decode_attend,
    pack_pages,
    page_shape,
    unpack_pages,
)


def remat_block(cls, remat):
    """Wrap a block module class per the family-wide ``remat`` knob.

    ``False`` — no remat.  ``True`` — full recompute (O(layers) less
    activation HBM for ~1 extra forward of FLOPs).  ``"dots"`` —
    ``jax.checkpoint_policies.dots_with_no_batch_dims_saveable``: MXU
    outputs (matmuls/convs) stay resident, only the cheap elementwise
    work recomputes — usually the better FLOPs/HBM trade on TPU when
    memory allows (the MFU-sweep knob; VERDICT r3 item 2).
    """
    if not remat:
        return cls
    policy = None
    if remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    elif remat is not True:
        raise ValueError(f"remat must be False|True|'dots', got {remat!r}")
    return nn.remat(cls, policy=policy)


def apply_rope(x, positions, theta: float = 10000.0):
    """Rotary position embedding on (B, H, T, hd) with positions (T,)
    or (B, T).  Rotates feature pairs (x[..., :hd/2], x[..., hd/2:])
    by position-scaled frequencies — attention scores then depend only
    on RELATIVE distance, so trained models extrapolate past max_len
    and need no learned position table."""
    hd = x.shape[-1]
    if hd % 2:
        raise ValueError(f"rope needs an even head_dim, got {hd}")
    half = hd // 2
    freqs = theta ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )  # (half,)
    pos = jnp.asarray(positions, jnp.float32)
    angles = pos[..., None] * freqs  # (T, half) or (B, T, half)
    if angles.ndim == 2:  # (T, half): shared across batch and heads
        angles = angles[None, None]
    elif angles.ndim == 3:  # (B, T, half): insert the head axis
        angles = angles[:, None]
    else:
        raise ValueError(f"positions must be (T,) or (B, T)")
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    """Weight-only root-mean-square norm over the last axis; the mean
    of squares is taken in float32 whatever the input's dtype."""

    eps: float = 1e-6
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype
        )
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps
        )
        dt = self.dtype if self.dtype is not None else x.dtype
        return (y * scale.astype(jnp.float32)).astype(dt)


class GatedMlp(nn.Module):
    """Bias-free gated feed-forward (SwiGLU): ``(silu(x W_gate) * x
    W_up) W_down``.  A dense layer's FFN, and the shared expert every
    token passes beside its routed ones."""

    mlp_dim: int
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        hid = nn.silu(dense(self.mlp_dim, "gate")(x)) \
            * dense(self.mlp_dim, "up")(x)
        return dense(x.shape[-1], "down")(hid)


class MultiHeadSelfAttention(nn.Module):
    """Self-attention with a key-side padding mask (B, T).

    ``use_flash``: None → Pallas kernel on TPU, reference elsewhere;
    True/False forces a path (tests force both and compare).

    On the chip (TPU v5e, jax 0.9.0; PR 21, PERF.md) the streamed-K/V
    kernel compiles and matches ``mha_reference`` to bf16 tolerance
    fwd+bwd for full / causal / window at T 128..16384 (333 unaligned),
    D 64 and 128, and runs per shard under a device mesh
    (ops/attention.py ``_per_shard``).  Where it beats XLA's fused
    attention was last measured in round 2 on an older jax (from
    T = 16k; behind at T <= 4096) — ROADMAP S6 selects by sequence
    length once that is re-measured.
    """

    num_heads: int
    qkv_features: int
    # Grouped-query attention: project K/V to ``num_kv_heads`` heads
    # (None = num_heads, plain MHA; 1 = multi-query).  Shrinks the
    # decode KV cache and K/V projection FLOPs by H/H_kv; each KV head
    # serves a contiguous group of query heads.
    num_kv_heads: int | None = None
    dtype: jnp.dtype | None = None  # None = promote (bf16 when the train step casts params)
    use_flash: bool | None = None
    causal: bool = False
    # Sliding-window (banded causal) attention: each query sees its
    # last ``window`` positions.  O(T*window) cost on the flash path —
    # off-diagonal blocks outside the band skip compute entirely.
    window: int | None = None
    # Rotary position embeddings applied to q/k (the model skips its
    # learned position table when this is on).
    rope: bool = False
    # Autoregressive inference: cache K/V per position in a 'cache'
    # variable collection (apply with mutable=['cache']).  Initialize
    # by running the module on a FULL-length input (flax convention:
    # the uninitialized pass behaves as a normal forward and sizes the
    # cache); then feed one position at a time.
    decode: bool = False
    # One (H + 2·H_kv, hd) projection instead of three — the MXU wants
    # fewer, LARGER matmuls: at short sequence lengths the three small
    # per-layer projections are dispatch/tiling-bound, and XLA does not
    # merge separate dots on its own.  Same math (the fused weight is
    # the block-stack of the three), same init variance (fan_in is the
    # model dim either way).  Trade: under tp>1 the fused head axis
    # cannot cleanly head-shard (parallel/sharding.py replicates it) —
    # Megatron-style tensor-parallel attention should set
    # fused_qkv=False.  Legacy separate-projection artifacts load via
    # ops.layers.migrate_separate_qkv (applied automatically on the
    # estimator load paths).
    fused_qkv: bool = True
    # Width of a head where it is not qkv_features / num_heads (the
    # heads' concatenation is then wider or narrower than the model).
    head_dim: int | None = None
    use_bias: bool = True
    rope_theta: float = 10000.0
    # Weight-only RMS norm of every head of q and k (over head_dim,
    # statistics in float32) before the rotation.
    qk_norm: bool = False
    qk_norm_eps: float = 1e-6
    # Attention in blocks of this many positions: position i sees j iff
    # block(j) <= block(i) (causal over blocks, full inside one).  In
    # decode mode a chunk of t positions is then one block: all of its
    # queries see every slot up to the chunk's last.  None is plain
    # causal, also inside a decode chunk.
    block: int | None = None
    param_dtype: jnp.dtype = jnp.float32
    # A factor on every key after its projection (and norm), before the
    # rotation and the cache: a model that scales its keys (a muP
    # multiplier).  1 adds nothing to the program.
    key_scale: float = 1.0

    @nn.compact
    def __call__(self, x, key_mask=None):
        b, t, _ = x.shape
        head_dim = self.head_dim
        if head_dim is None:
            head_dim = self.qkv_features // self.num_heads
            if head_dim * self.num_heads != self.qkv_features:
                raise ValueError(
                    "qkv_features must be divisible by num_heads"
                )
        inner = self.num_heads * head_dim
        kv_heads = self.num_heads if self.num_kv_heads is None \
            else self.num_kv_heads
        if kv_heads < 1:
            raise ValueError(f"num_kv_heads must be >= 1, got {kv_heads}")
        if self.num_heads % kv_heads:
            raise ValueError(
                f"num_heads={self.num_heads} not divisible by "
                f"num_kv_heads={kv_heads}"
            )

        if self.fused_qkv:
            qkv = nn.DenseGeneral(
                (self.num_heads + 2 * kv_heads, head_dim),
                dtype=self.dtype, name="qkv", use_bias=self.use_bias,
                param_dtype=self.param_dtype,
            )(x).transpose(0, 2, 1, 3)  # (B, H+2H_kv, T, hd)
            q = qkv[:, : self.num_heads]
            k = qkv[:, self.num_heads: self.num_heads + kv_heads]
            v = qkv[:, self.num_heads + kv_heads:]
        else:
            def proj(name, heads):
                y = nn.DenseGeneral(
                    (heads, head_dim), dtype=self.dtype, name=name,
                    use_bias=self.use_bias, param_dtype=self.param_dtype,
                )(x)
                return y.transpose(0, 2, 1, 3)  # (B, heads, T, hd)

            q = proj("query", self.num_heads)
            k = proj("key", kv_heads)
            v = proj("value", kv_heads)
        if self.qk_norm:
            q = RMSNorm(self.qk_norm_eps, param_dtype=self.param_dtype,
                        name="q_norm")(q)
            k = RMSNorm(self.qk_norm_eps, param_dtype=self.param_dtype,
                        name="k_norm")(k)
        if self.key_scale != 1.0:
            k = (k.astype(jnp.float32) * self.key_scale).astype(k.dtype)
        is_initialized = self.decode and self.has_variable(
            "cache", "cached_key"
        )
        if self.rope and not is_initialized:
            pos = jnp.arange(t)
            q = apply_rope(q, pos, self.rope_theta)
            k = apply_rope(k, pos, self.rope_theta)

        def out_proj(out):
            out = out.transpose(0, 2, 1, 3).reshape(b, t, inner)
            return nn.DenseGeneral(
                self.qkv_features, dtype=self.dtype, name="out",
                use_bias=self.use_bias, param_dtype=self.param_dtype,
            )(out)

        def widen(kv):
            # Broadcast each KV head to its query-head group.  The
            # repeat happens AFTER caching, so the cache (and its HBM
            # traffic) stays at kv_heads.
            if kv_heads == self.num_heads:
                return kv
            return jnp.repeat(kv, self.num_heads // kv_heads, axis=1)

        if self.decode:
            # Flax decode convention: the variables are declared once;
            # an uninitialized pass (module.init / eval_shape on the
            # FULL-length input) merely sizes them and falls through to
            # the normal forward below.
            # The K/V pages: ``page_pack`` positions a row of 128
            # lanes where a head is narrower (ops/decode_attention.py),
            # the same bytes row-major as (B, H_kv, T, hd).
            pages = page_shape(b, kv_heads, t, head_dim)
            ck = self.variable("cache", "cached_key", jnp.zeros,
                               pages, k.dtype)
            cv = self.variable("cache", "cached_value", jnp.zeros,
                               pages, v.dtype)
            ci = self.variable(
                "cache", "cache_index",
                lambda: jnp.zeros((), jnp.int32),
            )
            if is_initialized:
                # A (B,)-shaped cache_index means each batch row sits
                # at its OWN position — the continuous-batching engine
                # steps a mixed pool of sequences with one executable.
                # A scalar index keeps the classic lockstep semantics.
                idx = ci.value
                batched_idx = idx.ndim == 1
                if self.rope:
                    # Rotate at the CURRENT position before caching —
                    # the cache holds rotated keys, so lookups need no
                    # re-rotation.
                    pos1 = (idx[:, None] if batched_idx
                            else jnp.full((1,), idx)) + jnp.arange(t)
                    q = apply_rope(q, pos1, self.rope_theta)
                    k = apply_rope(k, pos1, self.rope_theta)
                if t != 1 and not batched_idx:
                    raise ValueError(
                        "a scalar cache_index feeds ONE position per "
                        f"step; got a {t}-token chunk (chunks need the "
                        "per-row index of the page pools)"
                    )
                pack = ck.value.shape[3] // head_dim
                tk_cache = ck.value.shape[2] * pack
                ci.value = idx + t
                # Causality is enforced HERE — the layer owns
                # cache_index, so it ANDs a validity mask (slots beyond
                # the just-written position are zero-initialized cache,
                # not real keys) into whatever key_mask the caller
                # passed, including none at all.  Flash brings nothing
                # for T_q == 1 queries.  The sliding window is likewise
                # the layer's invariant, not each decode loop's.
                slot = jnp.arange(tk_cache)[None, :]
                bound = idx[:, None] if batched_idx else idx
                if t == 1:
                    valid = slot <= bound
                    if self.window is not None:
                        valid = valid & (slot > (bound - self.window))
                else:
                    # (B, t, Tk): the in-chunk mask.  A block's queries
                    # all see up to the chunk's last slot; a causal
                    # chunk's query i sees up to its own.
                    last = bound[:, :, None] + (
                        t - 1 if self.block is not None
                        else jnp.arange(t)[None, :, None]
                    )
                    valid = slot[:, None, :] <= last
                    if self.window is not None:
                        valid = valid & (
                            slot[:, None, :] > (last - self.window)
                        )
                    if key_mask is not None:
                        key_mask = key_mask[:, None, :]
                key_mask = valid if key_mask is None else (
                    key_mask & valid
                )
                with jax.named_scope("block_attend"):
                    if batched_idx:
                        # Row r's t rows land at idx[r] .. idx[r]+t-1
                        # (one beyond the bucket is dropped) and the
                        # queries attend over the pages with them in:
                        # one pass, a kernel on the TPU.
                        out, ck.value, cv.value = cached_attend(
                            q, k, v, ck.value, cv.value, idx, key_mask
                        )
                    else:
                        k_all = jax.lax.dynamic_update_slice(
                            unpack_pages(ck.value, head_dim), k,
                            (0, 0, idx, 0),
                        )
                        v_all = jax.lax.dynamic_update_slice(
                            unpack_pages(cv.value, head_dim), v,
                            (0, 0, idx, 0),
                        )
                        ck.value = pack_pages(k_all, pack)
                        cv.value = pack_pages(v_all, pack)
                        out = grouped_decode_attend(
                            q, k_all, v_all, key_mask
                        )
                return out_proj(out)

        if self.block is not None:
            # The flash kernel has no block mask: the full forward of a
            # block model is the plain path (serving runs the cache).
            out = mha_reference(
                q, widen(k), widen(v), key_mask, block=self.block
            )
            return out_proj(out)
        use_flash = self.use_flash
        if use_flash is None:
            use_flash = jax.default_backend() == "tpu"
        attend = flash_attention if use_flash else mha_reference
        out = attend(
            q, widen(k), widen(v), key_mask,
            causal=self.causal, window=self.window,
        )  # (B,H,T,hd)
        return out_proj(out)


def migrate_separate_qkv(tree):
    """Convert a legacy separate-projection parameter tree
    (query/key/value DenseGeneral triplets) to the fused ``qkv``
    layout — the exact block-stack the fused layer computes, so
    outputs are bit-identical.  Non-matching subtrees pass through;
    the estimator load paths apply this automatically when they see
    the legacy pattern."""
    import numpy as np

    def _is_proj(node):
        return isinstance(node, dict) and "kernel" in node

    def walk(node):
        if not isinstance(node, dict):
            return node
        if (
            {"query", "key", "value"} <= set(node)
            and all(_is_proj(node[k]) for k in ("query", "key", "value"))
        ):
            node = dict(node)
            q = node.pop("query")
            k = node.pop("key")
            v = node.pop("value")
            node["qkv"] = {
                "kernel": np.concatenate(
                    [np.asarray(q["kernel"]), np.asarray(k["kernel"]),
                     np.asarray(v["kernel"])], axis=1,
                ),
                "bias": np.concatenate(
                    [np.asarray(q["bias"]), np.asarray(k["bias"]),
                     np.asarray(v["bias"])], axis=0,
                ),
            }
        return {kk: walk(vv) for kk, vv in node.items()}

    return walk(tree)


def has_separate_qkv(tree) -> bool:
    """True when the tree holds legacy query/key/value triplets."""
    found = {"hit": False}

    def walk(node):
        if not isinstance(node, dict) or found["hit"]:
            return
        if {"query", "key", "value"} <= set(node):
            found["hit"] = True
            return
        for v in node.values():
            walk(v)

    walk(tree)
    return found["hit"]
