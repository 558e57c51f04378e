"""Plain ``jax.numpy`` building blocks of the references: float32,
``highest`` matmul precision, no kernels, no cache, no batching tricks.
Nothing here imports the program.

``quant`` puts the reference in the control's place: ``None`` is the
reference itself; ``"int8"`` rounds both operands of every matmul to
127 levels per tensor, the nearest precision below the bfloat16
multiplications that both configurations state.  The dense matmuls
round the operands of their two backward matmuls as well (a path that
multiplied in int8 would); the two attention products round going
forward and pass gradients straight through.  ``"bf16"`` rounds the
same operands to bfloat16 instead: the precision both configurations
state, so no control but a second witness of what rounding alone does
to a number (``benchmarks/controls.py --witness``)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-6  # flax's LayerNorm default, which the program's blocks use
HI = jax.lax.Precision.HIGHEST


def fake_int8(a):
    scale = jnp.max(jnp.abs(a)) / 127.0 + 1e-30
    rounded = jnp.round(a / scale) * scale
    return a + jax.lax.stop_gradient(rounded - a)


def operand(a, quant):
    if quant is None:
        return a
    if quant == "int8":
        return fake_int8(a)
    if quant == "bf16":
        # reduce_precision and not a cast there and back, which XLA
        # drops (xla_allow_excess_precision) and the TPU did
        rounded = jax.lax.reduce_precision(a, exponent_bits=8,
                                           mantissa_bits=7)
        return a + jax.lax.stop_gradient(rounded - a)
    raise ValueError(f"unknown control precision {quant!r}")


def dot(x, w, quant=None):
    """``x`` (..., i) times ``w`` (i, o)."""
    if quant is None:
        return jnp.matmul(x, w, precision=HI)
    return _low_dot(x, w, quant)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _low_dot(x, w, quant):
    return jnp.matmul(operand(x, quant), operand(w, quant), precision=HI)


def _low_dot_fwd(x, w, quant):
    return _low_dot(x, w, quant), (x, w)


def _low_dot_bwd(quant, saved, dy):
    x, w = (operand(a, quant) for a in saved)
    dy = operand(dy, quant)
    dx = jnp.matmul(dy, w.T, precision=HI)
    dw = jnp.einsum("...i,...o->io", x, dy, precision=HI)
    return dx, dw


_low_dot.defvjp(_low_dot_fwd, _low_dot_bwd)


def layer_norm(x, scale, bias):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)
    ))


def attention(x, w, num_heads, key_mask, causal, quant=None):
    """Multi-head self-attention over (B, T, H); ``key_mask`` (B, T)
    marks the keys that may be seen (pad id 0 is never seen)."""
    b, t, h = x.shape
    hd = h // num_heads
    qkv = dot(x, w["qkv_w"], quant) + w["qkv_b"]

    def heads(a):
        return a.reshape(b, t, num_heads, hd).transpose(0, 2, 1, 3)

    q, k, v = (heads(a) for a in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", operand(q, quant), operand(k, quant),
        precision=HI,
    ) / jnp.sqrt(jnp.float32(hd))
    allowed = key_mask[:, None, None, :]
    if causal:
        allowed = allowed & jnp.tril(jnp.ones((t, t), bool))[None, None]
    s = jnp.where(allowed, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # A query that may see no key at all outputs exactly 0, as the
    # program's masked softmax does.
    p = jnp.where(jnp.any(allowed, -1, keepdims=True), p, 0.0)
    o = jnp.einsum(
        "bhqk,bhkd->bhqd", operand(p, quant), operand(v, quant),
        precision=HI,
    )
    o = o.transpose(0, 2, 1, 3).reshape(b, t, h)
    return dot(o, w["out_w"], quant) + w["out_b"]


def block(x, w, num_heads, key_mask, causal, quant=None):
    """The program's pre-LN transformer block (``models/text.py``)."""
    y = layer_norm(x, w["ln1_s"], w["ln1_b"])
    x = x + attention(y, w, num_heads, key_mask, causal, quant)
    y = layer_norm(x, w["ln2_s"], w["ln2_b"])
    y = gelu_tanh(dot(y, w["fc1_w"], quant) + w["fc1_b"])
    return x + dot(y, w["fc2_w"], quant) + w["fc2_b"]


def block_leaves(h: int, m: int) -> list:
    """One block's leaves, in the order every configuration lists them."""
    return [
        ("ln1_s", (h,), "ones"), ("ln1_b", (h,), "zeros"),
        ("qkv_w", (h, 3 * h), "normal"), ("qkv_b", (3 * h,), "zeros"),
        ("out_w", (h, h), "normal"), ("out_b", (h,), "zeros"),
        ("ln2_s", (h,), "ones"), ("ln2_b", (h,), "zeros"),
        ("fc1_w", (h, m), "normal"), ("fc1_b", (m,), "zeros"),
        ("fc2_w", (m, h), "normal"), ("fc2_b", (h,), "zeros"),
    ]


def block_program_tree(w: dict, num_heads: int) -> dict:
    """One block's leaves under the names and shapes the program's flax
    ``TransformerBlock`` holds them (fused qkv: heads q, then k, then v)."""
    h = w["out_w"].shape[0]
    hd = h // num_heads
    return {
        "LayerNorm_0": {"scale": w["ln1_s"], "bias": w["ln1_b"]},
        "MultiHeadSelfAttention_0": {
            "qkv": {
                "kernel": w["qkv_w"].reshape(h, 3 * num_heads, hd),
                "bias": w["qkv_b"].reshape(3 * num_heads, hd),
            },
            "out": {"kernel": w["out_w"], "bias": w["out_b"]},
        },
        "LayerNorm_1": {"scale": w["ln2_s"], "bias": w["ln2_b"]},
        "Dense_0": {"kernel": w["fc1_w"], "bias": w["fc1_b"]},
        "Dense_1": {"kernel": w["fc2_w"], "bias": w["fc2_b"]},
    }


def block_from_program(tree: dict) -> dict:
    """The inverse of :func:`block_program_tree`."""
    attn = tree["MultiHeadSelfAttention_0"]
    h = attn["out"]["kernel"].shape[0]
    return {
        "ln1_s": tree["LayerNorm_0"]["scale"],
        "ln1_b": tree["LayerNorm_0"]["bias"],
        "qkv_w": attn["qkv"]["kernel"].reshape(h, 3 * h),
        "qkv_b": attn["qkv"]["bias"].reshape(3 * h),
        "out_w": attn["out"]["kernel"], "out_b": attn["out"]["bias"],
        "ln2_s": tree["LayerNorm_1"]["scale"],
        "ln2_b": tree["LayerNorm_1"]["bias"],
        "fc1_w": tree["Dense_0"]["kernel"], "fc1_b": tree["Dense_0"]["bias"],
        "fc2_w": tree["Dense_1"]["kernel"], "fc2_b": tree["Dense_1"]["bias"],
    }


def split_qkv(flat: dict) -> dict:
    """Leaves as they are compared: the fused projection apart into q, k
    and v, so that the key's bias (whose gradient is nought under
    softmax) is a leaf of its own and not a third of one."""
    out = {}
    for name, a in flat.items():
        if name.endswith(("qkv_w", "qkv_b")):
            stem, kind = name[:-5], name[-1]
            for part, piece in zip("qkv", jnp.split(jnp.asarray(a), 3, -1)):
                out[f"{stem}{part}_{kind}"] = piece
        else:
            out[name] = jnp.asarray(a)
    return out
