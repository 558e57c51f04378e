"""Plain reference of ``models/hybrid.py`` ``HybridLM`` for the tests:
``jax.numpy`` in float32 at ``highest`` precision, one row at a time.
The Mamba-2 mixer as its SEQUENTIAL recurrence over positions (no chunk
form, no kernel, no cache), attention as a masked softmax.  It reads the
estimator's parameter tree and nothing else of the program."""

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _f32(a):
    return jnp.asarray(np.asarray(a, np.float32))


def dot(x, w):
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps
    ) * scale


def rope(x, theta):
    """Rotate-half on (T, heads, hd) at positions 0..T-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
         x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1,
    )


def attention(est, w, x, key_mask):
    """(T, H) -> (T, H): causal GQA softmax, keys scaled before the
    rotation, pad keys never seen."""
    heads, kvh, hd = est.num_heads, est.num_kv_heads, est.head_dim
    t, hidden = x.shape
    qkv = dot(x, _f32(w["qkv"]["kernel"]).reshape(hidden, -1)) \
        .reshape(t, heads + 2 * kvh, hd)
    q, k, v = qkv[:, :heads], qkv[:, heads:heads + kvh], qkv[:, heads + kvh:]
    q = rope(q, est.rope_theta)
    k = rope(k * est.key_multiplier, est.rope_theta)
    k, v = (jnp.repeat(a, heads // kvh, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(hd)
    keep = jnp.tril(jnp.ones((t, t), bool)) & key_mask[None, :]
    s = jnp.where(keep[None], s, -1e30)
    p = jnp.where(jnp.any(keep, -1)[None, :, None],
                  jax.nn.softmax(s, axis=-1), 0.0)
    y = jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
    return dot(y.reshape(t, heads * hd),
               _f32(w["out"]["kernel"]).reshape(heads * hd, -1))


def mamba(est, w, x, key_mask):
    """(T, H) -> (T, H): the in-projection with its multipliers, the
    causal convolution, then ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, ``y_t = S_t C_t + D x_t`` one position after the other; the
    gate and the grouped RMS norm; the out-projection.  A pad position
    is passed over: nothing of it enters the convolution or the state,
    and its step size is 0."""
    h, p, g, n = (est.mamba_heads, est.mamba_head_dim, est.mamba_groups,
                  est.mamba_state)
    t = x.shape[0]
    inner = h * p
    parts = (inner, inner, g * n, g * n, h)
    scale = np.concatenate([np.full(width, m, np.float32) for width, m
                            in zip(parts, est.ssm_multipliers)])
    zxbcdt = dot(x * est.ssm_in_multiplier,
                 _f32(w["in_proj"]["kernel"])) * scale
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * n], axis=-1)
    seen = key_mask.astype(jnp.float32)[:, None]
    kernel, bias = _f32(w["conv_kernel"]), _f32(w["conv_bias"])
    width = kernel.shape[0]
    padded = jnp.pad(xbc * seen, [(width - 1, 0), (0, 0)])
    conv = sum(padded[i:i + t] * kernel[i] for i in range(width)) + bias
    xbc = jax.nn.silu(conv) * seen
    xs, bs, cs = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    dt = jax.nn.softplus(dt + _f32(w["dt_bias"])) * seen
    a = -jnp.exp(_f32(w["A_log"]))
    d = _f32(w["D"])

    def step(state, row):
        xt, bt, ct, dtt = row
        xt = xt.reshape(h, p)
        bt, ct = (jnp.repeat(u.reshape(g, n), h // g, axis=0)
                  for u in (bt, ct))
        state = jnp.exp(dtt * a)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        y = jnp.einsum("hpn,hn->hp", state, ct, precision=HI) \
            + d[:, None] * xt
        return state, y.reshape(inner)

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                        (xs, bs, cs, dt))
    y = (y * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + est.norm_eps)
    y = y.reshape(t, inner) * _f32(w["norm_scale"])
    return dot(y, _f32(w["out_proj"]["kernel"]))


def forward(est, tokens):
    """(B, T) int tokens -> (B, T, V) float32 logits."""
    params = est.params["params"]
    gate_mult, down_mult = est.mlp_multipliers
    rows = []
    for row in np.asarray(tokens):
        x = _f32(params["Embed_0"]["embedding"])[row] \
            * est.embedding_multiplier
        key_mask = jnp.asarray(row != 0)
        for i in range(est.num_layers):
            w = params[f"HybridBlock_{i}"]
            h = rms_norm(x, _f32(w["input_norm"]["scale"]), est.norm_eps)
            x = x + est.ssm_out_multiplier * mamba(
                est, w["Mamba2Mixer_0"], h, key_mask,
            ) + est.attention_out_multiplier * attention(
                est, w["MultiHeadSelfAttention_0"],
                h * est.attention_in_multiplier, key_mask,
            )
            u = rms_norm(x, _f32(w["ffn_norm"]["scale"]), est.norm_eps)
            hid = dot(u, _f32(w["up"]["kernel"])) * jax.nn.silu(
                gate_mult * dot(u, _f32(w["gate"]["kernel"])))
            x = x + down_mult * dot(hid, _f32(w["down"]["kernel"]))
        x = rms_norm(x, _f32(params["final_norm"]["scale"]), est.norm_eps)
        rows.append(dot(x, _f32(params["head"])) * est.lm_head_multiplier)
    return jnp.stack(rows)


def served_gaps(est, prompt, served):
    """How far under the best logit each served token's lies, by ONE
    full forward over prompt + served."""
    row = np.array([list(prompt) + list(served)], np.int32)
    logits = np.asarray(forward(est, row)[0])
    at = np.arange(len(prompt) - 1, row.shape[1] - 1)
    return logits[at].max(-1) - logits[at, row[0, at + 1]]
