"""Plain reference of ``models/moe.py`` ``LatentMoELM`` for the tests:
``jax.numpy`` in float32 at ``highest`` precision, the NON-absorbed
attention (every head's ``k_nope`` and ``v`` made from the latent), the
experts one by one with the gate as a multiplier, no cache.  It reads
the estimator's parameter tree and nothing else of the program."""

import math

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


def yarn_inv_freq(dim, theta, sc):
    """The closed form, pair by pair, in python floats."""
    factor = sc["factor"]
    orig = sc["original_max_position_embeddings"]

    def pair_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(sc["beta_fast"])), 0)
    high = min(math.ceil(pair_of(sc["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        plain = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(plain / factor * ramp + plain * (1 - ramp))
    return np.array(out, np.float32)


def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope(x, pos, inv_freq):
    """Rotate-half on (..., T, hd) at positions ``pos`` (T,)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * inv_freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
         x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1,
    )


def attention(est, w, x, key_mask):
    """(T, H) -> (T, H): per-head keys and values, causal softmax."""
    heads, rank = est.num_heads, est.kv_lora_rank
    nope, rp, vd = (est.qk_nope_head_dim, est.qk_rope_head_dim,
                    est.v_head_dim)
    sc = est.rope_scaling or {"factor": 1.0}
    inv_freq = yarn_inv_freq(rp, est.rope_theta, sc) \
        if sc["factor"] > 1 else est.rope_theta ** (
            -np.arange(0, rp, 2, dtype=np.float32) / rp)
    m = mscale(sc["factor"], sc.get("mscale_all_dim", 0))
    scale = (nope + rp) ** -0.5 * m * m
    t = x.shape[0]
    pos = jnp.arange(t)
    c_q = rms_norm(dot(x, _f32(w["q_a"]["kernel"])),
                   _f32(w["q_a_norm"]["scale"]), est.norm_eps)
    q = dot(c_q, _f32(w["q_b"]["kernel"]).reshape(c_q.shape[-1], -1)) \
        .reshape(t, heads, nope + rp).transpose(1, 0, 2)  # (H, T, .)
    kv = dot(x, _f32(w["kv_a"]["kernel"]))
    c_kv = rms_norm(kv[:, :rank], _f32(w["kv_a_norm"]["scale"]),
                    est.norm_eps)
    k_pe = rope(kv[:, rank:], pos, inv_freq)  # (T, rp), all heads'
    q_pe = rope(q[..., nope:], pos, inv_freq)
    kvh = dot(c_kv, _f32(w["kv_b"]).reshape(rank, -1)) \
        .reshape(t, heads, nope + vd).transpose(1, 0, 2)
    k_nope, v = kvh[..., :nope], kvh[..., nope:]
    s = (jnp.einsum("hqn,hkn->hqk", q[..., :nope], k_nope, precision=HI)
         + jnp.einsum("hqr,kr->hqk", q_pe, k_pe, precision=HI)) * scale
    keep = jnp.tril(jnp.ones((t, t), bool)) & key_mask[None, :]
    s = jnp.where(keep[None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    p = jnp.where(jnp.any(keep, -1)[None, :, None], p, 0.0)
    o = jnp.einsum("hqk,hkv->qhv", p, v, precision=HI)
    return dot(o.reshape(t, heads * vd),
               _f32(w["out"]["kernel"]).reshape(heads * vd, -1))


def swiglu(x, w):
    return dot(jax.nn.silu(dot(x, _f32(w["gate"]["kernel"])))
               * dot(x, _f32(w["up"]["kernel"])),
               _f32(w["down"]["kernel"]))


def route(x, w, top_k, scale):
    """(gates (N, k), ids (N, k)): sigmoid scores, chosen by score +
    bias, weighed by the score over the chosen's sum, times scale."""
    s = jax.nn.sigmoid(dot(x, _f32(w["router"])))
    _, ids = jax.lax.top_k(s + _f32(w["score_bias"]), top_k)
    chosen = jnp.take_along_axis(s, ids, -1)
    return scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20), ids


def routed(x, w, top_k, scale, held=None):
    """Rows (N, H) through the experts ``held`` = (first, count) of the
    tree ``w`` (which holds just those), the gate a multiplier."""
    gates, ids = route(x, w, top_k, scale)
    first, count = held or (0, w["w_gate"].shape[0])
    out = jnp.zeros_like(x)
    for e in range(count):
        gate = jnp.where(ids == first + e, gates, 0.0).sum(-1)
        hid = jax.nn.silu(dot(x, _f32(w["w_gate"][e]))) \
            * dot(x, _f32(w["w_up"][e]))
        out = out + gate[:, None] * dot(hid, _f32(w["w_down"][e]))
    return out


def forward(est, tokens):
    """(B, T) int tokens -> (B, T, V) float32 logits."""
    params = est.params["params"]
    tokens = np.asarray(tokens)
    rows = []
    for row in tokens:
        x = _f32(params["Embed_0"]["embedding"])[row]
        key_mask = jnp.asarray(row != 0)
        for i in range(est.num_layers):
            w = params[f"LatentExpertBlock_{i}"]
            x = x + attention(
                est, w["LatentAttention_0"],
                rms_norm(x, _f32(w["attn_norm"]["scale"]), est.norm_eps),
                key_mask,
            )
            y = rms_norm(x, _f32(w["ffn_norm"]["scale"]), est.norm_eps)
            if i < est.first_dense_layers:
                x = x + swiglu(y, w["GatedMlp_0"])
            else:
                x = x + routed(
                    y, w["RoutedExperts_0"], est.experts_per_token,
                    est.routed_scale, est.experts_held,
                ) + swiglu(y, w["shared_expert"])
        x = rms_norm(x, _f32(params["final_norm"]["scale"]), est.norm_eps)
        rows.append(dot(x, _f32(params["head"]["kernel"])))
    return jnp.stack(rows)


def generate(est, prompt, max_new: int):
    """Greedy continuation by full forwards: the tokens a cache-free
    decode gives."""
    row = list(prompt)
    for _ in range(max_new):
        logits = forward(est, np.array([row], np.int32))[0, -1]
        row.append(int(jnp.argmax(logits)))
    return row
