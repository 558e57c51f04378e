"""Plain reference of the ``kimi-k2.6`` configuration (what the
published model states, and what is assumed, is in the .json beside
this file): the DeepSeek-V3 block as Kimi-K2's ``config.json``
instantiates it.  Token embedding; per layer ``h = x + MLA(RMSNorm(x))``,
``y = h + FFN(RMSNorm(h))``; final RMSNorm; an untied head without a
bias over the vocabulary rows held.

Attention, NOT absorbed: ``c_q = RMSNorm(x W_qa)``, a head's query
``[q_nope | q_pe] = c_q W_qb``; ``[c_kv | k_pe] = x W_kva``, ``c_kv =
RMSNorm(c_kv)``, ONE rotary key ``k_pe`` for all heads; every head's
``[k_nope | v] = c_kv W_kvb`` made whole; score ``(q_nope . k_nope +
RoPE(q_pe) . RoPE(k_pe)) * s`` with ``s = 192^-1/2 m^2``, ``m = 0.1
mscale_all_dim ln(factor) + 1``; causal softmax; ``o = concat_h(p v)
W_o``.  RoPE rotates halves ((x_i, x_{i+32}) a pair) by YaRN's
frequencies: ``theta^(-2i/64)`` blended with the same over ``factor`` by
the linear ramp between the pairs that turn ``beta_fast`` and
``beta_slow`` times in the original 4,096 positions, at every position.

FFN of layer 0: SwiGLU of width 18,432.  Of the others: ``s_e =
sigmoid(x W_r)_e`` over all 384 experts; the 8 with the largest ``s_e +
b_e``; weight ``2.827 s_e / (sum of the chosen s + 1e-20)``; the sum
over the chosen experts HELD here (12: a ``scan`` over them with the
gate as a multiplier) of their SwiGLUs of width 2,048, plus the shared
expert's.  What the 372 absent experts would add is left out.

float32 at ``highest`` precision, no cache, no kernel; one row of 2,048
positions at a time, layer by layer, the weights made here from the
seed leaf by leaf (the float32 draws of ``lobench/weights.py`` rounded
to bfloat16 with ``reduce_precision``).  It imports nothing of the
program."""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from lobench import plain, weights

HI = jax.lax.Precision.HIGHEST
#: The router's correction bias is its leaf (normal 0.02, rounded to
#: bfloat16) times this power of two (exact in bfloat16): std 0.0025,
#: about the distance between the 8th and 9th of 384 sigmoid scores, so
#: it changes the experts of a good share of the tokens without
#: deciding every choice alone.
BIAS_SCALE = 0.125


def _attention_leaves(cp: dict) -> list:
    h, heads = cp["hidden_dim"], cp["num_heads"]
    ql, rank = cp["q_lora_rank"], cp["kv_lora_rank"]
    nope, rope, vd = (cp["qk_nope_head_dim"], cp["qk_rope_head_dim"],
                      cp["v_head_dim"])
    return [
        ("attn_norm", (h,), "ones"),
        ("q_a", (h, ql), "normal"), ("q_a_norm", (ql,), "ones"),
        ("q_b", (ql, heads, nope + rope), "normal"),
        ("kv_a", (h, rank + rope), "normal"),
        ("kv_a_norm", (rank,), "ones"),
        ("kv_b", (rank, heads, nope + vd), "normal"),
        ("out_w", (heads, vd, h), "normal"),
        ("ffn_norm", (h,), "ones"),
    ]


def _layer_leaves(cp: dict, routed: bool) -> list:
    h = cp["hidden_dim"]
    if not routed:
        m = cp["mlp_dim"]
        return _attention_leaves(cp) + [
            ("gate", (h, m), "normal"), ("up", (h, m), "normal"),
            ("down", (m, h), "normal"),
        ]
    m, e = cp["expert_dim"], cp["num_experts"]
    held = cp["experts_held"][1]
    sm = cp["shared_experts"] * m
    return _attention_leaves(cp) + [
        ("router", (h, e), "normal"),
        ("score_bias", (e,), "normal"),  # times BIAS_SCALE where used
        ("w_gate", (held, h, m), "normal"), ("w_up", (held, h, m), "normal"),
        ("w_down", (held, m, h), "normal"),
        ("sh_gate", (h, sm), "normal"), ("sh_up", (h, sm), "normal"),
        ("sh_down", (sm, h), "normal"),
    ]


def leaves(cp: dict) -> list:
    h, v = cp["hidden_dim"], cp["vocab_size"]
    out = [("tok_emb", (v, h), "normal")]
    for layer in range(cp["num_layers"]):
        routed = layer >= cp["first_dense_layers"]
        out += [(f"l{layer}.{n}", s, i)
                for n, s, i in _layer_leaves(cp, routed)]
    return out + [("final_norm", (h,), "ones"), ("head_w", (h, v), "normal")]


def program_params(flat: dict, cp: dict) -> dict:
    """The flat leaves as the program's flax variables."""
    tree = {
        "Embed_0": {"embedding": flat["tok_emb"]},
        "final_norm": {"scale": flat["final_norm"]},
        "head": {"kernel": flat["head_w"]},
    }
    for layer in range(cp["num_layers"]):
        w = {k.split(".", 1)[1]: a for k, a in flat.items()
             if k.startswith(f"l{layer}.")}
        block = {
            "attn_norm": {"scale": w["attn_norm"]},
            "LatentAttention_0": {
                "q_a": {"kernel": w["q_a"]},
                "q_a_norm": {"scale": w["q_a_norm"]},
                "q_b": {"kernel": w["q_b"]},
                "kv_a": {"kernel": w["kv_a"]},
                "kv_a_norm": {"scale": w["kv_a_norm"]},
                "kv_b": w["kv_b"],
                "out": {"kernel": w["out_w"]},
            },
            "ffn_norm": {"scale": w["ffn_norm"]},
        }
        if "router" in w:
            block["RoutedExperts_0"] = {
                k: w[k] for k in ("router", "w_gate", "w_up", "w_down")
            }
            block["RoutedExperts_0"]["score_bias"] = \
                w["score_bias"] * w["score_bias"].dtype.type(BIAS_SCALE)
            block["shared_expert"] = {
                k: {"kernel": w[f"sh_{k}"]} for k in ("gate", "up", "down")
            }
        else:
            block["GatedMlp_0"] = {
                k: {"kernel": w[k]} for k in ("gate", "up", "down")
            }
        tree[f"LatentExpertBlock_{layer}"] = block
    return {"params": tree}


def leaf(key, index, shape, init: str):
    """Leaf ``index`` in float32, its values those of bfloat16."""
    return jax.lax.reduce_precision(
        weights.leaf(key, index, shape, init), exponent_bits=8,
        mantissa_bits=7,
    )


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps
    ) * scale


def yarn_inv_freq(dim: int, theta: float, sc: dict):
    """``dim / 2`` frequencies: pair i keeps ``theta^(-2i/dim)`` below
    the pair that turns ``beta_fast`` times in the original context,
    takes it over ``factor`` above the pair that turns ``beta_slow``
    times, and the linear blend between."""
    def pair_of(turns):
        return dim * math.log(
            sc["original_max_position_embeddings"] / (turns * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(pair_of(sc["beta_fast"])), 0)
    high = min(math.ceil(pair_of(sc["beta_slow"])), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    keep = theta ** (-2.0 * i / dim)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return keep / sc["factor"] * ramp + keep * (1.0 - ramp)


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope(x, inv_freq, scale: float):
    """Rotate-half of (T, ..., hd) rows at positions 0..T-1."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), half)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, w, cp, key_mask, quant):
    """One row (T, H), causal, keys of pad id 0 never seen."""
    heads, rank = cp["num_heads"], cp["kv_lora_rank"]
    nope, rp, vd = (cp["qk_nope_head_dim"], cp["qk_rope_head_dim"],
                    cp["v_head_dim"])
    sc = cp["rope_scaling"]
    inv_freq = yarn_inv_freq(rp, cp["rope_theta"], sc)
    m_all = yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    rot = yarn_mscale(sc["factor"], sc["mscale"]) / m_all
    scale = (nope + rp) ** -0.5 * m_all * m_all
    t = x.shape[0]
    c_q = rms_norm(plain.dot(x, w["q_a"], quant), w["q_a_norm"],
                   cp["norm_eps"])
    q = plain.dot(c_q, w["q_b"].reshape(w["q_b"].shape[0], -1), quant) \
        .reshape(t, heads, nope + rp)
    kv = plain.dot(x, w["kv_a"], quant)
    c_kv = rms_norm(kv[:, :rank], w["kv_a_norm"], cp["norm_eps"])
    k_pe = rope(kv[:, rank:], inv_freq, rot)  # (T, rp): all heads' key
    q_pe = rope(q[..., nope:], inv_freq, rot)
    kvh = plain.dot(c_kv, w["kv_b"].reshape(rank, -1), quant) \
        .reshape(t, heads, nope + vd)
    k = jnp.concatenate([
        kvh[..., :nope], jnp.broadcast_to(k_pe[:, None], (t, heads, rp)),
    ], -1)
    qf = jnp.concatenate([q[..., :nope], q_pe], -1)
    s = jnp.einsum(
        "qnd,knd->nqk", plain.operand(qf, quant), plain.operand(k, quant),
        precision=HI,
    ) * scale
    keep = jnp.tril(jnp.ones((t, t), bool)) & key_mask[None, :]
    s = jnp.where(keep[None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    p = jnp.where(jnp.any(keep, -1)[None, :, None], p, 0.0)
    o = jnp.einsum(
        "nqk,knd->qnd", plain.operand(p, quant),
        plain.operand(kvh[..., nope:], quant), precision=HI,
    )
    return plain.dot(o.reshape(t, heads * vd),
                     w["out_w"].reshape(heads * vd, -1), quant)


def swiglu(x, gate, up, down, quant):
    return plain.dot(
        jax.nn.silu(plain.dot(x, gate, quant)) * plain.dot(x, up, quant),
        down, quant,
    )


def experts(x, w, cp, quant):
    """Rows (N, H): the held experts' share of the routed sum, the gate
    a multiplier, plus the shared expert."""
    first, _count = cp["experts_held"]
    s = jax.nn.sigmoid(plain.dot(x, w["router"], quant))
    _, ids = jax.lax.top_k(
        s + BIAS_SCALE * w["score_bias"], cp["experts_per_token"]
    )
    chosen = jnp.take_along_axis(s, ids, -1)
    gates = cp["routed_scale"] * chosen / (
        jnp.sum(chosen, -1, keepdims=True) + 1e-20
    )

    def one(acc, packed):
        e, w_gate, w_up, w_down = packed
        gate = jnp.sum(jnp.where(ids == first + e, gates, 0.0), -1)
        return acc + gate[:, None] * swiglu(x, w_gate, w_up, w_down,
                                            quant), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (jnp.arange(w["w_gate"].shape[0]), w["w_gate"], w["w_up"],
         w["w_down"]),
    )
    return out + swiglu(x, w["sh_gate"], w["sh_up"], w["sh_down"], quant)


def _cp(cp_json: str) -> dict:
    return json.loads(cp_json)


@functools.partial(jax.jit, static_argnames=("cp_json",))
def _embed(key, tokens, *, cp_json):
    cp = _cp(cp_json)
    return leaf(key, 0, (cp["vocab_size"], cp["hidden_dim"]),
                "normal")[tokens]


@functools.partial(jax.jit, static_argnames=("cp_json", "routed", "quant"))
def _layer(key, base, x, key_mask, *, cp_json, routed, quant):
    """One layer over rows ``x`` (R, T, H), a row at a time, its
    weights made here from the seed (``base``: its first leaf's index,
    traced, so one program serves every routed layer)."""
    cp = _cp(cp_json)
    spec = _layer_leaves(cp, routed)
    w = {name: leaf(key, base + j, shape, init)
         for j, (name, shape, init) in enumerate(spec)}

    def row(args):
        xr, mask = args
        h = xr + attention(
            rms_norm(xr, w["attn_norm"], cp["norm_eps"]), w, cp, mask,
            quant,
        )
        y = rms_norm(h, w["ffn_norm"], cp["norm_eps"])
        if routed:
            return h + experts(y, w, cp, quant)
        return h + swiglu(y, w["gate"], w["up"], w["down"], quant)

    return jax.lax.map(row, (x, key_mask))


@functools.partial(jax.jit, static_argnames=("cp_json", "quant"))
def _head(key, x, *, cp_json, quant):
    cp = _cp(cp_json)
    n = len(leaves(cp))
    norm = leaf(key, n - 2, (cp["hidden_dim"],), "ones")
    head = leaf(key, n - 1, (cp["hidden_dim"], cp["vocab_size"]), "normal")
    return jax.lax.map(
        lambda xr: plain.dot(rms_norm(xr, norm, cp["norm_eps"]), head,
                             quant), x,
    )


def reference_logits(seed: int, cp: dict, tokens, quant=None):
    """(R, T, V) logits of one full forward over ``tokens`` (R, T),
    zero-padded rows allowed (pad keys are masked, and no query that
    matters sits on a pad)."""
    key = weights.key_for(seed)
    cp_json = json.dumps(cp, sort_keys=True)
    tokens = jnp.asarray(tokens, jnp.int32)
    key_mask = tokens != 0
    x = _embed(key, tokens, cp_json=cp_json)
    base = 1
    for layer in range(cp["num_layers"]):
        routed = layer >= cp["first_dense_layers"]
        x = _layer(key, jnp.int32(base), x, key_mask, cp_json=cp_json,
                   routed=routed, quant=quant)
        base += len(_layer_leaves(cp, routed))
    return _head(key, x, cp_json=cp_json, quant=quant)
