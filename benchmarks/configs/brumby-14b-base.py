"""Plain reference of the ``brumby-14b-base`` configuration (what the
published model states, and what is assumed, is in the .json beside
this file): the Qwen3 dense block with every attention layer a power
retention layer of degree 2 (Buckman, Gelada et al., "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239).  Token embedding;
per layer ``h = x + retention(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``
with the SwiGLU FFN of width 17,408; final RMSNorm; an untied head
without a bias over the whole vocabulary.

Retention, in the ATTENTION form only: ``q = RoPE(norm_q(x W_q))`` for
each of 40 heads of 128, ``k = RoPE(norm_k(x W_k))`` and ``v = x W_v``
for each of 8 (query head h reads key/value head h // 5); the norms
weight-only RMS norms over a head's 128, RoPE rotate-half with theta
1e6; ``log g = log sigmoid(x W_g + b_g)``, one value a key/value head.
For ``j <= i``, key ``j`` not the pad id: ``A_ij = (q_i . k_j /
sqrt(128))^2 exp(L_i - L_j)`` with ``L`` the prefix sums of ``log g``
over positions; ``y_i = sum_j A_ij v_j / (sum_j A_ij + 1e-6)``; the 40
heads' ``y`` concatenated, times ``W_o``.  No softmax and no maximum
subtracted; no state, no feature map, no cache: that the program is
recurrent and this quadratic is the point of the comparison.

float32 at ``highest`` precision, no kernel; one row of at most 1,024
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
#: The gate's bias is its leaf (normal 0.02) plus this, rounded to
#: bfloat16 like every leaf: ``g`` about 0.9975, half of a token's
#: weight left after some 280 positions.  At the leaf alone ``g`` would
#: be about 0.5, a memory of two tokens, and no comparison could see a
#: state lost, kept from the last request or decayed wrongly.
GATE_BIAS = 6.0
EPS = 1e-6  # the read-out's


def _layer_leaves(cp: dict) -> list:
    h, m = cp["hidden_dim"], cp["mlp_dim"]
    heads, kvh, hd = cp["num_heads"], cp["num_kv_heads"], cp["head_dim"]
    return [
        ("mixer_norm", (h,), "ones"),
        ("q_w", (h, heads, hd), "normal"), ("k_w", (h, kvh, hd), "normal"),
        ("v_w", (h, kvh, hd), "normal"),
        ("q_norm", (hd,), "ones"), ("k_norm", (hd,), "ones"),
        ("gate_w", (h, kvh), "normal"),
        ("gate_b", (kvh,), "normal"),  # plus GATE_BIAS where used
        ("out_w", (heads, hd, h), "normal"),
        ("ffn_norm", (h,), "ones"),
        ("gate", (h, m), "normal"), ("up", (h, m), "normal"),
        ("down", (m, h), "normal"),
    ]


def leaves(cp: dict) -> list:
    h, v = cp["hidden_dim"], cp["vocab_size"]
    out = [("tok_emb", (v, h), "normal")]
    for layer in range(cp["num_layers"]):
        out += [(f"l{layer}.{n}", s, i) for n, s, i in _layer_leaves(cp)]
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
        tree[f"RetentionBlock_{layer}"] = {
            "mixer_norm": {"scale": w["mixer_norm"]},
            "PowerRetention_0": {
                "query": {"kernel": w["q_w"]},
                "key": {"kernel": w["k_w"]},
                "value": {"kernel": w["v_w"]},
                "q_norm": {"scale": w["q_norm"]},
                "k_norm": {"scale": w["k_norm"]},
                "gate": {
                    "kernel": w["gate_w"],
                    "bias": w["gate_b"] + w["gate_b"].dtype.type(GATE_BIAS),
                },
                "out": {"kernel": w["out_w"]},
            },
            "ffn_norm": {"scale": w["ffn_norm"]},
            "GatedMlp_0": {
                k: {"kernel": w[k]} for k in ("gate", "up", "down")
            },
        }
    return {"params": tree}


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def leaf(key, index, shape, init: str):
    """Leaf ``index`` in float32, its values those of bfloat16."""
    return _bf16(weights.leaf(key, index, shape, init))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps
    ) * scale


def rope(x, theta: float):
    """Rotate-half of (T, heads, hd) rows at positions 0..T-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def retention(x, w, cp, key_mask, quant):
    """One row (T, H), causal, keys of pad id 0 never seen."""
    heads, kvh, hd = cp["num_heads"], cp["num_kv_heads"], cp["head_dim"]
    t, h = x.shape
    group = heads // kvh

    def proj(name, n):
        return plain.dot(x, w[name].reshape(h, n * hd), quant) \
            .reshape(t, n, hd)

    q = rope(rms_norm(proj("q_w", heads), w["q_norm"], cp["norm_eps"]),
             cp["rope_theta"])
    k = rope(rms_norm(proj("k_w", kvh), w["k_norm"], cp["norm_eps"]),
             cp["rope_theta"])
    v = proj("v_w", kvh)
    log_g = jax.nn.log_sigmoid(
        plain.dot(x, w["gate_w"], quant) + _bf16(w["gate_b"] + GATE_BIAS)
    )  # (T, H_kv)
    # the gates between key j and query i: a difference of prefix sums
    decay = jnp.repeat(jnp.cumsum(log_g, axis=0).T, group, axis=0)
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    s = jnp.einsum(
        "qnd,knd->nqk", plain.operand(q, quant), plain.operand(k, quant),
        precision=HI,
    ) / math.sqrt(hd)
    keep = jnp.tril(jnp.ones((t, t), bool)) & key_mask[None, :]
    a = plain.operand(jnp.square(s) * jnp.exp(jnp.where(
        keep[None], decay[:, :, None] - decay[:, None, :], -jnp.inf
    )), quant)
    y = jnp.einsum(
        "nqk,knd->qnd", a, plain.operand(v, quant), precision=HI,
    ) / (jnp.sum(a, -1).T[..., None] + EPS)
    return plain.dot(y.reshape(t, heads * hd),
                     w["out_w"].reshape(heads * hd, h), quant)


def swiglu(x, gate, up, down, quant):
    return plain.dot(
        jax.nn.silu(plain.dot(x, gate, quant)) * plain.dot(x, up, quant),
        down, quant,
    )


def _cp(cp_json: str) -> dict:
    return json.loads(cp_json)


@functools.partial(jax.jit, static_argnames=("cp_json",))
def _embed(key, tokens, *, cp_json):
    cp = _cp(cp_json)
    return leaf(key, 0, (cp["vocab_size"], cp["hidden_dim"]),
                "normal")[tokens]


@functools.partial(jax.jit, static_argnames=("cp_json", "quant"))
def _layer(key, base, x, key_mask, *, cp_json, quant):
    """One layer over rows ``x`` (R, T, H), a row at a time, its
    weights made here from the seed (``base``: its first leaf's index,
    traced, so one program serves every layer)."""
    cp = _cp(cp_json)
    w = {name: leaf(key, base + j, shape, init)
         for j, (name, shape, init) in enumerate(_layer_leaves(cp))}

    def row(args):
        xr, mask = args
        h = xr + retention(
            rms_norm(xr, w["mixer_norm"], cp["norm_eps"]), w, cp, mask,
            quant,
        )
        return h + swiglu(rms_norm(h, w["ffn_norm"], cp["norm_eps"]),
                          w["gate"], w["up"], w["down"], quant)

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
    matters sits on a pad).  At the cell's 16 rows of 1,024 the logits
    are 9.96 GB and stay on the device, where the comparison reads
    them: beside the head leaf (3.1 GB in float32) the head's program
    takes 13.4 GB of the chip's 16, the model unloaded before it.
    Brought to the host in halves they took a minute to travel."""
    key = weights.key_for(seed)
    cp_json = json.dumps(cp, sort_keys=True)
    tokens = jnp.asarray(tokens, jnp.int32)
    key_mask = tokens != 0
    x = _embed(key, tokens, cp_json=cp_json)
    per_layer = len(_layer_leaves(cp))
    for layer in range(cp["num_layers"]):
        x = _layer(key, jnp.int32(1 + layer * per_layer), x, key_mask,
                   cp_json=cp_json, quant=quant)
    return _head(key, x, cp_json=cp_json, quant=quant)
