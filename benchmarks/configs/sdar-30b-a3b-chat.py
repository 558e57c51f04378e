"""Plain reference of the ``sdar-30b-a3b-chat`` configuration (what the
published model states, and what is assumed, is in the .json beside
this file): token embedding; per layer ``h = x + Attn(RMSNorm(x))``,
``y = h + MoE(RMSNorm(h))``; final RMSNorm; an untied head without a
bias.  Attention: 32 query heads and 4 key/value heads of 128, no bias,
a weight-only RMS norm of every head of q and k, rotate-half RoPE
(theta 1e6) at each token's position id, softmax(q k^T / sqrt(128) +
mask) v.  MoE: ``softmax`` over 128 experts in float32, the 8 largest
with their gates renormalised to sum 1, SwiGLU experts 2048 -> 768 ->
2048; no shared expert, no capacity, no dropped token.

float32 at ``highest`` precision, no cache, no kernel.  The mask is an
explicit matrix that the caller gives with the position ids, so one
forward can hold a sequence beside noisy copies of it (see
``lobench/compare_blocks.py``).  Experts run in a ``scan`` over ALL of
them with the gate as a multiplier; the head runs over chunks of rows,
each reduced at once to what the comparison needs.  It imports nothing
of the program and makes its own weights from the seed, leaf by leaf:
the float32 draws of ``lobench/weights.py`` rounded to bfloat16 with
``reduce_precision`` (a cast there and back is dropped by XLA on the
TPU)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from lobench import plain, weights

HI = jax.lax.Precision.HIGHEST
HEAD_ROWS = 1024  # rows of logits (x 151,936 float32) held at a time


def _layer_leaves(cp: dict) -> list:
    h, hd, e, m = (cp["hidden_dim"], cp["head_dim"], cp["num_experts"],
                   cp["expert_dim"])
    heads, kvh = cp["num_heads"], cp["num_kv_heads"]
    return [
        ("attn_norm", (h,), "ones"),
        ("qkv_w", (h, heads + 2 * kvh, hd), "normal"),
        ("q_norm", (hd,), "ones"), ("k_norm", (hd,), "ones"),
        ("out_w", (heads * hd, h), "normal"),
        ("moe_norm", (h,), "ones"),
        ("router", (h, e), "normal"),
        ("w_gate", (e, h, m), "normal"), ("w_up", (e, h, m), "normal"),
        ("w_down", (e, m, h), "normal"),
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
        tree[f"RoutedExpertBlock_{layer}"] = {
            "attn_norm": {"scale": w["attn_norm"]},
            "MultiHeadSelfAttention_0": {
                "qkv": {"kernel": w["qkv_w"]},
                "q_norm": {"scale": w["q_norm"]},
                "k_norm": {"scale": w["k_norm"]},
                "out": {"kernel": w["out_w"]},
            },
            "moe_norm": {"scale": w["moe_norm"]},
            "RoutedExperts_0": {
                "router": w["router"], "w_gate": w["w_gate"],
                "w_up": w["w_up"], "w_down": w["w_down"],
            },
        }
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


def rope(x, pos, theta):
    """Rotate-half on (T, heads, hd) at position ids ``pos`` (T,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
         x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1,
    )


def attention(x, w, cp, pos, mask, quant):
    """One sequence (T, H) under ``mask`` (T, T) bool, query by key."""
    heads, kvh, hd = cp["num_heads"], cp["num_kv_heads"], cp["head_dim"]
    t = x.shape[0]
    qkv = plain.dot(x, w["qkv_w"].reshape(x.shape[1], -1), quant) \
        .reshape(t, heads + 2 * kvh, hd)
    q, k, v = qkv[:, :heads], qkv[:, heads: heads + kvh], \
        qkv[:, heads + kvh:]
    q = rope(rms_norm(q, w["q_norm"], cp["norm_eps"]), pos,
             cp["rope_theta"])
    k = rope(rms_norm(k, w["k_norm"], cp["norm_eps"]), pos,
             cp["rope_theta"])
    k = jnp.repeat(k, heads // kvh, axis=1)  # a KV head, 8 query heads
    v = jnp.repeat(v, heads // kvh, axis=1)
    s = jnp.einsum(
        "qnd,knd->nqk", plain.operand(q, quant), plain.operand(k, quant),
        precision=HI,
    ) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    p = jnp.where(jnp.any(mask, -1)[None, :, None], p, 0.0)
    o = jnp.einsum(
        "nqk,knd->qnd", plain.operand(p, quant), plain.operand(v, quant),
        precision=HI,
    )
    return plain.dot(o.reshape(t, heads * hd), w["out_w"], quant)


def experts(x, w, cp, quant):
    """Rows (N, H) through all the experts, the gate a multiplier."""
    probs = jax.nn.softmax(plain.dot(x, w["router"], quant), -1)
    top, ids = jax.lax.top_k(probs, cp["experts_per_token"])
    top = top / jnp.sum(top, -1, keepdims=True)

    def one(acc, packed):
        e, w_gate, w_up, w_down = packed
        gate = jnp.sum(jnp.where(ids == e, top, 0.0), -1)
        hid = jax.nn.silu(plain.dot(x, w_gate, quant)) \
            * plain.dot(x, w_up, quant)
        return acc + gate[:, None] * plain.dot(hid, w_down, quant), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (jnp.arange(cp["num_experts"]), w["w_gate"], w["w_up"],
         w["w_down"]),
    )
    return out


@functools.partial(jax.jit, static_argnames=("cp_items",))
def _embed(key, tokens, *, cp_items):
    cp = dict(cp_items)
    return leaf(key, 0, (cp["vocab_size"], cp["hidden_dim"]),
                "normal")[tokens]


@functools.partial(jax.jit, static_argnames=("cp_items", "quant"))
def _layer(key, layer, x, pos, mask, *, cp_items, quant):
    """Layer ``layer`` (a traced int: one program serves every layer)
    over rows ``x`` (R, T, H), each under its own ``mask`` (R, T, T),
    with its weights made here from the seed."""
    cp = dict(cp_items)
    spec = _layer_leaves(cp)
    base = 1 + layer * len(spec)
    w = {name: leaf(key, base + j, shape, init)
         for j, (name, shape, init) in enumerate(spec)}
    att = jax.lax.map(
        lambda row: attention(
            rms_norm(row[0], w["attn_norm"], cp["norm_eps"]), w, cp,
            row[1], row[2], quant,
        ), (x, pos, mask),
    )
    x = x + att
    flat = x.reshape(-1, x.shape[-1])
    moe = experts(rms_norm(flat, w["moe_norm"], cp["norm_eps"]), w, cp,
                  quant)
    return x + moe.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("cp_items", "quant"))
def _head(key, x, probe, *, cp_items, quant):
    """Rows (N, H) and one probe token a row -> per row the best token,
    its logit, the log of the softmax's denominator and the probe's
    logit, the logits themselves never held for more than
    ``HEAD_ROWS`` rows."""
    cp = dict(cp_items)
    n_leaves = len(leaves(cp))
    norm = leaf(key, n_leaves - 2, (cp["hidden_dim"],), "ones")
    head = leaf(key, n_leaves - 1,
                (cp["hidden_dim"], cp["vocab_size"]), "normal")

    def chunk(rows):
        xs, probes = rows
        logits = plain.dot(rms_norm(xs, norm, cp["norm_eps"]), head, quant)
        return (
            jnp.argmax(logits, -1).astype(jnp.int32),
            jnp.max(logits, -1),
            jax.scipy.special.logsumexp(logits, -1),
            jnp.take_along_axis(logits, probes[:, None], -1)[:, 0],
        )

    n = x.shape[0]
    pad = -n % HEAD_ROWS
    xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, HEAD_ROWS, x.shape[1])
    probes = jnp.pad(probe, (0, pad)).reshape(-1, HEAD_ROWS)
    out = jax.lax.map(chunk, (xs, probes))
    return tuple(a.reshape(-1)[:n] for a in out)


def reference_scores(seed: int, cp: dict, tokens, pos, mask, probe,
                     quant=None) -> dict:
    """One forward over ``tokens`` (R, T) at position ids ``pos`` (R, T)
    under ``mask`` (R, T, T) (query by key; a key of pad id 0 is masked
    besides).  For every position: ``best`` (the token of the largest
    logit), ``best_logit``, ``lse`` (log of the softmax's denominator:
    the log-confidence of ``best`` is ``best_logit - lse``) and
    ``probe_logit``, the logit of the token ``probe`` (R, T) names
    there."""
    key = weights.key_for(seed)
    items = tuple(sorted(cp.items()))
    tokens = jnp.asarray(tokens, jnp.int32)
    mask = jnp.asarray(mask) & (tokens != 0)[:, None, :]
    pos = jnp.asarray(pos, jnp.int32)
    x = _embed(key, tokens, cp_items=items)
    for layer in range(cp["num_layers"]):
        x = _layer(key, jnp.int32(layer), x, pos, mask, cp_items=items,
                   quant=quant)
    best, best_logit, lse, probe_logit = _head(
        key, x.reshape(-1, x.shape[-1]),
        jnp.asarray(probe, jnp.int32).reshape(-1), cp_items=items,
        quant=quant,
    )
    shape = tokens.shape
    return {"best": best.reshape(shape),
            "best_logit": best_logit.reshape(shape),
            "lse": lse.reshape(shape),
            "probe_logit": probe_logit.reshape(shape)}
