"""Plain reference of the ``falcon-h1-34b-instruct`` configuration (what
the published model states, and what is assumed, is in the .json beside
this file): every block runs a Mamba-2 mixer and grouped-query attention
side by side on the same normed input, then a SwiGLU FFN, with the
release's muP multipliers.  ``x = E[tok] * m_emb``; per layer ``h =
RMSNorm(x)``, ``x += m_ssm_out * Mamba(h) + m_attn_out * Attn(m_attn_in
* h)``, ``x += FFN(RMSNorm(x))``; ``logits = RMSNorm(x) W_head *
m_head`` over the whole vocabulary.

Attention: 20 query heads over 4 key/value heads of 128, ``k`` scaled by
``key_multiplier`` before RoPE (rotate-half, theta 1e11), a causal
softmax over the keys that are not the pad id, scale 1 / sqrt(128).

Mamba: ``zxbcdt = (m_in h) W_in * mu`` with ``mu`` the five
``ssm_multipliers`` over [z, x, B, C, dt]; ``xBC = silu(conv4(xBC) +
b)``, causal and depthwise; ``dt = softplus(dt + dt_bias)``, ``A =
-exp(A_log)``; per head ``j`` (group ``j // 16``) and position, ONE
AFTER THE OTHER: ``S = exp(dt A) S + dt B x^T``, ``y = S^T C + D x``;
``y * silu(z)`` RMS-normed over each of the 2 groups of 2,048, times
its weight, then ``W_out``.  No chunk form, no kernel, no cache: that
the program runs the chunk form or the kernel and this the plain
recurrence is the point of the comparison.

float32 at ``highest`` precision; one row of at most 512 positions at a
time, layer by layer, the weights made here from the seed leaf by leaf
(the float32 draws of ``lobench/weights.py`` rounded to bfloat16 with
``reduce_precision``; the seeded leaves of :func:`seeded` from them).
It imports nothing of the program."""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from lobench import plain, weights

HI = jax.lax.Precision.HIGHEST
CONV_WIDTH = 4
#: Step sizes drawn log-uniform over this range, as the family's
#: initialisation draws them (and floored at ``DT_FLOOR``).
DT_RANGE, DT_FLOOR = (1e-3, 1e-1), 1e-4
#: The heads' decay rates ``A = exp(A_log)``, spaced evenly in the
#: logarithm over this range: memories ``1 / (dt A)`` of 10 to 100,000
#: positions.  The family's ``A = 1 .. 32`` forgets within 1,000
#: positions in every head, and then ``S^T C`` reads a twentieth of
#: ``D x`` (one layer at the published widths, seeded weights): a state
#: lost, kept from the last request or rounded would hardly show.
A_RANGE = (1e-2, 1.0)


def _dims(cp: dict) -> dict:
    h, g, n = cp["mamba_heads"], cp["mamba_groups"], cp["mamba_state"]
    inner = h * cp["mamba_head_dim"]
    return {"inner": inner, "bc": g * n, "channels": inner + 2 * g * n,
            "proj": 2 * inner + 2 * g * n + h}


def _layer_leaves(cp: dict) -> list:
    hid, m = cp["hidden_dim"], cp["mlp_dim"]
    heads, kvh, hd = cp["num_heads"], cp["num_kv_heads"], cp["head_dim"]
    mh, dims = cp["mamba_heads"], _dims(cp)
    return [
        ("input_norm", (hid,), "ones"),
        ("in_proj", (hid, dims["proj"]), "normal"),
        ("conv_kernel", (CONV_WIDTH, dims["channels"]), "normal"),
        ("conv_bias", (dims["channels"],), "normal"),
        ("A_log", (mh,), "ones"), ("dt_bias", (mh,), "normal"),
        ("D", (mh,), "ones"), ("mamba_norm", (dims["inner"],), "ones"),
        ("out_proj", (dims["inner"], hid), "normal"),
        ("q_w", (hid, heads, hd), "normal"), ("k_w", (hid, kvh, hd), "normal"),
        ("v_w", (hid, kvh, hd), "normal"), ("o_w", (heads * hd, hid), "normal"),
        ("ffn_norm", (hid,), "ones"),
        ("gate", (hid, m), "normal"), ("up", (hid, m), "normal"),
        ("down", (m, hid), "normal"),
    ]


def leaves(cp: dict) -> list:
    hid, v = cp["hidden_dim"], cp["vocab_size"]
    out = [("tok_emb", (v, hid), "normal")]
    for layer in range(cp["num_layers"]):
        out += [(f"l{layer}.{n}", s, i) for n, s, i in _layer_leaves(cp)]
    return out + [("final_norm", (hid,), "ones"), ("head_w", (hid, v), "normal")]


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def seeded(name: str, drawn):
    """The leaves the family initialises otherwise than normal(0, 0.02),
    from their drawn leaf (its values those of bfloat16), rounded to
    bfloat16 again: the convolution's kernel and bias PyTorch's
    ``Conv1d`` default, uniform in +-1 / sqrt(4); ``dt_bias`` the
    inverse softplus of a step size drawn log-uniform in [1e-3, 1e-1];
    ``A_log`` spaced evenly over ``A_RANGE``'s logarithms.  A uniform
    draw is the drawn normal's own quantile.  With every leaf
    normal(0.02) the step sizes would all be softplus(0) = 0.69 and the
    state would forget within a few positions; ``D x`` would drown
    ``S^T C``.  Any other leaf as it is."""
    drawn = jnp.asarray(drawn, jnp.float32)
    uniform = 0.5 * (1.0 + jax.scipy.special.erf(
        drawn / (weights.STD * math.sqrt(2.0))))
    if name in ("conv_kernel", "conv_bias"):
        out = (2.0 * uniform - 1.0) / math.sqrt(CONV_WIDTH)
    elif name == "dt_bias":
        lo, hi = (math.log(r) for r in DT_RANGE)
        dt = jnp.maximum(jnp.exp(lo + uniform * (hi - lo)), DT_FLOOR)
        out = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "A_log":
        out = jnp.linspace(*(math.log(r) for r in A_RANGE), drawn.shape[0])
    else:
        return drawn
    return _bf16(out)


SEEDED = ("conv_kernel", "conv_bias", "dt_bias", "A_log")


def program_params(flat: dict, cp: dict) -> dict:
    """The flat leaves as the program's flax variables, the seeded ones
    (:func:`seeded`) in place of their draws."""
    import numpy as np

    tree = {
        "Embed_0": {"embedding": flat["tok_emb"]},
        "final_norm": {"scale": flat["final_norm"]},
        "head": flat["head_w"],
    }
    for layer in range(cp["num_layers"]):
        w = {k.split(".", 1)[1]: a for k, a in flat.items()
             if k.startswith(f"l{layer}.")}
        for name in SEEDED:
            w[name] = np.asarray(jax.device_get(seeded(name, w[name]))
                                 ).astype(w[name].dtype)
        tree[f"HybridBlock_{layer}"] = {
            "input_norm": {"scale": w["input_norm"]},
            "Mamba2Mixer_0": {
                "in_proj": {"kernel": w["in_proj"]},
                "conv_kernel": w["conv_kernel"], "conv_bias": w["conv_bias"],
                "A_log": w["A_log"], "dt_bias": w["dt_bias"], "D": w["D"],
                "norm_scale": w["mamba_norm"],
                "out_proj": {"kernel": w["out_proj"]},
            },
            "MultiHeadSelfAttention_0": {
                "qkv": {"kernel": np.concatenate(
                    [w["q_w"], w["k_w"], w["v_w"]], axis=1)},
                "out": {"kernel": w["o_w"]},
            },
            "ffn_norm": {"scale": w["ffn_norm"]},
            **{k: {"kernel": w[k]} for k in ("gate", "up", "down")},
        }
    return {"params": tree}


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


def attention(x, w, cp, key_mask, quant):
    """One row (T, H), causal, keys of pad id 0 never seen."""
    heads, kvh, hd = cp["num_heads"], cp["num_kv_heads"], cp["head_dim"]
    t, hid = x.shape

    def proj(name, n):
        return plain.dot(x, w[name].reshape(hid, n * hd), quant) \
            .reshape(t, n, hd)

    q = rope(proj("q_w", heads), cp["rope_theta"])
    k = rope(proj("k_w", kvh) * cp["key_multiplier"], cp["rope_theta"])
    k, v = (jnp.repeat(a, heads // kvh, axis=1)
            for a in (k, proj("v_w", kvh)))
    s = jnp.einsum(
        "qnd,knd->nqk", plain.operand(q, quant), plain.operand(k, quant),
        precision=HI,
    ) / math.sqrt(hd)
    keep = jnp.tril(jnp.ones((t, t), bool)) & key_mask[None, :]
    p = jnp.where(jnp.any(keep, -1)[None, :, None],
                  jax.nn.softmax(jnp.where(keep[None], s, -1e30), -1), 0.0)
    y = jnp.einsum("nqk,knd->qnd", plain.operand(p, quant),
                   plain.operand(v, quant), precision=HI)
    return plain.dot(y.reshape(t, heads * hd), w["o_w"], quant)


def mamba(x, w, cp, key_mask, quant):
    """One row (T, H): the mixer's recurrence one position at a time; a
    pad position enters neither the convolution nor the state."""
    h, p = cp["mamba_heads"], cp["mamba_head_dim"]
    g, n = cp["mamba_groups"], cp["mamba_state"]
    t, dims = x.shape[0], _dims(cp)
    inner, bc = dims["inner"], dims["bc"]
    mu = jnp.concatenate([
        jnp.full((width,), m, jnp.float32) for width, m in zip(
            (inner, inner, bc, bc, h), cp["ssm_multipliers"])
    ])
    zxbcdt = plain.dot(x * cp["ssm_in_multiplier"], w["in_proj"], quant) * mu
    z, xbc, dt = jnp.split(zxbcdt, [inner, inner + dims["channels"]], -1)
    seen = key_mask.astype(jnp.float32)[:, None]
    kernel, bias = (seeded(k, w[k]) for k in ("conv_kernel", "conv_bias"))
    padded = jnp.pad(xbc * seen, [(CONV_WIDTH - 1, 0), (0, 0)])
    xbc = jax.nn.silu(sum(padded[i:i + t] * kernel[i]
                          for i in range(CONV_WIDTH)) + bias) * seen
    xs, bs, cs = jnp.split(xbc, [inner, inner + bc], -1)
    dt = jax.nn.softplus(dt + seeded("dt_bias", w["dt_bias"])) * seen
    a = -jnp.exp(seeded("A_log", w["A_log"]))

    def step(state, row):
        xt, bt, ct, dtt = row
        xt = xt.reshape(h, p)
        bt, ct = (jnp.repeat(u.reshape(g, n), h // g, axis=0)
                  for u in (bt, ct))
        state = jnp.exp(dtt * a)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        y = jnp.einsum("hpn,hn->hp", state, ct, precision=HI) \
            + w["D"][:, None] * xt
        return state, y.reshape(inner)

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                        (xs, bs, cs, dt))
    y = (y * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + cp["norm_eps"])
    return plain.dot(y.reshape(t, inner) * w["mamba_norm"], w["out_proj"],
                     quant)


def _cp(cp_json: str) -> dict:
    return json.loads(cp_json)


@functools.partial(jax.jit, static_argnames=("cp_json",))
def _embed(key, tokens, *, cp_json):
    cp = _cp(cp_json)
    return leaf(key, 0, (cp["vocab_size"], cp["hidden_dim"]),
                "normal")[tokens] * cp["embedding_multiplier"]


@functools.partial(jax.jit, static_argnames=("cp_json", "quant"))
def _layer(key, base, x, key_mask, *, cp_json, quant):
    """One layer over rows ``x`` (R, T, H), a row at a time, its
    weights made here from the seed (``base``: its first leaf's index,
    traced, so one program serves every layer)."""
    cp = _cp(cp_json)
    w = {name: leaf(key, base + j, shape, init)
         for j, (name, shape, init) in enumerate(_layer_leaves(cp))}
    gate_mult, down_mult = cp["mlp_multipliers"]
    eps = cp["norm_eps"]

    def row(args):
        xr, mask = args
        h = rms_norm(xr, w["input_norm"], eps)
        xr = xr + cp["ssm_out_multiplier"] * mamba(h, w, cp, mask, quant) \
            + cp["attention_out_multiplier"] * attention(
                h * cp["attention_in_multiplier"], w, cp, mask, quant)
        u = rms_norm(xr, w["ffn_norm"], eps)
        hid = plain.dot(u, w["up"], quant) * jax.nn.silu(
            gate_mult * plain.dot(u, w["gate"], quant))
        return xr + down_mult * plain.dot(hid, w["down"], quant)

    return jax.lax.map(row, (x, key_mask))


@functools.partial(jax.jit, static_argnames=("cp_json", "quant"))
def _head(key, x, *, cp_json, quant):
    cp = _cp(cp_json)
    n = len(leaves(cp))
    norm = leaf(key, n - 2, (cp["hidden_dim"],), "ones")
    head = leaf(key, n - 1, (cp["hidden_dim"], cp["vocab_size"]), "normal")
    return jax.lax.map(
        lambda xr: plain.dot(rms_norm(xr, norm, cp["norm_eps"]), head, quant)
        * cp["lm_head_multiplier"], x,
    )


def reference_logits(seed: int, cp: dict, tokens, quant=None):
    """(R, T, V) logits of one full forward over ``tokens`` (R, T),
    zero-padded rows allowed (pad keys are masked, and no query that
    matters sits on a pad).  At the cell's rows of 512 positions over
    the whole vocabulary of 261,120 they stay on the device, where the
    comparison reads them, beside the head leaf (5.35 GB in float32),
    the model unloaded before it."""
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
