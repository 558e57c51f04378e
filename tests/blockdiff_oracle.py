"""Plain reference of the block-diffusion sparse-expert LM
(``models/moe.py BlockDiffusionMoELM``): float32 ``jax.numpy`` at
``highest`` precision, no cache, no kernel, no batching.  Experts are
a loop over ALL of them with the gate as a multiplier (0 for an expert
a token did not choose), the attention mask is an explicit (T, T)
matrix, and generation forwards the whole buffer anew at every
denoising step.  It takes the program's parameter tree and nothing
else of the program."""

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps
    ) * _f32(scale)


def rope(x, pos, theta):
    """Rotate-half on (T, heads, hd) at positions ``pos`` (T,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
         x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1,
    )


def block_mask(t: int, block):
    """(T, T) bool: query i sees key j.  ``block`` None: plain causal."""
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    return j <= i if block is None else (j // block) <= (i // block)


def forward(est, tokens, mask=None, pos=None):
    """(T, V) logits of one sequence ``tokens`` (T,) under ``mask``
    (T, T), default the model's own block mask, at position ids ``pos``
    (T,), default 0 .. T-1.  A key whose token is the pad id 0 is never
    seen."""
    p = est.params["params"]
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    if mask is None:
        mask = block_mask(t, est.block_length)
    mask = mask & (tokens != 0)[None, :]
    heads, kvh, hd = est.num_heads, est.num_kv_heads, est.head_dim
    x = _f32(p["Embed_0"]["embedding"])[tokens]
    pos = jnp.arange(t) if pos is None else jnp.asarray(pos)
    for layer in range(est.num_layers):
        lp = p[f"RoutedExpertBlock_{layer}"]
        ap = lp["MultiHeadSelfAttention_0"]
        y = rms_norm(x, lp["attn_norm"]["scale"], est.norm_eps)
        qkv = jnp.einsum("th,hnd->tnd", y, _f32(ap["qkv"]["kernel"]),
                         precision=HI)
        q, k, v = (qkv[:, :heads], qkv[:, heads: heads + kvh],
                   qkv[:, heads + kvh:])
        q = rope(rms_norm(q, ap["q_norm"]["scale"], est.norm_eps),
                 pos, est.rope_theta)
        k = rope(rms_norm(k, ap["k_norm"]["scale"], est.norm_eps),
                 pos, est.rope_theta)
        k = jnp.repeat(k, heads // kvh, axis=1)
        v = jnp.repeat(v, heads // kvh, axis=1)
        s = jnp.einsum("qnd,knd->nqk", q, k, precision=HI) / np.sqrt(hd)
        s = jnp.where(mask[None], s, -1e30)
        a = jax.nn.softmax(s, -1)
        a = jnp.where(mask.any(-1)[None, :, None], a, 0.0)
        o = jnp.einsum("nqk,knd->qnd", a, v, precision=HI)
        x = x + jnp.matmul(o.reshape(t, heads * hd),
                           _f32(ap["out"]["kernel"]), precision=HI)
        ep = lp["RoutedExperts_0"]
        y = rms_norm(x, lp["moe_norm"]["scale"], est.norm_eps)
        probs = jax.nn.softmax(
            jnp.matmul(y, _f32(ep["router"]), precision=HI), -1
        )
        top, ids = jax.lax.top_k(probs, est.experts_per_token)
        top = top / top.sum(-1, keepdims=True)
        moe = jnp.zeros_like(x)
        for e in range(est.num_experts):
            gate = jnp.where(ids == e, top, 0.0).sum(-1)  # (T,)
            hid = jax.nn.silu(
                jnp.matmul(y, _f32(ep["w_gate"][e]), precision=HI)
            ) * jnp.matmul(y, _f32(ep["w_up"][e]), precision=HI)
            moe = moe + gate[:, None] * jnp.matmul(
                hid, _f32(ep["w_down"][e]), precision=HI
            )
        x = x + moe
    x = rms_norm(x, p["final_norm"]["scale"], est.norm_eps)
    return jnp.matmul(x, _f32(p["head"]["kernel"]), precision=HI)


def choose(conf, masked, block: int, steps: int, step: int,
           remasking: str, threshold: float):
    """Which of a block's positions denoising step ``step`` of ``steps``
    fixes, a bool vector: of the ``masked`` ones the ``B // T`` (+1 for
    the first ``B % T`` steps) of highest confidence, ties to the
    earlier; ``low_confidence_dynamic`` fixes every one above the
    threshold instead where at least that many are."""
    conf = np.where(masked, conf, -np.inf)
    count = min(block // steps + (step < block % steps), masked.sum())
    pick = np.zeros(block, bool)
    pick[np.argsort(-conf, kind="stable")[:count]] = True
    if remasking == "low_confidence_dynamic":
        high = conf > threshold
        if high.sum() >= count:
            pick = high
    return pick


def generate(est, prompt, max_new: int, steps: int, remasking: str,
             threshold: float = 0.9):
    """(tokens (t0 + max_new,), {position: denoising step it was fixed
    at}) by the published procedure, the whole buffer forwarded anew at
    every denoising step, :func:`choose` fixing positions."""
    b, m = est.block_length, est.mask_token_id
    t0 = len(prompt)
    total = -(-(t0 + max_new) // b) * b
    buf = np.full(total, m, np.int32)
    buf[:t0] = prompt
    open_ = np.arange(total) >= t0  # not yet fixed (a fixed token may
    fixed_at = {}                   # be the mask id itself)
    for start in range(t0 // b * b, total, b):
        for step in range(steps):
            masked = open_[start: start + b]
            if not masked.any():
                break
            logits = np.asarray(forward(est, buf)[start: start + b])
            x0 = logits.argmax(-1)
            conf = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))[
                np.arange(b), x0
            ]
            pick = choose(conf, masked, b, steps, step, remasking,
                          threshold)
            for j in np.flatnonzero(pick):
                buf[start + j] = x0[j]
                open_[start + j] = False
                fixed_at[start + j] = step
    return buf[: t0 + max_new], fixed_at
